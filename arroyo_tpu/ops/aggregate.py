"""Keyed windowed aggregation on device: HBM-resident hash-table state.

This replaces the reference's DataFusion partial/finish aggregate plans
(crates/arroyo-worker/src/arrow/tumbling_aggregating_window.rs:49,
sliding_aggregating_window.rs:45) with a TPU-native design:

  state (HBM, persistent across micro-batches, donated through jit):
      keys      int64[cap]   -- 64-bit key hash (uint64 bits viewed as int64)
      bins      int32[cap]   -- window bin index (timestamp // bin_width)
      occupied  bool[cap]
      accs      tuple of [cap] arrays, one per accumulator

  step (jit, one fused XLA program per operator config):
      1. lexsort incoming (bin, key) pairs -> adjacent duplicates
      2. segment-reduce each accumulator -> <=B unique (bin, key) partials
      3. merge partials into the table with linear probing: matches combine
         via scatter; empty-slot claims race-resolved with a scatter-max of
         the contender index (classic GPU hash-build, expressed as XLA
         scatter/gather under lax.fori_loop so it compiles to one program)

  extract (jit): compact closed bins out of the table with an argsort on the
      close mask; destructive (tumbling close) or range-scan (sliding).

Static shapes everywhere: batches padded to ``batch_cap``, table capacity and
probe count fixed at trace time; no data-dependent control flow inside jit.
A NumPy mirror backend provides the CPU oracle for differential tests.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional, Sequence

import numpy as np

AGG_KINDS = ("sum", "count", "min", "max")

_I64_MAX = np.iinfo(np.int64).max
_I32_MAX = np.iinfo(np.int32).max


def acc_kinds_for(kind: str) -> tuple[str, ...]:
    """Accumulators backing one SQL aggregate (avg -> sum+count)."""
    if kind == "avg":
        return ("sum", "count")
    if kind in AGG_KINDS:
        return (kind,)
    raise ValueError(f"unsupported aggregate {kind}")


def finalize_aggs(kinds: Sequence[str], acc_arrays: list[np.ndarray]) -> list[np.ndarray]:
    """acc arrays (in acc_kinds_for order, flattened) -> one array per SQL agg."""
    out = []
    i = 0
    for kind in kinds:
        if kind == "avg":
            s, c = acc_arrays[i], acc_arrays[i + 1]
            i += 2
            out.append(np.divide(s, np.maximum(c, 1)).astype(np.float64))
        elif kind == "count_distinct":
            # a NULL (a padded join side, a row a FILTER dropped) is no value
            out.append(np.array([len(set(lst) - {None}) for lst in acc_arrays[i]],
                                dtype=np.int64))
            i += 1
        elif kind.startswith("udaf:"):
            from ..batch import Field
            from ..udf import lookup_udaf

            udaf = lookup_udaf(kind[len("udaf:"):])
            if udaf is None:
                raise RuntimeError(f"UDAF {kind[5:]!r} no longer registered")
            vals = [udaf.fn(np.asarray(lst)) for lst in acc_arrays[i]]
            i += 1
            if udaf.return_dtype == "string":
                from ..batch import object_column

                out.append(object_column(vals))
            else:
                out.append(np.array(vals, dtype=Field("_", udaf.return_dtype).numpy_dtype()))
        else:
            out.append(acc_arrays[i])
            i += 1
    return out


def drain_extract(extract_once, emit_cap: int, acc_kinds: Sequence[str],
                  acc_dtypes: Sequence[np.dtype], emit_lo: int, free_below: int):
    """Host-side drain loop shared by the single-chip and sharded
    aggregators. ``extract_once()`` performs one device extraction and
    returns (key_i64, bin, valid, accs, max_total) as numpy arrays/ints.

    Termination invariants: entries in the emit range are freed only when
    below ``free_below``, so a destructive close shrinks each round; a pure
    range scan (free_below <= emit_lo) must bail after one round or it would
    re-emit the same entries forever.

    The result is merged with combine_by_key_bin: in-place slot freeing
    punches holes in probe chains, so the table may hold duplicate (key, bin)
    entries whose accumulators each carry part of the total."""
    keys_out, bins_out = [], []
    accs_out: list[list[np.ndarray]] = [[] for _ in acc_dtypes]
    while True:
        k, b, valid, accs, max_total = extract_once()
        cnt = int(valid.sum())
        if cnt:
            keys_out.append(k[valid])
            bins_out.append(b[valid])
            for i, a in enumerate(accs):
                accs_out[i].append(a[valid])
        if max_total <= emit_cap or cnt == 0 or free_below <= emit_lo:
            break
    if not keys_out:
        return (
            np.empty(0, dtype=np.uint64),
            np.empty(0, dtype=np.int32),
            [np.empty(0, dtype=d) for d in acc_dtypes],
        )
    return combine_by_key_bin(
        acc_kinds,
        np.concatenate(keys_out).view(np.uint64),
        np.concatenate(bins_out),
        [np.concatenate(a) for a in accs_out],
    )


def combine_by_key_bin(
    acc_kinds: Sequence[str],
    keys: np.ndarray,
    bins: np.ndarray,
    accs: list[np.ndarray],
) -> tuple[np.ndarray, np.ndarray, list[np.ndarray]]:
    """Merge duplicate (key, bin) entries after a device extraction. The
    linear-probe table frees slots in place when bins close, which punches
    holes in probe chains: a later update of a live (key, bin) can claim a
    hole before reaching its original entry, leaving two entries whose
    accumulators each hold part of the total. Emission must re-combine them.
    What leaves is in (bin, key) order, duplicates or none: where the table
    put an entry depends on how the stream was cut into calls, and neither
    a window's rows nor a snapshot may."""
    if len(keys) <= 1:
        return keys, bins, accs
    signed = keys.view(np.int64)
    order = np.lexsort((signed, bins))
    k_s, b_s = signed[order], bins[order]
    newseg = np.ones(len(k_s), dtype=bool)
    newseg[1:] = (k_s[1:] != k_s[:-1]) | (b_s[1:] != b_s[:-1])
    if newseg.all():
        return k_s.view(np.uint64), b_s, [a[order] for a in accs]
    starts = np.flatnonzero(newseg)
    out_accs = []
    for kind, a in zip(acc_kinds, accs):
        a_s = a[order]
        if kind in ("sum", "count"):
            red = np.add.reduceat(a_s, starts)
        elif kind == "min":
            red = np.minimum.reduceat(a_s, starts)
        else:
            red = np.maximum.reduceat(a_s, starts)
        out_accs.append(red.astype(a.dtype))
    return k_s[starts].view(np.uint64), b_s[starts], out_accs


def combine_by_key(
    acc_kinds: Sequence[str], keys: np.ndarray, accs: list[np.ndarray]
) -> tuple[np.ndarray, list[np.ndarray]]:
    """Combine per-bin partials that share a key into one accumulator row per
    key (the sliding-window finish step: width/slide partial bins collapse to
    one output row — reference sliding_aggregating_window.rs:116-170). Host
    numpy: the input is already reduced to distinct (bin, key) pairs, so this
    is small relative to the event stream the device reduced."""
    if len(keys) == 0:
        return keys, accs
    signed = keys.view(np.int64)
    order = np.argsort(signed, kind="stable")
    k_s = signed[order]
    newseg = np.ones(len(k_s), dtype=bool)
    newseg[1:] = k_s[1:] != k_s[:-1]
    starts = np.flatnonzero(newseg)
    out_keys = k_s[starts].view(np.uint64)
    out_accs = []
    for kind, a in zip(acc_kinds, accs):
        a_s = a[order]
        if kind in ("sum", "count"):
            red = np.add.reduceat(a_s, starts)
        elif kind == "min":
            red = np.minimum.reduceat(a_s, starts)
        else:
            red = np.maximum.reduceat(a_s, starts)
        out_accs.append(red.astype(a.dtype))
    return out_keys, out_accs


def _identity(kind: str, dtype):
    if kind in ("sum", "count"):
        return np.array(0, dtype=dtype)
    if kind == "min":
        return np.array(np.iinfo(dtype).max if np.issubdtype(dtype, np.integer) else np.inf, dtype=dtype)
    if kind == "max":
        return np.array(np.iinfo(dtype).min if np.issubdtype(dtype, np.integer) else -np.inf, dtype=dtype)
    raise ValueError(kind)


# =========================================================================
# jax backend — traceable building blocks (shared by the single-chip step
# and the shard_map'd multi-chip step in arroyo_tpu.parallel)
# =========================================================================


def _combine_jnp(kind, a, b):
    import jax.numpy as jnp

    if kind in ("sum", "count"):
        return a + b
    if kind == "min":
        return jnp.minimum(a, b)
    return jnp.maximum(a, b)


def _seg_reduce_jnp(kind, vals, seg, valid, num_segments):
    import jax
    import jax.numpy as jnp

    if kind in ("sum", "count"):
        v = jnp.where(valid, vals, 0)
        return jax.ops.segment_sum(v, seg, num_segments=num_segments)
    if kind == "min":
        v = jnp.where(valid, vals, _identity("min", np.dtype(vals.dtype)))
        return jax.ops.segment_min(v, seg, num_segments=num_segments)
    v = jnp.where(valid, vals, _identity("max", np.dtype(vals.dtype)))
    return jax.ops.segment_max(v, seg, num_segments=num_segments)


def sort_reduce(acc_kinds, key, bins, valid, vals, batch_cap):
    """Collapse a padded batch to unique (bin, key) partials: lexsort so
    duplicates are adjacent, then segment-reduce each accumulator. Returns
    (u_key, u_bin, active_mask, u_accs), all of length batch_cap."""
    import jax
    import jax.numpy as jnp

    skey = jnp.where(valid, key, _I64_MAX)
    sbin = jnp.where(valid, bins, _I32_MAX)
    order = jnp.lexsort((sbin, skey))
    k_s = skey[order]
    b_s = sbin[order]
    valid_s = valid[order]
    newseg = jnp.concatenate(
        [jnp.ones(1, dtype=bool), (k_s[1:] != k_s[:-1]) | (b_s[1:] != b_s[:-1])]
    )
    seg = jnp.cumsum(newseg) - 1
    u_accs = tuple(
        _seg_reduce_jnp(acc_kinds[i], vals[i][order], seg, valid_s, batch_cap)
        for i in range(len(acc_kinds))
    )
    rows_per_seg = jax.ops.segment_sum(
        valid_s.astype(jnp.int32), seg, num_segments=batch_cap
    )
    # representative key/bin per segment (all rows in a segment are equal)
    u_key = jax.ops.segment_max(k_s, seg, num_segments=batch_cap)
    u_bin = jax.ops.segment_max(b_s, seg, num_segments=batch_cap)
    return u_key, u_bin, rows_per_seg > 0, u_accs


def probe_merge(acc_kinds, table, u_key, u_bin, active0, u_accs, cap, max_probes):
    """Merge unique partials into the (keys, bins, occ, accs) hash table with
    linear probing; empty-slot claim races resolved via scatter-max of the
    contender index. A round is one probe of every row that is still active;
    the loop ends with the first round that finds none active, and after
    ``max_probes`` rounds at the latest. A round with no active row writes
    nothing (every index is ``cap``, which ``mode="drop"`` discards), so the
    table and the mask are what ``max_probes`` rounds would leave, bit for
    bit. The condition reads this call's own rows and holds no collective:
    under shard_map each shard runs its own number of rounds. Returns
    (table', still_active_mask, rounds run as an int32 scalar)."""
    import jax
    import jax.numpy as jnp

    keys_t, bins_t, occ_t, accs_t = table
    mask_cap = cap - 1
    n_acc = len(acc_kinds)
    batch_cap = u_key.shape[0]

    z = u_key.astype(jnp.uint64) ^ (u_bin.astype(jnp.uint64) * jnp.uint64(0xFF51AFD7ED558CCD))
    z = (z ^ (z >> jnp.uint64(33))) * jnp.uint64(0xC4CEB9FE1A85EC53)
    z = z ^ (z >> jnp.uint64(33))
    h0 = (z & jnp.uint64(mask_cap)).astype(jnp.int32)
    seg_pos = jnp.arange(batch_cap, dtype=jnp.int32)

    def probe(carry):
        i, keys_c, bins_c, occ_c, accs_c, active = carry
        cand = (h0 + i) & mask_cap
        cur_key = keys_c[cand]
        cur_bin = bins_c[cand]
        cur_occ = occ_c[cand]
        match = active & cur_occ & (cur_key == u_key) & (cur_bin == u_bin)
        empty_here = active & ~cur_occ
        claim_idx = jnp.where(empty_here, cand, cap)
        claims = jnp.full(cap, -1, dtype=jnp.int32).at[claim_idx].max(seg_pos, mode="drop")
        won = empty_here & (claims[cand] == seg_pos)
        write = match | won
        safe = jnp.where(write, cand, cap)
        keys_c = keys_c.at[safe].set(u_key, mode="drop")
        bins_c = bins_c.at[safe].set(u_bin, mode="drop")
        occ_c = occ_c.at[safe].set(True, mode="drop")
        new_accs = []
        for j in range(n_acc):
            merged = _combine_jnp(acc_kinds[j], accs_c[j][cand], u_accs[j])
            val = jnp.where(match, merged, u_accs[j])
            new_accs.append(accs_c[j].at[safe].set(val, mode="drop"))
        return (i + 1, keys_c, bins_c, occ_c, tuple(new_accs), active & ~write)

    def rows_left(carry):
        return (carry[0] < max_probes) & carry[-1].any()

    rounds, keys_t, bins_t, occ_t, accs_t, still_active = jax.lax.while_loop(
        rows_left, probe,
        (jnp.int32(0), keys_t, bins_t, occ_t, tuple(accs_t), active0),
    )
    return (keys_t, bins_t, occ_t, accs_t), still_active, rounds


@functools.lru_cache(maxsize=None)
def _build_jax(acc_kinds: tuple[str, ...], acc_dtypes: tuple, cap: int, batch_cap: int,
               max_probes: int, emit_cap: int):
    import jax
    import jax.numpy as jnp

    mask_cap = cap - 1
    assert cap & mask_cap == 0, "table capacity must be a power of two"

    def step(state, key, bins, valid, vals):
        keys_t, bins_t, occ_t, accs_t, oflow_t = state
        u_key, u_bin, active0, u_accs = sort_reduce(
            acc_kinds, key, bins, valid, vals, batch_cap
        )
        (keys_t, bins_t, occ_t, accs_t), still_active, _rounds = probe_merge(
            acc_kinds, (keys_t, bins_t, occ_t, accs_t),
            u_key, u_bin, active0, u_accs, cap, max_probes,
        )
        # overflow accumulates in device state; the host checks it at the
        # next extract/snapshot boundary instead of syncing every batch
        oflow_t = oflow_t + jnp.sum(still_active, dtype=jnp.int32)
        return (keys_t, bins_t, occ_t, accs_t, oflow_t)

    def scan(state, emit_lo, emit_hi, chunk_start):
        """Non-destructive position-chunked read of entries with
        emit_lo <= bin < emit_hi. The host walks chunk_start over
        range(0, cap, emit_cap) so a range larger than emit_cap is never
        truncated (sliding-window combine reads the same bins repeatedly)."""
        keys_t, bins_t, occ_t, accs_t, _oflow = state
        sel = chunk_start + jnp.arange(emit_cap, dtype=jnp.int32)
        # out-of-bounds gathers clamp to cap-1 under jit, which would emit the
        # last slot once per clamped index when emit_cap doesn't divide cap
        in_bounds = sel < cap
        out_valid = in_bounds & occ_t[sel] & (bins_t[sel] >= emit_lo) & (bins_t[sel] < emit_hi)
        return keys_t[sel], bins_t[sel], out_valid, tuple(a[sel] for a in accs_t)

    def free(state, below):
        """Drop every entry with bin < below (sliding-window retention)."""
        keys_t, bins_t, occ_t, accs_t, oflow_t = state
        occ_t = occ_t & ~(bins_t < below)
        return (keys_t, bins_t, occ_t, accs_t, oflow_t)

    def extract(state, emit_lo, emit_hi, free_below):
        """Emit occupied entries with emit_lo <= bin < emit_hi (compacted to
        emit_cap rows); free entries with bin < free_below.

        Compaction is a cumsum-position scatter — O(cap) with cheap TPU
        scatters — instead of a full argsort of the table per window close
        (the previous design's dominant cost: extract fires on nearly every
        watermark under dense event-time streams)."""
        keys_t, bins_t, occ_t, accs_t, oflow_t = state
        emit_mask = occ_t & (bins_t >= emit_lo) & (bins_t < emit_hi)
        total = jnp.sum(emit_mask)
        pos = jnp.cumsum(emit_mask) - 1  # output slot per emitting entry
        # non-emitting entries and overflow beyond emit_cap scatter to the
        # dropped index emit_cap (the drain loop re-reads the leftovers)
        dest = jnp.where(emit_mask & (pos < emit_cap), pos, emit_cap)
        out_key = jnp.zeros(emit_cap, keys_t.dtype).at[dest].set(keys_t, mode="drop")
        out_bin = jnp.zeros(emit_cap, bins_t.dtype).at[dest].set(bins_t, mode="drop")
        out_accs = tuple(
            jnp.zeros(emit_cap, a.dtype).at[dest].set(a, mode="drop") for a in accs_t
        )
        out_valid = jnp.arange(emit_cap, dtype=jnp.int32) < jnp.minimum(total, emit_cap)
        # free expired entries OUTSIDE the emit range immediately; entries in
        # the emit range are freed only once actually emitted, so the drain
        # loop over emit_cap-sized chunks doesn't drop the tail
        emitted = emit_mask & (pos < emit_cap)
        free_mask = (occ_t & (bins_t < free_below) & ~emit_mask) | (
            emitted & (bins_t < free_below)
        )
        occ_t = occ_t & ~free_mask
        return (keys_t, bins_t, occ_t, accs_t, oflow_t), (out_key, out_bin, out_valid, out_accs, total)

    n_acc = len(acc_kinds)

    def _to_i64(a, dtype):
        """Lossless int64 lane for transport. Floats would need a 64-bit
        bitcast, which is unsupported under TPU x64 emulation — the host
        wrapper routes float accumulator sets to the unpacked extract/scan
        paths instead, so this only ever sees integer lanes there."""
        if np.issubdtype(np.dtype(dtype), np.floating):
            return jax.lax.bitcast_convert_type(a.astype(jnp.float64), jnp.int64)
        return a.astype(jnp.int64)

    def extract_packed(state, emit_lo, emit_hi, free_below):
        """Same semantics as extract, but the result is ONE int64 buffer:
        [total, overflow, keys[emit_cap], bins[emit_cap], acc0[emit_cap], ...]

        so the host pays a single device->host transfer per window close:
        every fetch is a sync point, and the unpacked extract costs 6+ of
        them per close against one update step."""
        keys_t, bins_t, occ_t, accs_t, oflow_t = state
        emit_mask = occ_t & (bins_t >= emit_lo) & (bins_t < emit_hi)
        total = jnp.sum(emit_mask)
        pos = jnp.cumsum(emit_mask) - 1
        dest = jnp.where(emit_mask & (pos < emit_cap), pos, emit_cap)
        outs = [
            jnp.zeros(emit_cap, jnp.int64).at[dest].set(keys_t, mode="drop"),
            jnp.zeros(emit_cap, jnp.int64).at[dest].set(
                bins_t.astype(jnp.int64), mode="drop"
            ),
        ]
        for a, d in zip(accs_t, acc_dtypes):
            outs.append(
                jnp.zeros(emit_cap, jnp.int64).at[dest].set(_to_i64(a, d), mode="drop")
            )
        emitted = emit_mask & (pos < emit_cap)
        free_mask = (occ_t & (bins_t < free_below) & ~emit_mask) | (
            emitted & (bins_t < free_below)
        )
        occ_t = occ_t & ~free_mask
        header = jnp.stack([total.astype(jnp.int64), oflow_t.astype(jnp.int64)])
        packed = jnp.concatenate([header] + outs)
        return (keys_t, bins_t, occ_t, accs_t, oflow_t), packed

    def scan_packed(state, emit_lo, emit_hi):
        """Non-destructive compacted read of bins in [emit_lo, emit_hi) as one
        packed buffer (sliding-window combine). If total > emit_cap the host
        falls back to the chunked scan."""
        keys_t, bins_t, occ_t, accs_t, oflow_t = state
        emit_mask = occ_t & (bins_t >= emit_lo) & (bins_t < emit_hi)
        total = jnp.sum(emit_mask)
        pos = jnp.cumsum(emit_mask) - 1
        dest = jnp.where(emit_mask & (pos < emit_cap), pos, emit_cap)
        outs = [
            jnp.zeros(emit_cap, jnp.int64).at[dest].set(keys_t, mode="drop"),
            jnp.zeros(emit_cap, jnp.int64).at[dest].set(
                bins_t.astype(jnp.int64), mode="drop"
            ),
        ]
        for a, d in zip(accs_t, acc_dtypes):
            outs.append(
                jnp.zeros(emit_cap, jnp.int64).at[dest].set(_to_i64(a, d), mode="drop")
            )
        header = jnp.stack([total.astype(jnp.int64), oflow_t.astype(jnp.int64)])
        return jnp.concatenate([header] + outs)

    step_j = jax.jit(step, donate_argnums=0)
    extract_j = jax.jit(extract, donate_argnums=0)
    scan_j = jax.jit(scan)
    free_j = jax.jit(free, donate_argnums=0)
    extract_packed_j = jax.jit(extract_packed, donate_argnums=0)
    scan_packed_j = jax.jit(scan_packed)
    return step_j, extract_j, scan_j, free_j, extract_packed_j, scan_packed_j


# =========================================================================
# host-facing wrapper
# =========================================================================


def _drain_extract_rounds(agg, first, next_round, emit_lo: int, free_below: int):
    """Shared drain loop for destructive extracts that return at most
    emit_cap rows per round. ``first`` is the already-fetched first round
    (keys_u64, bins, accs, total); ``next_round()`` dispatches + decodes one
    more round. Termination: a round that covered everything
    (total <= emit_cap), emitted nothing (no progress possible — all
    leftovers outside the emit range), or a non-destructive call
    (free_below <= emit_lo: re-reading would duplicate, not drain)."""
    keys_out, bins_out = [], []
    accs_out: list[list[np.ndarray]] = [[] for _ in agg.acc_dtypes]
    k, b, accs, total = first
    while True:
        if len(k):
            keys_out.append(k)
            bins_out.append(b)
            for i, a in enumerate(accs):
                accs_out[i].append(a)
        if total <= agg.emit_cap or len(k) == 0 or free_below <= emit_lo:
            break
        k, b, accs, total = next_round()
    if not keys_out:
        return (
            np.empty(0, dtype=np.uint64),
            np.empty(0, dtype=np.int32),
            [np.empty(0, dtype=d) for d in agg.acc_dtypes],
        )
    return combine_by_key_bin(
        agg.acc_kinds,
        np.concatenate(keys_out),
        np.concatenate(bins_out),
        [np.concatenate(a).astype(d) for a, d in zip(accs_out, agg.acc_dtypes)],
    )


class ExtractHandle:
    """In-flight window-close extraction: the device compaction has been
    dispatched and its packed result buffer is copying to host in the
    background. ``result()`` materializes (and runs rare overflow follow-up
    rounds synchronously); ``is_ready()`` is a non-blocking poll so the
    operator can pipeline emission behind subsequent update steps."""

    def __init__(self, agg: "DeviceHashAggregator", packed, emit_lo: int,
                 emit_hi: int, free_below: int):
        self._agg = agg
        self._packed = packed
        self._emit_lo = emit_lo
        self._emit_hi = emit_hi
        self._free_below = free_below

    def is_ready(self) -> bool:
        return self._packed.is_ready()

    def result(self) -> tuple[np.ndarray, np.ndarray, list[np.ndarray]]:
        agg = self._agg

        def next_round():
            agg.state, packed = agg._extract_packed(
                agg.state, np.int32(self._emit_lo), np.int32(self._emit_hi),
                np.int32(self._free_below),
            )
            return agg._unpack(np.asarray(packed))

        return _drain_extract_rounds(
            agg, agg._unpack(np.asarray(self._packed)), next_round,
            self._emit_lo, self._free_below,
        )


class ReadyHandle:
    """ExtractHandle-compatible wrapper over an already-materialized result
    (synchronous fallback paths)."""

    def __init__(self, result):
        self._result = result

    def is_ready(self) -> bool:
        return True

    def result(self):
        return self._result


class DeviceHashAggregator:
    """Streaming (bin, key) -> accumulators store.

    backend="jax": state lives in HBM, update/extract are single XLA programs.
    backend="numpy": dict-based host mirror (differential-test oracle).
    """

    def __init__(
        self,
        acc_kinds: Sequence[str],
        acc_dtypes: Sequence[np.dtype],
        cap: int = 65536,
        batch_cap: int = 8192,
        max_probes: int = 64,
        emit_cap: int = 8192,
        backend: str = "jax",
    ):
        self.acc_kinds = tuple(acc_kinds)
        self.acc_dtypes = tuple(np.dtype(d) for d in acc_dtypes)
        self.cap = cap
        self.batch_cap = batch_cap
        self.max_probes = max_probes
        self.emit_cap = emit_cap
        self.backend = backend
        # the single-buffer packed transport bitcasts float64 -> int64, which
        # TPU x64 emulation cannot compile; float accumulator sets use the
        # unpacked (multi-fetch) extract/scan paths instead
        self._packed_ok = not any(
            np.issubdtype(d, np.floating) for d in self.acc_dtypes
        )
        if backend == "jax":
            (self._step, self._extract, self._scan, self._free,
             self._extract_packed, self._scan_packed) = _build_jax(
                self.acc_kinds, self.acc_dtypes, cap, batch_cap, max_probes, emit_cap
            )
            self.state = self._init_jax_state()
        else:
            self.store: dict[tuple[int, int], list] = {}

    def _unpack(self, arr: np.ndarray) -> tuple[np.ndarray, np.ndarray, list[np.ndarray], int]:
        """Decode one packed extract/scan buffer -> (keys_u64, bins, accs, total)."""
        total, overflow = int(arr[0]), int(arr[1])
        if overflow > 0:
            raise RuntimeError(
                f"device aggregate table overflow ({overflow} entries dropped after "
                f"{self.max_probes} probes; cap={self.cap}) — raise device.table-capacity"
            )
        body = arr[2:].reshape(2 + len(self.acc_dtypes), self.emit_cap)
        cnt = min(total, self.emit_cap)
        keys = body[0, :cnt].copy().view(np.uint64)
        bins = body[1, :cnt].astype(np.int32)
        accs = []
        for i, d in enumerate(self.acc_dtypes):
            lane = body[2 + i, :cnt]
            if np.issubdtype(d, np.floating):
                accs.append(lane.copy().view(np.float64).astype(d))
            else:
                accs.append(lane.astype(d))
        return keys, bins, accs, total

    def _init_jax_state(self):
        import jax.numpy as jnp

        keys = jnp.zeros(self.cap, dtype=jnp.int64)
        bins = jnp.zeros(self.cap, dtype=jnp.int32)
        occ = jnp.zeros(self.cap, dtype=bool)
        accs = tuple(
            jnp.full(self.cap, _identity(k, d), dtype=d)
            for k, d in zip(self.acc_kinds, self.acc_dtypes)
        )
        return (keys, bins, occ, accs, jnp.zeros((), dtype=jnp.int32))

    # ------------------------------------------------------------- update

    def update(self, key_u64: np.ndarray, bins: np.ndarray, vals: Sequence[np.ndarray]) -> None:
        n = len(key_u64)
        if n == 0:
            return
        if self.backend == "numpy":
            self._update_numpy(key_u64, bins, vals)
            return
        for lo in range(0, n, self.batch_cap):
            hi = min(lo + self.batch_cap, n)
            self._update_chunk(key_u64[lo:hi], bins[lo:hi], [v[lo:hi] for v in vals])

    def _update_chunk(self, key_u64, bins, vals) -> None:
        m = len(key_u64)
        B = self.batch_cap
        key = np.zeros(B, dtype=np.int64)
        key[:m] = key_u64.astype(np.uint64).view(np.int64)
        b = np.zeros(B, dtype=np.int32)
        b[:m] = bins
        valid = np.zeros(B, dtype=bool)
        valid[:m] = True
        vs = []
        for v, dt in zip(vals, self.acc_dtypes):
            arr = np.zeros(B, dtype=dt)
            arr[:m] = v
            vs.append(arr)
        self.state = self._step(self.state, key, b, valid, tuple(vs))

    def _check_overflow(self) -> None:
        overflow = int(self.state[4])
        if overflow > 0:
            raise RuntimeError(
                f"device aggregate table overflow ({overflow} entries dropped after "
                f"{self.max_probes} probes; cap={self.cap}) — raise device.table-capacity"
            )

    def _update_numpy(self, key_u64, bins, vals) -> None:
        signed = key_u64.astype(np.uint64).view(np.int64)
        order = np.lexsort((signed, bins))
        k_s, b_s = signed[order], np.asarray(bins)[order]
        vs = [np.asarray(v)[order] for v in vals]
        newseg = np.ones(len(k_s), dtype=bool)
        newseg[1:] = (k_s[1:] != k_s[:-1]) | (b_s[1:] != b_s[:-1])
        starts = np.flatnonzero(newseg)
        ends = np.append(starts[1:], len(k_s))
        # groups enter the store in the order their first rows came (the
        # sort is stable), so it reads the same however the stream was cut
        # into calls
        arrival = np.argsort(order[starts], kind="stable")
        for s, e in zip(starts[arrival], ends[arrival]):
            kk = (int(b_s[s]), int(k_s[s]))
            cur = self.store.get(kk)
            parts = []
            for i, kind in enumerate(self.acc_kinds):
                seg = vs[i][s:e]
                red = seg.sum() if kind in ("sum", "count") else (seg.min() if kind == "min" else seg.max())
                if cur is not None:
                    red = (
                        cur[i] + red
                        if kind in ("sum", "count")
                        else (min(cur[i], red) if kind == "min" else max(cur[i], red))
                    )
                parts.append(self.acc_dtypes[i].type(red))
            self.store[kk] = parts

    # ------------------------------------------------------------- extract

    def extract(
        self, emit_lo: int, emit_hi: int, free_below: int
    ) -> tuple[np.ndarray, np.ndarray, list[np.ndarray]]:
        """Returns (key_u64, bin, acc_arrays) for bins in [emit_lo, emit_hi);
        frees all entries with bin < free_below. Host loops until drained."""
        if self.backend == "numpy":
            return self._extract_numpy(emit_lo, emit_hi, free_below)
        return self.extract_start(emit_lo, emit_hi, free_below).result()

    def _extract_unpacked(self, emit_lo: int, emit_hi: int, free_below: int):
        """Synchronous extract via the typed (non-packed) device path — used
        for float accumulator sets, where the packed int64 transport's
        float64 bitcast does not compile under TPU x64 emulation."""

        def round_():
            self.state, (k, b, valid, accs, total) = self._extract(
                self.state, np.int32(emit_lo), np.int32(emit_hi), np.int32(free_below)
            )
            valid = np.asarray(valid)
            return (
                np.asarray(k)[valid].view(np.uint64),
                np.asarray(b)[valid],
                [np.asarray(a)[valid] for a in accs],
                int(total),
            )

        out = _drain_extract_rounds(self, round_(), round_, emit_lo, free_below)
        self._check_overflow()
        return out

    def extract_start(self, emit_lo: int, emit_hi: int, free_below: int) -> ExtractHandle:
        """Dispatch a window-close extraction without blocking: the device
        compacts + frees immediately, the packed result streams to host in
        the background. The caller emits later via handle.result()."""
        if not self._packed_ok:
            return ReadyHandle(self._extract_unpacked(emit_lo, emit_hi, free_below))
        self.state, packed = self._extract_packed(
            self.state, np.int32(emit_lo), np.int32(emit_hi), np.int32(free_below)
        )
        packed.copy_to_host_async()
        return ExtractHandle(self, packed, emit_lo, emit_hi, free_below)

    def scan_range(self, emit_lo: int, emit_hi: int) -> tuple[np.ndarray, np.ndarray, list[np.ndarray]]:
        """Non-destructive read of every entry with bin in [emit_lo, emit_hi)
        — the sliding-window combine path (a bin participates in width/slide
        windows, so reads must not free)."""
        if self.backend == "numpy":
            ks, bs, accs = [], [], [[] for _ in self.acc_kinds]
            for (b, k), parts in self.store.items():
                if emit_lo <= b < emit_hi:
                    ks.append(k)
                    bs.append(b)
                    for i, p in enumerate(parts):
                        accs[i].append(p)
            return (
                np.array(ks, dtype=np.int64).view(np.uint64) if ks else np.empty(0, dtype=np.uint64),
                np.array(bs, dtype=np.int32),
                [np.array(a, dtype=d) for a, d in zip(accs, self.acc_dtypes)],
            )
        if self._packed_ok:
            # fast path: one packed transfer covers the whole range
            packed = np.asarray(self._scan_packed(
                self.state, np.int32(emit_lo), np.int32(emit_hi)))
            k, b, accs, total = self._unpack(packed)
            if total <= self.emit_cap:
                return combine_by_key_bin(self.acc_kinds, k, b, accs)
        else:
            self._check_overflow()
        keys_out, bins_out = [], []
        accs_out: list[list[np.ndarray]] = [[] for _ in self.acc_dtypes]
        for chunk in range(0, self.cap, self.emit_cap):
            k, b, valid, accs = self._scan(
                self.state, np.int32(emit_lo), np.int32(emit_hi), np.int32(chunk)
            )
            valid = np.asarray(valid)
            if valid.any():
                keys_out.append(np.asarray(k)[valid])
                bins_out.append(np.asarray(b)[valid])
                for i, a in enumerate(accs):
                    accs_out[i].append(np.asarray(a)[valid])
        if not keys_out:
            return (
                np.empty(0, dtype=np.uint64),
                np.empty(0, dtype=np.int32),
                [np.empty(0, dtype=d) for d in self.acc_dtypes],
            )
        return combine_by_key_bin(
            self.acc_kinds,
            np.concatenate(keys_out).view(np.uint64),
            np.concatenate(bins_out),
            [np.concatenate(a) for a in accs_out],
        )

    def free_bins_below(self, below: int) -> None:
        """Drop all entries with bin < below."""
        if self.backend == "numpy":
            for kk in [kk for kk in self.store if kk[0] < below]:
                del self.store[kk]
            return
        self.state = self._free(self.state, np.int32(below))

    def _extract_numpy(self, emit_lo, emit_hi, free_below):
        ks, bs, accs = [], [], [[] for _ in self.acc_kinds]
        for (b, k), parts in self.store.items():
            if emit_lo <= b < emit_hi:
                ks.append(k)
                bs.append(b)
                for i, p in enumerate(parts):
                    accs[i].append(p)
        for kk in [kk for kk in self.store if kk[0] < free_below]:
            del self.store[kk]
        return (
            np.array(ks, dtype=np.int64).view(np.uint64) if ks else np.empty(0, dtype=np.uint64),
            np.array(bs, dtype=np.int32),
            [np.array(a, dtype=d) for a, d in zip(accs, self.acc_dtypes)],
        )

    # ------------------------------------------------------------- state sync

    def snapshot(self) -> tuple[np.ndarray, np.ndarray, list[np.ndarray]]:
        """Full host copy of live entries (checkpoint path)."""
        if self.backend == "numpy":
            if not self.store:
                return (np.empty(0, dtype=np.uint64), np.empty(0, dtype=np.int32),
                        [np.empty(0, dtype=d) for d in self.acc_dtypes])
            items = list(self.store.items())
            ks = np.array([k for (_, k), _ in items], dtype=np.int64).view(np.uint64)
            bs = np.array([b for (b, _), _ in items], dtype=np.int32)
            accs = [np.array([p[i] for _, p in items], dtype=d)
                    for i, d in enumerate(self.acc_dtypes)]
            return ks, bs, accs
        keys_t, bins_t, occ_t, accs_t, oflow = self.state
        if int(oflow) > 0:
            self._check_overflow()
        occ = np.asarray(occ_t)
        return combine_by_key_bin(
            self.acc_kinds,
            np.asarray(keys_t)[occ].view(np.uint64),
            np.asarray(bins_t)[occ],
            [np.asarray(a)[occ] for a in accs_t],
        )

    def restore(self, key_u64: np.ndarray, bins: np.ndarray, accs: list[np.ndarray]) -> None:
        if self.backend == "numpy":
            signed = key_u64.astype(np.uint64).view(np.int64)
            self.store = {
                (int(bins[j]), int(signed[j])): [
                    self.acc_dtypes[i].type(accs[i][j]) for i in range(len(self.acc_kinds))
                ]
                for j in range(len(signed))
            }
            return
        self.state = self._init_jax_state()
        self.update(key_u64, bins.astype(np.int32), accs)
