"""Keyed windowed aggregation: what the aggregate stores share, the mesh
shard's kernels, and the host store.

This replaces the reference's DataFusion partial/finish aggregate plans
(crates/arroyo-worker/src/arrow/tumbling_aggregating_window.rs:49,
sliding_aggregating_window.rs:45). A store maps (bin, key hash) to one
accumulator a lane; the window operators pick one of three
(windows/tumbling.py make_window_aggregator):

  HostAggregator (here): a dict on the host. The ``numpy`` backend of the
      window operators, the spill tier of the one-chip table, and the
      oracle the device stores' tests compare with.
  SlotAggregator (ops/slot_agg.py): the table of one chip, a host slot
      directory and a scatter-only device step.
  ShardedAggregator (parallel/sharded_agg.py): the table of a mesh, each
      shard a probing hash table fed by ``sort_reduce`` and ``probe_merge``
      below (lexsort and segment-reduce a padded batch to unique (bin, key)
      partials, then merge them with linear probing, empty-slot claims
      resolved by a scatter-max of the contender index) and read through
      ``drain_extract``. Static shapes everywhere: batches padded, table
      capacity and the probe bound fixed at trace time.

``combine_by_key_bin`` / ``combine_by_key`` are the host's merges of what a
store hands back; ``acc_kinds_for`` / ``finalize_aggs`` map SQL aggregates
to accumulators and back.
"""

from __future__ import annotations

from typing import Sequence

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
    """The sharded aggregator's host-side drain loop.
    ``extract_once()`` performs one device extraction and
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


def combine_by_bin(
    acc_kinds: Sequence[str], ts: np.ndarray, bin_micros: int, lanes: list
) -> tuple[np.ndarray, np.ndarray, list[np.ndarray]]:
    """One batch of a keyless aggregate as one partial a bin, before it is
    staged (windows/tumbling.py StagedAggregate._stage_partials): the bin
    division of the event times ``ts`` and the reduce of every lane by bin
    (``lanes[i]``: accumulator i's input a row, None for a ``count``).
    Returns the distinct absolute bins, the rows of each and a lane's partial
    of each. One native call; without the library, or for a batch it does
    not take, numpy's, with the same values."""
    from .. import native

    made = native.bin_combine(ts, bin_micros, acc_kinds, lanes)
    if made is not None:
        return made
    ones = np.ones(len(ts), dtype=np.int64)
    _, bins, accs = combine_by_key_bin(
        tuple(acc_kinds) + ("count",), np.zeros(len(ts), dtype=np.uint64),
        np.asarray(ts) // bin_micros, [ones if v is None else v for v in lanes] + [ones])
    return bins, accs[-1], accs[:-1]


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
# traceable building blocks of the shard_map'd mesh step
# (arroyo_tpu.parallel.sharded_agg)
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


# =========================================================================
# the host store
# =========================================================================


class HostAggregator:
    """Streaming (bin, key) -> accumulators store on the host: a dict, in
    the order its groups first came."""

    def __init__(self, acc_kinds: Sequence[str], acc_dtypes: Sequence[np.dtype]):
        self.acc_kinds = tuple(acc_kinds)
        self.acc_dtypes = tuple(np.dtype(d) for d in acc_dtypes)
        self.store: dict[tuple[int, int], list] = {}

    def update(self, key_u64: np.ndarray, bins: np.ndarray, vals: Sequence[np.ndarray],
               partials: bool = False) -> None:
        # a count's input is its value either way (ones a row): partials
        # merge as rows do
        if len(key_u64) == 0:
            return
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

    def _rows(self, groups) -> tuple[np.ndarray, np.ndarray, list[np.ndarray]]:
        """(key_u64, bin, acc_arrays) of these ``(bin, key)`` groups."""
        return (
            np.array([k for _, k in groups], dtype=np.int64).view(np.uint64),
            np.array([b for b, _ in groups], dtype=np.int32),
            [np.array([self.store[kk][i] for kk in groups], dtype=d)
             for i, d in enumerate(self.acc_dtypes)],
        )

    def extract(
        self, emit_lo: int, emit_hi: int, free_below: int
    ) -> tuple[np.ndarray, np.ndarray, list[np.ndarray]]:
        """Returns (key_u64, bin, acc_arrays) for bins in [emit_lo, emit_hi);
        frees all entries with bin < free_below."""
        out = self.scan_range(emit_lo, emit_hi)
        self.free_bins_below(free_below)
        return out

    def scan_range(self, emit_lo: int, emit_hi: int) -> tuple[np.ndarray, np.ndarray, list[np.ndarray]]:
        """Non-destructive read of every entry with bin in [emit_lo, emit_hi)
        (a sliding window's bin participates in width/slide windows, so
        reads must not free)."""
        return self._rows([kk for kk in self.store if emit_lo <= kk[0] < emit_hi])

    def free_bins_below(self, below: int) -> None:
        """Drop all entries with bin < below."""
        for kk in [kk for kk in self.store if kk[0] < below]:
            del self.store[kk]

    def snapshot(self) -> tuple[np.ndarray, np.ndarray, list[np.ndarray]]:
        """Every live entry (checkpoint path)."""
        return self._rows(list(self.store))

    def restore(self, key_u64: np.ndarray, bins: np.ndarray, accs: list[np.ndarray]) -> None:
        signed = key_u64.astype(np.uint64).view(np.int64)
        self.store = {
            (int(bins[j]), int(signed[j])): [
                self.acc_dtypes[i].type(accs[i][j]) for i in range(len(self.acc_kinds))
            ]
            for j in range(len(signed))
        }
