"""Multi-chip keyed window aggregation: shard_map over a device mesh.

This replaces the reference's repartition shuffle (hash keys -> sort ->
slice per destination -> TCP, crates/arroyo-operator/src/context.rs:502-556 +
arroyo-worker/src/network_manager.rs) with an in-program exchange over ICI:

  per device (shard_map over the "data" mesh axis):
    1. sort_reduce the LOCAL micro-batch -> unique (bin, key) partials
       (pre-aggregation before the wire, like the reference's partial plans)
    2. owner = key-range map (same contiguous u64 ranges as
       arroyo-types/src/lib.rs:621 server_for_hash, so host and device
       agree on ownership)
    3. bucket partials into a fixed [n_dev, per_dest_cap] send buffer
       (sort by owner + rank-in-owner scatter); partials past a
       destination's cap are NOT dropped — they stay resident on the
       producing shard (skew tolerance: window close combines across
       shards on host, so non-owner residency is harmless)
    4. jax.lax.all_to_all over the mesh axis  <- the ICI shuffle
    Each side of the exchange runs at a width the device chooses, a step
    and a shard, off one short ladder of powers of two (_rungs): steps 1-3
    over the first rows of the shard's batch, at the narrowest rung that
    reaches its last valid row (the host deals a step's rows to the front),
    else at the batch's device.batch-capacity rows; steps 5-7 at the
    narrowest rung that holds the rows the exchange brought (the first
    counts[i] of each source's block of the receive buffer, counted on the
    device and brought to the front of a buffer of the rung's width by one
    gather), else, and whenever a row was kept local, at the merged buffer's
    receive buffer + batch (the wide rung). Every rung that holds the rows
    leaves the same state bit for bit; each shard counts the steps it ran
    behind the exchange on the wide one beside its probe rounds.
    5. sort_reduce the received rows (on the wide rung + the kept-local
       overflow) together
    6. probe_merge into this device's HBM hash-table shard: a probe round
       a pass over the rung's rows, until none is left unplaced and for
       device.max-probes rounds at most (each shard its own number: the
       loop holds no collective; the rounds run add up per shard beside
       the overflow counter)
    7. rows the table cannot place (probe exhaustion / table pressure)
       append into a per-shard HBM spill buffer instead of erroring — the
       sharded mirror of the single-chip host-spill tier (SURVEY §7
       hard-part 1)

  The whole thing is ONE jitted XLA program per step: hashing, partials,
  exchange, and state update all fuse; XLA schedules the all_to_all on ICI.
  The overflow counter trips only when even the spill buffer is full.

State layout: every table array gains a leading mesh dimension
[n_dev, cap] sharded on the "data" axis; extraction (window close) is a
per-shard compaction producing [n_dev, emit_cap] outputs, combined with the
spill rows on host.

The host-facing surface (update / extract / extract_start / scan_range /
free_bins_below / snapshot / restore) matches SlotAggregator so window
operators construct either interchangeably (windows/tumbling.py mesh mode).
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence

import numpy as np

from ..obs import trace as _trace
from ..ops.aggregate import (
    _identity,
    combine_by_key_bin,
    drain_extract,
    probe_merge,
    sort_reduce,
)
from .mesh import KEY_AXIS

_U64_MAX = (1 << 64) - 1

# the jax.named_scope of each phase of the shard_map'd step (exchange_merge)
# and of the close's per-shard read (local_extract): part of every device
# operation's name in a profiler trace, nothing else reads them
STEP_PHASES = ("mesh.local_sort_reduce", "mesh.owner_bucket", "mesh.all_to_all",
               "mesh.rung_select", "mesh.front_gather",
               "mesh.merge_sort_reduce", "mesh.probe_merge", "mesh.spill_append")
EXTRACT_PHASES = ("mesh.extract_select", "mesh.extract_gather", "mesh.extract_free")

# process-wide dispatch counters: how many jitted step programs ran, split
# by entry path (a fused step is one program for segment prefix + exchange
# + merge; a host step is one program for exchange + merge with the prefix
# done on host). tests/test_mesh_fused.py and chip_smoke.py hold "one
# jitted call per micro-batch step" to them.
_DISPATCH = {"host_steps": 0, "fused_steps": 0}


def _rungs(blen: int, full: int) -> tuple:
    """The narrow widths a side of the exchange may run at (exchange_merge),
    ascending, each a power of two under ``full``, the side's own width and
    its last rung, which is not in this list. Three, not one a power of two:
    each rung is one more copy of the side's phases for the compiler (a
    narrow copy compiles in a few seconds, the wide one in most of a
    minute). Of a shard's batch of ``blen`` rows: a quarter holds a shard's
    deal of a step the stage fills to ``device.batch-capacity`` rows over
    four shards, and what the exchange brings it of such a step whatever the
    keys; a sixteenth what is left of that once the sort-reduces have merged
    a stream's hot keys (q7's bids: ~290 of 1,900 rows a shard); the batch
    itself a step four times as full."""
    pow2 = (1 << max(w - 1, 0).bit_length() for w in (blen // 16, blen // 4, blen))
    return tuple(sorted({w for w in pow2 if w < full}))


def _rung_of(n, rungs: tuple):
    """The narrowest of the ascending ``rungs`` that holds ``n`` rows (a
    traced count), as its index; ``len(rungs)``, the wide one, if none."""
    import jax.numpy as jnp

    return jnp.sum(n > jnp.asarray(rungs, dtype=jnp.int32), dtype=jnp.int32)


def dispatch_counts() -> dict:
    return dict(_DISPATCH)


def reset_dispatch_counts() -> None:
    for k in _DISPATCH:
        _DISPATCH[k] = 0


class _ReadyHandle:
    """Synchronous stand-in for SlotExtractHandle: the sharded close gathers
    on the spot (the all_to_all path has no per-region async transport yet),
    so the pipelined emission path sees an always-ready handle."""

    program = "jit_local_extract"

    def __init__(self, value):
        self._value = value

    def is_ready(self) -> bool:
        return True

    def result(self):
        return self._value


class ShardedAggregator:
    """Key-space-sharded (bin, key) -> accumulators store over a mesh.

    update_sharded: [n_dev, B]-shaped per-device batches -> one fused step
    (local partials + all_to_all + merge). extract_all: per-shard compaction
    of closed bins, gathered to host. update/extract/snapshot/restore: the
    host-row surface shared with SlotAggregator.
    """

    backend = "jax"

    def __init__(
        self,
        mesh,
        acc_kinds: Sequence[str],
        acc_dtypes: Sequence[np.dtype],
        cap: int = 65536,
        batch_cap: int = 8192,
        per_dest_cap: Optional[int] = None,
        max_probes: int = 64,
        emit_cap: int = 8192,
        spill_cap: int = 2048,
    ):
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as PS

        self.mesh = mesh
        self.n_dev = int(mesh.devices.size)
        self.acc_kinds = tuple(acc_kinds)
        self.acc_dtypes = tuple(np.dtype(d) for d in acc_dtypes)
        self.cap = cap
        self.batch_cap = batch_cap
        # room for skew: by default each destination can receive up to half
        # the local batch from every source shard
        self.per_dest_cap = per_dest_cap or max(batch_cap // max(self.n_dev // 2, 1), 64)
        self.max_probes = max_probes
        self.emit_cap = emit_cap
        self.spill_cap = spill_cap

        n_dev = self.n_dev
        dest_cap = self.per_dest_cap
        acc_kinds_t = self.acc_kinds
        acc_dtypes_t = self.acc_dtypes
        recv_cap = n_dev * dest_cap
        spill_cap_ = spill_cap

        def unpack(state):
            (keys_t, bins_t, occ_t, accs_t, oflow_t,
             sp_key, sp_bin, sp_fill, sp_accs, wide_t, rounds_t) = state
            return (
                keys_t[0], bins_t[0], occ_t[0],
                tuple(a[0] for a in accs_t), oflow_t[0],
                sp_key[0], sp_bin[0], sp_fill[0],
                tuple(a[0] for a in sp_accs), wide_t[0], rounds_t[0],
            )

        def pack(keys_t, bins_t, occ_t, accs_t, oflow_t,
                 sp_key, sp_bin, sp_fill, sp_accs, wide_t, rounds_t):
            return (
                keys_t[None], bins_t[None], occ_t[None],
                tuple(a[None] for a in accs_t), oflow_t[None],
                sp_key[None], sp_bin[None], sp_fill[None],
                tuple(a[None] for a in sp_accs), wide_t[None], rounds_t[None],
            )

        def merge_at(width, table, spill, m_key, m_bin, m_valid, m_accs):
            """Steps 5-7 over a merged buffer of the STATIC ``width``: what
            they leave depends on the buffer's valid rows and their order
            alone (after the sort-reduce the unique rows are the prefix
            [0, n) in (key, bin) order on any width, and the probe's claim
            race and the spill's positions read a row's place in that
            prefix), so every width that holds the rows leaves the same
            state bit for bit. -> (table', spill', rows lost, rounds run)."""
            sp_key, sp_bin, sp_fill, sp_accs = spill
            # --- 5. combine the rows of every source shard
            with jax.named_scope("mesh.merge_sort_reduce"):
                c_key, c_bin, c_active, c_accs = sort_reduce(
                    acc_kinds_t, m_key, m_bin, m_valid, m_accs, width
                )
            # --- 6. merge into the local table shard, in as many probe
            # rounds as this shard's rows need (max_probes at most)
            with jax.named_scope("mesh.probe_merge"):
                table, still_active, rounds = probe_merge(
                    acc_kinds_t, table, c_key, c_bin, c_active, c_accs, cap, max_probes,
                )
            # --- 7. table-pressure spill: unplaced rows append into the
            # per-shard HBM spill buffer; only spill-buffer exhaustion counts
            # as overflow
            with jax.named_scope("mesh.spill_append"):
                sidx = sp_fill + jnp.cumsum(still_active.astype(jnp.int32)) - 1
                ok = still_active & (sidx < spill_cap_)
                pos = jnp.where(ok, sidx, spill_cap_)
                sp_key = sp_key.at[pos].set(c_key, mode="drop")
                sp_bin = sp_bin.at[pos].set(c_bin, mode="drop")
                sp_accs = tuple(
                    sp_accs[i].at[pos].set(c_accs[i], mode="drop")
                    for i in range(len(acc_kinds_t))
                )
                n_spilled = jnp.sum(ok, dtype=jnp.int32)
                n_lost = jnp.sum(still_active, dtype=jnp.int32) - n_spilled
                sp_fill = jnp.minimum(sp_fill + n_spilled, spill_cap_)
            return table, (sp_key, sp_bin, sp_fill, sp_accs), n_lost, rounds

        def bucket_at(width, blen, key, bins, valid, vals):
            """Steps 1-3 over the first ``width`` rows of the shard's batch
            of ``blen`` (both STATIC), which hold every valid row: the send
            buffers, and the partials no lane had room for, padded to
            ``blen``. The sort-reduce leaves the unique rows as the prefix
            [0, n) in (key, bin) order on any width and the owner sort is
            stable, so each row's lane and place in it, and the kept rows'
            order, are the same on every width that holds the rows."""
            key, bins, valid = key[:width], bins[:width], valid[:width]
            vals = tuple(v[:width] for v in vals)
            # --- 1. local pre-aggregation
            with jax.named_scope("mesh.local_sort_reduce"):
                u_key, u_bin, active, u_accs = sort_reduce(
                    acc_kinds_t, key, bins, valid, vals, width
                )
            with jax.named_scope("mesh.owner_bucket"):
                # --- 2. owners via contiguous u64 ranges (matching host
                # servers_for_hashes, including its n == 1 special case —
                # _U64_MAX // 1 + 1 would overflow uint64)
                if n_dev == 1:
                    owner = jnp.zeros(width, dtype=jnp.int32)
                else:
                    range_size = jnp.uint64(_U64_MAX // n_dev + 1)
                    owner = jnp.minimum(
                        u_key.astype(jnp.uint64) // range_size, jnp.uint64(n_dev - 1)
                    ).astype(jnp.int32)
                owner = jnp.where(active, owner, n_dev)  # sentinel sorts last
                # --- 3. bucket into [n_dev * dest_cap] send buffers
                order = jnp.argsort(owner)
                o_s = owner[order]
                starts = jnp.searchsorted(o_s, jnp.arange(n_dev, dtype=jnp.int32))
                rank = jnp.arange(width, dtype=jnp.int32) - starts[
                    jnp.clip(o_s, 0, n_dev - 1)
                ]
                sendable = (o_s < n_dev) & (rank < dest_cap)
                # skew: partials past the destination cap stay LOCAL (merged
                # into this shard's table below); close-time host combine
                # makes non-owner residency correct, so hot keys degrade, not
                # crash
                keep_local = (o_s < n_dev) & (rank >= dest_cap)
                slot = jnp.where(sendable, o_s * dest_cap + rank, recv_cap)

                def scatter(src, fill):
                    buf = jnp.full((recv_cap,), fill, dtype=src.dtype)
                    return buf.at[slot].set(src[order], mode="drop")

                s_key = scatter(u_key, jnp.int64(0))
                s_bin = scatter(u_bin, jnp.int32(0))
                s_valid = jnp.zeros((recv_cap,), dtype=bool).at[slot].set(
                    sendable, mode="drop"
                )
                s_accs = tuple(
                    scatter(u_accs[i],
                            jnp.asarray(_identity(acc_kinds_t[i], acc_dtypes_t[i])))
                    for i in range(len(acc_kinds_t))
                )

                def kept(x):
                    return jnp.pad(x[order], (0, blen - width))

                return ((s_key, s_bin, s_valid, s_accs),
                        (kept(u_key), kept(u_bin), jnp.pad(keep_local, (0, blen - width)),
                         tuple(kept(a) for a in u_accs)))

        def exchange_merge(parts, key, bins, valid, vals, blen):
            """The per-device exchange+merge body (steps 1-7), parametrized
            by the STATIC per-shard row count ``blen`` so the same code
            serves both the host-fed step (blen = batch_cap) and the fused
            segment step (blen = the traced prefix's padded shard length).
            Each side of the exchange runs at a width the device chooses
            from the rows that count, a step and a shard, off one ladder of
            narrow widths (``_rungs``): steps 1-3 (``bucket_at``) at the
            narrowest rung whose first rows hold every valid row of the
            shard's batch, else at ``blen``; steps 5-7 (``merge_at``) at the
            narrowest rung that holds the rows this shard received, which it
            counts after the exchange, else, and whenever a row was kept
            local, at the merged buffer's ``n_dev * per_dest_cap + blen``
            (the wide rung). The choices read the shard's own rows and no
            branch holds a collective, so each shard takes its own rungs.
            ``parts`` is the unpacked (leading-dim-stripped) state tuple;
            returns the updated parts."""
            (keys_t, bins_t, occ_t, accs_t, oflow_t,
             sp_key, sp_bin, sp_fill, sp_accs, wide_t, rounds_t) = parts
            # each phase under its jax.named_scope (STEP_PHASES): the only
            # way to split the step's device time; it changes no operation
            rungs = _rungs(blen, recv_cap + blen)
            fronts = tuple(w for w in rungs if w < blen)
            # --- the width of steps 1-3: the host deals a step's rows to
            # the front of each shard's batch (_distribute), a fused prefix
            # leaves them where its filter did
            with jax.named_scope("mesh.rung_select"):
                n_in = jnp.max(jnp.where(valid, jnp.arange(1, blen + 1, dtype=jnp.int32), 0))
            (s_key, s_bin, s_valid, s_accs), (k_key, k_bin, keep_local, k_accs) = jax.lax.switch(
                _rung_of(n_in, fronts),
                [functools.partial(bucket_at, w, blen) for w in fronts + (blen,)],
                key, bins, valid, vals)

            # --- 4. ICI exchange
            def a2a(x):
                return jax.lax.all_to_all(
                    x.reshape(n_dev, dest_cap, *x.shape[1:]),
                    KEY_AXIS, split_axis=0, concat_axis=0,
                ).reshape(recv_cap, *x.shape[1:])

            with jax.named_scope("mesh.all_to_all"):
                r_key = a2a(s_key)
                r_bin = a2a(s_bin)
                r_valid = a2a(s_valid)
                r_accs = tuple(a2a(a) for a in s_accs)
            # --- the width of steps 5-7: a source fills its block of the
            # receive buffer from the block's start (rank < dest_cap), so the
            # rows that count are the first counts[i] of block i, and under
            # skew the kept-local tail, which only the wide rung reads
            with jax.named_scope("mesh.rung_select"):
                counts = jnp.sum(r_valid.reshape(n_dev, dest_cap), axis=1, dtype=jnp.int32)
                ends = jnp.cumsum(counts)
                n_recv = ends[-1]
                rung = _rung_of(n_recv, rungs)
                kept = keep_local.any()
                rung = jnp.where(kept, len(rungs), rung)
                # a step on no rows (warm's) moves no counter
                took_wide = (rung == len(rungs)) & (kept | (n_recv > 0))

            def narrow(width, table, spill):
                # the valid rows to the front of a buffer of ``width``, by a
                # gather of that width: output row j lies in the block whose
                # cumulated count first passes j
                with jax.named_scope("mesh.front_gather"):
                    j = jnp.arange(width, dtype=jnp.int32)
                    blk = jnp.minimum(
                        jnp.sum(j[:, None] >= ends[None, :], axis=1, dtype=jnp.int32),
                        n_dev - 1)
                    src = jnp.where(
                        j < n_recv, blk * dest_cap + j - (ends - counts)[blk], 0)
                    m_key, m_bin = r_key[src], r_bin[src]
                    m_accs = tuple(a[src] for a in r_accs)
                return merge_at(width, table, spill, m_key, m_bin, j < n_recv, m_accs)

            def wide(table, spill):
                # the received rows + the kept-local overflow together
                with jax.named_scope("mesh.merge_sort_reduce"):
                    m_key = jnp.concatenate([r_key, k_key])
                    m_bin = jnp.concatenate([r_bin, k_bin])
                    m_valid = jnp.concatenate([r_valid, keep_local])
                    m_accs = tuple(jnp.concatenate(pair) for pair in zip(r_accs, k_accs))
                return merge_at(recv_cap + blen, table, spill, m_key, m_bin, m_valid, m_accs)

            ((keys_t, bins_t, occ_t, accs_t), (sp_key, sp_bin, sp_fill, sp_accs),
             n_lost, rounds) = jax.lax.switch(
                rung, [functools.partial(narrow, w) for w in rungs] + [wide],
                (keys_t, bins_t, occ_t, accs_t), (sp_key, sp_bin, sp_fill, sp_accs))
            return (keys_t, bins_t, occ_t, accs_t, oflow_t + n_lost,
                    sp_key, sp_bin, sp_fill, sp_accs,
                    wide_t + took_wide.astype(jnp.int32), rounds_t + rounds)

        def local_step(state, key, bins, valid, vals):
            """Per-device body under shard_map (leading mesh dim is 1)."""
            parts = unpack(state)
            key, bins, valid = key[0], bins[0], valid[0]
            vals = tuple(v[0] for v in vals)
            return pack(*exchange_merge(parts, key, bins, valid, vals,
                                        batch_cap))

        def spec_state():
            return (
                PS(KEY_AXIS, None), PS(KEY_AXIS, None), PS(KEY_AXIS, None),
                tuple(PS(KEY_AXIS, None) for _ in self.acc_kinds), PS(KEY_AXIS),
                PS(KEY_AXIS, None), PS(KEY_AXIS, None), PS(KEY_AXIS),
                tuple(PS(KEY_AXIS, None) for _ in self.acc_kinds), PS(KEY_AXIS),
                PS(KEY_AXIS),
            )

        spec_batch = PS(KEY_AXIS, None)
        self._step = jax.jit(
            jax.shard_map(
                local_step, mesh=mesh,
                in_specs=(spec_state(), spec_batch, spec_batch, spec_batch,
                          tuple(spec_batch for _ in self.acc_kinds)),
                out_specs=spec_state(),
            ),
            donate_argnums=0,
        )
        # fused-segment hook points (fused_step): the exchange+merge body,
        # the state (un)packers, and the state/batch specs
        self._exchange_merge = exchange_merge
        self._unpack = unpack
        self._pack = pack
        self._spec_state = spec_state
        self._spec_batch = spec_batch
        # observability (mesh_stats -> arroyo_mesh_* series): rows fed
        # through the keyed exchange, and the current spill-buffer residency
        # (refreshed opportunistically wherever sp_fill is already on host —
        # never a dedicated device sync)
        self.exchange_rows = 0
        self.overflow_rows = 0
        # steps this store ran, by entry path (mesh_stats; _DISPATCH is the
        # process's), and the inbox batches the next step is made of, as a
        # window operator that staged several says before it calls update
        # (agg.dispatch's ``batches``, as SlotAggregator's)
        self.host_steps = 0
        self.fused_steps = 0
        self.staged_batches = 1
        self._lane_bytes = sum(d.itemsize for d in self.acc_dtypes)
        # slots occupied over all shards when the last close began
        self.live_at_extract: Optional[int] = None
        # probe rounds the steps ran (_count_rounds): the sum over the
        # reads, the steps those reads covered, and the shards' sums as the
        # last read found them
        self.probe_rounds = 0
        self.probe_steps = 0
        self._rounds_read = np.zeros(self.n_dev, dtype=np.int32)
        # the steps of those that ran behind the exchange at a narrow rung
        # on every shard, read with the rounds from the shards' counts of
        # the steps that took the wide one
        self.narrow_steps = 0
        self._wide_read = np.zeros(self.n_dev, dtype=np.int32)
        # (rounds, narrow steps) as the last close's read added them
        self.counted_at_extract: Optional[tuple] = None

        emit_cap_ = self.emit_cap

        def local_extract(state, emit_lo, emit_hi, free_below):
            (keys_t, bins_t, occ_t, accs_t, oflow_t,
             sp_key, sp_bin, sp_fill, sp_accs, wide_t, rounds_t) = unpack(state)
            # the shard's occupied slots as the close finds them (agg.close's
            # ``live``): counted here, because a read of the table from the
            # host would have to land before the extraction could be queued
            live = jnp.sum(occ_t, dtype=jnp.int32)
            with jax.named_scope("mesh.extract_select"):
                emit_mask = occ_t & (bins_t >= emit_lo) & (bins_t < emit_hi)
                total = jnp.sum(emit_mask, dtype=jnp.int32)
                order = jnp.argsort(~emit_mask)
                sel = order[:emit_cap_]
            with jax.named_scope("mesh.extract_gather"):
                out_valid = emit_mask[sel]
                out_key = keys_t[sel]
                out_bin = bins_t[sel]
                out_accs = tuple(a[sel] for a in accs_t)
            with jax.named_scope("mesh.extract_free"):
                free_mask = occ_t & (bins_t < free_below) & ~emit_mask
                emitted_free = out_valid & (out_bin < free_below)
                occ_t = occ_t & ~free_mask
                occ_t = occ_t.at[jnp.where(emitted_free, sel, cap)].set(
                    False, mode="drop")
            return (
                pack(keys_t, bins_t, occ_t, accs_t, oflow_t,
                     sp_key, sp_bin, sp_fill, sp_accs, wide_t, rounds_t),
                (out_key[None], out_bin[None], out_valid[None],
                 tuple(a[None] for a in out_accs), total[None], live[None]),
            )

        spec_out = (
            PS(KEY_AXIS, None), PS(KEY_AXIS, None), PS(KEY_AXIS, None),
            tuple(PS(KEY_AXIS, None) for _ in self.acc_kinds), PS(KEY_AXIS),
            PS(KEY_AXIS),
        )
        self._extract = jax.jit(
            jax.shard_map(
                local_extract, mesh=mesh,
                in_specs=(spec_state(), PS(), PS(), PS()),
                out_specs=(spec_state(), spec_out),
            ),
            donate_argnums=0,
        )
        self.state = self._init_state()

    def _init_state(self):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as PS

        shard = NamedSharding(self.mesh, PS(KEY_AXIS, None))
        shard1 = NamedSharding(self.mesh, PS(KEY_AXIS))
        n, cap, sc = self.n_dev, self.cap, self.spill_cap
        return (
            jax.device_put(jnp.zeros((n, cap), dtype=jnp.int64), shard),
            jax.device_put(jnp.zeros((n, cap), dtype=jnp.int32), shard),
            jax.device_put(jnp.zeros((n, cap), dtype=bool), shard),
            tuple(
                jax.device_put(jnp.full((n, cap), _identity(k, d), dtype=d), shard)
                for k, d in zip(self.acc_kinds, self.acc_dtypes)
            ),
            jax.device_put(jnp.zeros((n,), dtype=jnp.int32), shard1),
            jax.device_put(jnp.zeros((n, sc), dtype=jnp.int64), shard),
            jax.device_put(jnp.zeros((n, sc), dtype=jnp.int32), shard),
            jax.device_put(jnp.zeros((n,), dtype=jnp.int32), shard1),
            tuple(
                jax.device_put(jnp.full((n, sc), _identity(k, d), dtype=d), shard)
                for k, d in zip(self.acc_kinds, self.acc_dtypes)
            ),
            jax.device_put(jnp.zeros((n,), dtype=jnp.int32), shard1),
            jax.device_put(jnp.zeros((n,), dtype=jnp.int32), shard1),
        )

    def warm(self) -> None:
        """Run the step and the extraction once on no rows (every row
        invalid, an empty range of bins): the state is left as it is, no
        probe round runs, no counter moves, and the two programs are
        compiled when this returns (windows/tumbling.py prepare)."""
        shape = (self.n_dev, self.batch_cap)
        self.state = self._step(
            self.state, np.zeros(shape, np.int64), np.zeros(shape, np.int32),
            np.zeros(shape, bool),
            tuple(np.full(shape, _identity(k, d), dtype=d)
                  for k, d in zip(self.acc_kinds, self.acc_dtypes)))
        self.state, _none = self._extract(self.state, np.int32(0), np.int32(0), np.int32(0))

    # ------------------------------------------------------- sharded surface

    def update_sharded(self, key_i64, bins, valid, vals) -> None:
        """key_i64/bins/valid: [n_dev, batch_cap] (device-local rows);
        vals: one [n_dev, batch_cap] array per accumulator."""
        _DISPATCH["host_steps"] += 1
        self.host_steps += 1
        self.state = self._step(self.state, key_i64, bins, valid, tuple(vals))

    # ------------------------------------------------------- fused segments

    def fused_step(self, prefix_fn, n_inputs: int, n_aux: int):
        """Build ONE shard_map'd jitted program fusing a traced segment
        prefix (engine/segment.py mesh path) with this store's exchange+
        merge: per-shard projection/key-hash -> owner bucketing ->
        all_to_all -> sort_reduce/probe_merge, with no host round trip
        between projection and state update.

        ``prefix_fn(arrays, valid, base_bin, ontime) -> (key_i64, bins_i32,
        insert_valid, vals_tuple, aux_tuple)`` runs per shard on
        [P_dev]-length arrays (``n_inputs`` of them); ``aux_tuple`` is a
        flat tuple of ``n_aux`` scalars (watermark max/count pairs over
        PRE-late rows). Row validity (padding tail) is computed HERE from
        the global row count so the prefix stays mesh-agnostic.

        Returns ``step(state, n, base_bin, ontime2d, *arrays2d) ->
        (state', aux_shards)`` — jitted, state donated, aux gathered as
        one [n_dev] array per scalar. The caller runs it via
        ``update_fused`` so counters stay correct.
        """
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as PS

        exchange = self._exchange_merge
        unpack, pack = self._unpack, self._pack

        def local(state, n, base_bin, ontime, *arrays):
            parts = unpack(state)
            ontime = ontime[0]
            arrays = tuple(a[0] for a in arrays)
            pd = ontime.shape[0]
            # this shard owns global rows [d*pd, (d+1)*pd); rows >= n are
            # the padding tail (dtype pinned: LR304)
            row0 = jax.lax.axis_index(KEY_AXIS).astype(jnp.int64) * pd
            valid = (row0 + jnp.arange(pd, dtype=jnp.int64)) < n
            key_i64, bins, ins_valid, vals, aux = prefix_fn(
                arrays, valid, base_bin, ontime)
            parts = exchange(parts, key_i64, bins, ins_valid, vals, pd)
            return pack(*parts), tuple(jnp.asarray(a)[None] for a in aux)

        sb = self._spec_batch
        step = jax.jit(
            jax.shard_map(
                local, mesh=self.mesh,
                in_specs=(self._spec_state(), PS(), PS(), sb)
                + tuple(sb for _ in range(n_inputs)),
                out_specs=(self._spec_state(),
                           tuple(PS(KEY_AXIS) for _ in range(n_aux))),
            ),
            donate_argnums=0,
        )
        return step

    def update_fused(self, step, n: int, base_bin: int, ontime, arrays):
        """Run one fused segment+exchange program built by ``fused_step``;
        ``ontime``/``arrays`` are [n_dev, P_dev]-shaped. Returns the
        per-shard aux arrays ([n_dev] each, host numpy)."""
        _DISPATCH["fused_steps"] += 1
        self.fused_steps += 1
        self.exchange_rows += int(n)
        with _trace.step_dispatched(n, 1, self.n_dev, int(np.size(ontime)),
                                    self._lane_bytes):
            self.state, aux = step(self.state, np.int64(n), np.int64(base_bin),
                                   ontime, *arrays)
        return [np.asarray(a) for a in aux]

    def mesh_stats(self) -> dict:
        """Counters behind the arroyo_mesh_* series and ``explain``'s
        ``mesh:`` line (obs/profile.py reads this through the operator's
        mesh_stats hook). ``overflow_rows`` is the spill buffers' fill, as
        the last close or snapshot found it; ``probe_rounds`` the rounds of
        probe_merge's loop that ``probe_steps`` of the steps ran (the steps
        before the last close or snapshot), of ``max_probes`` a step at
        most; ``narrow_steps`` those of ``probe_steps`` whose sort-reduce,
        probe and spill append behind the exchange ran at a narrow rung of
        ``_rungs`` on every shard, and not at the merged buffer's width."""
        return {"exchange_rows": self.exchange_rows,
                "overflow_rows": self.overflow_rows,
                "shards": self.n_dev,
                "host_steps": self.host_steps,
                "fused_steps": self.fused_steps,
                "probe_rounds": self.probe_rounds,
                "probe_steps": self.probe_steps,
                "narrow_steps": self.narrow_steps,
                "max_probes": self.max_probes}

    def _count_rounds(self) -> tuple:
        """Read the shards' probe-round sums and their counts of steps that
        took the wide rung, and return what the steps since the last read
        added: the rounds on the shard that ran the most (a step is as long
        as its slowest shard's loop), and the steps that no shard ran wide
        (all of them less the count of the shard that took the wide rung
        most often). A read of device state: only where the state has landed
        anyway, after a close's extraction or in a snapshot, never before an
        extraction is queued (a wait on the host there lets another
        aggregate's steps into the device's queue ahead of the close). The
        device's int32 sums may wrap; their differences do not."""
        now, wide = np.asarray(self.state[-1]), np.asarray(self.state[-2])
        new = int((now - self._rounds_read).max())
        steps = self.host_steps + self.fused_steps
        narrow = max(steps - self.probe_steps - int((wide - self._wide_read).max()), 0)
        self._rounds_read, self._wide_read = now, wide
        self.probe_rounds += new
        self.narrow_steps += narrow
        self.probe_steps = steps
        return new, narrow

    def _drain_spill(self, emit_lo: int, emit_hi: int, free_below: int):
        """Host-side spill-buffer drain: gather the (small) per-shard spill
        arrays, emit rows in range, drop rows below free_below, write the
        compacted remainder back (sharded)."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as PS

        (keys_t, bins_t, occ_t, accs_t, oflow_t,
         sp_key, sp_bin, sp_fill, sp_accs, wide_t, rounds_t) = self.state
        fill = np.asarray(sp_fill)
        if int(fill.sum()) == 0:
            self.overflow_rows = 0
            return (np.empty(0, dtype=np.uint64), np.empty(0, dtype=np.int32),
                    [np.empty(0, dtype=d) for d in self.acc_dtypes])
        k = np.asarray(sp_key)
        b = np.asarray(sp_bin)
        accs = [np.asarray(a) for a in sp_accs]
        n, sc = self.n_dev, self.spill_cap
        in_fill = np.arange(sc)[None, :] < fill[:, None]
        emit = in_fill & (b >= emit_lo) & (b < emit_hi)
        keep = in_fill & ~(b < free_below)
        out = (k[emit].view(np.uint64), b[emit].astype(np.int32),
               [a[emit] for a in accs])
        # compact kept rows per shard and write back
        new_k = np.zeros((n, sc), dtype=np.int64)
        new_b = np.zeros((n, sc), dtype=np.int32)
        new_accs = [np.full((n, sc), _identity(kk, d), dtype=d)
                    for kk, d in zip(self.acc_kinds, self.acc_dtypes)]
        new_fill = np.zeros(n, dtype=np.int32)
        for d_i in range(n):
            sel = np.flatnonzero(keep[d_i])
            m = len(sel)
            new_fill[d_i] = m
            new_k[d_i, :m] = k[d_i, sel]
            new_b[d_i, :m] = b[d_i, sel]
            for j in range(len(accs)):
                new_accs[j][d_i, :m] = accs[j][d_i, sel]
        self.overflow_rows = int(new_fill.sum())
        shard = NamedSharding(self.mesh, PS(KEY_AXIS, None))
        shard1 = NamedSharding(self.mesh, PS(KEY_AXIS))
        self.state = (
            keys_t, bins_t, occ_t, accs_t, oflow_t,
            jax.device_put(new_k, shard),
            jax.device_put(new_b, shard),
            jax.device_put(new_fill, shard1),
            tuple(jax.device_put(a, shard) for a in new_accs),
            wide_t, rounds_t,
        )
        return out

    def extract_all(self, emit_lo: int, emit_hi: int, free_below: int):
        """Close bins across all shards; returns host (key_u64, bin, accs).
        Drains per emit_cap chunk until every shard is empty; shard outputs
        are [n_dev, emit_cap] and flattened before the shared drain logic.
        Spill-buffer rows for the range are combined in on host."""
        import jax

        self.live_at_extract = None

        def extract_once():
            self.state, outs = self._extract(
                self.state, np.int32(emit_lo), np.int32(emit_hi), np.int32(free_below)
            )
            # queued; the task now waits behind every step it has run ahead
            # of the device until the extraction's outputs have landed
            with _trace.wait(_trace.DEVICE_WAIT, "agg.fetch", program="jit_local_extract"):
                jax.block_until_ready(outs)  # lint: waive LR104 — a close gathers on the spot (A5); this is its wait, named
            k, b, v, accs, total, live = outs
            if self.live_at_extract is None:  # as the first round found the table
                self.live_at_extract = int(np.asarray(live).sum())
            return (
                np.asarray(k).reshape(-1),
                np.asarray(b).reshape(-1),
                np.asarray(v).reshape(-1),
                [np.asarray(a).reshape(-1) for a in accs],
                int(np.asarray(total).max()),
            )

        out = drain_extract(extract_once, self.emit_cap, self.acc_kinds,
                            self.acc_dtypes, emit_lo, free_below)
        sk, sb, saccs = self._drain_spill(emit_lo, emit_hi, free_below)
        if len(sk):
            out = combine_by_key_bin(
                self.acc_kinds,
                np.concatenate([out[0], sk]),
                np.concatenate([out[1], sb]),
                [np.concatenate([a, s]) for a, s in zip(out[2], saccs)],
            )
        self.counted_at_extract = self._count_rounds()
        overflow = int(np.asarray(self.state[4]).sum())
        if overflow > 0:
            raise RuntimeError(
                f"sharded aggregate overflow ({overflow} entries lost: table and "
                f"spill buffer both full) — raise table capacity or spill_cap"
            )
        return out

    # ---------------------------------------------------- SlotAggregator API

    def _distribute(self, key_i64, bins, vals):
        """Round-robin host rows into [n_dev, batch_cap] chunks (initial
        placement is arbitrary — the in-program all_to_all re-routes by key
        ownership, like the reference's source->shuffle edge)."""
        n = len(key_i64)
        n_dev, B = self.n_dev, self.batch_cap
        per_step = n_dev * B
        for lo in range(0, n, per_step):
            hi = min(lo + per_step, n)
            m = hi - lo
            k = np.zeros((n_dev, B), dtype=np.int64)
            b = np.zeros((n_dev, B), dtype=np.int32)
            valid = np.zeros((n_dev, B), dtype=bool)
            vs = [np.full((n_dev, B), _identity(kk, d), dtype=d)
                  for kk, d in zip(self.acc_kinds, self.acc_dtypes)]
            rows = np.arange(lo, hi)
            dev = (rows - lo) % n_dev
            pos = (rows - lo) // n_dev
            k[dev, pos] = key_i64[lo:hi]
            b[dev, pos] = bins[lo:hi]
            valid[dev, pos] = True
            for j, v in enumerate(vals):
                vs[j][dev, pos] = v[lo:hi]
            yield m, k, b, valid, vs

    def update(self, key_u64, bins, vals, partials: bool = False) -> None:
        # the step adds a count lane's values (ones a row), so partials (a
        # restore's; a keyless stage keeps the rows on a mesh) merge as rows do
        self.exchange_rows += len(key_u64)
        key_i64 = np.ascontiguousarray(key_u64, dtype=np.uint64).view(np.int64)
        bins = np.asarray(bins, dtype=np.int32)
        vals = [np.asarray(v, dtype=d) for v, d in zip(vals, self.acc_dtypes)]
        room = self.n_dev * self.batch_cap
        for m, k, b, valid, vs in self._distribute(key_i64, bins, vals):
            # agg.dispatch: the call returns once the runtime has queued the
            # step; the host runs ahead of a mesh that sets the pace, and the
            # task waits for the device in its next close or snapshot
            with _trace.step_dispatched(m, self.staged_batches, self.n_dev, room,
                                        self._lane_bytes):
                self.update_sharded(k, b, valid, vs)
            self.staged_batches = 1

    def extract(self, emit_lo: int, emit_hi: int, free_below: int):
        return self.extract_start(emit_lo, emit_hi, free_below).result()

    def extract_start(self, emit_lo: int, emit_hi: int, free_below: int):
        # agg.close: the whole synchronous gather (the per-shard reads, the
        # spill buffers' drain, the combine on the host); trace_id is the
        # window operator's (trace.window). The table is fullest here,
        # before the closing bins free their slots
        with _trace.span("agg.close") as close:
            out = self.extract_all(emit_lo, emit_hi, free_below)
            close.note(rows=len(out[0]))
            _trace.table_state(close, self.n_dev * self.cap, self.live_at_extract,
                               *self.counted_at_extract)
        return _ReadyHandle(out)

    def free_bins_below(self, below: int) -> None:
        # empty emit range: frees every table + spill row with bin < below
        self.extract_all(below, below, below)

    def scan_range(self, emit_lo: int, emit_hi: int):
        k, b, accs = self.snapshot()
        sel = (b >= emit_lo) & (b < emit_hi)
        return k[sel], b[sel], [a[sel] for a in accs]

    def snapshot(self):
        """Exact non-destructive state readout: gather the sharded table +
        spill buffers and combine on host (checkpoint path; off the hot
        loop, so a full [n_dev, cap] gather is acceptable)."""
        with _trace.span("agg.snapshot") as snap:
            out, live = self._snapshot()
            snap.note(rows=len(out[0]))
            _trace.table_state(snap, self.n_dev * self.cap, live, *self._count_rounds())
        return out

    def _snapshot(self):
        """-> (the combined rows, the slots occupied over all shards)."""
        import jax

        # a snapshot reads the state itself, the output of the last step
        # the task has queued: that is what it waits for
        with _trace.wait(_trace.DEVICE_WAIT, "agg.fetch", program="jit_local_step"):
            jax.block_until_ready(self.state)  # lint: waive LR104 — a snapshot reads the state; this is its wait, named
        (keys_t, bins_t, occ_t, accs_t, _oflow_t,
         sp_key, sp_bin, sp_fill, sp_accs, _wide_t, _rounds_t) = self.state
        occ = np.asarray(occ_t)
        live = int(np.count_nonzero(occ))
        keys = np.asarray(keys_t)[occ].view(np.uint64)
        bins = np.asarray(bins_t)[occ].astype(np.int32)
        accs = [np.asarray(a)[occ] for a in accs_t]
        fill = np.asarray(sp_fill)
        self.overflow_rows = int(fill.sum())
        if int(fill.sum()):
            in_fill = np.arange(self.spill_cap)[None, :] < fill[:, None]
            keys = np.concatenate([keys, np.asarray(sp_key)[in_fill].view(np.uint64)])
            bins = np.concatenate([bins, np.asarray(sp_bin)[in_fill].astype(np.int32)])
            accs = [np.concatenate([a, np.asarray(s)[in_fill]])
                    for a, s in zip(accs, sp_accs)]
        if not len(keys):
            return (np.empty(0, dtype=np.uint64), np.empty(0, dtype=np.int32),
                    [np.empty(0, dtype=d) for d in self.acc_dtypes]), live
        return combine_by_key_bin(self.acc_kinds, keys, bins, accs), live

    def restore(self, key_u64, bins, accs) -> None:
        """Merge snapshotted partials back in: the sharded kernel combines
        count like sum (partials arrive as values), so update() is the
        correct merge path — unlike SlotAggregator's constant-increment hot
        step, no separate merge mode is needed."""
        self.state = self._init_state()
        self._rounds_read = np.zeros(self.n_dev, dtype=np.int32)
        self._wide_read = np.zeros(self.n_dev, dtype=np.int32)
        self.update(np.asarray(key_u64, dtype=np.uint64),
                    np.asarray(bins, dtype=np.int32), accs)
