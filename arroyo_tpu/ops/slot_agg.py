"""Slot-directory windowed aggregation: scatter-only device path.

On a TPU a dynamic gather is the slow XLA primitive and a scatter with a
combiner is a cheap one, so no (bin, key) is probed for on the device: the
work is split by what each side is good at:

  host (the directory: two passes of the C++ runtime a step, numpy without it):
      (bin, key) -> device slot assignment. Slots live in fixed-size
      REGIONS; each window bin owns a chain of regions, so a window close
      maps to contiguous device slices, never a table compaction. The
      directory is open-addressing over 64-bit mixed codes with monotone
      bin-boundary liveness (window close is always "bin < boundary", so
      dead entries need no tombstones).

  device (one jitted step per operator config):
      state = one [cap] array per accumulator, nothing else in HBM.
      update = n_acc scatter-combines (.at[slots].add/min/max) — no gather,
      no sort, no probe loop. Window close = dynamic_slice of the closing
      bin's regions packed into ONE int64 buffer (single host round trip,
      fetched asynchronously), plus a dynamic_update_slice clear.

  growth: when every region is in use the table doubles (SlotAggregator.
      _grow): the directory gains regions in place, the device state is
      padded with each lane's identity, the programs of the new capacity
      are built and run once on scratch arrays, and the batch is resolved
      again. Slots that are assigned keep their numbers, so closes already
      in flight are untouched. `device.table-capacity` is the size a table
      starts at.

  spill tier, the last resort: past the ceiling (a stated share of the
      device's memory, _TABLE_MEMORY_SHARE) new (bin, key) groups aggregate
      into a host dict store (ops/aggregate.py HostAggregator) instead of
      erroring — the overflow-to-host policy SURVEY.md hard-part #1 calls
      for.

Reference behavior being replaced: the per-bin DataFusion partial
aggregation plans of crates/arroyo-worker/src/arrow/
tumbling_aggregating_window.rs:49 and sliding_aggregating_window.rs:45.
"""

from __future__ import annotations

import functools
import os
import time
from typing import Optional, Sequence

import numpy as np

from ..hashing import splitmix64
from ..obs import trace as _trace
from .aggregate import (
    _I32_MAX,
    HostAggregator,
    _identity,
    combine_by_key_bin,
)

_BIN_MIX = np.uint64(0x9E3779B97F4A7C15)
_DEAD_BIN = -(2**62)
# the share of the device's memory (memory_stats()["bytes_limit"]) that one
# table's accumulator lanes may take, all lanes counted: a growth holds the
# old state, the new one and the warm-up's scratch at once (three to four
# tables), and a job keeps several tables on one chip
_TABLE_MEMORY_SHARE = 1 / 16
# what a backend that reports no limit (the CPU's) is taken to have
_UNREPORTED_MEMORY_BYTES = 1 << 32
# the host pays more for a slot than the device does: slot_keys and
# slot_bins (16 bytes) and the open-addressing table, four positions a slot
# of code, bin and slot (96 bytes). The directory may take the same share
# of the host's memory as the lanes take of the device's
_DIRECTORY_BYTES_PER_SLOT = 16 + 4 * 24
# region counts one close read is bucketed to (_read_regions)
_READ_BUCKETS = (1, 2, 4, 8, 16)


def _mix(keys_u64: np.ndarray, bins_i64: np.ndarray) -> np.ndarray:
    """The 64-bit code of each (bin, key), as cpp ah_dir_resolve mixes it."""
    return splitmix64(keys_u64 ^ (bins_i64.astype(np.uint64) * _BIN_MIX))


def _host_memory_bytes() -> int:
    try:
        return os.sysconf("SC_PHYS_PAGES") * os.sysconf("SC_PAGE_SIZE")
    except (ValueError, OSError, AttributeError):
        return _UNREPORTED_MEMORY_BYTES


class BinSlotDirectory:
    """Host-side (bin, key) -> device-slot map with region-chained bins.

    With the native library a step is resolved and its first-seen groups
    placed in two calls (cpp ah_dir_resolve, ah_dir_claim; SlotAggregator.
    _resolve_slots), this class keeping the regions (``_alloc_ranges``).
    ``lookup_or_assign`` is the path of a host without the library, and the
    oracle of the tests: probing is vectorized numpy over the batch's unique
    codes, each round gathering one candidate directory row per pending
    code and resolving match / claim / advance, so cost is O(rounds) numpy
    passes, not a Python loop per key."""

    def __init__(self, cap: int, region_size: int):
        assert cap % region_size == 0
        self.cap = cap
        self.R = region_size
        self.n_regions = cap // region_size
        self.free_regions = list(range(self.n_regions - 1, -1, -1))
        self.bin_regions: dict[int, list[int]] = {}
        self.region_fill = np.zeros(self.n_regions, dtype=np.int64)
        self.allocated = 0  # slots handed out so far: a step's difference is its first-seen groups
        # per-slot identity (for emission: device stores only accumulators)
        self.slot_keys = np.zeros(cap, dtype=np.int64)
        self.slot_bins = np.full(cap, _DEAD_BIN, dtype=np.int64)
        self._init_table()
        self.boundary = _DEAD_BIN  # bins below this are closed (monotone)

    def _init_table(self) -> None:
        """The open-addressing directory, empty: mixed code -> slot."""
        self.hcap = 1 << (self.cap.bit_length() + 1)  # ~4x cap
        self.hmask = np.uint64(self.hcap - 1)
        self.hcode = np.zeros(self.hcap, dtype=np.uint64)
        self.hbin = np.full(self.hcap, _DEAD_BIN, dtype=np.int64)
        self.hslot = np.full(self.hcap, -1, dtype=np.int64)

    # ------------------------------------------------------------- growth

    def live_slots(self) -> int:
        """Slots assigned in the bins still open."""
        regs = [r for chain in self.bin_regions.values() for r in chain]
        return int(self.region_fill[regs].sum()) if regs else 0

    def grow(self, cap: int) -> None:
        """Room for ``cap`` slots, in place. The new regions join the free
        list; slots that are assigned keep their numbers; the
        open-addressing table is rebuilt at its new size from the bins
        still open, so closed bins' entries fall out."""
        assert cap % self.R == 0 and cap > self.cap
        n_regions = cap // self.R
        self.free_regions = (list(range(n_regions - 1, self.n_regions - 1, -1))
                             + self.free_regions)
        self.region_fill = np.concatenate(
            [self.region_fill, np.zeros(n_regions - self.n_regions, dtype=np.int64)])
        self.slot_keys = np.concatenate(
            [self.slot_keys, np.zeros(cap - self.cap, dtype=np.int64)])
        self.slot_bins = np.concatenate(
            [self.slot_bins, np.full(cap - self.cap, _DEAD_BIN, dtype=np.int64)])
        self.cap, self.n_regions = cap, n_regions
        self._init_table()
        live = [r * self.R + np.arange(self.region_fill[r], dtype=np.int64)
                for chain in self.bin_regions.values() for r in chain]
        if live:
            self._insert(np.concatenate(live))

    def _insert(self, slots: np.ndarray) -> None:
        """Enter assigned slots into the open-addressing table by the
        identities they hold (linear probing, as lookup_or_assign claims)."""
        keys, bins = self.slot_keys[slots], self.slot_bins[slots]
        codes = _mix(keys.view(np.uint64), bins)
        h = (codes & self.hmask).astype(np.int64)
        pending = np.arange(len(slots))
        while len(pending):
            empty = pending[self.hslot[h[pending]] < 0]
            # several may want one position: the first takes it
            _, first = np.unique(h[empty], return_index=True)
            won = empty[first]
            pos = h[won]
            self.hcode[pos] = codes[won]
            self.hbin[pos] = bins[won]
            self.hslot[pos] = slots[won]
            pending = np.setdiff1d(pending, won, assume_unique=True)
            h[pending] = (h[pending] + 1) & int(self.hmask)

    # ------------------------------------------------------------- alloc

    def _alloc_ranges(self, b: int, n: int) -> list[tuple[int, int]]:
        """Up to n device slots for bin b, chaining regions, as ranges
        (first slot, count) in plain ints; fewer than n in all when no
        region is free (the aggregator then grows the table and asks again
        for the rest; past its ceiling it spills them). The one place that
        knows the region policy."""
        regs = self.bin_regions.get(b)
        if regs is None:
            regs = self.bin_regions[b] = []
        ranges = []
        while n > 0:
            if regs and self.region_fill[regs[-1]] < self.R:
                r = regs[-1]
                fill = int(self.region_fill[r])
                take = min(n, self.R - fill)
                ranges.append((r * self.R + fill, take))
                self.region_fill[r] = fill + take
                self.allocated += take
                n -= take
            elif self.free_regions:
                r = self.free_regions.pop()
                self.region_fill[r] = 0
                regs.append(r)
            else:
                break
        if not regs:
            del self.bin_regions[b]
        return ranges

    def _alloc(self, b: int, n: int) -> np.ndarray:
        """``_alloc_ranges`` slot by slot, for the numpy path."""
        chunks = [np.arange(first, first + take, dtype=np.int64)
                  for first, take in self._alloc_ranges(b, n)]
        if not chunks:
            return np.empty(0, dtype=np.int64)
        return np.concatenate(chunks) if len(chunks) > 1 else chunks[0]

    def live_bins(self) -> list[int]:
        return sorted(self.bin_regions)

    def close_bin(self, b: int) -> list[int]:
        """Release bin b's regions for reuse; returns the region ids (the
        caller must have dispatched the device-side clear first)."""
        regs = self.bin_regions.pop(b, [])
        for r in regs:
            self.free_regions.append(r)
        return regs

    # ------------------------------------------------------------- lookup

    def lookup_or_assign(
        self, codes: np.ndarray, keys: np.ndarray, bins: np.ndarray
    ) -> np.ndarray:
        """codes: unique uint64 mixed (bin,key) codes; keys/bins: the exact
        identities behind each code. Returns int64 slots; -1 = no region
        left (nothing is entered for such a code)."""
        m = len(codes)
        out = np.full(m, -1, dtype=np.int64)
        if m == 0:
            return out
        h = (codes & self.hmask).astype(np.int64)
        pending = np.arange(m)
        spill_blocked = False
        for _ in range(self.hcap):
            if len(pending) == 0:
                break
            hp = h[pending]
            cp = codes[pending]
            hc = self.hcode[hp]
            live = (self.hslot[hp] >= 0) & (self.hbin[hp] >= self.boundary)
            match = live & (hc == cp)
            if match.any():
                mi = pending[match]
                s = self.hslot[h[mi]]
                bad = (self.slot_keys[s] != keys[mi]) | (self.slot_bins[s] != bins[mi])
                if bad.any():
                    raise RuntimeError(
                        "64-bit (bin,key) code collision in slot directory"
                    )
                out[mi] = s
            empty = ~live
            claim = pending[empty]
            if len(claim):
                # claim conflicts within the batch: first code per position
                # wins, the rest advance and keep probing
                hcl = h[claim]
                uniq, first = np.unique(hcl, return_index=True)
                winners = claim[first]
                if not spill_blocked:
                    order = np.argsort(bins[winners], kind="stable")
                    winners_sorted = winners[order]
                    wb = bins[winners_sorted]
                    seg = np.ones(len(wb), dtype=bool)
                    seg[1:] = wb[1:] != wb[:-1]
                    starts = np.flatnonzero(seg)
                    ends = np.append(starts[1:], len(wb))
                    for s0, s1 in zip(starts, ends):
                        grp = winners_sorted[s0:s1]
                        slots = self._alloc(int(wb[s0]), len(grp))
                        if len(slots) < len(grp):
                            spill_blocked = True  # unallocated stay -1
                            grp = grp[: len(slots)]
                        if len(grp) == 0:
                            continue
                        self.slot_keys[slots] = keys[grp]
                        self.slot_bins[slots] = bins[grp]
                        pos = h[grp]
                        self.hcode[pos] = codes[grp]
                        self.hbin[pos] = bins[grp]
                        self.hslot[pos] = slots
                        out[grp] = slots
            # still pending: not matched and not successfully claimed
            resolved = out[pending] >= 0
            give_up = np.zeros(len(pending), dtype=bool)
            if spill_blocked:
                give_up = ~resolved & empty  # nothing left to allocate
            keep = ~resolved & ~give_up
            nxt = pending[keep]
            h[nxt] = (h[nxt] + 1) & int(self.hmask)
            pending = nxt
        return out


class SlotExtractHandle:
    """In-flight window close: per-region packed buffers are streaming to
    host; identities (key hash, bin) were snapshotted host-side at dispatch
    so region reuse can't race the fetch."""

    program = "jit_go"  # what result() waits for (ops/prefetch.py submit)

    def __init__(self, agg: "SlotAggregator", groups, spill, close=_trace.NO_SPAN):
        self._agg = agg
        # the open agg.close span (begun where the close was dispatched)
        # and the task it belongs to: result() may run on a prefetch worker
        self._close = close
        self._lane, self._trace_id = _trace.current_window()
        # groups: list of (regs, int_buf|None, float_buf|None) where regs is
        # [(bin, keys_i64_copy, fill), ...] in buffer order
        self._groups = groups
        self._spill = spill  # (keys_u64, bins_i32, [acc arrays]) or None

    def is_ready(self) -> bool:
        return all(
            (ib is None or ib.is_ready()) and (fb is None or fb.is_ready())
            for (_regs, ib, fb) in self._groups
        )

    def result(self) -> tuple[np.ndarray, np.ndarray, list[np.ndarray]]:
        from .prefetch import wait_buffers_ready

        with _trace.wait(_trace.DEVICE_WAIT, "agg.fetch", lane=self._lane,
                         trace_id=self._trace_id, program=self.program) as waiting:
            wait_buffers_ready([b for (_r, ib, fb) in self._groups for b in (ib, fb)],
                               waiting=waiting)
        try:
            return self._assemble()
        finally:
            self._close.end()  # rows on the host

    def _assemble(self) -> tuple[np.ndarray, np.ndarray, list[np.ndarray]]:
        agg = self._agg
        R = agg.region_size
        int_idx = [i for i, d in enumerate(agg.acc_dtypes)
                   if not np.issubdtype(d, np.floating)]
        flt_idx = [i for i, d in enumerate(agg.acc_dtypes)
                   if np.issubdtype(d, np.floating)]
        keys_out, bins_out = [], []
        accs_out: list[list[np.ndarray]] = [[] for _ in agg.acc_dtypes]
        for regs, ibuf, fbuf in self._groups:
            # a zero-length fetch is still a fetch and a sync point, so
            # absent lane classes are never materialized (buf is None); the
            # padded tail regions (bases duplicated) are simply not in regs
            ilanes = flanes = None
            if ibuf is not None:
                a = np.asarray(ibuf)
                ilanes = a.reshape(-1, len(int_idx), R)
            if fbuf is not None:
                a = np.asarray(fbuf)
                flanes = a.reshape(-1, len(flt_idx), R)
            for pos, (b, keys_i64, fill) in enumerate(regs):
                if fill == 0:
                    continue
                keys_out.append(keys_i64.view(np.uint64))
                bins_out.append(np.full(fill, b, dtype=np.int32))
                for j, i in enumerate(int_idx):
                    accs_out[i].append(ilanes[pos, j, :fill].astype(agg.acc_dtypes[i]))
                for j, i in enumerate(flt_idx):
                    accs_out[i].append(flanes[pos, j, :fill].astype(agg.acc_dtypes[i]))
        if self._spill is not None and len(self._spill[0]):
            sk, sb, sa = self._spill
            keys_out.append(sk)
            bins_out.append(sb)
            for i, a in enumerate(sa):
                accs_out[i].append(a)
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
            [np.concatenate(a) for a in accs_out],
        )


@functools.lru_cache(maxsize=None)
def _build_slot_jax(acc_kinds: tuple, acc_dtypes: tuple, cap: int, region_size: int):
    import jax
    import jax.numpy as jnp

    idents = tuple(
        np.full(region_size, _identity(k, np.dtype(d)), dtype=d)
        for k, d in zip(acc_kinds, acc_dtypes)
    )

    def _mk_step(merge: bool):
        # hot path (merge=False): count lanes take no val array — the
        # increment is a constant 1, so shipping a batch-length ones lane
        # over the host->device link (256 KB/batch at 32k rows) would be
        # pure waste. Merge mode (update's ``partials``: a restore, a keyless
        # aggregate's stage) scatters the provided partial counts instead;
        # it compiles at its table's first such step, and a table fed
        # partials alone never runs the other.
        def step(state, slots, vals):
            out = []
            vi = 0
            for kind, a in zip(acc_kinds, state):
                if kind == "count" and not merge:
                    out.append(a.at[slots].add(np.asarray(1, a.dtype), mode="drop"))
                    continue
                v = vals[vi]
                vi += 1
                if kind in ("sum", "count"):
                    out.append(a.at[slots].add(v, mode="drop"))
                elif kind == "min":
                    out.append(a.at[slots].min(v, mode="drop"))
                else:
                    out.append(a.at[slots].max(v, mode="drop"))
            return tuple(out)

        return step

    step = _mk_step(merge=False)
    step_merge = _mk_step(merge=True)

    # The TPU compiler refuses a 64-bit bitcast ("UNIMPLEMENTED: While
    # rewriting computation to not contain X64 element types ...
    # bitcast-convert", v5e, PR 21), so integer and float accumulators travel
    # in two separately-typed buffers (still one fetch each, started together)
    def _pack(state, base):
        ilanes, flanes = [], []
        for a, d in zip(state, acc_dtypes):
            sl = jax.lax.dynamic_slice(a, (base,), (region_size,))
            if np.issubdtype(np.dtype(d), np.floating):
                flanes.append(sl.astype(jnp.float64))
            else:
                ilanes.append(sl.astype(jnp.int64))
        ibuf = jnp.concatenate(ilanes) if ilanes else jnp.zeros(0, jnp.int64)
        fbuf = jnp.concatenate(flanes) if flanes else jnp.zeros(0, jnp.float64)
        return ibuf, fbuf

    def _clear(state, base):
        return tuple(
            jax.lax.dynamic_update_slice(a, jnp.asarray(i), (base,))
            for a, i in zip(state, idents)
        )

    def clear(state, base):
        return _clear(state, base)

    # multi-region read: one device call + ONE host fetch per window close
    # regardless of how many bins/regions it spans (each fetch is its own
    # device->host transfer and sync point). k is static per jit;
    # callers bucket k and pad bases by duplicating bases[0] (duplicate
    # clears are idempotent, duplicate reads are ignored).
    @functools.lru_cache(maxsize=None)
    def make_read_multi(k: int, do_clear: bool):
        def go(state, bases):
            ibufs, fbufs = [], []
            for j in range(k):
                ibuf, fbuf = _pack(state, bases[j])
                ibufs.append(ibuf)
                fbufs.append(fbuf)
            ib = jnp.concatenate(ibufs) if ibufs[0].shape[0] else ibufs[0]
            fb = jnp.concatenate(fbufs) if fbufs[0].shape[0] else fbufs[0]
            if do_clear:
                for j in range(k):
                    state = _clear(state, bases[j])
                return state, ib, fb
            return ib, fb

        if do_clear:
            return jax.jit(go, donate_argnums=0)
        return jax.jit(go)

    # point reads for updating aggregates: one small gather of the touched
    # slots per flush interval (a bounded gather once a second is fine; the
    # per-batch hot loop stays scatter-only)
    @functools.lru_cache(maxsize=None)
    def make_read_slots(k: int):
        def go(state, slots):
            outs = []
            for a, d in zip(state, acc_dtypes):
                sl = a[slots]
                if np.issubdtype(np.dtype(d), np.floating):
                    outs.append(sl.astype(jnp.float64))
                else:
                    outs.append(sl.astype(jnp.int64))
            return tuple(outs)

        return jax.jit(go)

    return (
        jax.jit(step, donate_argnums=0),
        jax.jit(step_merge, donate_argnums=0),
        make_read_multi,
        jax.jit(clear, donate_argnums=0),
        make_read_slots,
    )


@functools.lru_cache(maxsize=None)
def _build_pad(acc_kinds: tuple, acc_dtypes: tuple, cap: int, new_cap: int):
    """state[cap] -> state[new_cap], each lane padded with its identity:
    the one program of a growth. Not donating: a buffer of the old size
    cannot back the new one (jax would only warn), and the old state is
    released when the aggregator lets go of it."""
    import jax
    import jax.numpy as jnp

    def pad(state):
        return tuple(
            jnp.concatenate([a, jnp.full(new_cap - cap, _identity(k, np.dtype(d)), dtype=d)])
            for a, k, d in zip(state, acc_kinds, acc_dtypes))

    return jax.jit(pad)


class SlotAggregator:
    """The one-chip device store of (bin, key) -> accumulators: the host
    slot directory, the scatter-only device step, and past the ceiling a
    HostAggregator as the spill tier."""

    def __init__(
        self,
        acc_kinds: Sequence[str],
        acc_dtypes: Sequence[np.dtype],
        cap: int = 65536,
        batch_cap: int = 8192,
        region_size: int = 2048,
    ):
        self.region_size = region_size
        self.acc_kinds = tuple(acc_kinds)
        self.acc_dtypes = tuple(np.dtype(d) for d in acc_dtypes)
        self.cap = cap
        self.batch_cap = batch_cap
        (self._step, self._step_merge, self._read_multi, self._clear,
         self._read_slots) = \
            _build_slot_jax(self.acc_kinds, self.acc_dtypes, cap, region_size)
        self._partials = False  # what the update under way said of its values
        self._made = False  # the chunk under way came made to the step's shapes (update_made)
        self._n_flt_lanes = sum(
            1 for d in self.acc_dtypes if np.issubdtype(d, np.floating))
        self._n_int_lanes = len(self.acc_dtypes) - self._n_flt_lanes
        # read_slots buckets met so far: a growth warms these again
        self._read_slot_buckets: set[int] = set()
        self._ceiling_slots: Optional[int] = None
        # inbox batches the next step is made of: a window operator that
        # staged several says so before it calls update (agg.dispatch's
        # ``batches``); one for whoever hands over a batch at a time
        self.staged_batches = 1
        # rows of the inbox the next step's rows were combined from, where
        # they are a keyless stage's partials (agg.dispatch's ``rows_in``);
        # 0: the step carries the rows themselves
        self.staged_rows = 0
        self.state = self._init_jax_state()

    def _init_jax_state(self):
        self.directory = BinSlotDirectory(self.cap, self.region_size)
        # fed past the ceiling
        self._spill_tier = HostAggregator(self.acc_kinds, self.acc_dtypes)
        return self._empty_state()

    @property
    def spill(self) -> dict[tuple[int, int], list]:
        """The spill tier's groups: (bin, key) -> [acc parts]."""
        return self._spill_tier.store

    def _empty_state(self):
        """Every lane at its identity, at the current capacity."""
        import jax.numpy as jnp

        return tuple(
            jnp.full(self.cap, _identity(k, d), dtype=d)
            for k, d in zip(self.acc_kinds, self.acc_dtypes)
        )

    # ------------------------------------------------------------- growth

    def _ceiling(self) -> int:
        """The most slots the table may grow to: _TABLE_MEMORY_SHARE of the
        memory of the device that holds the state, all lanes counted, and
        the same share of the host's memory for the directory. Neither
        limit changes while the process lives: read once."""
        if self._ceiling_slots is None:
            device = next(iter(self.state[0].devices()))
            limit = (device.memory_stats() or {}).get("bytes_limit") or _UNREPORTED_MEMORY_BYTES
            lane_bytes = sum(d.itemsize for d in self.acc_dtypes)
            self._ceiling_slots = int(min(
                limit * _TABLE_MEMORY_SHARE // lane_bytes,
                _host_memory_bytes() * _TABLE_MEMORY_SHARE // _DIRECTORY_BYTES_PER_SLOT))
        return self._ceiling_slots

    def _grow(self, new_cap: Optional[int] = None) -> bool:
        """No region is free: double the table (``restore`` names the
        capacity its snapshot needs instead), unless that passes the
        ceiling. The directory grows in place, the device state is padded,
        and every program of the new capacity runs once on scratch arrays
        before the task goes on, so that nothing compiles later. Closes in
        flight hold their own buffers and key copies and are not touched."""
        import jax

        cap, new_cap = self.cap, new_cap or 2 * self.cap
        if new_cap > self._ceiling():
            return False
        with _trace.span("agg.grow", cap_before=cap, cap_after=new_cap) as grow:
            live = self.directory.live_slots()
            self.directory.grow(new_cap)
            # lint: waive LR109 — the pad's wait is an arg of the agg.grow span, once per growth
            t0 = time.monotonic()
            # lint: waive LR104 — once per growth: the old state is let go only when the new one stands
            self.state = jax.block_until_ready(
                _build_pad(self.acc_kinds, self.acc_dtypes, cap, new_cap)(self.state))
            # lint: waive LR109 — see above
            pad_ms = (time.monotonic() - t0) * 1e3
            self.cap = new_cap
            (self._step, self._step_merge, self._read_multi, self._clear,
             self._read_slots) = _build_slot_jax(
                self.acc_kinds, self.acc_dtypes, new_cap, self.region_size)
            lane_bytes = sum(d.itemsize for d in self.acc_dtypes)
            # pad_ms is the host's wait for the pad program, its compile
            # included; pad_bytes what it has to move (old read, new written)
            grow.note(warmed=self._warm(), pad_ms=pad_ms,
                      pad_bytes=(cap + new_cap) * lane_bytes)
            _trace.table_grew(grow, cap, new_cap, live)
        return True

    def _warm(self) -> int:
        """Run, on scratch arrays of the state's shapes, every program the
        aggregate can meet at its capacity: both steps, every close-read
        bucket with and without clearing, the clear, and the point reads
        met so far. Calling them (not lower().compile()) is what fills the
        jit call cache. Returns the programs run."""
        import jax

        B = self.batch_cap
        slots = np.full(B, self.cap, dtype=self._slot_index_dtype())  # all dropped
        calls = []
        for merge, step in ((False, self._step), (True, self._step_merge)):
            vs = tuple(np.full(B, _identity(k, dt), dtype=dt)
                       for k, dt in zip(self.acc_kinds, self.acc_dtypes)
                       if merge or k != "count")
            calls.append((step, slots, vs))
        for k in _READ_BUCKETS:
            if k * self.region_size > self.cap:
                break
            bases = np.zeros(k, dtype=np.int64)
            calls += [(self._read_multi(k, do_clear), bases) for do_clear in (True, False)]
        calls.append((self._clear, np.int64(0)))
        calls += [(self._read_slots(k), np.zeros(k, dtype=slots.dtype))
                  for k in sorted(self._read_slot_buckets)]
        for fn, *args in calls:
            # lint: waive LR104 — once per growth: a program counts as warm once it has run to its end
            jax.block_until_ready(fn(self._empty_state(), *args))
        return len(calls)

    def _slot_index_dtype(self):
        # int32 slot indices: halves the per-batch index transfer and keeps
        # the scatter index math native on TPU (int64 is x64-emulated)
        return np.int32 if self.cap < _I32_MAX else np.int64

    def _grow_and_resolve(self, ks, bins, row_slots, unplaced):
        """Rows whose (bin, key) found no free region: grow the table until
        they have their slots (written into ``row_slots``). Returns the rows
        still without one: the table is at its ceiling."""
        while unplaced.any() and self._grow():
            sel = np.flatnonzero(unplaced)
            with _trace.span("agg.directory") as directory:
                row_slots[sel] = self._resolve_slots(
                    ks[sel].view(np.uint64), bins[sel], directory)[2]
            unplaced = row_slots < 0
        return unplaced

    # ------------------------------------------------------------- update

    def update(self, key_u64: np.ndarray, bins: np.ndarray, vals: Sequence[np.ndarray],
               partials: bool = False) -> None:
        """``partials``: the values are accumulators to merge into their
        groups', one value a lane, a count's among them (a restore's rows, a
        keyless stage's partials), and not rows to count one by one: the
        step is then ``step_merge``. (``_update_chunk`` keeps the parameters
        the benchmark's probes wrap it by.)"""
        self._partials = partials
        n = len(key_u64)
        for lo in range(0, n, self.batch_cap):
            hi = min(lo + self.batch_cap, n)
            self._update_chunk(key_u64[lo:hi], bins[lo:hi], [v[lo:hi] for v in vals])

    def update_made(self, rows: int, keys: np.ndarray, bins: np.ndarray, lanes: list) -> None:
        """One step of rows whose inputs the host library made to the
        device's shapes (native.StepMaker; windows/tumbling.py _run_made):
        ``keys`` (int64) and ``bins`` (int32) a step's width long with
        ``rows`` of them filled, ``lanes`` an entry an accumulator: its
        values in its dtype, padded to the width with its identity, None for
        a count (the step adds one a row). The directory then writes the
        slots in the step's index dtype, padded with the capacity, and
        nothing is filled, cast or copied on the way to the jitted call."""
        self._partials = False
        self._made = True
        self._update_chunk(keys[:rows].view(np.uint64), bins[:rows], lanes)

    def _update_chunk(self, key_u64, bins, vals) -> None:
        made, self._made = self._made, False
        B, idx_dt = self.batch_cap, self._slot_index_dtype()
        with _trace.span("agg.directory") as directory:
            ks, bins, row_slots, unplaced = self._resolve_slots(
                key_u64, bins, directory, (B, idx_dt, self.cap) if made else None)
        m = len(ks)
        if made and (unplaced or len(row_slots) != B or row_slots.dtype != idx_dt):
            # growth, spill or the fallback directory: they take the
            # unpadded rows, and the step is then padded as any other
            made, row_slots = False, row_slots[:m]
            vals = [np.ones(m, dtype=dt) if v is None else v[:m]
                    for v, dt in zip(vals, self.acc_dtypes)]
        if not made:
            vals = [np.asarray(v) for v in vals]
        # the count comes with the slots: no pass over a step's rows to learn
        # that every one of them has its slot
        if unplaced:
            spill_rows = self._grow_and_resolve(ks, bins, row_slots, row_slots < 0)
            if spill_rows.any():
                sel = np.flatnonzero(spill_rows)
                with _trace.span("agg.spill", rows=len(sel)):
                    self._spill_update(ks[sel], bins[sel], [v[sel] for v in vals])
                keep = np.flatnonzero(~spill_rows)
                row_slots = row_slots[keep]
                vals = [v[keep] for v in vals]
                m = len(keep)
        with _trace.step_dispatched(m, self.staged_batches, rows_in=self.staged_rows, made=made):
            if made:
                self.state = self._step(
                    self.state, row_slots, tuple(v for v in vals if v is not None))
            else:
                self._dispatch_step(m, row_slots, vals)
        self.staged_batches, self.staged_rows = 1, 0

    def _resolve_slots(self, key_u64, bins, span=_trace.NO_SPAN, padded=None):
        """(bin, key) -> device slot per row through the host directory;
        -1 = no region left. Returns (keys as int64, bins, slots, rows left
        at -1) and says on ``span`` what the step was. ``padded``: (width,
        index dtype, pad) of a step made to the device's shapes: the two
        native calls then write the slots as the step's index input, the
        entries past the rows at ``pad`` (native.dir_resolve).

        With the library: one native pass resolves every row whose group
        owns a slot and counts the first-seen groups by bin, the allocator
        sets each bin's slots aside as ranges, and a second native pass
        places the groups and fills their rows in: two calls, and no numpy
        call over the step's rows or its misses. Without it (and for a step
        whose misses span more bins than a claim takes, or a probe that
        wrapped: a fallback step, counted) numpy's unique and
        ``lookup_or_assign`` resolve the step whole."""
        from .. import native

        ku = np.ascontiguousarray(key_u64, dtype=np.uint64)
        ks = ku.view(np.int64)
        d = self.directory
        table = (d.hcode, d.hbin, d.hslot, d.boundary, d.slot_keys, d.slot_bins)
        res = native.dir_resolve(ks, bins, *table, padded)
        if res is not None and res[-1] is not None:
            *found, by_bin = res
            row_slots, misses, unplaced = found[0], len(found[2]), 0
            if misses:
                ranges = [(b, first, take) for b, n in by_bin
                          for first, take in d._alloc_ranges(b, n)]
                unplaced = native.dir_claim(*found, *table, ranges)
            _trace.directory_step(span, len(ks), misses, native=True)
            return ks, bins, row_slots, unplaced
        bins = np.ascontiguousarray(bins, dtype=np.int64)
        before = d.allocated
        uniq, first, inv = np.unique(_mix(ku, bins), return_index=True, return_inverse=True)
        slots_u = d.lookup_or_assign(uniq, ks[first], bins[first])
        row_slots = slots_u[inv]
        _trace.directory_step(
            span, len(ks), d.allocated - before + int((slots_u < 0).sum()),
            native=False, fell_back=native.available())
        return ks, bins, row_slots, int((row_slots < 0).sum())

    def _dispatch_step(self, m: int, row_slots, vals) -> None:
        """Pad and cast one chunk to the step's fixed shapes, hand it to
        the device, run the scatter step."""
        B = self.batch_cap
        idx_dt = self._slot_index_dtype()
        merge = self._partials
        if m == B:
            # full-width chunk (steady state): no padding copies needed
            slots = row_slots.astype(idx_dt, copy=False)
            vs = [np.asarray(v, dtype=dt)
                  for v, k, dt in zip(vals, self.acc_kinds, self.acc_dtypes)
                  if merge or k != "count"]
        else:
            slots = np.full(B, self.cap, dtype=idx_dt)  # pad -> dropped
            slots[:m] = row_slots
            vs = []
            for v, k, dt in zip(vals, self.acc_kinds, self.acc_dtypes):
                if not merge and k == "count":
                    continue
                arr = np.full(B, _identity(k, dt), dtype=dt)
                arr[:m] = v
                vs.append(arr)
        step = self._step_merge if merge else self._step
        self.state = step(self.state, slots, tuple(vs))

    def _spill_update(self, keys_i64, bins_i64, vals) -> None:
        self._spill_tier.update(keys_i64.view(np.uint64), bins_i64, vals)

    # ------------------------------------------------------------- extract

    def _collect_regions(self, emit_lo: int, emit_hi: int):
        """[(bin, base, fill, keys_copy)] for every region of bins in range."""
        d = self.directory
        out = []
        for b in d.live_bins():
            if not (emit_lo <= b < emit_hi):
                continue
            for r in d.bin_regions.get(b, ()):
                base = r * self.region_size
                fill = int(d.region_fill[r])
                out.append((b, base, fill, d.slot_keys[base : base + fill].copy()))
        return out

    def _read_regions(self, regs, do_clear: bool):
        """Batch region reads: <=16 regions per device call, k bucketed to a
        power of two (bases padded by duplication) so each close costs one
        fetch, not one per region."""
        groups = []
        i = 0
        while i < len(regs):
            chunk = regs[i : i + _READ_BUCKETS[-1]]
            i += _READ_BUCKETS[-1]
            k = next(b for b in _READ_BUCKETS if b >= len(chunk))
            bases = np.array(
                [c[1] for c in chunk] + [chunk[0][1]] * (k - len(chunk)),
                dtype=np.int64,
            )
            fn = self._read_multi(k, do_clear)
            if do_clear:
                self.state, ibuf, fbuf = fn(self.state, bases)
            else:
                ibuf, fbuf = fn(self.state, bases)
            ibuf = ibuf if self._n_int_lanes else None
            fbuf = fbuf if self._n_flt_lanes else None
            for buf in (ibuf, fbuf):
                if buf is not None:
                    buf.copy_to_host_async()
            groups.append(([(b, keys, fill) for (b, _base, fill, keys) in chunk],
                           ibuf, fbuf))
        return groups

    def extract_start(self, emit_lo: int, emit_hi: int, free_below: int) -> SlotExtractHandle:
        # agg.close: from here until the rows are on the host (the handle
        # ends it); trace_id is the window operator's (trace.window)
        with _trace.open_span("agg.close") as close:
            return self._extract_start(emit_lo, emit_hi, free_below, close)

    def _extract_start(self, emit_lo, emit_hi, free_below, close) -> SlotExtractHandle:
        d = self.directory
        regs_destr = self._collect_regions(emit_lo, min(emit_hi, free_below))
        regs_keep = self._collect_regions(max(emit_lo, free_below), emit_hi)
        # the table is fullest here, before the closing bins give their
        # regions back
        # lanes: the accumulators a row of the table holds, the key's value
        # lanes among them (a distinct split's first level: __n, one a filter, x)
        close.note(rows=sum(r[2] for r in regs_destr + regs_keep), lanes=len(self.acc_kinds))
        _trace.table_state(close, self.cap, d.live_slots())
        groups = self._read_regions(regs_destr, do_clear=True)
        groups += self._read_regions(regs_keep, do_clear=False)
        for b in [b for b in d.live_bins() if b < free_below]:
            if not (emit_lo <= b < emit_hi):
                # non-emitted expired bins: clear without reading
                for r in d.bin_regions.get(b, ()):
                    self.state = self._clear(self.state, np.int64(r * self.region_size))
            d.close_bin(b)
        spill = self._spill_tier.extract(emit_lo, emit_hi, free_below)
        if free_below > d.boundary:
            d.boundary = free_below
        return SlotExtractHandle(self, groups, spill, close)

    def extract(self, emit_lo: int, emit_hi: int, free_below: int):
        """Returns (key_u64, bin, acc_arrays) for bins in [emit_lo, emit_hi);
        frees all entries with bin < free_below."""
        return self.extract_start(emit_lo, emit_hi, free_below).result()

    def scan_range(self, emit_lo: int, emit_hi: int):
        """Non-destructive read of every entry with bin in [emit_lo, emit_hi)."""
        groups = self._read_regions(self._collect_regions(emit_lo, emit_hi),
                                    do_clear=False)
        spill = self._spill_tier.scan_range(emit_lo, emit_hi)
        return SlotExtractHandle(self, groups, spill).result()

    def free_bins_below(self, below: int) -> None:
        """Drop all entries with bin < below."""
        d = self.directory
        for b in d.live_bins():
            if b < below:
                for r in d.bin_regions.get(b, ()):
                    self.state = self._clear(self.state, np.int64(r * self.region_size))
                d.close_bin(b)
        self._spill_tier.free_bins_below(below)
        if below > d.boundary:
            d.boundary = below

    def read_slots(self, slots: np.ndarray) -> list[np.ndarray]:
        """Current accumulator values at the given device slots (one gather,
        one fetch; slot count bucketed to powers of two for jit reuse).
        Used by the updating-aggregate flush; window paths never gather."""
        n = len(slots)
        if n == 0:
            return [np.empty(0, dtype=d) for d in self.acc_dtypes]
        k = 64
        while k < n:
            k *= 2
        self._read_slot_buckets.add(k)
        padded = np.zeros(k, dtype=self._slot_index_dtype())
        padded[:n] = slots
        outs = self._read_slots(k)(self.state, padded)
        from .prefetch import wait_buffers_ready

        with _trace.wait(_trace.DEVICE_WAIT, "agg.fetch", program="jit_go") as waiting:
            wait_buffers_ready(outs, waiting=waiting)
        return [np.asarray(o)[:n].astype(d, copy=False)
                for o, d in zip(outs, self.acc_dtypes)]

    def slots_of(self, key_u64: np.ndarray) -> np.ndarray:
        """Device slots currently assigned to these (bin=0) keys; -1 for
        keys living in the host spill tier. Read-only: never allocates."""
        from .. import native

        d = self.directory
        ks = np.ascontiguousarray(key_u64, dtype=np.uint64).view(np.int64)
        zeros = np.zeros(len(ks), dtype=np.int64)
        res = native.dir_resolve(ks, zeros, d.hcode, d.hbin, d.hslot,
                                 d.boundary, d.slot_keys, d.slot_bins)
        if res is not None:
            return res[0]  # misses stay -1 (unallocated)
        codes = splitmix64(key_u64.astype(np.uint64))
        out = np.full(len(ks), -1, dtype=np.int64)
        for i, (c, k) in enumerate(zip(codes, ks)):
            h = int(c & d.hmask)
            for _ in range(d.hcap):
                if d.hslot[h] < 0 or d.hbin[h] < d.boundary:
                    break
                if d.hcode[h] == c and d.slot_keys[d.hslot[h]] == k:
                    out[i] = d.hslot[h]
                    break
                h = (h + 1) & int(d.hmask)
        return out

    # ------------------------------------------------------------- state sync

    def restore(self, key_u64, bins, accs) -> None:
        self.state = self._init_jax_state()
        # the capacity the snapshot needs, at once: one pad and one warm-up
        # instead of one of each per doubling on the way in. Past the
        # ceiling the update below spills what is left, as ever
        regions = int((-(-np.unique(bins, return_counts=True)[1] // self.region_size)).sum())
        need = self.cap
        while need // self.region_size < regions and 2 * need <= self._ceiling():
            need *= 2
        if need > self.cap:
            self._grow(need)
        self.update(key_u64, bins.astype(np.int32), accs, partials=True)

    def snapshot(self):
        """Full host copy of live entries (checkpoint path)."""
        with _trace.span("agg.snapshot") as snap:
            out = self._snapshot()
            snap.note(rows=len(out[0]))
            _trace.table_state(snap, self.cap, self.directory.live_slots())
            return out

    def _snapshot(self):
        d = self.directory
        live = d.live_bins()
        spill_bins = [b for (b, _k) in self.spill]
        if not live and not spill_bins:
            return (np.empty(0, dtype=np.uint64), np.empty(0, dtype=np.int32),
                    [np.empty(0, dtype=dt) for dt in self.acc_dtypes])
        lo = min(live + spill_bins)
        hi = max(live + spill_bins) + 1
        return self.scan_range(lo, hi)
