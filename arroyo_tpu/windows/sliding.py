"""Sliding (hop) window aggregate operator.

Reference behavior: crates/arroyo-worker/src/arrow/
sliding_aggregating_window.rs:45 — bin incoming rows by the *slide*; keep
per-bin partial aggregates; at each slide boundary the watermark passes,
combine the partials of the ``width/slide`` bins in [end-width, end) and emit
one row per key, stamping the window start as the output timestamp (:194,
:217-225); partials are retained until the last window containing them closes
(:161-162 flush/expire at ``bin_end - width + slide``).

TPU-native redesign: the per-bin partials live in the store the tumbling
operator uses (make_window_aggregator; bin = slide index). On the device
path each bin is read from the table once, when the watermark completes it,
and a window close is a host combine-by-key of its cached bins (or a slide
of the last window's rows) — they are already reduced to distinct (bin,
key) pairs, so tiny relative to the event stream the device reduced. The
numpy backend range-scans the host store at each close.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import numpy as np

from ..batch import KEY_FIELD, TIMESTAMP_FIELD, Batch
from ..config import config
from ..engine.engine import register_operator
from ..expr import eval_expr
from ..graph import OpName
from ..obs import trace as _trace
from ..operators.base import TableSpec, persist_mark, restore_marks
from ..types import Signal, Watermark
from .tumbling import (WINDOW_END, WINDOW_START, KeyDictionary, RowStage,
                       StagedAggregate, acc_plan, dtype_of_from_config,
                       make_window_aggregator, record_mesh_overflow)


class _Pane(NamedTuple):
    """The running window: the combined rows of the last window closed."""

    start: int            # its rel start bin
    block: np.ndarray     # int64 rows: keys in signed-key order, presence, a row a lane
    rows: int             # the block's filled columns
    first: Optional[tuple]  # bin ``start``'s (keys, accs), held apart from _bin_cache: the next slide retires it


class SlidingAggregate(StagedAggregate):
    """config: width_micros, slide_micros, key_fields: list[str], aggregates:
    [(name, kind, Expr|None)], final_projection: [(name, Expr)]|None,
    input_dtype_of, backend override."""

    def __init__(self, cfg: dict):
        self.width = int(cfg["width_micros"])
        self.slide = self._bin_micros = int(cfg["slide_micros"])
        if self.width % self.slide != 0 or self.width <= 0 or self.slide <= 0:
            raise ValueError(
                f"hop window width ({self.width}us) must be a positive multiple "
                f"of the slide ({self.slide}us)"
            )
        self.nb = self.width // self.slide  # bins per window
        self.key_fields: list[str] = list(cfg.get("key_fields", ()))
        self.aggregates = cfg["aggregates"]
        self.final_projection = cfg.get("final_projection")
        # the first level of a distinct split (sql/planner.py): its output
        # rows are the (window, group keys, value) pairs its table held
        self.counts_pairs = (cfg.get("distinct") or {}).get("level") == 1
        dtype_of = dtype_of_from_config(cfg)
        self.acc_kinds, self.acc_dtypes, self.acc_inputs = acc_plan(self.aggregates, dtype_of)
        self.n_user_accs = len(self.acc_kinds)
        self.backend = cfg.get("backend") or (
            "jax" if config().get("device.enabled") else "numpy"
        )
        self._agg = None
        # key transport split (same as tumbling): numeric group-by columns
        # ride the aggregate store as extra max-lanes — every row of a key
        # holds the same value — so only non-numeric keys pay the host
        # KeyDictionary's per-key Python cost
        self.lane_key_fields: Optional[list[str]] = None
        self.dict_key_fields: list[str] = []
        self.key_dict = KeyDictionary([])
        self.base_bin: Optional[int] = None  # abs slide-bin offset
        self.min_bin: Optional[int] = None  # earliest live rel bin
        self.max_bin: Optional[int] = None  # latest rel bin seen
        self.next_window: Optional[int] = None  # rel start-bin of next window to emit
        self.late_rows = 0  # state: ephemeral — observability counter (obs/profile.py export); never read into emitted data
        self._mesh_oflow_hwm = 0  # state: ephemeral — MESH_OVERFLOW event throttle high-water mark
        # device-path incremental extraction: each slide bin is fetched from
        # the device EXACTLY ONCE (destructively) when the watermark completes
        # it, asynchronously via the shared prefetcher; windows combine the
        # host-cached bins. This replaces the nb-way-redundant synchronous
        # scan-per-window (measured 38s for 1M events on the remote device
        # link — one ~70ms fetch sync per window close).
        self.open_bins: set[int] = set()  # rel bins with device-resident data
        self._bin_cache: dict[int, tuple] = {}  # rel bin -> (keys_u64, accs)  # state: ephemeral — folded into the 't' snapshot at every barrier; restore returns those bins to the device store
        self._bin_pending: dict = {}  # rel bin -> Future[(keys, bins, accs)]  # state: ephemeral — force-resolved at every barrier (handle_checkpoint) before the snapshot
        # extraction progress (NOT the late boundary): reset on restore so
        # bins folded back into the device store are re-extracted
        self._extracted_before: Optional[int] = None  # state: ephemeral — restored bins return to the device store and must re-extract; the late boundary persists separately
        # late-drop boundary; checkpointed into the "e" global table at
        # every barrier and restored in on_start, so replay drops exactly
        # the rows the original run dropped
        self._late_before: Optional[int] = None
        self._target_window: Optional[int] = None  # emit windows <= this  # state: ephemeral — re-derived from the first post-restore watermark; emission only reorders against input batches, never against forwarded watermarks
        self._wm_queue: list = []  # (target_window, Watermark) held in order  # state: ephemeral — fully drained by the forced _drain at every barrier
        self._wake = None  # state: ephemeral — the task's inbox wake (ctx.wake), taken anew at every on_start
        self._wm_edge: Optional[int] = None  # state: ephemeral — the edge (value // slide) of the last watermark handled: one that repeats it may wait behind staged rows; unknown after a restore, so the first is handled
        self._stage = RowStage(self._partial_kinds())  # state: ephemeral — run dry by flush_staged before every snapshot, close and wait of the task
        # the running window (device path): the combined rows of the last
        # window closed, which the next close slides by one bin where the
        # accumulators can be retracted (_retracts); every other close
        # combines the window's bins anew, and seeds this
        self._pane: Optional[_Pane] = None  # state: ephemeral — derived from the bins; no snapshot writes it, and the first close after a start, a restore or an event-time gap combines anew and seeds it
        self._full_why: Optional[str] = None  # why closes cannot slide ("" where they can)  # state: ephemeral — read off the accumulators' kinds and dtypes at the first close

    # ------------------------------------------------------------------

    def tables(self):
        # a bin's partials live until the last window containing it closes;
        # "e" holds the late-drop boundary (global: survives an empty
        # partial snapshot, where a column on the "t" batch would vanish)
        return [TableSpec("t", "expiring_time_key", retention_micros=self.width),
                TableSpec("e", "global_keyed")]

    def _aggregator(self):
        if self._agg is None:
            # mesh mode shares tumbling's construction path: per-bin partials
            # sharded over the key space; the incremental per-bin extraction
            # drives extract_start(b, b+1, b+1), which the sharded store
            # serves synchronously
            self._agg = make_window_aggregator(
                self.acc_kinds, self.acc_dtypes, self.backend)
        return self._agg

    def _setup_key_transport(self, batch: Batch) -> None:
        lane, dicty = [], []
        for f in self.key_fields:
            col = np.asarray(batch[f])
            if np.issubdtype(col.dtype, np.integer) or np.issubdtype(col.dtype, np.floating):
                lane.append((f, col.dtype))
            else:
                dicty.append(f)
        self.lane_key_fields = [f for f, _ in lane]
        self.dict_key_fields = dicty
        self.key_dict = KeyDictionary(dicty)
        from ..expr import Col

        self.acc_kinds = self.acc_kinds + tuple("max" for _ in lane)
        self.acc_dtypes = self.acc_dtypes + tuple(np.dtype(d) for _, d in lane)
        self.acc_inputs = self.acc_inputs + tuple(Col(f) for f, _ in lane)

    def on_start(self, ctx):
        self._wake = ctx.wake
        tbl = ctx.table_manager.expiring_time_key("t", self.width)
        batches = tbl.all_batches()
        if batches:
            self._restore_from_batch(Batch.concat(batches))
            tbl.replace_all([])
        # late-drop boundary (ABSOLUTE slide bin): replay must drop exactly
        # the rows the original run dropped; max merges subtasks/rescales
        barriers = restore_marks(ctx, "e")
        if barriers:
            lb_abs = max(barriers)
            if self.base_bin is None:
                # empty partial snapshot: anchor the bin space at the boundary
                self.base_bin = lb_abs
            self._late_before = lb_abs - self.base_bin

    def _restore_from_batch(self, b: Batch) -> None:
        if self.lane_key_fields is None:
            self._setup_key_transport(b)
        hashes = b.keys.astype(np.uint64)
        bins_abs = b.timestamps // self.slide
        self.base_bin = int(bins_abs.min())
        rel = (bins_abs - self.base_bin).astype(np.int32)
        accs = [b[f"__acc_{i}"].astype(d)
                for i, d in enumerate(self.acc_dtypes[: self.n_user_accs])]
        accs += [np.asarray(b[f]).astype(d)
                 for f, d in zip(self.lane_key_fields,
                                 self.acc_dtypes[self.n_user_accs:])]
        self._aggregator().restore(hashes, rel, accs)
        self._pane = None
        self.open_bins = set(np.unique(rel).tolist())
        self.min_bin = int(rel.min())
        self.max_bin = int(rel.max())
        if "__next_window" in b:
            # stored absolute; aligned barriers mean all prior subtasks saw the
            # same watermark, so max is a safe merge across rescaled inputs
            self.next_window = int(b["__next_window"].max()) - self.base_bin
        else:
            self.next_window = self.min_bin - self.nb + 1
        if self.key_fields:
            self.key_dict.observe(hashes, rel, b)

    # ------------------------------------------------------------------

    def _rows_coming(self, collector) -> None:
        if self._bin_pending or self._wm_queue:
            self._drain(collector)

    def _anchored(self) -> bool:
        # the first rows set the bin space and the first window
        return self.next_window is not None

    def _late_boundary(self) -> Optional[int]:
        """A row is late if its bin's last window already fired, or (device
        path) the bin was already destructively extracted — both are
        watermark-contract violations by the producer."""
        marks = [m for m in (self.next_window, self._late_before) if m is not None]
        return max(marks) if marks else None

    def _note_bins(self, bins: list) -> None:
        """The distinct bins of admitted rows (at least one), and with the
        stream's first the first window."""
        if self.backend != "numpy":  # numpy path never reads the set
            self.open_bins.update(bins)
        lo, hi = min(bins), max(bins)
        self.min_bin = lo if self.min_bin is None else min(self.min_bin, lo)
        self.max_bin = hi if self.max_bin is None else max(self.max_bin, hi)
        if self.next_window is None:
            self.next_window = self.min_bin - self.nb + 1

    def _moves_nothing(self, watermark) -> bool:
        """The edge ``value // slide`` (bins complete below it; windows
        closed ``nb`` behind it) is the one the last watermark handled had,
        no extraction is in flight, no watermark held and every window up
        to the target out: handled now or behind any rows on time, this one
        extracts no bin, emits no window, moves no boundary and forwards
        the value that one forwarded."""
        return (not watermark.is_idle and not self._bin_pending
                and not self._wm_queue and self._caught_up()
                and watermark.value // self.slide == self._wm_edge)

    def _on_watermark(self, watermark, collector):
        if watermark.is_idle:
            self._drain(collector, force=True)
            return watermark
        self._wm_edge = watermark.value // self.slide
        # future emissions are stamped with window starts strictly after the
        # last closed boundary; forward that lower bound (see tumbling)
        held = ((watermark.value - self.width) // self.slide + 1) * self.slide
        out_wm = Watermark.event_time(min(watermark.value, held))
        if self.base_bin is None:
            return out_wm
        if self.backend == "numpy":
            last_closed = (watermark.value - self.width) // self.slide - self.base_bin
            self._emit_through(int(last_closed), collector)
            return out_wm
        # device path: bins complete once the watermark passes their end;
        # dispatch their (destructive) extraction, then emit whatever windows
        # have all bins resolved — later watermarks/batches drain the rest
        complete_before = int(watermark.value // self.slide - self.base_bin)
        self._dispatch_extracts(complete_before)
        last_closed = int((watermark.value - self.width) // self.slide - self.base_bin)
        if self._target_window is None or last_closed > self._target_window:
            self._target_window = last_closed
        self._drain(collector)
        if self._caught_up() and not self._wm_queue:
            return out_wm
        self._wm_queue.append((self._target_window, out_wm))
        return None

    def on_close(self, ctx, collector):
        self.flush_staged(ctx, collector)
        if self.max_bin is None:
            return
        if self.backend == "numpy":
            self._emit_through(self.max_bin, collector)
            return
        self._dispatch_extracts(self.max_bin + 1)
        self._target_window = max(self._target_window or self.max_bin, self.max_bin)
        self._drain(collector, force=True)

    def _caught_up(self) -> bool:
        return (self.next_window is None or self._target_window is None
                or self.next_window > self._target_window)

    def _dispatch_extracts(self, complete_before: int) -> None:
        """Start the one-time extraction of every complete data-carrying bin
        below complete_before (ascending, so the slot directory's monotone
        close boundary is respected)."""
        if self._extracted_before is not None and complete_before <= self._extracted_before:
            return
        ready = sorted(b for b in self.open_bins if b < complete_before)
        if ready:
            agg = self._aggregator()
            from ..ops.prefetch import shared_prefetcher

            pf = shared_prefetcher()
            for b in ready:
                # bin b is the last of the window that ends where it ends
                with _trace.window((b + 1 + self.base_bin) * self.slide):
                    handle = agg.extract_start(b, b + 1, b + 1)
                self._bin_pending[b] = pf.submit(handle.result, on_done=self._wake,
                                                 program=getattr(handle, "program", None))
                self.open_bins.discard(b)
        self._extracted_before = complete_before
        if self._late_before is None or complete_before > self._late_before:
            self._late_before = complete_before

    def _resolve_bins(self, bins: list[int], force: bool,
                      woke: bool = False) -> bool:
        """Move resolved futures into the cache; True when every requested
        bin is available (cached or known-empty)."""
        ok = True
        for b in bins:
            fut = self._bin_pending.get(b)
            if fut is None:
                continue
            if force or fut.is_ready():
                _trace.close_left((b + 1 + self.base_bin) * self.slide, woke)
                keys, _bins, accs = fut.result()
                if len(keys):
                    self._bin_cache[b] = (keys, accs)
                del self._bin_pending[b]
            else:
                ok = False
        return ok

    def closes_in_flight(self) -> bool:
        return bool(self._bin_pending)

    def drain_ready(self, ctx, collector):
        self.flush_staged(ctx, collector)
        self._drain(collector, woke=True)

    def _drain(self, collector, force: bool = False, woke: bool = False) -> None:
        """Emit in-order every window whose bins are all resolved — fused
        into ONE output batch per drain (tail closes and catch-up used to
        emit one tiny batch per window) — then forward watermarks whose
        windows are out. The closes one watermark dispatched land one by
        one, each waking the task: unforced, the drain waits for the last
        of them, so the round leaves whole and not a batch a landing.
        ``woke``: called from drain_ready, on a completion wake."""
        if not force and not all(f.is_ready() for f in self._bin_pending.values()):
            return
        fused: list[dict] = []
        while not self._caught_up():
            w = self.next_window
            # event-time gap fast-forward: if no bin anywhere could feed a
            # window starting at w, jump straight to the earliest window the
            # live data can touch (a clock jump would otherwise make this
            # loop iterate once per empty slide bin across the gap)
            live = [b for src in (self._bin_cache, self._bin_pending, self.open_bins)
                    for b in src if b >= w]
            if not live:
                self.next_window = self._target_window + 1
                self.key_dict.evict_closed(self.next_window)
                break
            earliest = min(live)
            if earliest >= w + self.nb:
                self.next_window = min(earliest - self.nb + 1, self._target_window + 1)
                self.key_dict.evict_closed(self.next_window)
                continue
            needed = list(range(w, w + self.nb))
            if not self._resolve_bins(needed, force, woke):
                break
            parts = [self._bin_cache[b] for b in needed if b in self._bin_cache]
            if parts:
                fused.append(self._close_window(w, parts, len(parts)))
            else:
                self._pane = None
            self.next_window = w + 1
            # lint: waive LR204 — eviction only: deletes closed cache bins; no row is built or emitted from this loop
            for b in [b for b in self._bin_cache if b < self.next_window]:
                del self._bin_cache[b]
            self.key_dict.evict_closed(self.next_window)
        self._emit_fused(fused, collector)
        why = self._retracts()
        _trace.pane_cache(self.nb, sum(len(p[0]) for p in self._bin_cache.values()),
                          f"full ({why})" if why else "running")
        while self._wm_queue and (self.next_window is None
                                  or self._wm_queue[0][0] < self.next_window):
            _t, wm = self._wm_queue.pop(0)
            collector.broadcast(Signal.watermark_of(wm))

    def _emit_through(self, last_start_rel: int, collector) -> None:
        """numpy-backend path: synchronous scan per window (the dict store
        has no fetch latency to hide); all closing windows fuse into one
        emitted batch."""
        if self.next_window is None:
            return
        agg = self._aggregator()
        fused: list[dict] = []
        while self.next_window <= last_start_rel:
            b = self.next_window
            if self.max_bin is not None and b > self.max_bin:
                # nothing at or after this window's start; fast-forward
                self.next_window = last_start_rel + 1
                break
            if self.min_bin is not None and b + self.nb <= self.min_bin:
                # gap: window lies entirely before the earliest live bin
                nw = min(last_start_rel + 1, self.min_bin - self.nb + 1)
                self.next_window = max(nw, b + 1)
                agg.free_bins_below(self.next_window)
                self.key_dict.evict_closed(self.next_window)
                continue
            keys, _bins, accs = agg.scan_range(b, b + self.nb)
            if len(keys) == 0:
                # bins < b are freed, so an empty scan proves every live bin
                # is >= b + nb: re-arm the gap fast-forward above
                self.min_bin = b + self.nb
            if len(keys):
                # the scan hands the window's bins over in one piece; how
                # many they were is counted only for a span that is recorded
                fused.append(self._close_window(b, [(keys, accs)],
                                                lambda: len(np.unique(_bins))))
            self.next_window = b + 1
            # bins below the next window's range are done
            agg.free_bins_below(self.next_window)
            self.key_dict.evict_closed(self.next_window)
            if self.min_bin is not None:
                self.min_bin = max(self.min_bin, self.next_window)
        self._emit_fused(fused, collector)

    def _retracts(self) -> str:
        """Why this aggregate's closes cannot slide, "" where they can: a
        window's rows less a bin's are exact only for ``sum`` and ``count``
        over 8-byte integers (wrap-around included; a float ``sum``
        retracted is another number than the same bins summed, ``min`` and
        ``max`` cannot be retracted at all), the key lanes are carried as
        8-byte integers, and the slide is one call into the host library."""
        if self._full_why is not None:
            return self._full_why
        why = self._why_full()
        if self.lane_key_fields is not None:  # else the key lanes are not known yet
            self._full_why = why
        return why

    def _why_full(self) -> str:
        from .. import native

        if self.backend == "numpy":
            return "the numpy backend hands a window over in one piece"
        for i, (kind, dt) in enumerate(zip(self.acc_kinds, self.acc_dtypes)):
            if i < self.n_user_accs and kind not in ("sum", "count"):
                return f"{kind} is not retractable"
            if i < self.n_user_accs and dt.kind == "f":
                return "a float sum is not retracted exactly"
            if dt.kind not in "iu" or dt.itemsize != 8:
                return f"a {dt} lane is not an 8-byte integer"
        return "" if native.available() else "the host library is not loaded"

    def _close_window(self, w: int, parts: list, bins) -> dict:
        """The columns of the window that starts at rel bin ``w``, on the
        task's own thread: ``parts`` are ``(keys, accs)`` pieces of its
        ``bins`` bins (a number, or a call that counts them), one row a
        (bin, key); out comes one row a key, in key order. Slid from the
        last window's rows where those stand for ``w - 1``, else combined
        anew. One ``agg.combine`` span, named by the window's end like its
        close."""
        end = (w + self.nb + self.base_bin) * self.slide
        with _trace.pane_combine(end, bins) as span:
            made = None
            if self._pane is not None and self._pane.start == w - 1:
                made = self._slide(w)
            running = made is not None
            keys, accs, rows_in = made if running else self._combine(w, parts)
            cols = self._window_cols(w, keys, accs)
            _trace.pane_combined(span, rows_in, len(keys), running)
        return cols

    def _slide(self, w: int) -> Optional[tuple]:
        """Window ``w`` from window ``w - 1``: its rows less the bin it
        started with, plus the bin ``w`` ends with, one native merge of three
        runs in key order (a bin leaves ``combine_by_key_bin`` so, one row a
        key). The rows go into arrays made for this close, which the emitted
        batch keeps: the last window's are read and never written. None
        where the pass met what it cannot merge: the close combines anew."""
        from .. import native

        last, add = self._pane, self._bin_cache.get(w + self.nb - 1)
        retire = last.first
        slid = native.pane_slide(last.block, last.rows, add, retire, self.n_user_accs)
        if slid is None:
            return None
        block, rows = slid
        block.flags.writeable = False  # the emitted batch and the next slide both read it
        self._pane = _Pane(w, block, rows, self._bin_cache.get(w))
        accs = [block[2 + i, :rows].view(d) for i, d in enumerate(self.acc_dtypes)]
        rows_in = (len(add[0]) if add else 0) + (len(retire[0]) if retire else 0)
        return block[0, :rows].view(np.uint64), accs, rows_in

    def _combine(self, start_rel: int, parts: list) -> tuple:
        """The pane combine: every bin of the window concatenated and
        combined by key, each again in every one of the ``nb`` windows it
        feeds. Where the aggregate's closes can slide (_retracts), the rows
        are kept as the running window, with each key's presence (in how
        many of the bins it holds a row: a ``sum`` may be 0 while its key is
        still in the window) from one more lane of ones, counted."""
        from ..ops.aggregate import combine_by_key

        seeds = not self._retracts()
        keys, accs = parts[0]
        if len(parts) > 1:
            keys = np.concatenate([p[0] for p in parts])
            accs = [np.concatenate([p[1][i] for p in parts])
                    for i in range(len(self.acc_kinds))]
        rows_in = len(keys)
        if seeds:
            keys, accs = combine_by_key(self.acc_kinds + ("count",), keys,
                                        list(accs) + [np.ones(rows_in, dtype=np.int64)])
            block = np.stack([keys.view(np.int64), accs.pop()]
                             + [a.view(np.int64) for a in accs])
            self._pane = _Pane(start_rel, block, len(keys), self._bin_cache.get(start_rel))
        else:
            keys, accs = combine_by_key(self.acc_kinds, keys, accs)
        return keys, accs, rows_in

    def _window_cols(self, start_rel: int, keys, accs) -> dict:
        """Pre-projection output columns for one closed window (key lookups
        resolved eagerly, BEFORE the caller evicts the window's keys)."""
        from ..ops.aggregate import finalize_aggs

        start = (start_rel + self.base_bin) * self.slide
        n = len(keys)
        cols: dict[str, np.ndarray] = {}
        if self.dict_key_fields:
            cols.update(self.key_dict.lookup_columns(keys))
        for f, lane in zip(self.lane_key_fields or [], accs[self.n_user_accs:]):
            cols[f] = lane
        cols[WINDOW_START] = np.full(n, start, dtype=np.int64)
        cols[WINDOW_END] = np.full(n, start + self.width, dtype=np.int64)
        finals = finalize_aggs([a[1] for a in self.aggregates],
                               accs[: self.n_user_accs])
        for (name, _k, _e), arr in zip(self.aggregates, finals):
            cols[name] = arr
        # reference stamps the window start as the output event time (:217)
        cols[TIMESTAMP_FIELD] = np.full(n, start, dtype=np.int64)
        return cols

    def _emit_fused(self, fused: list[dict], collector) -> None:
        """One collect for ALL windows closed in this drain: concatenate the
        per-window columns, apply the final projection once (row-wise, so
        fusing cannot change its values)."""
        if not fused:
            return
        if len(fused) == 1:
            cols = fused[0]
        else:
            names = fused[0].keys()
            cols = {f: np.concatenate([c[f] for c in fused]) for f in names}
        out = Batch(cols)
        if self.final_projection is not None:
            n = out.num_rows
            proj = {name: eval_expr(e, out.columns, n)
                    for name, e in self.final_projection}
            if TIMESTAMP_FIELD not in proj:
                proj[TIMESTAMP_FIELD] = out.timestamps
            out = Batch(proj)
        # the watermark trail: the rows of the windows ending up to here leave
        _trace.mark("rows.out", int(fused[-1][WINDOW_END][0]), rows=out.num_rows)
        if self.counts_pairs:
            _trace.distinct_pairs(out.num_rows)
        collector.collect(out)

    # ------------------------------------------------------------------

    def handle_checkpoint(self, barrier, ctx, collector):
        # the snapshot holds every row before the barrier
        self.flush_staged(ctx, collector)
        # flush every emittable window first (rows precede the barrier), then
        # fold host-cached bins — destructively extracted off the device but
        # still feeding future windows — into the snapshot
        self._drain(collector, force=True)
        self._resolve_bins(sorted(self._bin_pending), force=True)
        # the late-drop boundary persists UNCONDITIONALLY — an empty
        # partial snapshot must not lose it. Fold in next_window: on the
        # numpy backend the live late filter is next_window itself
        # (_late_before is device-path-only), and its __next_window column
        # vanishes with an empty snapshot
        rel_marks = [m for m in (self._late_before, self.next_window)
                     if m is not None]
        persist_mark(ctx, "e",
                     None if not rel_marks
                     else max(rel_marks) + (self.base_bin or 0))
        tbl = ctx.table_manager.expiring_time_key("t", self.width)
        if self._agg is None:
            # no data yet: building the aggregator now would freeze
            # acc_kinds before _setup_key_transport appends the key lanes
            tbl.replace_all([])
            return
        keys, bins, accs = self._aggregator().snapshot()
        record_mesh_overflow(self, ctx)
        cached = sorted(self._bin_cache)
        if cached:
            keys = np.concatenate([keys] + [self._bin_cache[b][0] for b in cached])
            bins = np.concatenate(
                [bins] + [np.full(len(self._bin_cache[b][0]), b, dtype=np.int32)
                          for b in cached])
            accs = [np.concatenate([a] + [self._bin_cache[b][1][i] for b in cached])
                    for i, a in enumerate(accs)]
        if len(keys) == 0:
            tbl.replace_all([])
            return
        starts = (bins.astype(np.int64) + (self.base_bin or 0)) * self.slide
        cols: dict[str, np.ndarray] = {
            TIMESTAMP_FIELD: starts,
            KEY_FIELD: keys,
            "__next_window": np.full(
                len(keys), (self.next_window or 0) + (self.base_bin or 0), dtype=np.int64
            ),
        }
        if self.dict_key_fields:
            cols.update(self.key_dict.lookup_columns(keys))
        for f, lane in zip(self.lane_key_fields or [], accs[self.n_user_accs:]):
            cols[f] = lane
        for i, a in enumerate(accs[: self.n_user_accs]):
            cols[f"__acc_{i}"] = a
        tbl.replace_all([Batch(cols)])


@register_operator(OpName.SLIDING_AGGREGATE)
def _make_sliding(cfg: dict):
    return SlidingAggregate(cfg)
