"""Tumbling window aggregate operator.

Reference behavior: crates/arroyo-worker/src/arrow/
tumbling_aggregating_window.rs:49 — bin incoming rows by the window width,
feed per-bin partial aggregates incrementally, and on watermark >= bin end
run the finish plan + optional final projection, stamping the window start as
the output timestamp; partials checkpoint into an ExpiringTimeKey table
(:470-483) and are re-binned on restore (:234-248).

TPU-native redesign: partials live in the store make_window_aggregator
builds, keyed by (bin, key-hash): on one chip a SlotAggregator, whose host
directory gives each group a slot and whose device step is one scatter a
lane, on a mesh a ShardedAggregator, under the numpy backend a host dict. A
window close reads the closing bins' regions in one packed buffer, fetched
ASYNCHRONOUSLY — emission and the forwarded watermark are pipelined behind
subsequent update steps so the host never blocks on a device round trip in
the hot loop. Numeric group-by key
VALUES ride along as extra max-accumulator lanes in HBM (all rows of a key
agree, so max is the identity function); only string-typed keys fall back to
a host-side hash -> values dictionary.
"""

from __future__ import annotations

from collections import deque
from typing import Optional

import numpy as np

from ..batch import KEY_FIELD, TIMESTAMP_FIELD, Batch
from ..config import config
from ..engine.engine import register_operator
from ..expr import Col, Expr, eval_expr
from ..graph import OpName
from ..obs import trace as _trace
from ..operators.base import Operator, TableSpec, persist_mark, restore_marks
from ..types import Signal, Watermark

WINDOW_START = "window_start"
WINDOW_END = "window_end"

# in-flight window-close policy: extraction results materialize on the
# shared prefetch pool (ops/prefetch.py) so the hot loop never blocks on a
# device->host round trip; the worker wakes the task when one has landed
# (drain_ready); the queue force-drains past _PIPELINE_DEPTH
_PIPELINE_DEPTH = 16


def dtype_of_from_config(cfg: dict):
    """Accumulator-input dtype resolver: the in-process planner hands a live
    callable; graphs that crossed a process boundary (shipped IR) carry the
    declarative "input_dtypes" column map instead and rebuild it here."""
    fn = cfg.get("input_dtype_of")
    if fn is not None:
        return fn
    dtypes = cfg.get("input_dtypes")
    if dtypes:
        from ..batch import Field
        from ..sql.compile import infer_dtype

        dmap = dict(dtypes)
        return lambda e: Field("_", infer_dtype(e, dmap)).numpy_dtype()
    return lambda e: np.dtype(np.float64)


class CollectingAggregator:
    """Wraps the numeric aggregator with host-side object lanes for
    "collect"-kind accumulators (array_agg / UDAF state). Numeric lanes ride
    the wrapped slot tables untouched; list state lives in a host dict keyed
    (rel_bin, key_hash). Positional acc layout is preserved end-to-end so
    the window operators need no index remapping. Synchronous only — the
    planner forces backend="numpy" when a collect accumulator is present."""

    def __init__(self, acc_kinds, acc_dtypes, inner_factory):
        self.kinds = tuple(acc_kinds)
        self.col_idx = [i for i, k in enumerate(acc_kinds) if k == "collect"]
        self.num_idx = [i for i, k in enumerate(acc_kinds) if k != "collect"]
        # the inner aggregator tracks (key, bin) membership; with no numeric
        # user lane a hidden count keeps every group represented
        self._hidden = not self.num_idx
        inner_kinds = tuple(acc_kinds[i] for i in self.num_idx) or ("count",)
        inner_dtypes = (tuple(acc_dtypes[i] for i in self.num_idx)
                        or (np.dtype(np.int64),))
        self.inner = inner_factory(inner_kinds, inner_dtypes)
        # (rel_bin, key_hash) -> [list per collect acc]
        self.store: dict[tuple[int, int], list[list]] = {}

    def update(self, hashes, rel, vals, partials: bool = False) -> None:
        assert not partials  # a collected list is never staged as a partial
        nvals = [vals[i] for i in self.num_idx]
        if self._hidden:
            nvals = [np.ones(len(hashes), dtype=np.int64)]
        self.inner.update(hashes, rel, nvals)
        # store keys use the SIGNED view of the hash, matching _assemble/
        # restore and the inner aggregator's convention (ops/aggregate.py)
        signed = hashes.astype(np.uint64).view(np.int64)
        order = np.lexsort((signed, rel))
        h_s = signed[order]
        r_s = rel[order]
        brk = np.ones(len(h_s), dtype=bool)
        if len(h_s) > 1:
            brk[1:] = (h_s[1:] != h_s[:-1]) | (r_s[1:] != r_s[:-1])
        starts = np.flatnonzero(brk)
        ends = np.append(starts[1:], len(h_s))
        cvals = [np.asarray(vals[i], dtype=object)[order] for i in self.col_idx]
        for s, e in zip(starts, ends):
            ent = self.store.setdefault(
                (int(r_s[s]), int(h_s[s])), [[] for _ in self.col_idx])
            for j, cv in enumerate(cvals):
                ent[j].extend(cv[s:e].tolist())

    def _assemble(self, keys, bins, naccs, pop: bool):
        """Positionally recombine numeric lanes with collect lists for the
        given (key, bin) rows; pop=True consumes store entries (extract)."""
        from ..batch import object_column

        out: list = [None] * len(self.kinds)
        ni = 0
        for i in self.num_idx:
            out[i] = naccs[ni]
            ni += 1
        if len(keys):
            signed = keys.astype(np.uint64).view(np.int64)
            for j, i in enumerate(self.col_idx):
                if pop and j == len(self.col_idx) - 1:
                    ents = [self.store.pop((int(b), int(k)), None)
                            for k, b in zip(signed, bins)]
                else:
                    ents = [self.store.get((int(b), int(k)))
                            for k, b in zip(signed, bins)]
                out[i] = object_column(
                    (list(e[j]) if e is not None else []) for e in ents)
        else:
            for i in self.col_idx:
                out[i] = np.empty(0, dtype=object)
        return out

    def extract(self, lo, hi, before):
        keys, bins, naccs = self.inner.extract(lo, hi, before)
        return keys, bins, self._assemble(keys, bins, naccs, pop=True)

    def snapshot(self):
        keys, bins, naccs = self.inner.snapshot()
        return keys, bins, self._assemble(keys, bins, naccs, pop=False)

    def restore(self, hashes, rel, accs) -> None:
        naccs = [accs[i] for i in self.num_idx]
        if self._hidden:
            # rebuild the hidden count lane from the collect list lengths
            naccs = [np.array([len(l) for l in accs[self.col_idx[0]]],
                              dtype=np.int64)]
        self.inner.restore(hashes, rel, naccs)
        signed = hashes.astype(np.uint64).view(np.int64)
        for row, (k, b) in enumerate(zip(signed, rel)):
            ent = self.store.setdefault((int(b), int(k)), [[] for _ in self.col_idx])
            for j, i in enumerate(self.col_idx):
                ent[j] = list(accs[i][row])


def record_mesh_overflow(op, ctx) -> int:
    """Throttled MESH_OVERFLOW WARN, called from the window operators'
    handle_checkpoint right after the snapshot (which refreshes the sharded
    store's spill residency with no extra device sync). Key skew past a
    fixed-capacity exchange lane parks rows in the per-shard HBM spill
    buffer — correct but slower, and the operator should hear about it
    before the buffer itself fills (which IS an error). The doubling
    high-water mark keeps a steadily-skewed job from flooding the feed."""
    stats_fn = getattr(op._agg, "mesh_stats", None)
    if stats_fn is None:
        return 0
    rows = int(stats_fn().get("overflow_rows", 0))
    if rows > op._mesh_oflow_hwm:
        op._mesh_oflow_hwm = rows * 2
        from ..obs.events import recorder

        ti = ctx.task_info
        recorder.record(
            ti.job_id, "WARN", "MESH_OVERFLOW",
            message=(f"{rows} rows resident in the sharded aggregate's "
                     f"per-shard HBM spill buffer (key skew past a "
                     f"fixed-capacity exchange lane; raise "
                     f"device.spill-capacity before it exhausts)"),
            node=ti.node_id, subtask=ti.subtask_index,
            data={"overflow_rows": rows})
    return rows


def make_window_aggregator(acc_kinds, acc_dtypes, backend: str):
    """The store of a window operator's partials, and the one place that
    knows the three: the HostAggregator for the numpy backend, on the device
    the key-space-sharded ShardedAggregator of a mesh (device.mesh-devices
    > 1) or the SlotAggregator of one chip — one construction path shared
    by every window operator so capacity knobs cannot drift between them.
    collect-kind accumulators (array_agg / UDAF state) wrap a host store
    with host-side object lanes."""
    if "collect" in acc_kinds:
        return CollectingAggregator(
            acc_kinds, acc_dtypes,
            lambda ks, ds: make_window_aggregator(ks, ds, "numpy"))
    if backend == "numpy":
        from ..ops.aggregate import HostAggregator

        return HostAggregator(acc_kinds, acc_dtypes)
    dev = config().section("device")
    mesh_n = int(dev.get("mesh-devices", 0) or 0)
    if mesh_n > 1:
        from ..parallel import ShardedAggregator, make_mesh

        return ShardedAggregator(
            make_mesh(mesh_n),
            acc_kinds,
            acc_dtypes,
            cap=dev.get("table-capacity", 65536),
            batch_cap=dev.get("batch-capacity", 8192),
            max_probes=dev.get("max-probes", 64),
            emit_cap=dev.get("emit-capacity", 8192),
            spill_cap=dev.get("spill-capacity", 2048),
        )
    from ..ops.slot_agg import SlotAggregator

    return SlotAggregator(
        acc_kinds,
        acc_dtypes,
        cap=dev.get("table-capacity", 65536),
        batch_cap=dev.get("batch-capacity", 8192),
        region_size=dev.get("region-size", 2048),
    )


def acc_plan(aggregates: list[tuple[str, str, Optional[Expr]]], schema_dtype_of) -> tuple:
    """Flatten SQL aggregates into accumulator (kind, dtype, input) triples.

    aggregates: [(out_name, kind, input_expr|None)]; count has no input.
    Returns (acc_kinds, acc_dtypes, input_specs) where input_specs[i] is the
    Expr for that accumulator or None for a count-style all-ones input.
    """
    kinds, dtypes, inputs = [], [], []
    for _name, kind, expr in aggregates:
        if kind == "count":
            kinds.append("count")
            dtypes.append(np.dtype(np.int64))
            inputs.append(None)
        elif kind == "avg":
            kinds.extend(["sum", "count"])
            dtypes.extend([np.dtype(np.float64), np.dtype(np.int64)])
            inputs.extend([expr, None])
        elif kind.startswith("udaf:") or kind in ("collect", "count_distinct"):
            # UDAF state / array_agg / COUNT(DISTINCT) = collected input
            # values (host-resident python lists; planner allows session +
            # tumbling windows)
            kinds.append("collect")
            dtypes.append(np.dtype(object))
            inputs.append(expr)
        else:
            kinds.append(kind)
            dtypes.append(schema_dtype_of(expr))
            inputs.append(expr)
    return tuple(kinds), tuple(dtypes), tuple(inputs)


class KeyDictionary:
    """hash -> key-column values, for reconstructing group-by columns at
    emission (device state stores only the 64-bit hash). Entries are evicted
    once every bin that saw the key has closed, bounding host memory. Used
    only for non-numeric key columns; numeric keys travel through HBM."""

    def __init__(self, key_fields: list[str]):
        self.key_fields = key_fields
        self.values: dict[int, tuple] = {}
        self.last_bin: dict[int, int] = {}

    def observe(self, hashes: np.ndarray, bins: np.ndarray, batch: Batch) -> None:
        if not self.key_fields:
            return
        u, first = np.unique(hashes, return_index=True)
        u_list = u.tolist()
        # conservative liveness: every key seen in this batch is live through
        # the batch's max bin. The update must be monotone — out-of-order
        # batches (normal after a keyed shuffle at parallelism>1) may carry a
        # lower max bin, and lowering a key's horizon would let evict_closed
        # delete values still resident on device.
        mx = int(bins.max()) if len(bins) else 0
        lb = self.last_bin
        for h in u_list:
            v = lb.get(h)
            if v is None or v < mx:  # rel bins can be negative: no sentinel
                lb[h] = mx
        vals = self.values
        new = [h for h in u_list if h not in vals]
        if new:
            cols = [batch[f] for f in self.key_fields]
            idx_of = dict(zip(u_list, first.tolist()))
            for h in new:
                i = idx_of[h]
                vals[h] = tuple(c[i] for c in cols)

    def evict_closed(self, rel_before: int) -> None:
        dead = [h for h, b in self.last_bin.items() if b < rel_before]
        for h in dead:
            del self.values[h]
            del self.last_bin[h]

    def lookup_columns(self, hashes: np.ndarray) -> dict[str, np.ndarray]:
        out: dict[str, np.ndarray] = {}
        if not self.key_fields:
            return out
        rows = [self.values[int(h)] for h in hashes]
        for j, f in enumerate(self.key_fields):
            vals = [r[j] for r in rows]
            sample = vals[0] if vals else None
            if isinstance(sample, (str, type(None))):
                out[f] = np.array(vals, dtype=object)
            else:
                out[f] = np.array(vals)
        return out


_U64 = (1 << 64) - 1


def stages_partials(key_fields, acc_kinds, acc_dtypes) -> bool:
    """Whether a window aggregate stages partials, not rows (RowStage):
    the plan gives it no key, so its table holds one slot a bin, and every
    accumulator combines exactly however the rows are grouped: a sum, count,
    min or max over signed 4- or 8-byte integers. A float lane (a sum by
    partials is another number than the sum row by row), a collected list
    or any key field keeps the rows."""
    return not key_fields and all(
        k in ("sum", "count", "min", "max") and d.kind == "i" and d.itemsize in (4, 8)
        for k, d in zip(acc_kinds, map(np.dtype, acc_dtypes)))


class RowStage:
    """The batches a window aggregate has taken from its inbox and not yet
    run its hook over. The hook (bin, late filter, accumulator inputs, key
    dictionary, directory, device step) costs what it costs whatever the
    rows, so the operator runs it once over what its inbox held, up to a
    step's width (``device.batch-capacity``: the step then pads nothing),
    and never lets a row wait here while its task sleeps
    (``Operator.flush_staged``). Everything that must see the rows runs the
    hook over them first, in arrival order; a watermark that moves nothing
    waits behind them instead, and only the newest of those is kept.

    A keyless aggregate (``kinds`` given: stages_partials) stages partials:
    each batch comes combined to one row a bin (ops/aggregate.py
    combine_by_bin) and is merged here into the partial of its bin, plain
    ints a lane, so the stage holds a row an open bin whatever its inbox
    held, never reaches the width, and is run by what runs every stage: an
    empty inbox, a watermark that moves something, a barrier, a close, a
    stop."""

    __slots__ = ("width", "batches", "rows", "watermark", "kinds", "partials", "staged")

    def __init__(self, kinds: Optional[tuple] = None):
        # read with the first batch, where the operator's aggregator reads it
        self.width: Optional[int] = None
        self.batches: list[Batch] = []
        self.rows = 0
        self.watermark: Optional[Watermark] = None
        self.kinds = kinds
        self.partials: dict[int, list] = {}  # absolute bin -> [rows, a lane's partial ...]
        self.staged = 0  # inbox batches the partials were made of

    def _read_width(self) -> None:
        if self.width is None:
            self.width = int(config().get("device.batch-capacity", 8192))

    def add(self, batch: Batch) -> None:
        self._read_width()
        if batch.num_rows:
            self.batches.append(batch)
            self.rows += batch.num_rows

    def take(self) -> tuple[Batch, int]:
        """Up to a step's width of rows, in arrival order, and the inbox
        batches they came in; a batch that would pass the width is split at
        it and its rest stays staged."""
        n = len(self.batches)
        rows = Batch.concat(self.batches)
        if rows.num_rows > self.width:
            rest = rows.slice(self.width, rows.num_rows)
            rows = rows.slice(0, self.width)
            self.batches, self.rows = [rest], rest.num_rows
        else:
            self.batches, self.rows = [], 0
        return rows, n

    def take_pieces(self) -> tuple[list[Batch], int]:
        """``take`` without the concat, for the native pass: up to a step's
        width of rows as the staged batches they lie in, in arrival order,
        and how many those are; a batch that would pass the width is split
        at it (two views) and its rest stays staged."""
        room, out = self.width, []
        for i, b in enumerate(self.batches):
            n = b.num_rows
            if n > room:
                out.append(b.slice(0, room))
                self.batches = [b.slice(room, n)] + self.batches[i + 1:]
                break
            out.append(b)
            room -= n
            if not room:
                self.batches = self.batches[i + 1:]
                break
        else:
            self.batches = []
        self.rows = sum(b.num_rows for b in self.batches)
        return out, len(out)

    def add_partials(self, bins, rows, vals) -> None:
        """One batch's partials, one row a distinct bin, into those staged.
        A sum is kept modulo 2**64, as the lane wraps."""
        self._read_width()
        self.staged += 1
        cols = [rows.tolist()] + [v.tolist() for v in vals]
        for j, b in enumerate(bins.tolist()):
            new = [c[j] for c in cols]
            cur = self.partials.get(b)
            if cur is None:
                self.partials[b] = new
                continue
            cur[0] += new[0]
            for i, kind in enumerate(self.kinds, 1):
                cur[i] = (min(cur[i], new[i]) if kind == "min" else
                          max(cur[i], new[i]) if kind == "max" else cur[i] + new[i])
        self.rows = len(self.partials)

    def take_partials(self, dtypes) -> tuple[np.ndarray, np.ndarray, list, int]:
        """The staged partials in bin order: their absolute bins, the rows
        each stands for, a lane's values of each in the lane's dtype, and
        the inbox batches they came in."""
        bins = sorted(self.partials)
        cols = list(zip(*(self.partials[b] for b in bins)))
        vals = [np.array([v & _U64 for v in col], dtype=np.uint64).view(np.int64).astype(dt)
                for col, dt in zip(cols[1:], dtypes)]
        n, self.partials, self.rows, self.staged = self.staged, {}, 0, 0
        return (np.array(bins, dtype=np.int64), np.array(cols[0], dtype=np.int64), vals, n)


class StagedAggregate(Operator):
    """What the tumbling and the sliding aggregate share: the hooks through
    which rows and watermarks reach the store and ``_on_watermark``, and the
    mesh counters. A subclass keeps ``_stage`` and ``_bin_micros`` (the
    event time a bin spans) and gives ``_anchored`` (False until the
    stream's first rows have set its bin space), ``_rows_coming`` (emit the
    closes that have landed), ``_late_boundary``, ``_note_bins``,
    ``_moves_nothing`` and ``_on_watermark``.

    Rows enter three ways: ``_run_staged`` (a staged batch),
    ``insert_arrays`` (a compiled segment, engine/segment.py: the traced
    prefix has evaluated hashes, absolute bins and accumulator inputs) and
    ``mesh_insert_begin`` (the host half of the fused mesh step: the update
    itself runs inside the shard_map'd program). All three pass the
    subclass's late boundary (``_late_boundary``: ``_admit``, or the native
    pass, which is handed the same number) and keep its bins through its
    one ``_note_bins``, so checkpoints and the late boundary are the same
    whichever way the rows came.

    The hook over a staged step of rows is one native pass where the plan
    allows (``_plan_maker``, read off the operator when its first batch has
    shown its columns: native.StepMaker, cpp ah_step_make): it reads the
    staged batches' columns where they lie and writes the step's inputs as
    the device takes them, so that between the stage and the jitted call the
    task hands the interpreter lock over for the directory alone. Everything
    else keeps the hook in numpy (``_hook``), which is also the pass's
    oracle (tests/test_step_make.py)."""

    _stage: RowStage
    _bin_micros: int
    _maker = None  # native.StepMaker, or False: the numpy hook; None until the first batch

    def process_batch(self, batch, ctx, collector, input_index=0):
        self._rows_coming(collector)
        self._stage_batch(batch, ctx, collector)

    def _partial_kinds(self) -> Optional[tuple]:
        """The accumulator kinds where this aggregate stages partials
        (stages_partials), None where it stages rows. Not on a mesh
        (device.mesh-devices > 1), though the sharded store would take
        them: measured on four chips (PERF.md section 6, PR 52), q7-mesh4
        ran 14-20% slower with them. A mesh step costs its host 12-14 ms
        whatever it carries and the two aggregates' steps exclude each
        other, and the deployment's rate is the slower of two scans under
        one interpreter lock: the scan the global max no longer held back
        took the lock from the other (ROADMAP A3 (4), C17 (c))."""
        on_mesh = self.backend == "jax" and int(config().get("device.mesh-devices") or 0) > 1
        if on_mesh or not stages_partials(self.key_fields, self.acc_kinds, self.acc_dtypes):
            return None
        return self.acc_kinds

    def _run_staged(self, collector) -> None:
        stage = self._stage
        if stage.kinds is None:
            if self._maker is None:
                self._plan_maker(stage.batches[0])
            if self._maker:
                return self._run_made()
        with _trace.span("agg.make", made="numpy") as make:
            step = self._hook(make)
        self._update(step)

    def _update(self, step) -> None:
        if step is not None:
            hashes, rel, vals, partials = step
            self._aggregator().update(hashes, rel, vals, partials)
            self._note_rel(rel)

    def _hook(self, make, taken: Optional[tuple] = None):
        """The hook in numpy, from the stage to the store's ``update``: what
        to hand it (key hashes, relative bins, one input array an
        accumulator, whether they are partials), None where no row stayed.
        ``taken``: rows already off the stage and the batches they came in
        (a step the native pass gave back)."""
        stage = self._stage
        partials = stage.kinds is not None
        if partials:
            # one row a bin, each standing for ``weights`` rows of the inbox
            bins_abs, weights, vals, batches = stage.take_partials(self.acc_dtypes)
        else:
            batch, batches = taken or stage.take()
            if self.lane_key_fields is None:
                self._setup_key_transport(batch)
            bins_abs, weights = batch.timestamps // self._bin_micros, None
        make.note(rows=len(bins_abs), batches=batches)
        rel, keep = self._admit(bins_abs, weights)
        if not len(rel):
            return None
        agg = self._aggregator()
        if partials:
            if keep is not None:
                weights, vals = weights[keep], [v[keep] for v in vals]
            # the key is the plan's, none, whatever _key the rows carried
            hashes = np.zeros(len(rel), dtype=np.uint64)
            agg.staged_rows = int(weights.sum())
        else:
            if keep is not None:
                batch = batch.filter(keep)
            n = batch.num_rows
            if KEY_FIELD in batch:
                hashes = batch.keys.astype(np.uint64)
            else:
                hashes = np.zeros(n, dtype=np.uint64)
            self.key_dict.observe(hashes, rel, batch)
            vals = self._acc_vals(batch)
        agg.staged_batches = batches
        return hashes, rel, vals, partials

    def _plan_maker(self, batch: Batch) -> None:
        """Which way this operator's staged steps are made, read off its plan
        and its first batch's columns, once: the native pass where the store
        is one chip's SlotAggregator (not a mesh's, not the numpy backend's,
        no collected list), every key field travels as a lane (a
        KeyDictionary needs the rows as a batch), the stage holds rows (a
        keyless aggregate's partials have their own native call) and every
        accumulator is a sum, count, min or max over 4- or 8-byte integers
        or floats whose input the pass can read: a plain column of such a
        type, or an expression, which ``eval_expr`` evaluates as ever and
        hands to the pass as one more column."""
        from .. import native
        from ..ops.slot_agg import SlotAggregator

        if self.lane_key_fields is None:
            self._setup_key_transport(batch)
        # state: ephemeral — how staged steps are made: read off the plan and the first batch's columns at the first step of every incarnation
        self._maker = False
        if self.dict_key_fields or type(self._aggregator()) is not SlotAggregator:
            return
        sources, columns = [], []
        for inp, dt in zip(self.acc_inputs, self.acc_dtypes):
            if inp is None:
                columns.append(None)  # a count: the device adds one a row
                continue
            plain = isinstance(inp, Col) and inp.name in batch
            sources.append(inp.name if plain else inp)
            columns.append(batch[inp.name].dtype if plain else dt)
        if native.StepMaker.takes(self.acc_kinds, self.acc_dtypes, columns):
            self._maker = native.StepMaker(self.acc_kinds, self.acc_dtypes, columns)
            # state: ephemeral — set with _maker, above
            self._lane_sources = sources

    def _run_made(self) -> None:
        """One staged step through the native pass; a step the pass gives
        back (a column of another dtype or layout than the first batch's, over
        64 distinct bins) runs the numpy hook over the same rows."""
        agg = self._aggregator()
        with _trace.span("agg.make", made="native") as make:
            pieces, batches = self._stage.take_pieces()
            make.note(rows=sum(b.num_rows for b in pieces), batches=batches)
            made = self._maker.make(self._step_inputs(pieces), self._bin_micros, self.base_bin,
                                    self._late_boundary(), agg.batch_cap)
            if made is None:
                make.note(made="numpy")
                step = self._hook(_trace.NO_SPAN, (Batch.concat(pieces), batches))
        if made is None:
            return self._update(step)
        # the subclasses restore both (on_start; audited there), as what _admit sets
        self.base_bin = made.base  # state: ephemeral — in this base class alone: each subclass's on_start restores it from its snapshot and its "e" table
        self.late_rows += made.late  # state: ephemeral — observability counter, as the subclasses declare it
        if made.rows:
            agg.staged_batches = batches
            agg.update_made(made.rows, made.keys, made.rel, made.lanes)
            self._note_bins(made.bins)

    def _step_inputs(self, pieces: list[Batch]) -> list:
        """The pieces as StepMaker.make takes them: event times, keys, and
        the column each shipped lane reads. An expression is evaluated over
        the step's rows at once, on the columns it names alone, and cut to
        the pieces."""
        sources = self._lane_sources
        evaluated: dict[int, np.ndarray] = {}
        exprs = [(j, e) for j, e in enumerate(sources) if not isinstance(e, str)]
        if exprs:
            names = set().union(*(e.columns() for _j, e in exprs))
            cols = {c: pieces[0].columns[c] if len(pieces) == 1
                    else np.concatenate([b.columns[c] for b in pieces]) for c in names}
            n = sum(b.num_rows for b in pieces)
            shipped = self._maker.shipped
            for j, e in exprs:
                evaluated[j] = np.ascontiguousarray(
                    eval_expr(e, cols, n), dtype=self.acc_dtypes[shipped[j]])
        out, at = [], 0
        for b in pieces:
            c, r = b.columns, b.num_rows
            out.append((c[TIMESTAMP_FIELD], c.get(KEY_FIELD),
                        [c[e] if isinstance(e, str) else evaluated[j][at:at + r]
                         for j, e in enumerate(sources)]))
            at += r
        return out

    def _admit(self, bins_abs, rows=None):
        """Anchor the bin space at the stream's first rows and pass rows (at
        least one) by the late boundary (``_late_boundary``): rows behind it
        are dropped and counted (the reference drops late data rather than
        re-opening closed windows; ``rows``: how many rows each element
        stands for, a staged partial's; None: one). The compare is in int64,
        the cast to the store's int32 after it. Returns the relative bins of
        the rows that stay and the mask that kept them (None: all)."""
        if self.base_bin is None:
            self.base_bin = int(bins_abs.min())
        rel = (bins_abs - self.base_bin).astype(np.int64, copy=False)
        late_before = self._late_boundary()
        keep = None
        if late_before is not None:
            late = rel < late_before
            if late.any():
                self.late_rows += int(late.sum() if rows is None else rows[late].sum())
                keep = ~late
                rel = rel[keep]
        return rel.astype(np.int32), keep

    def _note_rel(self, rel) -> None:
        """The bins of rows the numpy hook admitted (at least one)."""
        self._note_bins(np.unique(rel).tolist())

    def _stage_partials(self, batch: Batch) -> None:
        """A keyless aggregate stages partials, not rows: the batch's rows
        combined to one row a bin in one native call (ops/aggregate.py
        combine_by_bin) and merged into the stage's. The late boundary is
        passed when the stage is run, a partial then counting for its rows:
        it moves only in ``_on_watermark``, which runs the stage first, so
        the rows dropped are the rows a step over the rows dropped."""
        from ..ops.aggregate import combine_by_bin

        if self.lane_key_fields is None:
            self._setup_key_transport(batch)
        n = batch.num_rows
        lanes = [None if inp is None
                 else np.asarray(eval_expr(inp, batch.columns, n)).astype(dt, copy=False)
                 for inp, dt in zip(self.acc_inputs, self.acc_dtypes)]
        self._stage.add_partials(
            *combine_by_bin(self.acc_kinds, batch.timestamps, self._bin_micros, lanes))

    def insert_arrays(self, hashes, bins_abs, vals, collector) -> None:
        """A compiled segment's rows (engine/segment.py). Only reached when
        the compile gate proved there are no host key dictionary fields and
        no collect accumulators."""
        self.flush_staged(None, collector)  # rows a batch routed interpreted left
        self._rows_coming(collector)
        if len(hashes) == 0:
            return
        rel, keep = self._admit(bins_abs)
        if not len(rel):
            return
        if keep is not None:
            hashes = hashes[keep]
            vals = [v[keep] for v in vals]
        self._aggregator().update(hashes, rel, vals)
        self._note_rel(rel)

    def mesh_insert_begin(self, bins_abs, collector):
        """Host half of the fused mesh step (engine/segment.py
        _mesh_execute), without the aggregator update. Returns the on-time
        row mask (None = every row inserts)."""
        self.flush_staged(None, collector)  # rows a batch routed interpreted left
        self._rows_coming(collector)
        if len(bins_abs) == 0:
            return None
        rel, ontime = self._admit(bins_abs)
        if len(rel):
            self._note_rel(rel)
        return ontime

    def _acc_vals(self, batch: Batch) -> list:
        """One input array an accumulator: ones for a count."""
        n = batch.num_rows
        return [np.ones(n, dtype=dt) if inp is None
                else np.asarray(eval_expr(inp, batch.columns, n)).astype(dt)
                for inp, dt in zip(self.acc_inputs, self.acc_dtypes)]

    def mesh_stats(self):
        """Mesh-execution residency counters (None off the sharded path);
        obs/profile.py exports them as the arroyo_mesh_* series."""
        stats = getattr(self._agg, "mesh_stats", None)
        return stats() if stats is not None else None

    def _stage_batch(self, batch, ctx, collector) -> None:
        stage = self._stage
        if stage.kinds is None:
            stage.add(batch)
        elif batch.num_rows:
            self._stage_partials(batch)
        if not self._anchored():
            # the stream's first rows anchor the bin space: alone, as ever
            self.flush_staged(ctx, collector)
        while stage.rows >= stage.width:
            self._run_staged(collector)

    def flush_staged(self, ctx, collector):
        """Run the hook over what is staged, then handle the watermark that
        waited behind the rows. Opens every hook that must see the rows
        (barrier, close, wake, the compiled twins), and is what the task
        calls before it waits or stops."""
        stage = self._stage
        while stage.rows:
            self._run_staged(collector)
        wm, stage.watermark = stage.watermark, None
        if wm is not None:
            out = self._on_watermark(wm, collector)
            if out is not None:
                collector.broadcast(Signal.watermark_of(out))

    def handle_watermark(self, watermark, ctx, collector):
        stage = self._stage
        if stage.rows and self._moves_nothing(watermark):
            stage.watermark = watermark  # behind the staged rows; the newest wins
            return None
        while stage.rows:
            self._run_staged(collector)
        stage.watermark = None  # this one is newer
        return self._on_watermark(watermark, collector)


class TumblingAggregate(StagedAggregate):
    """config: width_micros, key_fields: list[str], aggregates:
    [(name, kind, Expr|None)], final_projection: [(name, Expr)]|None,
    input_dtype_of: callable Expr -> np.dtype (planner-provided), backend
    override "jax"|"numpy"|None."""

    def __init__(self, cfg: dict):
        self.width = self._bin_micros = int(cfg["width_micros"])
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
        # the planner's dtype of a column, where it gave the columns'
        # (prepare: the key transport split before the first batch)
        self._key_dtype_of = dtype_of if (
            cfg.get("input_dtype_of") or cfg.get("input_dtypes")) else None
        self._prepared_for: Optional[tuple] = None  # state: ephemeral — the (kinds, dtypes) prepare built the store for
        # key transport split, decided from the first batch's column dtypes
        self.lane_key_fields: Optional[list[str]] = None  # numeric: HBM lanes
        self.dict_key_fields: list[str] = []  # strings: host dictionary
        self.key_dict = KeyDictionary([])
        self.base_bin: Optional[int] = None  # micros bin offset for int32 device bins
        self.open_bins: set[int] = set()  # relative bins resident on device
        # late-data boundary; checkpointed into the "e" global table at
        # every barrier and restored in on_start (replay must drop exactly
        # the rows the original run dropped)
        self.emitted_before_rel: Optional[int] = None
        self.late_rows = 0  # state: ephemeral — observability counter (obs/profile.py export); never read into emitted data
        # in-flight closes: (future of a SlotExtractHandle's result|None, rel_before|None, Watermark|None, _batch_seq)
        self._pending: deque = deque()  # state: ephemeral — force-drained at every barrier (handle_checkpoint) before the snapshot
        self._batch_seq = 0  # state: ephemeral — orders in-flight closes within one incarnation; the queue is empty at every barrier
        self._wake = None  # state: ephemeral — the task's inbox wake (ctx.wake), taken anew at every on_start
        self._mesh_oflow_hwm = 0  # state: ephemeral — MESH_OVERFLOW event throttle high-water mark
        self._wm_edge: Optional[int] = None  # state: ephemeral — the edge (value // width) of the last watermark handled: one that repeats it may wait behind staged rows; unknown after a restore, so the first is handled
        self._stage = RowStage(self._partial_kinds())  # state: ephemeral — run dry by flush_staged before every snapshot, close and wait of the task

    # ------------------------------------------------------------------

    def tables(self):
        # retention = width: a bin's partials live until its window closes;
        # "e" holds the late-data barrier (same convention as session/
        # window_fn/InstantJoin) — global, so it survives an EMPTY partial
        # snapshot (every window closed at the barrier) where a column on
        # the "t" batch would be silently dropped
        return [TableSpec("t", "expiring_time_key", retention_micros=self.width),
                TableSpec("e", "global_keyed")]

    def _setup_key_transport(self, batch: Batch) -> None:
        """Split group-by columns by dtype: numeric values are carried in HBM
        as extra max-lanes (every row of a key holds the same value); the
        rest go through the host KeyDictionary."""
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
        self.acc_kinds = self.acc_kinds + tuple("max" for _ in lane)
        self.acc_dtypes = self.acc_dtypes + tuple(np.dtype(d) for _, d in lane)
        self.acc_inputs = self.acc_inputs + tuple(Col(f) for f, _ in lane)
        if self._agg is not None and self._prepared_for != (self.acc_kinds, self.acc_dtypes):
            self._agg = None  # prepared from the plan for other lanes: built anew

    def prepare(self):
        """On a mesh, build the sharded store now and run its two programs
        once on no rows: their compile (a minute and more each on a cold
        cache) is then part of the job's start, where Engine.build calls
        this, and not a stall of the stream at its first batch and again at
        its first close, with the barriers of that time queued behind it
        and every window of that time closing in one burst after it. Needs
        the planner's dtypes for the key columns (the lanes the first batch
        will ask for); a store the first batch finds built for other lanes
        is dropped (_setup_key_transport)."""
        mesh_n = int(config().get("device.mesh-devices") or 0)
        if (self.backend != "jax" or mesh_n <= 1 or self._key_dtype_of is None
                or "collect" in self.acc_kinds):
            return
        lanes = tuple(d for d in (np.dtype(self._key_dtype_of(Col(f))) for f in self.key_fields)
                      if np.issubdtype(d, np.integer) or np.issubdtype(d, np.floating))
        self._prepared_for = (self.acc_kinds + tuple("max" for _ in lanes),
                              self.acc_dtypes + lanes)
        self._agg = make_window_aggregator(*self._prepared_for, self.backend)
        self._agg.warm()

    def _aggregator(self):
        if self._agg is None:
            # mesh execution mode (device.mesh-devices > 1): key-space-
            # sharded state, keyed exchange = in-program all_to_all over ICI
            # (replaces the reference's repartition shuffle,
            # crates/arroyo-operator/src/context.rs:502-556)
            self._agg = make_window_aggregator(
                self.acc_kinds, self.acc_dtypes, self.backend)
        return self._agg

    def on_start(self, ctx):
        self._wake = ctx.wake
        tbl = ctx.table_manager.expiring_time_key("t", self.width)
        batches = tbl.all_batches()
        if batches:
            restored = Batch.concat(batches)
            self._restore_from_batch(restored)
            tbl.replace_all([])
        # late-data boundary (ABSOLUTE bin): replay must drop exactly the
        # rows the original run dropped, or window contents diverge after a
        # restore. Watermark-aligned, so max merges subtasks/rescales.
        barriers = restore_marks(ctx, "e")
        if barriers:
            eb_abs = max(barriers)
            if self.base_bin is None:
                # empty partial snapshot (every window closed at the
                # barrier): anchor the bin space at the boundary itself
                self.base_bin = eb_abs
            self.emitted_before_rel = eb_abs - self.base_bin

    def _restore_from_batch(self, b: Batch) -> None:
        # checkpoints carry every key field as a named column, so the
        # transport split can be re-derived from the checkpoint batch itself
        if self.lane_key_fields is None:
            self._setup_key_transport(b)
        hashes = b.keys.astype(np.uint64)
        starts = b.timestamps
        bins_abs = starts // self.width
        self.base_bin = int(bins_abs.min())
        rel = (bins_abs - self.base_bin).astype(np.int32)
        accs = [b[f"__acc_{i}"].astype(d)
                for i, d in enumerate(self.acc_dtypes[: self.n_user_accs])]
        accs += [np.asarray(b[f]).astype(d)
                 for f, d in zip(self.lane_key_fields,
                                 self.acc_dtypes[self.n_user_accs:])]
        self._aggregator().restore(hashes, rel, accs)
        self.open_bins = set(np.unique(rel).tolist())
        if self.dict_key_fields:
            self.key_dict.observe(hashes, rel, b)

    # ------------------------------------------------------------------

    def _rows_coming(self, collector) -> None:
        self._batch_seq += 1
        if self._pending:
            self._drain_pending(collector)

    def _anchored(self) -> bool:
        return self.base_bin is not None

    def _late_boundary(self) -> Optional[int]:
        # rows behind already-emitted windows are late
        return self.emitted_before_rel

    def _note_bins(self, bins: list) -> None:
        self.open_bins.update(bins)

    # ------------------------------------------------------------- emission

    def _drain_pending(self, collector, force: bool = False,
                       woke: bool = False) -> None:
        """Emit completed in-flight closes in order; each close's watermark
        broadcasts only after its rows, preserving downstream lateness
        semantics. ``woke``: called from drain_ready, on a completion wake."""
        while self._pending:
            fut, rel_before, wm, _seq = self._pending[0]
            if fut is not None and not force and not fut.is_ready():
                return
            self._pending.popleft()
            if fut is not None:
                _trace.close_left((rel_before + self.base_bin) * self.width, woke)
                keys, bins, accs = fut.result()
                if len(keys):
                    self._emit_entries(keys, bins, accs, collector)
                if self.dict_key_fields:
                    self.key_dict.evict_closed(rel_before)
            if wm is not None:
                collector.broadcast(Signal.watermark_of(wm))

    def closes_in_flight(self) -> bool:
        return bool(self._pending)

    def drain_ready(self, ctx, collector):
        self.flush_staged(ctx, collector)
        self._drain_pending(collector, woke=True)

    def _moves_nothing(self, watermark) -> bool:
        """The edge ``value // width`` is the one the last watermark
        handled had, and no close is in flight or held: handled now or
        behind any rows on time, this one closes no bin, moves no boundary
        and forwards the value that one forwarded."""
        return (not watermark.is_idle and not self._pending
                and watermark.value // self.width == self._wm_edge)

    def _on_watermark(self, watermark, collector):
        if watermark.is_idle:
            self._drain_pending(collector, force=True)
            return watermark
        if self._pending:
            # closes that landed since the last hook and whose wake the task
            # has not taken yet (it was busy, or its inbox never ran dry)
            self._drain_pending(collector)
        closed_before_abs = self._wm_edge = watermark.value // self.width
        # Future emissions are stamped with a window start >= bin_start(w);
        # forward that instead of w so downstream operators (e.g. windowed
        # joins) never see our output as late. The reference forwards w
        # unchanged and relies on sparse watermarks; with dense per-batch
        # watermarks the adjusted value is required for correctness.
        out_wm = Watermark.event_time(closed_before_abs * self.width)
        scheduled = self._schedule_close(closed_before_abs, out_wm, collector)
        if scheduled or self._pending:
            return None  # watermark rides the pending queue, in order
        return out_wm

    def on_close(self, ctx, collector):
        self.flush_staged(ctx, collector)
        self._schedule_close(None, None, collector)
        self._drain_pending(collector, force=True)

    def _hold_watermark(self, out_wm: Optional[Watermark], collector) -> bool:
        """No bins are closing: if earlier closes are still in flight, queue
        the watermark behind them (bounded by the pipeline depth); returns
        True when held, False when the caller should forward it."""
        if out_wm is None or not self._pending:
            return False
        tail = self._pending[-1]
        if tail[0] is None and tail[2] is not None:
            # consecutive watermarks with no rows between them collapse to
            # the newest — only the latest matters downstream, and appending
            # each would churn the depth bound into needless force-drains
            self._pending[-1] = (None, None, out_wm, tail[3])
            return True
        if len(self._pending) >= _PIPELINE_DEPTH:
            self._drain_pending(collector, force=True)
            return False
        self._pending.append((None, None, out_wm, self._batch_seq))
        return True

    def _schedule_close(self, closed_before_abs: Optional[int],
                        out_wm: Optional[Watermark], collector) -> bool:
        """Dispatch the device extraction for every bin closed by the
        watermark; returns True if a close (or watermark hold) was queued."""
        if self.base_bin is None or not self.open_bins:
            return self._hold_watermark(out_wm, collector)
        if closed_before_abs is None:
            rel_before = max(self.open_bins) + 1
        else:
            rel_before = int(closed_before_abs - self.base_bin)
        if self.emitted_before_rel is None or rel_before > self.emitted_before_rel:
            self.emitted_before_rel = rel_before
        closing = sorted(b for b in self.open_bins if b < rel_before)
        if not closing:
            return self._hold_watermark(out_wm, collector)
        agg = self._aggregator()
        self.open_bins -= set(closing)
        if self.backend == "numpy":
            keys, bins, accs = agg.extract(min(closing), rel_before, rel_before)
            if len(keys):
                self._emit_entries(keys, bins, accs, collector)
            if self.dict_key_fields:
                self.key_dict.evict_closed(rel_before)
            return False  # synchronous: caller forwards the watermark itself
        if len(self._pending) >= _PIPELINE_DEPTH:
            self._drain_pending(collector, force=True)
        # every window that ends at or before this edge closes here
        with _trace.window((rel_before + self.base_bin) * self.width):
            handle = agg.extract_start(min(closing), rel_before, rel_before)
        from ..ops.prefetch import shared_prefetcher

        fut = shared_prefetcher().submit(handle.result, on_done=self._wake,
                                        program=getattr(handle, "program", None))
        self._pending.append((fut, rel_before, out_wm, self._batch_seq))
        return True

    def _emit_entries(self, keys, bins, accs, collector) -> None:
        from ..ops.aggregate import finalize_aggs

        starts = (bins.astype(np.int64) + self.base_bin) * self.width
        cols: dict[str, np.ndarray] = {}
        if self.dict_key_fields:
            cols.update(self.key_dict.lookup_columns(keys))
        for f, lane in zip(self.lane_key_fields, accs[self.n_user_accs:]):
            cols[f] = lane
        cols[WINDOW_START] = starts
        cols[WINDOW_END] = starts + self.width
        finals = finalize_aggs([a[1] for a in self.aggregates], accs[: self.n_user_accs])
        for (name, _k, _e), arr in zip(self.aggregates, finals):
            cols[name] = arr
        # reference stamps the window start as the output event time
        cols[TIMESTAMP_FIELD] = starts
        out = Batch(cols)
        if self.final_projection is not None:
            n = out.num_rows
            proj = {name: eval_expr(e, out.columns, n) for name, e in self.final_projection}
            if TIMESTAMP_FIELD not in proj:
                proj[TIMESTAMP_FIELD] = out.timestamps
            out = Batch(proj)
        # the watermark trail: the rows of the windows ending up to here leave
        _trace.mark("rows.out", int(starts.max()) + self.width, rows=out.num_rows)
        if self.counts_pairs:
            _trace.distinct_pairs(out.num_rows)
        collector.collect(out)

    # ------------------------------------------------------------------

    def handle_checkpoint(self, barrier, ctx, collector):
        # the snapshot holds every row before the barrier
        self.flush_staged(ctx, collector)
        # flush in-flight emissions first: their rows/watermarks must precede
        # the barrier, and the snapshot must not race follow-up extractions
        self._drain_pending(collector, force=True)
        # the late-data barrier persists UNCONDITIONALLY — an empty partial
        # snapshot (all windows closed) must not lose the boundary
        persist_mark(ctx, "e",
                     None if self.emitted_before_rel is None
                     else self.emitted_before_rel + (self.base_bin or 0))
        tbl = ctx.table_manager.expiring_time_key("t", self.width)
        if self._agg is None:
            # no data yet: building the aggregator here would freeze acc_kinds
            # before _setup_key_transport appends the numeric key lanes, so
            # later updates would silently drop lane values (zip truncation)
            tbl.replace_all([])
            return
        keys, bins, accs = self._agg.snapshot()
        record_mesh_overflow(self, ctx)
        if len(keys) == 0:
            tbl.replace_all([])
            return
        starts = (bins.astype(np.int64) + (self.base_bin or 0)) * self.width
        cols: dict[str, np.ndarray] = {
            TIMESTAMP_FIELD: starts,
            KEY_FIELD: keys,
        }
        if self.dict_key_fields:
            cols.update(self.key_dict.lookup_columns(keys))
        for f, lane in zip(self.lane_key_fields or [], accs[self.n_user_accs:]):
            cols[f] = lane
        for i, a in enumerate(accs[: self.n_user_accs]):
            cols[f"__acc_{i}"] = a
        tbl.replace_all([Batch(cols)])


@register_operator(OpName.TUMBLING_AGGREGATE)
def _make_tumbling(cfg: dict):
    return TumblingAggregate(cfg)
