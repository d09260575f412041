"""A keyed aggregate's hook over a staged step is one native pass (ISSUE 53):
``native.StepMaker`` (cpp ``ah_step_make``) reads the staged batches' event
times, keys and accumulator columns where they lie and writes the step's
inputs as the device takes them: the kept rows' keys and relative bins for
the directory, each shipped lane cast to its dtype and padded to
``device.batch-capacity`` with its identity, nothing for a ``count``. The
directory's two calls then write the slots in the step's index dtype, padded
with the capacity. What reaches the directory and the jitted step, what is
counted late, which bins stay open, what leaves at a close and what a
checkpoint holds are what the numpy hook gives over the same batches
(``op._maker = False``: the parent's path, and this file's oracle).

The rig (an operator inside a real Task, a recording sink, the checkpoint
read back column by column) is ``tests/test_stage.py``'s.
"""

from __future__ import annotations

import numpy as np
import pytest
from test_keyless_stage import after_the_barrier, no_library  # noqa: F401 (fixture)
from test_stage import (WIDTH, Recorder, _closes_land_at_once,  # noqa: F401 (fixtures)
                        _shipped_step_width, bare, barrier, checkpointed, rig, wm)

from arroyo_tpu.batch import KEY_FIELD, TIMESTAMP_FIELD, Batch
from arroyo_tpu.hashing import hash_columns
from arroyo_tpu.obs import trace
from arroyo_tpu.types import Signal, Watermark

W = 1_000_000  # micros: tumbling width, sliding slide (its width is 3 slides)
DTYPES = [np.int32, np.int64, np.float32, np.float64]
KINDS = ["sum", "count", "min", "max"]
ALL_FOUR = [("cnt", "count", None), ("sm", "sum", "v"), ("mn", "min", "v"), ("mx", "max", "v")]


class Keyed:
    """A window aggregate grouped by ``k`` as the planner builds it; with
    ``numpy_hook`` its staged steps keep the hook in numpy."""

    def __init__(self, op_name, aggregates=ALL_FOUR, dtype=np.int64, numpy_hook=False,
                 key_fields=("k",), backend=None):
        self.name, self.op_name = op_name.split("_")[0], op_name
        self.aggregates, self.dtype = aggregates, np.dtype(dtype)
        self.numpy_hook, self.key_fields, self.backend = numpy_hook, list(key_fields), backend

    def make(self, backend):
        from arroyo_tpu.engine.engine import construct_operator
        from arroyo_tpu.expr import Col
        from arroyo_tpu.graph import OpName

        cfg = {"width_micros": W, "key_fields": self.key_fields,
               "backend": self.backend or backend,
               "aggregates": [(n, k, Col(e) if isinstance(e, str) else e)
                              for n, k, e in self.aggregates],
               "input_dtype_of": lambda e: self.dtype}
        if self.op_name == "sliding_aggregate":
            cfg.update(width_micros=3 * W, slide_micros=W)
        op = construct_operator(OpName(self.op_name), cfg)
        if self.numpy_hook:
            op._maker = False
        return op


def rows(ts, seed, dtype=np.int64, keys=97) -> Batch:
    """Keyed rows: a key column and a value of ``dtype``, the value of
    either sign and small enough that a float32 sum of a window is exact."""
    ts = np.asarray(ts, dtype=np.int64)
    rng = np.random.default_rng(seed)
    k = rng.integers(0, keys, len(ts))
    return Batch({TIMESTAMP_FIELD: ts, "k": k.astype(dtype),
                  "v": rng.integers(-500, 500, len(ts)).astype(dtype),
                  KEY_FIELD: hash_columns([k])})


def stream(dtype=np.int64, per_batch=471, n_batches=40):
    """Windows 5..10 in event-time order in batches that straddle a bin's
    end, every eleventh row four and a half windows behind its neighbours
    (late once its window has closed: in the middle of its batch), a
    watermark that moves nothing behind most batches and one that closes
    behind every fourth, a barrier in the middle, end of data."""
    n = per_batch * n_batches
    ts = 5 * W + np.arange(n, dtype=np.int64) * (6 * W // n)
    ts[::11] -= 9 * W // 2
    items = []
    for i in range(n_batches):
        lo, hi = i * per_batch, (i + 1) * per_batch
        items.append(rows(ts[lo:hi], i, dtype))
        if i % 3 == 0:
            items.append(wm(ts[:hi].max() - 3 * W // 2))
        if i % 4 == 3:
            items.append(wm(ts[:hi].max() - W // 7))
        if i == n_batches // 2:
            items.append(barrier())
    items.append(Signal.end_of_data())
    return items


def whole_and_restored(rig, kind, job, items):
    """The stream run whole, its checkpoint, and the rest of it run from the
    checkpoint: what left, the late rows, the state, the bins left open."""
    a = rig(kind, "jax", job).backlog(items).join()
    state = checkpointed(a, kind, "jax")
    b = rig(kind, "jax", job, restore_epoch=1).backlog(after_the_barrier(items)).join()
    ops = a.agg_op(), b.agg_op()
    return (a.sink.events(), state, b.sink.events(),
            [(op.late_rows, sorted(op.open_bins), getattr(op, "min_bin", None),
              getattr(op, "max_bin", None)) for op in ops]), a


# ------------------------------------------------ end to end, a kind a lane


@pytest.mark.parametrize("op_name", ["tumbling_aggregate", "sliding_aggregate"])
@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: np.dtype(d).name)
@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("library", [True, False], ids=["library", "no-library"])
def test_the_pass_leaves_what_the_numpy_hook_leaves(rig, request, op_name, dtype, kind, library):
    """One accumulator of each kind over each lane dtype, and a key lane of
    that dtype beside it: the rows that leave at every close, the watermarks
    behind them, ``late_rows``, the bins left open, the table a barrier
    snapshots in the middle of a window (every column's bytes) and the rest
    of the stream from that snapshot equal the numpy hook's; without the
    library the same operator runs the numpy hook itself."""
    aggs = [("a", kind, None if kind == "count" else "v")]
    items = stream(dtype)
    want, by_numpy = whole_and_restored(
        rig, Keyed(op_name, aggs, dtype, numpy_hook=True), "numpy", items)
    if not library:
        request.getfixturevalue("no_library")
    got, by_pass = whole_and_restored(rig, Keyed(op_name, aggs, dtype), "pass", items)
    assert by_numpy.agg_op()._maker is False and bool(by_pass.agg_op()._maker) == library
    events, state, rest, ops = want
    assert sum(e[0] == "row" for e in events) > 300 and ops[0][0] > 100 and ops[1][0] > 0
    assert len(state) == 2 and len(state[1]) >= 4  # "e", and "t" with its columns
    assert got == want
    # every step but the restore's (its rows are partials: the merge step,
    # as ever); the two runs share a job, so one task's counters
    made = [s.args["made"] for s in trace.spans("agg.dispatch", job=by_pass.job)]
    assert len(made) > 5 and made.count("numpy") == (1 if library else len(made))
    assert by_pass.counters()["arroyo_worker_steps_made_native"] == made.count("native")


# ------------------------------------------- step by step, what the device takes


@pytest.fixture
def steps(monkeypatch):
    """Every slot aggregate built inside keeps what its directory and its
    jitted step were handed: ``agg.seen`` = [(keys, bins)] a chunk,
    ``agg.shipped`` = [(slots, lanes)] a step, copies, pad included."""
    from arroyo_tpu.ops.slot_agg import SlotAggregator

    init, chunk = SlotAggregator.__init__, SlotAggregator._update_chunk

    def built(self, *a, **k):
        init(self, *a, **k)
        self.seen, self.shipped, step = [], [], self._step

        def recorded(state, slots, lanes):
            self.shipped.append((np.array(slots), [np.array(v) for v in lanes]))
            return step(state, slots, lanes)
        self._step = recorded

    def update_chunk(self, key_u64, bins, vals):
        self.seen.append((np.array(key_u64), np.array(bins)))
        return chunk(self, key_u64, bins, vals)

    monkeypatch.setattr(SlotAggregator, "__init__", built)
    monkeypatch.setattr(SlotAggregator, "_update_chunk", update_chunk)


def drive(kind, storage, dtype):
    """The cases one after the other on one operator, and what each left."""
    op, ctx, col = bare(kind, "jax", storage)
    at = lambda w, n, step=1: w * W + np.arange(n, dtype=np.int64) * step
    log = []

    def note(case):
        log.append((case, op.late_rows, sorted(op.open_bins), getattr(op, "min_bin", None),
                    getattr(op, "max_bin", None), op._stage.rows, len(op._agg.shipped)))

    # the stream's first rows anchor the bin space alone, bin 7 among them
    # though bin 5 comes first in event time only from the second row on
    first = rows(np.concatenate([at(7, 40), at(5, 60)]), 0, dtype)
    op.process_batch(first, ctx, col)
    note("first")
    # a backlog past a step's width: seventeen 512-row batches, the last one
    # split at the width with its rest staged; one batch spans three bins
    for i in range(17):
        ts = at(5, 512, 3 * W // 512) if i == 4 else at(5 + i % 2, 512, 1000)
        op.process_batch(rows(ts, i + 1, dtype), ctx, col)
    note("split")
    assert op._stage.rows == 17 * 512 - WIDTH
    op.flush_staged(ctx, col)
    note("rest")
    # a watermark closes window 5 (the sliding aggregate: extracts bin 5);
    # then rows late in the middle of a batch, and a batch of late rows alone
    op.handle_watermark(Watermark.event_time(6 * W + 5), ctx, col)
    note("closed")
    ts = at(6, 400, 2000)
    ts[100:250] = at(5, 150, 100)
    op.process_batch(rows(ts, 30, dtype), ctx, col)
    op.process_batch(rows(at(7, 300, 1000), 31, dtype), ctx, col)
    op.flush_staged(ctx, col)
    note("late in the middle")
    op.process_batch(rows(at(5, 200, 50), 32, dtype), ctx, col)
    op.flush_staged(ctx, col)
    note("every row late")
    op.on_close(ctx, col)
    note("end")
    agg = op._agg
    out = [(sorted(b.columns), [b.columns[c].tobytes() for c in sorted(b.columns)])
           if isinstance(b, Batch) else ("wm", b.watermark.value) for b in col.items]
    return op, log, agg.seen, agg.shipped, out


@pytest.mark.parametrize("op_name", ["tumbling_aggregate", "sliding_aggregate"])
@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: np.dtype(d).name)
def test_a_made_step_is_the_numpy_hooks_step(steps, _storage, op_name, dtype):
    """All four kinds and a key lane of one dtype, step by step: the keys
    and relative bins the directory is handed, the slots and every shipped
    lane the jitted step is handed (dtype, values, the pad to the step's
    width), ``late_rows``, ``open_bins``, ``min_bin`` / ``max_bin`` and the
    rows staged after each case, and every batch and watermark that left."""
    from arroyo_tpu import native

    if not native.available():
        pytest.skip("the native library is not built")
    by_pass, log, seen, shipped, out = drive(Keyed(op_name, dtype=dtype), _storage, dtype)
    by_numpy, want_log, want_seen, want_shipped, want_out = drive(
        Keyed(op_name, dtype=dtype, numpy_hook=True), _storage, dtype)
    assert by_pass._maker and by_numpy._maker is False
    assert log == want_log
    cases = {c[0]: c for c in log}
    assert cases["first"][6] == 1 and len(seen[0][0]) == 100  # alone, and whole
    assert cases["split"][5] == 17 * 512 - WIDTH and cases["split"][6] == 2
    assert cases["late in the middle"][1] == 150 and cases["every row late"][1] == 350
    assert cases["every row late"][6] == cases["late in the middle"][6]  # no step for no row
    assert len(seen) == len(want_seen) == len(shipped) == len(want_shipped) == 4
    for (keys, bins), (want_keys, want_bins) in zip(seen, want_seen):
        assert bins.dtype == want_bins.dtype == np.int32 and keys.dtype == np.uint64
        assert keys.tolist() == want_keys.tolist() and bins.tolist() == want_bins.tolist()
    assert len(set(seen[1][1].tolist())) == 3  # the step that spans three bins
    for (slots, lanes), (want_slots, want_lanes) in zip(shipped, want_shipped):
        assert slots.dtype == want_slots.dtype and len(slots) == WIDTH
        assert slots.tobytes() == want_slots.tobytes()
        # sum, min, max and the key lane: the count ships nothing
        assert len(lanes) == len(want_lanes) == 4
        for lane, want in zip(lanes, want_lanes):
            assert lane.dtype == want.dtype == np.dtype(dtype) and lane.tobytes() == want.tobytes()
    assert out == want_out and len(out) >= 2


def test_an_expression_lane_is_one_more_column(steps, _storage):
    """An accumulator whose input is an expression (q15's filtered counts:
    a sum of a CASE) is evaluated by ``eval_expr`` over the step's rows and
    handed to the pass as a column beside the plain ones."""
    from arroyo_tpu import native
    from arroyo_tpu.expr import BinOp, Col, Lit

    if not native.available():
        pytest.skip("the native library is not built")
    aggs = [("cnt", "count", None), ("big", "sum", BinOp(">", Col("v"), Lit(0))),
            ("twice", "max", BinOp("*", Col("v"), Lit(2))), ("mn", "min", "v")]
    by_pass, log, seen, shipped, out = drive(Keyed("tumbling_aggregate", aggs), _storage, np.int64)
    _, want_log, want_seen, want_shipped, want_out = drive(
        Keyed("tumbling_aggregate", aggs, numpy_hook=True), _storage, np.int64)
    assert by_pass._maker and by_pass._lane_sources[2:] == ["v", "k"]
    assert (log, out) == (want_log, want_out)
    for (slots, lanes), (want_slots, want_lanes) in zip(shipped, want_shipped):
        assert slots.tobytes() == want_slots.tobytes()
        assert [a.tobytes() for a in lanes] == [a.tobytes() for a in want_lanes]


def test_a_step_the_pass_gives_back_runs_the_numpy_hook(steps, _storage):
    """A batch whose column is not what the first batch's was (another
    dtype) is no step the pass takes: the numpy hook runs over the same
    rows, and the span and the counter say so."""
    from arroyo_tpu import native

    if not native.available():
        pytest.skip("the native library is not built")
    op, ctx, col = bare(Keyed("tumbling_aggregate"), "jax", _storage)
    op.process_batch(rows(5 * W + np.arange(100), 0), ctx, col)
    odd = rows(5 * W + np.arange(300), 1)
    odd = odd.with_column("v", odd["v"].astype(np.int32))
    op.process_batch(rows(5 * W + np.arange(200), 2), ctx, col)
    op.process_batch(odd, ctx, col)
    op.flush_staged(ctx, col)
    assert [len(k) for k, _b in op._agg.seen] == [100, 500]
    want, wctx, wcol = bare(Keyed("tumbling_aggregate", numpy_hook=True), "jax", _storage)
    want.process_batch(rows(5 * W + np.arange(100), 0), wctx, wcol)
    want.process_batch(rows(5 * W + np.arange(200), 2), wctx, wcol)
    want.process_batch(odd, wctx, wcol)
    want.flush_staged(wctx, wcol)
    for got, w in zip(op._agg.snapshot()[2], want._agg.snapshot()[2]):
        assert got.tolist() == w.tolist()


# ------------------------------------------------------------------ who stays


@pytest.mark.parametrize("kind,why", [
    (Keyed("tumbling_aggregate", key_fields=["k", "ch"]), "a KeyDictionary key"),
    (Keyed("sliding_aggregate", key_fields=["k", "ch"]), "a KeyDictionary key"),
    (Keyed("tumbling_aggregate", [("vs", "collect", "v")], backend="numpy"), "a collecting lane"),
    (Keyed("tumbling_aggregate", backend="numpy"), "the numpy backend"),
    (Keyed("sliding_aggregate", backend="numpy"), "the numpy backend"),
    (Keyed("tumbling_aggregate"), "a mesh"),
    (Keyed("tumbling_aggregate"), "no library"),
    (Keyed("sliding_aggregate"), "no library"),
    (Keyed("tumbling_aggregate", key_fields=[]), "a keyless aggregate's partials"),
    (Keyed("tumbling_aggregate", dtype=np.uint64), "an unsigned lane"),
    (Keyed("tumbling_aggregate"), None),
    (Keyed("sliding_aggregate"), None),
], ids=lambda p: p.name if isinstance(p, Keyed) else str(p).replace(" ", "-"))
def test_who_keeps_the_numpy_hook(rig, request, kind, why):
    """A string key field, a collected list, the numpy backend, a mesh, a
    host without the library, a keyless aggregate's partials and a lane the
    pass has no type for keep the hook in numpy; ``agg.make``,
    ``agg.dispatch``'s ``made`` and the task's counter say which ran."""
    from arroyo_tpu import config as cfg

    if why == "no library":
        request.getfixturevalue("no_library")
    if why == "a mesh":
        cfg.update({"device.mesh-devices": 4, "device.batch-capacity": 1024})

    def keyed(ts, seed):
        b = rows(ts, seed, np.uint64 if why == "an unsigned lane" else np.int64)
        return b.with_column("ch", np.array(["a", "b"], dtype=object)[b["k"].astype(int) % 2])

    items = [keyed(5 * W + np.arange(100), 0), keyed(5 * W + np.arange(600) * 3000, 1),
             keyed(6 * W + np.arange(500), 2), wm(9 * W), Signal.end_of_data()]
    r = rig(kind, "jax", "who").backlog(items).join()
    op = r.agg_op()
    native_steps = r.counters()["arroyo_worker_steps_made_native"]
    steps = trace.spans("agg.dispatch", job=r.job)
    makes = trace.spans("agg.make", job=r.job)
    assert sum(it.num_rows for it in r.sink.items if isinstance(it, Batch)) > 0
    if why is None:
        assert op._maker and native_steps == len(steps) == len(makes) == 2
        assert {s.args["made"] for s in steps + makes} == {"native"}
        assert [(s.args["rows"], s.args["batches"]) for s in makes] == [(100, 1), (1100, 2)]
    else:
        assert not op._maker and native_steps == 0, why
        assert {s.args["made"] for s in steps + makes} <= {"numpy"}
        assert len(makes) >= 2  # the numpy hook's same stretch, named on this side too
        # only the slot aggregate on one chip records its steps as spans
        assert bool(steps) == (kind.backend != "numpy")


# --------------------------------------------------------------------- the lock


@pytest.mark.parametrize("op_name,aggs", [
    ("sliding_aggregate", [("cnt", "count", None)]),   # q5's first level: a count and its key lane
    ("tumbling_aggregate", [("mx", "max", "v")]),      # q7's per-auction table: a max and its key lane
], ids=["sliding-count", "tumbling-max"])
def test_a_staged_step_hands_the_lock_over_for_the_directory_and_the_step(op_name, aggs):
    """A staged step at the cells' shapes (512-row inbox batches with event
    times, a BIGINT key column, ``_key`` and a value) lets go of the
    interpreter lock in at most 8 places where it is padded (4,608 rows) and
    at most 7 where it is full (8,192): the directory's call or two and the
    jitted call's three; the numpy hook did in 24-28 and 19-25 (ISSUE 53,
    step 0). The call is repeated: a busy machine misses most hand-overs of
    one run (PERF.md section 7, "From PR 49" (5)), and the count is never
    too high."""
    from interpreter_lock import hand_overs

    from arroyo_tpu import config as cfg
    from arroyo_tpu import native

    if not native.available():
        pytest.skip("the native library is not built")
    # the table as shipped: 9,000 keys never grow it
    cfg.update({"device.table-capacity": 65536, "device.region-size": 2048})
    col = Recorder()
    pool = [rows(60 * W + (np.arange(512) * 7 + i) % 900_000, i, keys=9000) for i in range(64)]
    for batches, most in ((9, 8), (16, 7)):
        op = Keyed(op_name, aggs).make("jax")
        op.process_batch(pool[0], None, col)
        op.flush_staged(None, col)
        assert op._maker
        seq = iter(range(1, 10 ** 6))

        def step():
            for _ in range(batches):
                op._stage.add(pool[next(seq) % 64])
            op.flush_staged(None, col)

        assert 1 <= hand_overs(step, runs=30) <= most
