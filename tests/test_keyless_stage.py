"""A keyless aggregate stages partials, not rows (ISSUE 52): where the plan
gives a window aggregate no key and every accumulator combines exactly
(``sum`` / ``count`` / ``min`` / ``max`` over signed integers), each inbox
batch is combined to one row a bin in one native call as it is staged, the
stage keeps a row an open bin, and a flush is one merge step. What leaves,
what is counted late and what a checkpoint holds are what the step over the
rows themselves gives, on every store, with and without the host library.

The rig (an operator inside a real Task, a recording sink, the checkpoint
read back column by column) is ``tests/test_stage.py``'s.
"""

from __future__ import annotations

import numpy as np
import pytest
from test_stage import (_closes_land_at_once, _shipped_step_width, rig,  # noqa: F401 (fixtures)
                        barrier, checkpointed, wait_until, wm)

from arroyo_tpu.batch import KEY_FIELD, TIMESTAMP_FIELD, Batch
from arroyo_tpu.hashing import hash_columns
from arroyo_tpu.obs import trace
from arroyo_tpu.types import Signal, SignalKind

W = 1_000_000  # micros: tumbling width, sliding slide (its width is 3 slides)
FOUR = [("cnt", "count", None), ("sm", "sum", "v"), ("mn", "min", "v"), ("mx", "max", "v")]


class Keyless:
    """A window aggregate as the planner builds it, grouped by the window
    alone unless ``key_fields`` says otherwise."""

    def __init__(self, op_name, aggregates=FOUR, dtype=np.int64, key_fields=(),
                 rows_path=False):
        self.name, self.op_name = op_name.split("_")[0], op_name
        self.aggregates, self.dtype, self.key_fields = aggregates, np.dtype(dtype), list(key_fields)
        self.rows_path = rows_path

    def make(self, backend):
        from arroyo_tpu.engine.engine import construct_operator
        from arroyo_tpu.expr import Col
        from arroyo_tpu.graph import OpName
        from arroyo_tpu.windows.tumbling import RowStage

        cfg = {"width_micros": W, "key_fields": self.key_fields, "backend": backend,
               "aggregates": [(n, k, Col(e) if e else None) for n, k, e in self.aggregates],
               "input_dtype_of": lambda e: self.dtype}
        if self.op_name == "sliding_aggregate":
            cfg.update(width_micros=3 * W, slide_micros=W)
        op = construct_operator(OpName(self.op_name), cfg)
        if self.rows_path:
            op._stage = RowStage()  # the parent's path: the hook over the rows themselves
        return op


def rows(ts, seed, carried_key=False, dtype=np.int64) -> Batch:
    """Rows with a value that takes either sign and most of its width."""
    ts = np.asarray(ts, dtype=np.int64)
    rng = np.random.default_rng(seed)
    info = np.iinfo(dtype)
    cols = {TIMESTAMP_FIELD: ts,
            "v": rng.integers(info.min // 8192, info.max // 8192, len(ts), dtype=dtype)}
    if carried_key:
        # what a join upstream leaves on its rows: its own routing key
        cols[KEY_FIELD] = hash_columns([rng.integers(0, 5, len(ts))])
    return Batch(cols)


def stream(carried_key=False, dtype=np.int64):
    """Windows 5..9 in event-time order: batches that straddle a bin's end
    (two bins in one batch), a watermark that moves nothing and one that
    closes behind most batches, rows four and a half windows behind their
    neighbours before and after the watermarks that make them late, a
    barrier in the middle of window 7, end of data."""
    n, per_batch = 4000, 250
    ts = 5 * W + np.arange(n, dtype=np.int64) * (5 * W // n)
    ts[::11] -= 9 * W // 2
    items = []
    for i in range(n // per_batch):
        lo, hi = i * per_batch, (i + 1) * per_batch
        items.append(rows(ts[lo:hi], i, carried_key, dtype))
        if i % 3 == 0:
            items.append(wm(ts[:hi].max() - 3 * W // 2))  # most repeat the edge
        if i % 4 == 3:
            items.append(wm(ts[:hi].max() - W // 7))
        if i == 9:
            items.append(barrier())
    items.append(Signal.end_of_data())
    return items


@pytest.fixture
def no_library():
    """A host without the library: every native entry point falls back."""
    from arroyo_tpu import native

    saved = native._lib, native._lib_failed
    native._lib, native._lib_failed = None, True
    yield
    native._lib, native._lib_failed = saved


def after_the_barrier(items):
    return items[next(i for i, it in enumerate(items) if isinstance(it, Signal)
                      and it.kind == SignalKind.BARRIER) + 1:]


def whole_and_restored(rig, kind, backend, job, items):
    """The stream run whole, its checkpoint, and the rest of it run from the
    checkpoint: the events that left, the late rows, the state."""
    a = rig(kind, backend, job).backlog(items).join()
    state = checkpointed(a, kind, backend)
    b = rig(kind, backend, job, restore_epoch=1).backlog(after_the_barrier(items)).join()
    return (a.sink.events(), a.agg_op().late_rows, state,
            b.sink.events(), b.agg_op().late_rows), a


# ------------------------------------------------------------- equivalence


@pytest.mark.parametrize("op_name", ["tumbling_aggregate", "sliding_aggregate"])
@pytest.mark.parametrize("backend,library", [
    ("jax", True), ("jax", False), ("numpy", True), ("numpy", False)],
    ids=["slot-table", "slot-table-no-library", "numpy-backend", "numpy-backend-no-library"])
def test_partials_leave_what_the_rows_leave(rig, request, op_name, backend, library):
    """count, sum, min and max over int64 with no key: the rows out, the
    watermarks behind them, ``late_rows`` and the table a barrier snapshots
    in the middle of a window, then the rest of the stream from that
    snapshot, equal the parent's path (the hook over the rows) on the same
    store, whether the combine is the native call or numpy's; and the
    tumbling aggregate's on the numpy backend equal the slot table's. (The
    sliding aggregate's two backends never drew the late boundary alike:
    only the device path counts a bin late once it is extracted.)"""
    items = stream()
    want, by_rows = whole_and_restored(rig, Keyless(op_name, rows_path=True), backend, "rows", items)
    if backend == "numpy" and op_name == "tumbling_aggregate":
        assert want[:2] == whole_and_restored(
            rig, Keyless(op_name, rows_path=True), "jax", "slot-rows", items)[0][:2]
    if not library:
        request.getfixturevalue("no_library")
    got, by_partials = whole_and_restored(rig, Keyless(op_name), backend, "partials", items)
    assert by_rows.agg_op()._stage.kinds is None and by_partials.agg_op()._stage.kinds
    events, late, state, rest, rest_late = want
    assert sum(e[0] == "row" for e in events) >= 5 and late > 100 and rest_late > 0
    assert len(state) == 2 and len(state[1]) >= 5  # "e", and "t" with its columns
    assert got == want
    # one row a window left: the table held one slot a bin
    starts = [e for e in events if e[0] == "row"]
    assert len({e[-2:] for e in starts}) == len(starts)


@pytest.mark.parametrize("dtype", [np.int32, np.int64], ids=["int32", "int64"])
@pytest.mark.parametrize("kind", ["count", "sum", "min", "max"])
def test_each_kind_and_width_alone(rig, kind, dtype):
    """One accumulator a run, over 4- and 8-byte lanes: partials equal rows."""
    aggs = [("a", kind, None if kind == "count" else "v")]
    items = stream(dtype=dtype)
    want, _ = whole_and_restored(rig, Keyless("tumbling_aggregate", aggs, dtype, rows_path=True),
                                 "jax", "rows", items)
    got, r = whole_and_restored(rig, Keyless("tumbling_aggregate", aggs, dtype), "jax",
                                "partials", items)
    assert r.agg_op()._stage.kinds == (kind,)
    assert got == want and want[1] > 100


def test_a_sum_wraps_as_the_lane_does(rig):
    """Partials merged in the stage and in the table wrap where a sum of
    the rows wraps: modulo 2**64."""
    big = np.iinfo(np.int64).max // 3
    b = lambda seed: Batch({TIMESTAMP_FIELD: np.full(7, 5 * W + seed, dtype=np.int64),
                            "v": np.full(7, big, dtype=np.int64)})
    items = [b(0), b(1), b(2), wm(7 * W), Signal.end_of_data()]
    aggs = [("sm", "sum", "v")]
    want = rig(Keyless("tumbling_aggregate", aggs, rows_path=True), "jax", "rows").backlog(items).join()
    got = rig(Keyless("tumbling_aggregate", aggs), "jax", "partials").backlog(items).join()
    out = [e for e in got.sink.events() if e[0] == "row"]
    assert out == [e for e in want.sink.events() if e[0] == "row"] and len(out) == 1
    assert 21 * big > 2 ** 64 and (21 * big + 2 ** 63) % 2 ** 64 - 2 ** 63 in out[0]


# ---------------------------------------------------- who keeps the row path


@pytest.mark.parametrize("kind,why", [
    (Keyless("tumbling_aggregate", [("sm", "sum", "v")], np.float64), "a float sum"),
    (Keyless("sliding_aggregate", [("sm", "sum", "v")], np.float64), "a float sum"),
    (Keyless("tumbling_aggregate", FOUR, key_fields=["v"]), "a key field"),
    (Keyless("sliding_aggregate", FOUR, key_fields=["v"]), "a key field"),
    (Keyless("tumbling_aggregate", [("vs", "collect", "v")]), "a collected list"),
    (Keyless("tumbling_aggregate", [("mx", "max", "v")], np.uint64), "an unsigned lane"),
], ids=lambda p: p if isinstance(p, str) else p.name)
def test_who_stays_on_the_row_path(kind, why):
    op = kind.make("numpy" if "collect" in why else "jax")
    assert op._stage.kinds is None, why
    op._stage.add(rows(5 * W + np.arange(10), 0))
    assert op._stage.rows == 10 and not op._stage.partials


def test_on_a_mesh_a_keyless_aggregate_keeps_the_rows():
    """``device.mesh-devices`` > 1: the sharded store would take partials,
    but q7-mesh4 ran a seventh slower with them (PERF.md section 6, PR 52)."""
    from arroyo_tpu import config as cfg

    cfg.update({"device.mesh-devices": 4})
    assert Keyless("tumbling_aggregate").make("jax")._stage.kinds is None
    assert Keyless("sliding_aggregate").make("jax")._stage.kinds is None
    assert Keyless("tumbling_aggregate").make("numpy")._stage.kinds  # the host store is no mesh
    cfg.update({"device.mesh-devices": 0})
    assert Keyless("tumbling_aggregate").make("jax")._stage.kinds


def test_a_keyed_aggregate_steps_over_its_rows(rig):
    r = rig(Keyless("tumbling_aggregate", FOUR, key_fields=["v"]), "jax", "keyed")
    r.backlog([rows(5 * W + np.arange(300), 1), wm(7 * W), Signal.end_of_data()]).join()
    steps = trace.spans("agg.dispatch", job=r.job)
    assert [(s.args["rows"], s.args["rows_in"]) for s in steps] == [(300, 300)]
    assert r.counters()["arroyo_worker_rows_precombined"] == 0


# ------------------------------------------------------- the key is the plan's


@pytest.mark.parametrize("op_name", ["tumbling_aggregate", "sliding_aggregate"])
def test_a_carried_key_does_not_split_a_keyless_window(rig, op_name):
    """Rows that still carry ``_key`` (a join upstream grouped them by its
    routing key) into an aggregate the plan gives no key: one row a window,
    where the hook over the rows keyed the table by what it found and gave a
    row a carried key (ROADMAP C17 (e))."""
    items = stream(carried_key=True)
    got = rig(Keyless(op_name), "jax", "partials").backlog(items).join()
    by_rows = rig(Keyless(op_name, rows_path=True), "jax", "rows").backlog(items).join()
    out = [e for e in got.sink.events() if e[0] == "row"]
    plain = rig(Keyless(op_name), "jax", "plain").backlog(stream()).join()
    assert out == [e for e in plain.sink.events() if e[0] == "row"]  # same seeds, same values
    assert len({e[-2:] for e in out}) == len(out) >= 5
    assert len([e for e in by_rows.sink.events() if e[0] == "row"]) > 2 * len(out)


# ------------------------------------------------- the span arg and the counter


@pytest.mark.parametrize("op_name", ["tumbling_aggregate", "sliding_aggregate"])
def test_a_backlog_is_one_step_of_a_row_a_bin(rig, op_name):
    """Twenty batches of 471 rows over two bins behind the stream's first:
    9,420 rows, past a step's width, are one step of two rows; ``rows_in``
    and the task's counter say what they were combined from."""
    first = rows(5 * W + np.arange(100), 0)
    backlog = [rows((5 + i // 10) * W + np.arange(471), i + 1) for i in range(20)]
    r = rig(Keyless(op_name), "jax", "20").backlog([first] + backlog + [wm(5 * W + 3), barrier()])
    assert wait_until(lambda: r.asleep.is_set() and not r.inbox.has_items())
    steps = trace.spans("agg.dispatch", job=r.job)
    assert [(s.args["rows"], s.args["batches"], s.args["rows_in"]) for s in steps] == \
        [(1, 1, 100), (2, 20, 9420)]
    assert [s.args["rows"] for s in trace.spans("agg.directory", job=r.job)] == [1, 2]
    c = r.counters()
    assert c["arroyo_worker_rows_precombined"] == 9520 == c["arroyo_worker_messages_recv"]
    assert c["arroyo_worker_steps_dispatched"] == 2 and c["arroyo_worker_batches_staged"] == 21
    assert set(r.staged_asleep) == {0}  # no partial waited while the task slept
    r.inbox.put(0, Signal.end_of_data())
    r.join()
    marks = trace.spans("task.account", job=r.job)
    assert marks and marks[-1].args["rows_precombined"] == 9520
    from arroyo_tpu.obs.profile import _annotations, job_profile

    text = "\n".join(_annotations(job_profile({"agg": dict(c, busy_pct=1.0)})["agg"]))
    assert "steps 2 of 21 batches (10.5 a step), 9,520 rows combined before them" in text


def test_the_table_fed_partials_runs_the_merge_step_alone():
    """The partials' step is the restore's program, ``step_merge``: a count
    lane adds its value. The table never runs ``step``, so one program
    compiles at its first step, where ``step`` compiled."""
    from test_stage import Recorder

    # accumulators no other test of this process builds: the programs of one
    # (kinds, dtypes, capacity) are shared by every table of them
    op = Keyless("tumbling_aggregate", [("cnt", "count", None), ("c2", "count", None),
                                        ("mn", "min", "v")]).make("jax")
    col = Recorder()
    op.process_batch(rows(5 * W + np.arange(100), 0), None, col)
    op.process_batch(rows(5 * W + np.arange(2000), 1), None, col)
    op.flush_staged(None, col)
    agg = op._agg
    assert (agg._step._cache_size(), agg._step_merge._cache_size()) == (0, 1)
    _keys, _bins, accs = agg.snapshot()
    assert accs[0].tolist() == accs[1].tolist() == [2100]


# --------------------------------------------------------------- the lock


def test_a_staged_batch_hands_the_lock_over_once():
    """Staging a batch of a keyless aggregate lets go of the interpreter
    lock in one place, the native call; the hook over 8,192 rows did in
    fourteen (nineteen in the sliding aggregate: ISSUE 52, step 0). The
    batches here are 65,536 rows, so that the call lasts long enough for
    the thread that counts to wake inside it."""
    from interpreter_lock import hand_overs
    from test_stage import Recorder

    from arroyo_tpu import native

    if not native.available():
        pytest.skip("the native library is not built")
    col = Recorder()
    for op_name in ("tumbling_aggregate", "sliding_aggregate"):
        op = Keyless(op_name, [("mx", "max", "v")]).make("jax")
        pool = [rows(60 * W + np.arange(65536) % 1000, i) for i in range(40)]
        op.process_batch(pool[0], None, col)
        op.flush_staged(None, col)
        seq = iter(range(1, 10 ** 6))
        stage = lambda: op._stage_batch(pool[next(seq) % 40], None, col)
        assert hand_overs(stage, runs=100, until=2) == 1
        assert op._stage.rows == 1 and op._stage.staged >= 1
        # and the flush of that one row: the directory's call or two, the
        # padded step's fills, casts and dispatch
        flush = lambda: (op._stage_batch(pool[next(seq) % 40], None, col),
                         op.flush_staged(None, col))
        assert hand_overs(flush, runs=30) <= 8
