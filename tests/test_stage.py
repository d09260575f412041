"""The window aggregates take what their inbox holds (ISSUE 36): a tumbling
or sliding aggregate stages the batches its task hands it and runs its hook
(bin, late filter, accumulator inputs, key dictionary, directory, device
step) once over what it staged, when that reaches a step's width
(``device.batch-capacity``), when something that must see the rows arrives,
or when the task finds its inbox empty. The results are the same rows in the
same order, the same forwarded watermarks and the same checkpointed state,
however the stream reached the task.

Each operator runs inside a real Task (its own thread, a real TaskInbox and
Collector). ``backlog`` queues the whole stream before the task starts;
``trickle`` hands it one item at a time and waits, each time, until the task
is back in ``get`` on an empty inbox.
"""

from __future__ import annotations

import queue
import threading
import time

import numpy as np
import pytest

from arroyo_tpu.batch import KEY_FIELD, TIMESTAMP_FIELD, Batch
from arroyo_tpu.hashing import hash_columns
from arroyo_tpu.obs import trace
from arroyo_tpu.types import (CheckpointBarrier, Signal, SignalKind, TaskInfo,
                              Watermark)

W = 1_000_000  # micros: tumbling width, sliding slide (its width is 3 slides)
WIDTH = 8192  # rows a device step carries as shipped


@pytest.fixture(autouse=True)
def _shipped_step_width():
    # tests/conftest.py shrinks the step to 1,024 rows; these are about the
    # width a cell runs
    from arroyo_tpu import config as cfg

    cfg.update({"device.batch-capacity": WIDTH})


@pytest.fixture(autouse=True)
def _closes_land_at_once(monkeypatch):
    """A window close handed to the fetch pool lands before ``submit``
    returns: when a close's rows leave does not depend on a worker thread's
    luck, so two runs of one stream can be compared event for event."""
    from arroyo_tpu.ops.prefetch import Future, Prefetcher

    def submit(self, fn, on_done=None, program=None):
        fut = Future(fn, on_done)
        fut._run()
        return fut

    monkeypatch.setattr(Prefetcher, "submit", submit)


def wait_until(pred, timeout=20.0):
    limit = time.monotonic() + timeout
    while time.monotonic() < limit:
        if pred():
            return True
        time.sleep(0.0005)
    return pred()


# ---------------------------------------------------------------- the stream


def rows(ts, keys) -> Batch:
    ts = np.asarray(ts, dtype=np.int64)
    k = np.asarray(keys, dtype=np.int64)
    return Batch({TIMESTAMP_FIELD: ts, "k": k, "v": k * 7 + ts % 13,
                  KEY_FIELD: hash_columns([k])})


def wm(value: int) -> Signal:
    return Signal.watermark_of(Watermark.event_time(int(value)))


def barrier(epoch=1) -> Signal:
    return Signal.barrier_of(CheckpointBarrier(epoch=epoch))


def stream(batch_rows: int, n_batches: int, rows_per_window: int, barrier_at=None,
           seed=7):
    """Batches of ``batch_rows`` rows in event-time order, a watermark behind
    every batch (most repeat the operator's edge, some move it), every
    eleventh row four and a half windows behind its neighbours (late once its
    window has closed), a barrier behind batch ``barrier_at``, end of data."""
    rng = np.random.default_rng(seed)
    n = batch_rows * n_batches
    step = W // rows_per_window
    ts = np.arange(n, dtype=np.int64) * step + 5 * W
    ts[::11] -= 9 * W // 2
    keys = rng.integers(0, 97, n)
    items = []
    for i in range(n_batches):
        lo, hi = i * batch_rows, (i + 1) * batch_rows
        items.append(rows(ts[lo:hi], keys[lo:hi]))
        items.append(wm(ts[:hi].max() - W // 7))
        if barrier_at == i:
            items.append(barrier())
    items.append(Signal.end_of_data())
    return items


# ------------------------------------------------------------------- the rig


class Tumbling:
    name, op_name = "tumbling", "tumbling_aggregate"

    def cfg(self, backend):
        return {"width_micros": W, "key_fields": ["k"], "backend": backend,
                "aggregates": [("cnt", "count", None), ("mx", "max", "v"), ("sm", "sum", "v")],
                "input_dtype_of": lambda e: np.dtype(np.int64)}

    def make(self, backend):
        from arroyo_tpu.engine.engine import construct_operator
        from arroyo_tpu.expr import Col
        from arroyo_tpu.graph import OpName

        cfg = self.cfg(backend)
        cfg["aggregates"] = [(n, k, Col(e) if e else None) for n, k, e in cfg["aggregates"]]
        return construct_operator(OpName(self.op_name), cfg)


class Sliding(Tumbling):
    name, op_name = "sliding", "sliding_aggregate"

    def cfg(self, backend):
        return dict(super().cfg(backend), width_micros=3 * W, slide_micros=W)


OPERATORS = [Tumbling(), Sliding()]
by_operator = pytest.mark.parametrize("kind", OPERATORS, ids=lambda k: k.name)
by_backend = pytest.mark.parametrize("backend", ["numpy", "jax"])


class Sink:
    """Duck-types TaskInbox.put: what reached the sink, in order."""

    def __init__(self):
        self.items: list = []

    def put(self, input_index, item):
        self.items.append(item)

    def events(self) -> list:
        """Every row in the order it left, and each watermark and barrier
        where it left; a watermark that repeats the one before it is what the
        next task's merge drops (engine/task.py), and is dropped here."""
        out, last_wm = [], None
        for it in list(self.items):
            if isinstance(it, Batch):
                names = sorted(it.columns)
                out.extend(("row",) + tuple(int(it.columns[c][i]) for c in names)
                           for i in range(it.num_rows))
            elif it.kind == SignalKind.WATERMARK:
                if it.watermark.value != last_wm:
                    out.append(("wm", it.watermark.value))
                last_wm = it.watermark.value
            elif it.kind == SignalKind.BARRIER:
                out.append(("barrier", it.barrier.epoch))
            elif it.kind == SignalKind.END_OF_DATA:
                out.append(("end",))
        return out


class Rig:
    """One operator inside a real Task with one input and a recording sink."""

    def __init__(self, kind, backend, storage, job, restore_epoch=None):
        from arroyo_tpu.engine.queues import TaskInbox
        from arroyo_tpu.engine.task import Task
        from arroyo_tpu.graph import EdgeType
        from arroyo_tpu.operators.base import OperatorContext
        from arroyo_tpu.operators.collector import Collector, OutEdge
        from arroyo_tpu.state.tables import TableManager

        self.op, self.sink, self.job = kind.make(backend), Sink(), job
        ti = TaskInfo(job, "op", self.op.name(), 0, 1)
        self.tm = TableManager(ti, storage)
        if restore_epoch is not None:
            self.tm.restore(restore_epoch, self.op.tables())
        # room for a whole stream: the producer of a test never blocks
        self.inbox = TaskInbox(1, 1 << 40)
        # set when the task asks an empty inbox for its next item: it sleeps
        self.asleep = threading.Event()
        self.staged_asleep: list[int] = []
        self.slept_ns: list[int] = []  # when each of those waits began
        get = self.inbox.get

        def watched_get(timeout=None):
            if not self.inbox.has_items():
                self.staged_asleep.append(self.agg_op()._stage.rows)
                self.slept_ns.append(time.monotonic_ns())
                self.asleep.set()
            return get(timeout=timeout)

        self.inbox.get = watched_get
        ctx = OperatorContext(ti, None, self.tm)
        self.resps: "queue.Queue" = queue.Queue()
        collector = Collector([OutEdge(EdgeType.FORWARD, [self.sink], [0])], 0)
        self.task = Task(ti, self.op, self.inbox, collector, ctx, self.resps, n_inputs=1)

    def agg_op(self):
        return getattr(self.op, "members", [self.op])[-1]

    def backlog(self, items) -> "Rig":
        for it in items:
            self.inbox.put(0, it)
        self.task.start()
        return self

    def trickle(self, items) -> "Rig":
        self.task.start()
        for it in items:
            assert wait_until(lambda: self.asleep.is_set() and not self.inbox.has_items())
            self.asleep.clear()
            self.inbox.put(0, it)
        return self

    def join(self) -> "Rig":
        self.task.join(60)
        assert not self.task.thread.is_alive()
        return self

    def abort(self) -> None:
        self.inbox.close()
        self.task.join(10)

    def counters(self) -> dict:
        return self.task.metrics.counters


@pytest.fixture
def rig(tmp_path, request):
    rigs = []

    def make(kind, backend, job, **kw):
        r = Rig(kind, backend, str(tmp_path / "ck"), f"{request.node.name}-{job}", **kw)
        rigs.append(r)
        return r

    yield make
    for r in rigs:
        if r.task.thread is not None and r.task.thread.is_alive():
            r.abort()


def checkpointed(r: Rig, kind, backend, epoch=1) -> list:
    """What epoch ``epoch`` of the rig's job holds, table by table, every
    column's dtype and bytes."""
    from arroyo_tpu.state.tables import TableManager

    op = kind.make(backend)
    tm = TableManager(TaskInfo(r.job, "op", op.name(), 0, 1), r.tm.storage_url)
    tm.restore(epoch, op.tables())
    out = [("e", sorted(tm.global_keyed("e").items()))]
    for b in tm.expiring_time_key("t").all_batches():
        out.append([(c, str(a.dtype), a.tobytes() if a.dtype != object else a.tolist())
                    for c, a in sorted(b.columns.items())])
    return out


# ------------------------------------------------------------- equivalence


@by_operator
@by_backend
@pytest.mark.parametrize("batch_rows,n_batches,per_window", [
    (1, 240, 40), (471, 60, 3000), (3768, 12, 9000), (9000, 6, 13000)])
def test_backlog_and_trickle_give_the_same(rig, kind, backend, batch_rows, n_batches,
                                           per_window):
    """One stream, once queued whole before the task starts and once handed
    over item by item into an empty inbox: the same rows out in the same
    order, the same watermark behind each close, the same late rows, the
    same state in the checkpoint taken in the middle of the backlog and,
    restored from it, the same rest."""
    items = stream(batch_rows, n_batches, per_window, barrier_at=n_batches // 2)
    a = rig(kind, backend, "backlog").backlog(items).join()
    b = rig(kind, backend, "trickle").trickle(items).join()
    ev_a, ev_b = a.sink.events(), b.sink.events()
    assert sum(e[0] == "row" for e in ev_a) > 50 and ("barrier", 1) in ev_a
    assert ev_a == ev_b
    assert a.agg_op().late_rows == b.agg_op().late_rows > 0
    # no row waited in the operator while its task slept
    assert set(a.staged_asleep) <= {0} and set(b.staged_asleep) == {0}
    state_a, state_b = checkpointed(a, kind, backend), checkpointed(b, kind, backend)
    assert len(state_a) == 2 and state_a == state_b
    # restored from the checkpoint, the rest of the stream
    rest = items[items.index(next(i for i in items if isinstance(i, Signal)
                                  and i.kind == SignalKind.BARRIER)) + 1:]
    ra = rig(kind, backend, "backlog", restore_epoch=1).backlog(rest).join()
    rb = rig(kind, backend, "trickle", restore_epoch=1).trickle(rest).join()
    assert ra.sink.events() == rb.sink.events()
    assert ra.agg_op().late_rows == rb.agg_op().late_rows
    # and it is the rest of the run that was not interrupted
    tail = ev_a[ev_a.index(("barrier", 1)) + 1:]
    assert [e for e in ra.sink.events() if e[0] == "row"] == [e for e in tail if e[0] == "row"]
    if backend == "jax":
        # the backlog was taken in steps of a step's width, not a batch each
        steps, staged = (a.counters()[c] for c in (
            "arroyo_worker_steps_dispatched", "arroyo_worker_batches_staged"))
        if batch_rows <= WIDTH // 16:
            assert steps < b.counters()["arroyo_worker_steps_dispatched"]
            assert staged > 2 * steps


# ------------------------------------------------------- what ends a stage


class Recorder:
    """A collector that keeps what it is handed, in order."""

    def __init__(self):
        self.items: list = []

    def collect(self, batch):
        self.items.append(batch)

    def broadcast(self, signal):
        self.items.append(signal)

    def watermarks(self):
        return [i.watermark.value for i in self.items
                if isinstance(i, Signal) and i.kind == SignalKind.WATERMARK]

    def count(self) -> int:
        """Input rows behind the rows that left."""
        return int(sum(b["cnt"].sum() for b in self.items if isinstance(b, Batch)))


def bare(kind, backend, storage):
    """An operator with a context and no task: the test is the loop."""
    from arroyo_tpu.operators.base import OperatorContext
    from arroyo_tpu.state.tables import TableManager

    op = kind.make(backend)
    ti = TaskInfo("bare", "op", op.name(), 0, 1)
    ctx = OperatorContext(ti, None, TableManager(ti, storage))
    op.on_start(ctx)
    return op, ctx, Recorder()


def window_rows(w: int, n: int, seed=0) -> Batch:
    """n rows spread over window/slide w, keys 0..96."""
    rng = np.random.default_rng(seed + w)
    return rows(w * W + np.sort(rng.integers(0, W, n)), rng.integers(0, 97, n))


def held_rows(op) -> int:
    """Rows the aggregator has taken (every key's count, open bins only)."""
    if op._agg is None:
        return 0
    return int(np.sum(op._agg.snapshot()[2][0]))


@by_operator
@by_backend
def test_a_closing_watermark_sees_every_row_before_it_and_none_after(kind, backend, _storage):
    op, ctx, col = bare(kind, backend, _storage)
    op.process_batch(window_rows(5, 100), ctx, col)  # the first rows: alone, as ever
    assert op._stage.rows == 0 and held_rows(op) == 100
    assert op.handle_watermark(Watermark.event_time(5 * W + 10), ctx, col) is not None
    for i in range(3):
        op.process_batch(window_rows(5, 200, seed=i + 1), ctx, col)
    assert op._stage.rows == 600 and held_rows(op) == 100  # staged: the hook has not run
    # the watermark that closes window 5 (sliding: the windows that end with
    # slide 5): the staged rows are in what it closes
    op.handle_watermark(Watermark.event_time(9 * W), ctx, col)
    assert op._stage.rows == 0
    op.process_batch(window_rows(5, 50, seed=9), ctx, col)  # behind the close: late
    op.process_batch(window_rows(9, 70), ctx, col)
    assert op.late_rows == 0 and op._stage.rows == 120  # not before the hook runs
    op.on_close(ctx, col)
    assert op.late_rows == 50
    per_row = 3 if kind.name == "sliding" else 1  # windows a row is counted in
    assert col.count() == (700 + 70) * per_row


@by_operator
def test_a_barrier_snapshots_the_staged_rows(kind, _storage):
    op, ctx, col = bare(kind, "jax", _storage)
    op.process_batch(window_rows(5, 100), ctx, col)
    op.process_batch(window_rows(5, 300, seed=1), ctx, col)
    op.process_batch(window_rows(6, 300), ctx, col)
    assert op._stage.rows == 600
    op.handle_checkpoint(CheckpointBarrier(epoch=1), ctx, col)
    assert op._stage.rows == 0
    state = Batch.concat(ctx.table_manager.expiring_time_key("t", W).all_batches())
    assert int(state["__acc_0"].sum()) == 700
    op.process_batch(window_rows(6, 40, seed=2), ctx, col)  # after the barrier: not in it
    assert op._stage.rows == 40 and int(state["__acc_0"].sum()) == 700


@by_operator
@pytest.mark.parametrize("ender", ["stop", "end_of_data"])
def test_stop_and_end_of_data_see_the_rows_before_them(rig, kind, ender):
    batches = [window_rows(5, 100)] + [window_rows(5 + i // 3, 471, seed=i) for i in range(6)]
    after = window_rows(7, 333)
    end = Signal.stop() if ender == "stop" else Signal.end_of_data()
    r = rig(kind, "jax", ender).backlog(batches + [end, after]).join()
    op = r.agg_op()
    assert op._stage.rows == 0
    n = 100 + 6 * 471
    if ender == "stop":
        # nothing leaves at a hard stop, and the table holds every row before it
        assert held_rows(op) == n and r.sink.events() == []
    else:
        per_row = 3 if kind.name == "sliding" else 1
        assert sum(e[2] for e in r.sink.events() if e[0] == "row") == n * per_row
    assert r.counters()["arroyo_worker_messages_recv"] == n  # ``after`` was never taken


@by_operator
@by_backend
def test_watermarks_that_move_nothing_wait_behind_the_rows_and_collapse(kind, backend,
                                                                        _storage):
    op, ctx, col = bare(kind, backend, _storage)
    op.process_batch(window_rows(5, 100), ctx, col)
    first = op.handle_watermark(Watermark.event_time(5 * W + 10), ctx, col)
    assert first is not None and col.items == []
    order = []
    run_staged, on_watermark = op._run_staged, op._on_watermark

    def spy_run(collector):
        order.append(("rows", op._stage.rows))
        return run_staged(collector)

    def spy_wm(watermark, collector):
        order.append(("wm", watermark.value, op._stage.rows))
        return on_watermark(watermark, collector)

    op._run_staged, op._on_watermark = spy_run, spy_wm
    for i in range(3):
        op.process_batch(window_rows(5, 200, seed=i + 1), ctx, col)
        # the same edge as the one handled: held behind the rows, not forwarded
        assert op.handle_watermark(Watermark.event_time(5 * W + 20 + i), ctx, col) is None
        assert op._stage.watermark.value == 5 * W + 20 + i
    assert order == [] and col.items == [] and op._stage.rows == 600
    op.flush_staged(ctx, col)  # the task, before it waits
    # the rows, then the newest of the three and only it: no staged row behind it
    assert order == [("rows", 600), ("wm", 5 * W + 22, 0)]
    assert col.watermarks() == [first.value] and op._stage.watermark is None
    # with nothing staged, a watermark is handled where it arrives, as ever
    assert op.handle_watermark(Watermark.event_time(5 * W + 30), ctx, col).value == first.value
    assert order[-1] == ("wm", 5 * W + 30, 0)
    # one that moves the edge runs the staged rows first and is not held
    op.process_batch(window_rows(6, 50), ctx, col)
    op.handle_watermark(Watermark.event_time(6 * W + 1), ctx, col)
    assert order[-2:] == [("rows", 50), ("wm", 6 * W + 1, 0)] and op._stage.watermark is None
    # an idle watermark ends the stage too
    op.process_batch(window_rows(6, 60, seed=3), ctx, col)
    assert op.handle_watermark(Watermark.idle(), ctx, col).is_idle
    assert order[-2][0] == "rows" and op._stage.rows == 0


@by_operator
def test_an_edge_repeated_while_a_close_is_in_flight_ends_the_stage(kind, _storage, monkeypatch):
    """Any watermark while a close is in flight or held runs the staged rows:
    the close's rows and the watermark behind them may be ready to leave."""
    from arroyo_tpu.ops.prefetch import Future, Prefetcher

    parked, hold = [], [True]

    def submit(self, fn, on_done=None, program=None):
        fut = Future(fn, on_done)
        if hold[0]:
            parked.append(fut)
        else:
            fut._run()
        return fut

    monkeypatch.setattr(Prefetcher, "submit", submit)
    op, ctx, col = bare(kind, "jax", _storage)
    op.process_batch(window_rows(5, 100), ctx, col)
    op.handle_watermark(Watermark.event_time(9 * W), ctx, col)  # closes; nothing lands
    assert parked and op.closes_in_flight()
    op.process_batch(window_rows(9, 200), ctx, col)
    assert op._stage.rows == 200
    assert op.handle_watermark(Watermark.event_time(9 * W + 5), ctx, col) is None
    assert op._stage.rows == 0 and op._stage.watermark is None
    hold[0] = False
    for f in parked:
        f._run()
    op.on_close(ctx, col)
    assert col.count() == 300 * (3 if kind.name == "sliding" else 1)


@by_operator
def test_a_batch_into_an_empty_inbox_is_dispatched_before_the_task_waits(rig, kind):
    """The paced cells' guard: an item arrives every few milliseconds into
    an empty inbox, and its step must not wait for the next one."""
    r = rig(kind, "jax", "paced")
    sizes = [100, 471, 9, 3768]
    r.trickle([window_rows(5, n, seed=n) for n in sizes] + [wm(5 * W + 1)])
    assert wait_until(lambda: r.asleep.is_set() and not r.inbox.has_items())
    assert r.agg_op()._stage.rows == 0 and set(r.staged_asleep) == {0}
    steps = trace.spans("agg.dispatch", job=r.job)
    assert [(s.args["rows"], s.args["batches"]) for s in steps] == [(n, 1) for n in sizes]
    # every step ended before the task next asked its empty inbox, and that
    # was before the next batch was handed over: no step waited for a batch
    ends = [s.t1_ns for s in steps]
    for end, nxt in zip(ends, [s.t0_ns for s in steps[1:]] + [time.monotonic_ns()]):
        assert any(end <= t <= nxt for t in r.slept_ns)
    r.inbox.put(0, Signal.end_of_data())
    r.join()


# ------------------------------------------------------------- the counter


@by_operator
def test_a_backlog_of_small_batches_is_one_step(rig, kind):
    """17 batches of 471 rows behind the stream's first: one step of 8,007
    rows, not 17; span args and counters say so, in the account marks too."""
    first = window_rows(5, 100)
    backlog = [window_rows(5, 471, seed=i + 1) for i in range(17)]
    r = rig(kind, "jax", "17").backlog([first] + backlog + [wm(5 * W + 3), barrier()])
    assert wait_until(lambda: r.asleep.is_set() and not r.inbox.has_items())
    steps = trace.spans("agg.dispatch", job=r.job)
    assert [(s.args["rows"], s.args["batches"]) for s in steps] == [(100, 1), (8007, 17)]
    assert len(trace.spans("agg.directory", job=r.job)) == 2
    c = r.counters()
    assert c["arroyo_worker_steps_dispatched"] == 2 and c["arroyo_worker_batches_staged"] == 18
    assert c["arroyo_worker_batches_recv"] == 18 and c["arroyo_worker_messages_recv"] == 8107
    r.inbox.put(0, Signal.end_of_data())
    r.join()
    marks = trace.spans("task.account", job=r.job)
    assert marks and marks[-1].args["steps_dispatched"] == 2
    assert marks[-1].args["batches_staged"] == 18


@by_operator
def test_a_backlog_past_a_steps_width_is_cut_at_it(rig, kind):
    """20 batches of 471 rows: 9,420 rows are one full step, which pads
    nothing, and a remainder; the batch cut at the width counts in both."""
    first = window_rows(5, 100)
    backlog = [window_rows(5, 471, seed=i + 1) for i in range(20)]
    r = rig(kind, "jax", "20").backlog([first] + backlog + [Signal.end_of_data()]).join()
    steps = trace.spans("agg.dispatch", job=r.job)
    assert [(s.args["rows"], s.args["batches"]) for s in steps] == [
        (100, 1), (WIDTH, 18), (9420 - WIDTH, 3)]
    per_row = 3 if kind.name == "sliding" else 1
    assert sum(e[2] for e in r.sink.events() if e[0] == "row") == 9520 * per_row


def test_explain_prints_the_steps_and_their_batches():
    from arroyo_tpu.obs.profile import _annotations, job_profile

    counters = {"arroyo_worker_steps_dispatched": 120, "arroyo_worker_batches_staged": 1500}
    prof = job_profile({"agg": {"busy_pct": 1.0, **counters}})["agg"]
    assert {k: prof.get(k) for k in counters} == counters
    assert any(ln.startswith("waits:") and "steps 120 of 1500 batches (12.5 a step)" in ln
               for ln in _annotations(prof))
    # a task that dispatched nothing says nothing
    quiet = job_profile({"agg": {"busy_pct": 1.0, "arroyo_worker_steps_dispatched": 0}})["agg"]
    assert not any("steps" in ln for ln in _annotations(quiet))


def test_a_chain_passes_the_tasks_call_to_the_member_that_stages(_storage):
    """A pass-through projection and the aggregate fused into one task: the
    chain hands ``flush_staged`` to the aggregate, and only to it."""
    from arroyo_tpu.expr import Col
    from arroyo_tpu.operators.base import Operator, OperatorContext
    from arroyo_tpu.operators.chained import ChainedOperator
    from arroyo_tpu.state.tables import TableManager

    value = {"projections": [(c, Col(c)) for c in ("k", "v", KEY_FIELD, TIMESTAMP_FIELD)]}
    cfg = Tumbling().cfg("numpy")
    cfg["aggregates"] = [(n, k, Col(e) if e else None) for n, k, e in cfg["aggregates"]]
    op = ChainedOperator({"members": [("value", value), ("tumbling_aggregate", cfg)]})
    assert type(op).flush_staged is not Operator.flush_staged and op._stagers == [1]
    ti = TaskInfo("chain", "op", op.name(), 0, 1)
    ctx, col = OperatorContext(ti, None, TableManager(ti, _storage)), Recorder()
    op.on_start(ctx)
    op.process_batch(window_rows(5, 100), ctx, col)
    op.process_batch(window_rows(5, 200, seed=1), ctx, col)
    agg = op.members[1]
    assert agg._stage.rows == 200 and held_rows(agg) == 100
    op.flush_staged(ctx, col)
    assert agg._stage.rows == 0 and held_rows(agg) == 300


@pytest.mark.parametrize("kinds", [("count", "max"), ("count",), ("sum", "min")])
def test_a_full_step_runs_the_program_a_padded_one_compiled(kinds):
    """The step that pads nothing hands the device the shapes and dtypes the
    padded one does: a backlog's first full step compiles nothing (a compile
    inside a cell's measured window fails its run)."""
    from arroyo_tpu.ops.slot_agg import SlotAggregator

    agg = SlotAggregator(list(kinds), [np.dtype(np.int64)] * len(kinds), cap=1 << 15,
                         batch_cap=WIDTH, region_size=2048)
    rng = np.random.default_rng(3)

    def step(n):
        keys = rng.integers(0, 5000, n).astype(np.uint64)
        agg.update(keys, np.zeros(n, dtype=np.int32),
                   [rng.integers(0, 100, n) for _ in kinds])

    # the jitted program is _build_slot_jax's, cached by shape: every
    # aggregator of these kinds and this capacity in the process shares it,
    # so a step another test ran at another width is in its count already
    before = agg._step._cache_size()
    step(471)
    compiled = agg._step._cache_size()
    assert compiled <= before + 1
    step(WIDTH)
    step(WIDTH + 9)  # a full chunk and a padded remainder
    assert agg._step._cache_size() == compiled
