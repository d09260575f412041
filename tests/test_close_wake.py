"""The completion wake (ISSUE 26): an in-flight window close leaves the
operator when its host copy lands, not at the operator's next input.

Deterministic on the CPU: the device handle of every close is wrapped in a
stand-in whose ``result()`` blocks on a ``threading.Event``, so a test says
when the "copy" lands. Each operator runs inside a real Task (its own
thread, a real TaskInbox and Collector) with two inputs; the sink records
what arrives, in order.
"""

from __future__ import annotations

import queue
import threading
import time

import numpy as np
import pytest

from arroyo_tpu.batch import KEY_FIELD, TIMESTAMP_FIELD, Batch
from arroyo_tpu.hashing import hash_columns
from arroyo_tpu.types import (CheckpointBarrier, Signal, SignalKind, TaskInfo,
                              Watermark)

W = 1000  # micros: tumbling width, sliding slide, distance of the join's windows
FIRST = 5  # the first window fed (sliding looks one slide back)


def wait_until(pred, timeout=5.0):
    limit = time.monotonic() + timeout
    while time.monotonic() < limit:
        if pred():
            return True
        time.sleep(0.002)
    return pred()


# ------------------------------------------------------ the stand-in handle


class Gated:
    """What an operator hands the fetch pool instead of the device handle:
    ``result()`` blocks until the test releases it, then gives the real
    rows (or raises ``error``)."""

    def __init__(self, inner):
        self._inner = inner
        self.gate = threading.Event()
        self.error = None

    def result(self):
        assert self.gate.wait(20), "the test never released this close"
        if self.error is not None:
            raise self.error
        return self._inner()


class Gates:
    """The stand-in handles of one test, in dispatch order."""

    def __init__(self):
        self.handles: list[Gated] = []
        self.hold = True  # False: closes dispatched from now on land at once

    def wrap(self, inner) -> Gated:
        h = Gated(inner)
        if not self.hold:
            h.gate.set()
        self.handles.append(h)
        return h

    def dispatched(self, n: int) -> bool:
        return wait_until(lambda: len(self.handles) >= n)

    def release(self, i: int, error=None) -> None:
        self.handles[i].error = error
        self.handles[i].gate.set()


@pytest.fixture
def gates(monkeypatch):
    """Every window close and every device join of the test is gated."""
    from arroyo_tpu import config as cfg
    from arroyo_tpu.ops import join_probe
    from arroyo_tpu.ops.slot_agg import SlotAggregator

    g = Gates()
    extract = SlotAggregator.extract_start

    def extract_start(self, *a, **kw):
        return g.wrap(extract(self, *a, **kw).result)

    def device_join_start(lk, rk):
        return g.wrap(lambda: join_probe.host_join_indices(lk, rk))

    monkeypatch.setattr(SlotAggregator, "extract_start", extract_start)
    monkeypatch.setattr(join_probe, "device_join_start", device_join_start)
    # the join takes its device path on a host CPU, at any size
    cfg.update({"device.force-device-join": True, "device.join-min-rows": 1})
    return g


# ------------------------------------------------------------ the operators


def _rows(ts: int, keys, vname="v") -> Batch:
    k = np.asarray(keys, dtype=np.int64)
    return Batch({
        TIMESTAMP_FIELD: np.full(len(k), ts, dtype=np.int64),
        "k": k,
        vname: k * 10,
        KEY_FIELD: hash_columns([k]),
    })


class Tumbling:
    """Window w is [w*W, (w+1)*W); its close carries the window's end."""

    name = "tumbling"
    op_name = "tumbling_aggregate"
    edge_of_input = None

    def cfg(self):
        return {"width_micros": W, "key_fields": ["k"],
                "aggregates": [("cnt", "count", None)], "backend": "jax"}

    def make(self):
        from arroyo_tpu.engine.engine import construct_operator
        from arroyo_tpu.graph import OpName

        return construct_operator(OpName(self.op_name), self.cfg())

    def batches(self, w):
        return [(0, _rows(w * W + 1, [1, 2, 2 + w]))]

    def closing_watermark(self, w):
        return (w + 1) * W

    def out_key(self, w):
        return w * W  # window_start of the rows that leave

    def out_watermark(self, w):
        return (w + 1) * W

    def trace_id(self, w):
        return (w + 1) * W

    def keys_of(self, batch):
        return np.unique(np.asarray(batch["window_start"])).tolist()


class Sliding(Tumbling):
    """Width 2 slides. The watermark past bin w completes the window that
    starts one slide earlier; the close of bin w carries that window's end."""

    name = "sliding"
    op_name = "sliding_aggregate"

    def cfg(self):
        return dict(super().cfg(), width_micros=2 * W, slide_micros=W)

    def out_key(self, w):
        return (w - 1) * W

    def out_watermark(self, w):
        return w * W


class Join:
    """Window w is the rows stamped w*W on both sides."""

    name = "join"
    op_name = "instant_join"
    edge_of_input = staticmethod(lambda i: (i, 0))
    make = Tumbling.make

    def cfg(self):
        return {"join_type": "inner", "backend": "jax",
                "left_names": [("k", "k"), ("lv", "v")],
                "right_names": [("rk", "k"), ("rv", "v")]}

    def batches(self, w):
        return [(0, _rows(w * W, [1, 2, 2 + w])), (1, _rows(w * W, [2, 2 + w, 99]))]

    def closing_watermark(self, w):
        return w * W + 1

    def out_key(self, w):
        return w * W

    def out_watermark(self, w):
        return w * W + 1

    def trace_id(self, w):
        return w * W  # the join knows no width: the window's start

    def keys_of(self, batch):
        return np.unique(batch.timestamps).tolist()


OPERATORS = [Tumbling(), Sliding(), Join()]
by_operator = pytest.mark.parametrize("kind", OPERATORS, ids=lambda k: k.name)


class Chained(Tumbling):
    """A pass-through projection and the aggregate, fused into one task."""

    def __init__(self, inner):
        self.inner, self.name = inner, "chained-" + inner.name
        self.out_key, self.out_watermark = inner.out_key, inner.out_watermark

    def make(self):
        from arroyo_tpu.expr import Col
        from arroyo_tpu.operators.chained import ChainedOperator

        value = {"projections": [(c, Col(c)) for c in ("k", "v", KEY_FIELD, TIMESTAMP_FIELD)]}
        return ChainedOperator({"members": [("value", value),
                                            (self.inner.op_name, self.inner.cfg())]})


# ------------------------------------------------------------------ the rig


class Sink:
    """Duck-types TaskInbox.put: what reached the sink, in order."""

    def __init__(self):
        self.items: list = []

    def put(self, input_index, item):
        self.items.append(item)

    def events(self, kind) -> list:
        out = []
        for it in list(self.items):
            if isinstance(it, Batch):
                out.extend(("rows", k) for k in kind.keys_of(it))
            elif it.kind == SignalKind.WATERMARK:
                out.append(("wm", it.watermark.value))
            elif it.kind == SignalKind.BARRIER:
                out.append(("barrier", it.barrier.epoch))
        return out

    def rows(self) -> list:
        out = []
        for it in list(self.items):
            if isinstance(it, Batch):
                out.extend(tuple(sorted(r.items())) for r in it.to_pylist())
        return sorted(out)


class Rig:
    """One operator inside a real Task with two inputs and a recording
    sink. ``wake=False`` takes the task's wake away from the operator: the
    engine as it was before the completion wake."""

    N_INPUTS = 2

    def __init__(self, kind, storage, job, wake=True, restore_epoch=None):
        from arroyo_tpu.engine.queues import TaskInbox
        from arroyo_tpu.engine.task import Task
        from arroyo_tpu.graph import EdgeType
        from arroyo_tpu.operators.base import OperatorContext
        from arroyo_tpu.operators.collector import Collector, OutEdge
        from arroyo_tpu.state.tables import TableManager

        self.kind, self.op, self.sink = kind, kind.make(), Sink()
        ti = TaskInfo(job, "op", self.op.name(), 0, 1)
        tm = TableManager(ti, storage)
        if restore_epoch is not None:
            tm.restore(restore_epoch, self.op.tables())
        self.inbox = TaskInbox(self.N_INPUTS, 8192)
        # the wakes that reached the inbox, counted where they arrive
        self.wakes = 0
        inbox_wake = self.inbox.wake

        def counted_wake():
            inbox_wake()
            self.wakes += 1

        self.inbox.wake = counted_wake
        ctx = OperatorContext(ti, None, tm, in_edge_of_input=kind.edge_of_input)
        self.resps: "queue.Queue" = queue.Queue()
        collector = Collector([OutEdge(EdgeType.FORWARD, [self.sink], [0])], 0)
        self.task = Task(ti, self.op, self.inbox, collector, ctx, self.resps,
                         n_inputs=self.N_INPUTS)
        if not wake:
            ctx.wake = None
        self.task.start()

    # feeding ----------------------------------------------------------

    def feed(self, w: int, watermark=True) -> None:
        """Window w's rows, then the watermark that closes it (both inputs)."""
        for idx, batch in self.kind.batches(w):
            self.inbox.put(idx, batch)
        if watermark:
            self.watermark(self.kind.closing_watermark(w))

    def watermark(self, value: int) -> None:
        for idx in range(self.N_INPUTS):
            self.inbox.put(idx, Signal.watermark_of(Watermark.event_time(value)))

    def barrier(self, idx: int, epoch=1) -> None:
        self.inbox.put(idx, Signal.barrier_of(CheckpointBarrier(epoch=epoch)))

    # observing --------------------------------------------------------

    def events(self) -> list:
        return self.sink.events(self.kind)

    def left(self, w: int) -> list:
        """A window's rows and, after them, the watermark held behind them."""
        return [("rows", self.kind.out_key(w)), ("wm", self.kind.out_watermark(w))]

    def batches_processed(self) -> int:
        return self.task.metrics.counters["arroyo_worker_batches_recv"]

    def responses(self) -> list:
        out = []
        while True:
            try:
                out.append(self.resps.get_nowait())
            except queue.Empty:
                return out

    # ending -----------------------------------------------------------

    def finish(self) -> None:
        """End of data on every input; the task drains and ends."""
        for idx in range(self.N_INPUTS):
            self.inbox.put(idx, Signal.end_of_data())
        self.task.join(10)
        assert not self.task.thread.is_alive()

    def abort(self) -> None:
        self.inbox.close()
        self.task.join(10)
        assert not self.task.thread.is_alive()


@pytest.fixture
def rig(gates, tmp_path, request):
    rigs = []

    def make(kind, **kw):
        r = Rig(kind, str(tmp_path / "ck"), kw.pop("job", request.node.name), **kw)
        rigs.append(r)
        return r

    yield make
    for g in gates.handles:  # leave no fetch worker blocked behind a test
        g.gate.set()
    for r in rigs:
        if r.task.thread.is_alive():
            r.abort()


# ---------------------------------------------------------------- the inbox


def test_inbox_wake_is_sticky_and_not_an_item():
    from arroyo_tpu.engine.queues import TaskInbox

    inbox = TaskInbox(1, 100)
    inbox.wake()  # nobody waits: a bare notify would be lost here
    t0 = time.monotonic()
    assert inbox.get(timeout=5) is None and time.monotonic() - t0 < 1
    t0 = time.monotonic()
    assert inbox.get(timeout=0.05) is None and time.monotonic() - t0 >= 0.04  # taken once
    # a wake never overtakes or replaces an item, and takes no row budget
    b = Batch({"x": np.arange(60)})
    inbox.put(0, b)
    inbox.wake()
    assert inbox.used_rows() == 60 and inbox.depth() == 1
    assert inbox.get(timeout=1) == (0, b)
    assert inbox.get(timeout=5) is None  # the wake, once the queue ran dry
    # a sleeping consumer is woken
    got = []
    t = threading.Thread(target=lambda: got.append(inbox.get(timeout=5)))
    t.start()
    time.sleep(0.05)
    inbox.wake()
    t.join(1)
    assert got == [None] and not t.is_alive()


def test_inbox_wake_after_close_is_a_noop():
    from arroyo_tpu.engine.queues import TaskInbox

    inbox = TaskInbox(1, 100)
    inbox.close()
    inbox.wake()
    assert not inbox._woken and inbox.get(timeout=0.01) is None


def test_no_wake_is_lost_under_contention():
    """More wakers than cores against one consumer, the interpreter switching
    threads every 10 us: every round's wake, given at any point around the
    consumer's going to sleep, ends that round's ``get`` long before its
    timeout — a lost wake would sit the timeout out."""
    import sys

    from arroyo_tpu.engine.queues import TaskInbox

    inbox, rounds, wakers = TaskInbox(1, 100), 150, 16
    go = [threading.Event() for _ in range(rounds)]

    def waker(k):
        for i in range(k, rounds, wakers):
            assert go[i].wait(30)
            inbox.wake()

    threads = [threading.Thread(target=waker, args=(k,), daemon=True) for k in range(wakers)]
    before = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        worst = 0.0
        for i in range(rounds):
            t0 = time.monotonic()
            go[i].set()  # the wake races this thread into get()
            assert inbox.get(timeout=10) is None
            worst = max(worst, time.monotonic() - t0)
        for t in threads:
            t.join(10)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(before)
    assert worst < 5, worst
    assert not inbox._woken  # each wake was taken exactly once


def test_future_calls_on_done_after_ready_also_on_error():
    from arroyo_tpu.ops.prefetch import Prefetcher

    pf, seen = Prefetcher(workers=1), []
    ok = pf.submit(lambda: 7, on_done=lambda: seen.append(("ok", ok.is_ready())))
    bad = pf.submit(lambda: 1 / 0, on_done=lambda: seen.append(("bad", bad.is_ready())))
    assert wait_until(lambda: len(seen) == 2)
    assert seen == [("ok", True), ("bad", True)] and ok.result() == 7
    with pytest.raises(ZeroDivisionError):
        bad.result()
    assert pf.submit(lambda: 8).result() == 8  # no callback asked for


# ------------------------------------------------- (a) no further input


@by_operator
def test_close_leaves_without_further_input(kind, rig, gates):
    r = rig(kind)
    r.feed(FIRST)
    assert gates.dispatched(1)
    time.sleep(0.05)
    assert r.events() == []  # dispatched, held: the copy has not landed
    gates.release(0)
    # no batch, no watermark follows: the wake alone brings them out
    assert wait_until(lambda: r.events() == r.left(FIRST), timeout=1.0), r.events()
    assert not r.op.closes_in_flight()
    r.finish()  # (the sliding aggregate's open windows leave at the end of data)
    assert r.events()[:2] == r.left(FIRST)


# --------------------------------------------------- (b) the lost wake


@by_operator
def test_wake_before_the_task_sleeps_is_not_lost(kind, rig, gates):
    r = rig(kind)
    handle_watermark = r.op.handle_watermark

    def slow(watermark, ctx, collector):
        out = handle_watermark(watermark, ctx, collector)
        if gates.handles and not gates.handles[0].gate.is_set():
            # the close was dispatched and found not ready; its copy lands,
            # and the wake is given, while the task is still in the hook
            gates.release(0)
            assert wait_until(lambda: r.wakes >= 1)
        return out

    r.op.handle_watermark = slow
    r.feed(FIRST)
    assert wait_until(lambda: r.events() == r.left(FIRST), timeout=1.0), r.events()
    r.finish()


# ------------------------------------------- (c) completion out of order


@by_operator
def test_out_of_order_completion_leaves_in_program_order(kind, rig, gates):
    r = rig(kind)
    r.feed(FIRST)
    r.feed(FIRST + 1)
    assert gates.dispatched(2)
    gates.release(1)  # the later close lands first
    assert wait_until(lambda: r.wakes >= 1)
    time.sleep(0.05)
    assert r.events() == []  # it waits for the head
    gates.release(0)  # whose own completion wakes the task again
    want = r.left(FIRST) + r.left(FIRST + 1)
    assert wait_until(lambda: len(r.events()) == 4, timeout=1.0), r.events()
    got = r.events()
    # windows in order, watermarks in order, each watermark after its rows
    # (the sliding aggregate fuses the windows of one drain into one batch)
    assert [e for e in got if e[0] == "rows"] == [want[0], want[2]]
    assert [e for e in got if e[0] == "wm"] == [want[1], want[3]]
    assert got.index(want[0]) < got.index(want[1]) and got.index(want[2]) < got.index(want[3])
    r.finish()
    assert r.events()[:4] == got


def test_a_sliding_round_of_several_closes_leaves_whole(rig, gates):
    """One watermark that closes two slide bins dispatches two closes, which
    land one by one and each wake the task. The first to land brings nothing
    out (a drain at every landing would split the round into a batch a
    close); the last brings both windows out as one batch."""
    kind = Sliding()
    r = rig(kind)
    r.feed(FIRST, watermark=False)
    r.feed(FIRST + 1)  # its watermark is past both bins
    assert gates.dispatched(2)
    gates.release(0)
    assert wait_until(lambda: r.wakes >= 1)
    time.sleep(0.05)
    assert r.events() == []
    gates.release(1)
    want = [("rows", kind.out_key(FIRST)), ("rows", kind.out_key(FIRST + 1)),
            ("wm", kind.out_watermark(FIRST + 1))]
    assert wait_until(lambda: r.events() == want, timeout=1.0), r.events()
    assert len([it for it in r.sink.items if isinstance(it, Batch)]) == 1
    assert not r.op.closes_in_flight()
    r.finish()


# ---------------------------------------- (d) during barrier alignment


def _aligned_run(kind, rig, gates, wake: bool):
    """Window FIRST closes (gated); input 0 delivers the barrier and then a
    batch of the next window, which the alignment holds; the close lands;
    input 1 delivers the barrier. Then the task is lost and a second
    incarnation restores from the checkpoint and finishes the stream."""
    job = f"align-{kind.name}-{wake}"
    n0 = len(gates.handles)
    r = rig(kind, job=job, wake=wake)
    r.feed(FIRST)
    assert gates.dispatched(n0 + 1)
    processed = len(kind.batches(FIRST))
    assert wait_until(lambda: r.batches_processed() == processed)
    r.barrier(0)
    held_idx, held = kind.batches(FIRST + 1)[0]
    assert held_idx == 0
    r.inbox.put(0, held)
    gates.release(n0)
    if wake:
        # the wake drains while input 0 is blocked ...
        assert wait_until(lambda: r.events() == r.left(FIRST), timeout=1.0), r.events()
    else:
        time.sleep(0.1)
        assert r.events() == []  # as before: nothing until the barrier forces it
    # ... and the held batch stays held
    assert r.batches_processed() == processed
    r.barrier(1)
    assert wait_until(lambda: ("barrier", 1) in r.events())
    assert r.events()[:3] == r.left(FIRST) + [("barrier", 1)]
    assert wait_until(lambda: r.batches_processed() == processed + 1)
    assert any(resp.kind == "checkpoint_completed" for resp in r.responses())
    before = r.sink.rows()
    r.abort()

    gates.hold = False
    r2 = rig(kind, job=job, wake=wake, restore_epoch=1)
    for idx, batch in kind.batches(FIRST + 1):
        r2.inbox.put(idx, batch)
    r2.finish()
    gates.hold = True
    return before, r2.sink.rows()


@by_operator
def test_wake_during_alignment_drains_and_held_items_stay_held(kind, rig, gates):
    with_wake = _aligned_run(kind, rig, gates, wake=True)
    without = _aligned_run(kind, rig, gates, wake=False)
    assert with_wake == without
    assert with_wake[0] and with_wake[1]


# ------------------------------------------------- (e) a close that raised


@by_operator
def test_failed_close_surfaces_at_the_drain(kind, rig, gates):
    r = rig(kind)
    r.feed(FIRST)
    assert gates.dispatched(1)
    gates.release(0, error=RuntimeError("the copy failed"))
    # the task fails now, not one slide later at its next input
    assert wait_until(lambda: not r.task.thread.is_alive(), timeout=1.0)
    failed = [resp for resp in r.responses() if resp.kind == "task_failed"]
    assert len(failed) == 1 and "the copy failed" in failed[0].error


# --------------------------------------------- (f) after the task ended


@by_operator
def test_wake_after_the_task_ended_is_a_noop(kind, rig, gates):
    from arroyo_tpu.ops.prefetch import shared_prefetcher

    r = rig(kind)
    r.feed(FIRST)
    assert gates.dispatched(1)
    r.abort()  # the engine gave the pipeline up with the close in flight
    gates.release(0)
    assert wait_until(lambda: r.wakes >= 1)
    assert r.inbox.get(timeout=0.01) is None and not r.inbox._woken
    assert r.events() == []
    # the fetch worker that gave the wake lives on
    assert shared_prefetcher().submit(lambda: 5).result() == 5


# ------------------------------------------------------ (g) chaining on


@pytest.mark.parametrize("kind", [Chained(Tumbling()), Chained(Sliding())],
                         ids=lambda k: k.name)
def test_chained_close_leaves_without_further_input(kind, rig, gates):
    r = rig(kind)
    assert len(r.op.members) == 2 and r.op._closers == [1]
    r.feed(FIRST)
    assert gates.dispatched(1)
    assert r.op.closes_in_flight()
    gates.release(0)
    assert wait_until(lambda: r.events() == r.left(FIRST), timeout=1.0), r.events()
    assert not r.op.closes_in_flight()
    r.finish()


# ------------------------------------- (h) the mark and the two counters


def _close_counters(r):
    c = r.task.metrics.counters
    return c["arroyo_worker_closes_on_wake"], c["arroyo_worker_closes_on_input"]


@by_operator
def test_mark_and_counters_when_the_wake_wins(kind, rig, gates):
    from arroyo_tpu.metrics import registry
    from arroyo_tpu.obs import trace

    r = rig(kind, job=f"wake-wins-{kind.name}")
    r.feed(FIRST)
    assert gates.dispatched(1)
    gates.release(0)
    assert wait_until(lambda: r.events() == r.left(FIRST), timeout=1.0)
    assert _close_counters(r) == (1, 0)
    marks = trace.spans("close.wake", job=r.task.task_info.job_id)
    assert [(m.node, m.trace_id) for m in marks] == [("op", kind.trace_id(FIRST))]
    # after the rows' own mark, before the watermark's
    out = trace.spans("rows.out", job=r.task.task_info.job_id)
    assert marks[0].t0_ns <= out[-1].t0_ns
    text = registry.prometheus_text()
    label = f'{{job="{r.task.task_info.job_id}",operator="op",subtask="0"}}'
    assert f"arroyo_worker_closes_on_wake{label} 1" in text
    assert f"arroyo_worker_closes_on_input{label} 0" in text
    r.finish()
    assert _close_counters(r) == (1, 0)


@by_operator
def test_mark_and_counters_when_the_next_batch_wins(kind, rig, gates):
    from arroyo_tpu.obs import trace

    r = rig(kind, job=f"batch-wins-{kind.name}")
    handle_watermark, in_hook, go_on = r.op.handle_watermark, threading.Event(), threading.Event()

    def slow(watermark, ctx, collector):
        out = handle_watermark(watermark, ctx, collector)
        if gates.handles and not in_hook.is_set():
            in_hook.set()
            assert go_on.wait(10)
        return out

    r.op.handle_watermark = slow
    r.feed(FIRST)
    assert in_hook.wait(5)
    # while the task is busy the copy lands and the next window's rows arrive
    gates.release(0)
    assert wait_until(lambda: r.wakes >= 1)
    r.feed(FIRST + 1, watermark=False)
    go_on.set()
    # the batch is taken first and its hook's own drain emits the close
    assert wait_until(lambda: r.events() == r.left(FIRST), timeout=1.0), r.events()
    assert _close_counters(r) == (0, 1)
    assert trace.spans("close.wake", job=r.task.task_info.job_id) == []
    # the second close is forced out by the end of data: not a wake either
    r.watermark(kind.closing_watermark(FIRST + 1))
    assert gates.dispatched(2)
    gates.release(1)
    r.finish()
    wake, other = _close_counters(r)
    assert wake + other == 2 and r.events()[:2] == r.left(FIRST)
    assert ("rows", kind.out_key(FIRST + 1)) in r.events()[2:]


def test_explain_shows_the_closes_on_the_waits_line():
    from arroyo_tpu.obs.profile import _annotations

    prof = {"busy_pct": 1.0, "account": {"inbox_wait": 2.0},
            "arroyo_worker_closes_on_wake": 16, "arroyo_worker_closes_on_input": 1}
    lines = _annotations(prof)
    assert "waits: starved 2.00s  closes 16 on wake, 1 on input" in lines
    prof["account"] = {}
    assert "waits: closes 16 on wake, 1 on input" in _annotations(prof)
    prof.update({"arroyo_worker_closes_on_wake": 0, "arroyo_worker_closes_on_input": 0})
    assert not [ln for ln in _annotations(prof) if ln.startswith("waits:")]


def test_operators_without_closes_are_never_asked_to_drain():
    from arroyo_tpu.operators.base import Operator

    op = Operator()
    assert op.closes_in_flight() is False
    assert op.drain_ready(None, None) is None
