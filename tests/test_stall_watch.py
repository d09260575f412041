"""The watch thread (obs/trace.py): every wait for the device stands in a
table while it lasts, one thread looks at the table ten times a second, and
a wait that has been open for a second is written down while it still is:
one ``device.stall`` mark with what every other task and thread was inside
of, how late the watch itself woke, the scheduler's counters and an
allocator call's answer; the counter, the ``task.account`` key, the job
event and ``explain``'s line. Fake buffers stand in for the device."""

import threading
import time

import pytest

import arroyo_tpu
from arroyo_tpu import config as cfg
from arroyo_tpu.engine import Engine
from arroyo_tpu.metrics import TaskMetrics, registry
from arroyo_tpu.obs import trace
from arroyo_tpu.obs.events import recorder as events
from arroyo_tpu.obs.profile import job_profile, render_explain
from arroyo_tpu.ops.prefetch import Future, wait_buffers_ready
from arroyo_tpu.sql import plan_query

arroyo_tpu._load_operators()

STALL_FIELDS = {"waited", "program", "age_ms", "watch_late_ms", "open", "threads",
                "sched", "bytes_in_use", "memory_stats_ms"}


class SlowBuffer:
    """A device buffer whose copy lands ``seconds`` after it was made."""

    def __init__(self, seconds: float):
        self.ready_at = time.monotonic() + seconds

    def is_ready(self) -> bool:
        return time.monotonic() >= self.ready_at


def on_threads(*fns):
    """Run each fn on a thread of its own, side by side; their results."""
    box = [None] * len(fns)

    def run(i):
        box[i] = fns[i]()

    threads = [threading.Thread(target=run, args=(i,), name=f"stall-test-{i}")
               for i in range(len(fns))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return box


def watch_threads() -> list:
    return [t for t in threading.enumerate() if t.name == "arroyo-watch" and t.is_alive()]


def no_watch_left(within_s: float = 2.0) -> bool:
    limit = time.monotonic() + within_s
    while watch_threads() and time.monotonic() < limit:
        time.sleep(0.01)
    return not watch_threads()


def fetch(job: str, node: str, seconds: float, trace_id=None):
    """A task that binds itself and waits ``seconds`` for a close's buffers."""
    m = registry.task(job, node, 0)

    def task():
        lane = trace.bind(job, node, 0, m)
        with trace.window(trace_id), trace.span("agg.close"):
            with trace.wait(trace.DEVICE_WAIT, "agg.fetch", program="jit_go") as waiting:
                wait_buffers_ready([SlowBuffer(seconds)], waiting=waiting)
        lane.account(force=True)
        trace.unbind()

    return task


def test_a_wait_that_outlasts_a_second_is_written_down_while_it_lasts():
    job = "stall-one"
    seen_open = []

    def onlooker():
        # what a reader sees while the wait is still open
        limit = time.monotonic() + 5
        while time.monotonic() < limit:
            marks = trace.spans("device.stall", job=job)
            if marks:
                seen_open.append((marks, trace.spans("agg.fetch", job=job)))
                return
            time.sleep(0.01)

    on_threads(fetch(job, "agg_1", 1.6, trace_id=20_000_000), onlooker)
    (mark,) = trace.spans("device.stall", job=job)
    assert seen_open and seen_open[0][1] == []  # the mark came before the wait's end
    assert (mark.node, mark.subtask, mark.trace_id) == ("agg_1", 0, 20_000_000)
    a = mark.args
    assert set(a) == STALL_FIELDS
    assert (a["waited"], a["program"]) == ("agg.fetch", "jit_go")
    assert 1000 <= a["age_ms"] < 1600
    this_tick, worst = a["watch_late_ms"]
    assert 0 <= this_tick <= worst < 900  # the host ran on: only the device was late
    # nobody else waited or was inside of anything; every thread has a frame
    assert [e for e in a["open"] if e[0] == "agg_1"] == []
    assert all(len(e) == 3 and e[2] >= 0 for e in a["open"])
    names = [t[0] for t in a["threads"]]
    assert "stall-test-0" in names and "arroyo-watch" in names and len(names) <= 32
    where = dict(map(tuple, a["threads"]))["stall-test-0"]
    assert "prefetch.py:" in where and "wait_buffers_ready" in where
    assert isinstance(a["sched"], dict) and isinstance(a["bytes_in_use"], (list, type(None)))
    assert 0 <= a["memory_stats_ms"] < 1000 and mark.t1_ns >= mark.t0_ns
    # the wait's own record, written at its end as ever, now says so
    (rec,) = trace.spans("agg.fetch", job=job)
    assert rec.args == {"program": "jit_go", "stalled": True}
    assert rec.t0_ns < mark.t0_ns < rec.t1_ns and rec.trace_id == 20_000_000
    # the counter, the account's key, the event, explain's line
    m = registry.task(job, "agg_1", 0)
    assert m.counters["arroyo_worker_device_stalls"] == 1
    assert 1600 <= m.device_stall_max_ms < 5000
    assert trace.spans("task.account", job=job)[-1].args["device_stalls"] == 1
    (ev,) = [e for e in events.events(job) if e["code"] == "DEVICE_STALLED"]
    assert ev["level"] == "WARN" and ev["node"] == "agg_1"
    assert ev["data"]["stalls"] == 1 and ev["data"]["program"] == "jit_go"
    metrics = registry.job_metrics(job)
    text = render_explain([{"id": "agg_1", "op": "x", "parallelism": 1}], [],
                          job_profile(metrics))
    assert f"stalls 1 (longest {m.device_stall_max_ms:,.0f} ms)" in text
    assert f'arroyo_worker_device_stalls{{job="{job}",operator="agg_1",subtask="0"}} 1' \
        in registry.prometheus_text()


def test_a_fetch_workers_wait_is_flagged_under_the_tasks_name():
    job = "stall-worker"
    m = registry.task(job, "join_7", 0)

    def owner():
        lane = trace.bind(job, "join_7", 0, m)
        # the worker waits on the task's behalf; the task idles meanwhile
        worker = threading.Thread(target=on_worker, args=(lane,), name="arroyo-prefetch-t")
        worker.start()
        with trace.wait(trace.INBOX_WAIT, "task.inbox_wait"):
            worker.join()
        trace.unbind()

    def on_worker(lane):
        with trace.wait(trace.DEVICE_WAIT, "join.fetch", lane=lane, trace_id=30_000_000,
                        program="jit_probe") as waiting:
            wait_buffers_ready([SlowBuffer(1.6)], waiting=waiting)

    on_threads(owner)
    (mark,) = trace.spans("device.stall", job=job)
    assert (mark.node, mark.trace_id) == ("join_7", 30_000_000)
    assert (mark.args["waited"], mark.args["program"]) == ("join.fetch", "jit_probe")
    assert ["join_7", "task.inbox_wait"] in [e[:2] for e in mark.args["open"]]
    assert m.counters["arroyo_worker_device_stalls"] == 1
    assert m.account["device_wait"] == 0.0  # the task's own thread did not wait for it
    (rec,) = trace.spans("join.fetch", job=job)
    assert rec.args["stalled"] is True and rec.node == "join_7"


def test_a_short_wait_is_no_stall():
    job = "stall-none"
    on_threads(fetch(job, "agg_1", 0.3))
    assert trace.spans("device.stall", job=job) == []
    (rec,) = trace.spans("agg.fetch", job=job)
    assert rec.args == {"program": "jit_go"}
    assert registry.task(job, "agg_1", 0).counters["arroyo_worker_device_stalls"] == 0
    assert [e for e in events.events(job) if e["code"] == "DEVICE_STALLED"] == []
    assert "stalls" not in render_explain(
        [{"id": "agg_1", "op": "x", "parallelism": 1}], [],
        job_profile(registry.job_metrics(job)))


def test_two_tasks_stalled_at_once_see_each_other_and_share_one_event():
    job = "stall-two"
    on_threads(fetch(job, "agg_4", 1.6), fetch(job, "agg_9", 1.6))
    marks = trace.spans("device.stall", job=job)
    assert sorted(m.node for m in marks) == ["agg_4", "agg_9"]
    for m in marks:
        other = "agg_9" if m.node == "agg_4" else "agg_4"
        waits = [e for e in m.args["open"] if e[:2] == [other, "agg.fetch"]]
        assert len(waits) == 1 and waits[0][2] >= 900  # the other's wait, as old as this one
    # what ends it: both waits' records end within a few ms of each other
    ends = [s.t1_ns for s in trace.spans("agg.fetch", job=job)]
    assert len(ends) == 2 and abs(ends[0] - ends[1]) < 200e6
    # at most one event a minute a job
    evs = [e for e in events.events(job) if e["code"] == "DEVICE_STALLED"]
    assert len(evs) == 1 and evs[0]["data"]["stalls"] == 1


def test_a_watch_that_oversleeps_says_so(monkeypatch):
    """The pulse: a watch thread held off its CPU, or off the interpreter
    lock, wakes late; the tick's mark and a stall's ``watch_late_ms`` carry
    by how much."""
    job = "stall-late"
    nap = trace._nap
    overslept = []

    def oversleeping(stop, seconds):
        # one long sleep, begun while the wait is young: a frozen process
        extra = 0.0
        if not overslept and any(w.lane.ident[0] == job
                                 for w in list(trace._open_waits.values())):
            overslept.append(True)
            extra = 1.2
        return nap(stop, seconds + extra)

    monkeypatch.setattr(trace, "_nap", oversleeping)

    def then_idle():
        # one lane bound all through: the watch that overslept lives to
        # write its second's tick
        trace.bind(job, "agg_1", 0, registry.task(job, "agg_1", 0))
        with trace.wait(trace.DEVICE_WAIT, "agg.fetch", program="jit_go") as waiting:
            wait_buffers_ready([SlowBuffer(2.0)], waiting=waiting)
        time.sleep(1.3)
        trace.unbind()

    t0 = time.monotonic_ns()
    on_threads(then_idle)
    (mark,) = trace.spans("device.stall", job=job)
    this_tick, worst = mark.args["watch_late_ms"]
    assert 1100 <= this_tick <= worst and mark.args["age_ms"] >= 1200
    ticks = trace.spans("watch.tick", t0=t0)
    assert ticks and all(s.node == "watch" and s.job is None for s in ticks)
    assert max(s.args["late_max_ms"] for s in ticks) >= 1100
    assert all(s.args["ticks"] >= 1 for s in ticks)
    # each of the scheduler's counters is there, or left out: never a guess
    assert set(ticks[0].args) - {"late_max_ms", "ticks"} <= {
        "cpu_ms", "run_delay_ms", "invol_switches", "steal_ms"}
    assert ticks[0].args["cpu_ms"] >= 0
    assert set(mark.args["sched"]) == set(ticks[0].args) - {"late_max_ms", "ticks"}


def test_a_wait_given_up_says_so():
    job = "stall-gave-up"
    m = TaskMetrics(job, "agg_1", 0)

    def task():
        trace.bind(job, "agg_1", 0, m)
        with trace.wait(trace.DEVICE_WAIT, "agg.fetch", program="jit_go") as waiting:
            wait_buffers_ready([SlowBuffer(60.0)], deadline_s=0.05, waiting=waiting)
        wait_buffers_ready([SlowBuffer(60.0)], deadline_s=0.01)  # under no wait: as before
        trace.unbind()

    on_threads(task)
    (rec,) = trace.spans("agg.fetch", job=job)
    assert rec.args == {"program": "jit_go", "gave_up": True}
    assert 0.04 < (rec.t1_ns - rec.t0_ns) / 1e9 < 1.0


def test_a_forced_drain_names_the_program_it_waits_for():
    job = "stall-drain"
    m = TaskMetrics(job, "agg_1", 0)
    fut = Future(lambda: 7, program="jit_go")

    def task():
        trace.bind(job, "agg_1", 0, m)
        threading.Timer(0.01, fut._run).start()
        out = fut.result()
        trace.unbind()
        return out

    assert on_threads(task) == [7]
    (rec,) = trace.spans("agg.drain", job=job)
    assert rec.args == {"program": "jit_go"}
    assert trace._open_waits == {} or all(
        w.lane.ident[0] != job for w in list(trace._open_waits.values()))


class SlowArray(SlowBuffer):
    """A device array: ready after ``seconds``, then readable."""

    def __init__(self, seconds: float, values):
        SlowBuffer.__init__(self, seconds)
        self.values = values

    def __array__(self, dtype=None, copy=None):
        import numpy as np

        return np.asarray(self.values, dtype=dtype)


def test_the_joins_wait_for_its_probe_is_join_fetch_under_the_window_it_is_for():
    """``JoinHandle.result`` runs on a fetch worker; its wait is recorded
    under the join task's name with the window the probe was dispatched
    for, inside the ``join.probe`` span."""
    from arroyo_tpu.ops.join_probe import JoinHandle

    job = "stall-join"
    m = TaskMetrics(job, "join_16", 0)
    box = []

    def task():
        trace.bind(job, "join_16", 0, m)
        probe = trace.join_probe(40_000_000, 2, 2, (64, 64))
        with trace.window(40_000_000), probe:
            # keys [5, 7] probe build keys [7, 5]: order, lo, hi as the device gives them
            box.append((JoinHandle(2, 2, SlowArray(0.02, [1, 0]), SlowArray(0.0, [0, 1]),
                                   SlowArray(0.0, [1, 2])), probe))
        trace.unbind()

    on_threads(task)
    handle, probe = box[0]

    def worker():
        li, ri = handle.result()
        probe.note(pairs=len(li))
        probe.end()
        return li.tolist(), ri.tolist()

    assert on_threads(worker) == [([0, 1], [1, 0])]
    (fetch_rec,) = trace.spans("join.fetch", job=job)
    (probe_rec,) = trace.spans("join.probe", job=job)
    assert (fetch_rec.node, fetch_rec.trace_id) == ("join_16", 40_000_000)
    assert fetch_rec.args == {"program": "jit_probe"}
    assert probe_rec.t0_ns <= fetch_rec.t0_ns and fetch_rec.t1_ns <= probe_rec.t1_ns
    assert m.account["device_wait"] == 0.0  # a fetch worker waited, not the join's thread


def test_a_lane_says_what_its_thread_is_inside_of():
    m = TaskMetrics("stall-open", "n", 0)

    def task():
        lane = trace.bind("stall-open", "n", 0, m)
        seen = [lane.open]
        with trace.span("agg.dispatch"):
            seen.append(lane.open[0])
            with trace.wait(trace.DEVICE_WAIT, "agg.fetch"):
                seen.append(lane.open[0])
                n_open = sum(w.lane is lane for w in list(trace._open_waits.values()))
            seen.append(lane.open[0])
        with trace.open_span("agg.close") as close:
            seen.append(lane.open[0])
        seen.append(lane.open)  # the with block of a deferred span is its beginning
        close.end()
        trace.unbind()
        return seen, n_open

    ((seen, n_open),) = on_threads(task)
    assert seen == [None, "agg.dispatch", "agg.fetch", "agg.dispatch", "agg.close", None]
    assert n_open == 1


SQL = """CREATE TABLE nexmark ("bid" BOOLEAN, "bid.auction" BIGINT, "bid.price" BIGINT)
WITH (connector = 'nexmark', inter_event_micros = 5000, first_event_micros = 0,
      event_count = 200000, event_rate = 2000, seed = 7);
CREATE TABLE out (auction BIGINT, price BIGINT) WITH (connector = 'blackhole', type = 'sink');
INSERT INTO out SELECT "bid.auction", "bid.price" FROM nexmark WHERE "bid";
"""


@pytest.mark.parametrize("enabled", [True, False], ids=["profile-on", "profile-off"])
def test_the_watch_lives_as_long_as_a_task_is_bound(enabled, tmp_path):
    """One watch thread from the first bind() of a process to the last
    unbind(): there while an engine's tasks run, gone after Engine.stop;
    never there under ``profile.enabled: false``, which binds no task."""
    assert no_watch_left()
    job = f"stall-engine-{enabled}"
    with cfg.scoped({"profile.enabled": enabled, "pipeline.source-batch-size": 256}):
        engine = Engine(plan_query(SQL).graph, job_id=job, storage_url=str(tmp_path))
        engine.start()
        try:
            limit = time.monotonic() + 10  # until the stream has started
            while not trace.spans(job=job) and enabled and time.monotonic() < limit:
                time.sleep(0.01)
            time.sleep(0.05)
            alive = len(watch_threads())
        finally:
            engine.stop()
            engine.join(30)
    assert alive == (1 if enabled else 0)
    assert (trace.spans(job=job) != []) == enabled
    assert no_watch_left()
