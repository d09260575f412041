"""The span ring and the task time account (obs/trace.py, engine/task.py,
engine/queues.py): the ring is bounded, the account adds up, a slow sink
reads as a blocked source and a slow source as a starved aggregate, a
window's stamps come in causal order on every operator of its path, the
profiler's host plane shows the same spans on the device trace's clock —
and the names the benchmark's harness still wraps from outside exist."""

import glob
import inspect
import os
import threading
import time

import numpy as np
import pytest

import arroyo_tpu
from arroyo_tpu import config as cfg
from arroyo_tpu.connectors import register_sink
from arroyo_tpu.engine import Engine
from arroyo_tpu.metrics import ACCOUNT_KEYS, ACCOUNT_SERIES, TaskMetrics, registry
from arroyo_tpu.obs import trace
from arroyo_tpu.operators.base import Operator
from arroyo_tpu.sql import plan_query

arroyo_tpu._load_operators()

# coalescing off: a batch, and so a watermark, every 256 events
SMALL = {"device.table-capacity": 4096, "pipeline.source-batch-size": 256,
         "engine.coalesce.enabled": False}
WIDTH, SLIDE = 10_000_000, 2_000_000
_SLOW_SINK_S = [0.0]


class _TestSink(Operator):
    def __init__(self, cfg_):
        pass

    def process_batch(self, batch, ctx, collector, input_index=0):
        if _SLOW_SINK_S[0]:
            time.sleep(_SLOW_SINK_S[0])


register_sink("span_test_sink")(_TestSink)

SOURCE = """CREATE TABLE nexmark ("bid" BOOLEAN, "bid.auction" BIGINT, "bid.price" BIGINT)
WITH (connector = 'nexmark', inter_event_micros = 5000, first_event_micros = 0,
      event_count = {events}, event_rate = {rate}, seed = 7);
"""
TUMBLING = SOURCE + """CREATE TABLE out (auction BIGINT, price BIGINT, ws TIMESTAMP)
WITH (connector = 'span_test_sink', type = 'sink');
INSERT INTO out SELECT P.auction, P.mx, P.window.start FROM (
  SELECT "bid.auction" AS auction, max("bid.price") AS mx,
    tumble(interval '10 seconds') AS window
  FROM nexmark WHERE "bid" GROUP BY "bid.auction", window) AS P
JOIN (SELECT max("bid.price") AS mx, tumble(interval '10 seconds') AS window
  FROM nexmark WHERE "bid" GROUP BY window) AS G
ON P.window = G.window AND P.mx = G.mx;
"""
SLIDING = SOURCE + """CREATE TABLE out (auction BIGINT, num BIGINT, ws TIMESTAMP)
WITH (connector = 'span_test_sink', type = 'sink');
INSERT INTO out SELECT auction, num, window.start FROM (
  SELECT "bid.auction" AS auction, count(*) AS num,
    hop(interval '2 seconds', interval '10 seconds') AS window
  FROM nexmark WHERE "bid" GROUP BY "bid.auction", window);
"""
PASS_THROUGH = SOURCE + """CREATE TABLE out (auction BIGINT, price BIGINT)
WITH (connector = 'span_test_sink', type = 'sink');
INSERT INTO out SELECT "bid.auction", "bid.price" FROM nexmark WHERE "bid";
"""


def run_sql(sql: str, job: str, tmp_path, events=20_000, rate=0, settings=None):
    graph = plan_query(sql.format(events=events, rate=rate)).graph
    with cfg.scoped(dict(SMALL, **(settings or {}))):
        Engine(graph, job_id=job, storage_url=str(tmp_path / job)).run_to_completion()
    return graph


def accounts(job: str) -> dict:
    """node -> the differences of its last and first task.account mark,
    with the wall time between them."""
    out = {}
    for s in trace.spans("task.account", job=job):
        out.setdefault(s.node, []).append(s)
    return {node: dict({k: marks[-1].args[k] - marks[0].args[k] for k in marks[0].args},
                       wall=(marks[-1].t0_ns - marks[0].t0_ns) / 1e9, marks=len(marks))
            for node, marks in out.items()}


def on_own_thread(fn):
    """Run fn on a thread of its own (a ring belongs to its thread)."""
    box = []
    t = threading.Thread(target=lambda: box.append(fn()))
    t.start()
    t.join()
    return box[0]


# ----------------------------------------------------------------- the ring


def test_ring_is_bounded_and_keeps_the_newest(monkeypatch):
    monkeypatch.setattr(trace, "RING_CAPACITY", 128)

    def fill():
        trace.bind("ring-job", "n", 0, TaskMetrics("ring-job", "n", 0))
        for i in range(1000):
            trace.mark("wm.in", i)
        with trace.span("agg.dispatch", rows=3):
            pass
        trace.unbind()

    on_own_thread(fill)
    got = trace.spans(job="ring-job")
    assert len(got) == 128
    assert [s.name for s in got[-2:]] == ["agg.dispatch", "task.account"]
    assert got[-2].args == {"rows": 3} and got[-2].t1_ns >= got[-2].t0_ns
    marks = [s.trace_id for s in got if s.name == "wm.in"]
    assert marks == list(range(1000 - len(marks), 1000))


def test_an_unbound_thread_records_nothing():
    before = len(trace.spans())

    def quiet():
        with trace.span("agg.dispatch"), trace.wait(trace.DEVICE_WAIT, "agg.fetch"):
            trace.mark("wm.in", 1)
        with trace.open_span("agg.close") as tok:
            pass
        tok.end()
        return trace.current()

    assert on_own_thread(quiet) is None
    assert len(trace.spans()) == before


def test_profile_disabled_binds_no_task(tmp_path):
    run_sql(PASS_THROUGH, "span-off", tmp_path, events=2_000,
            settings={"profile.enabled": False})
    assert trace.spans(job="span-off") == []


def test_a_wait_is_charged_less_its_cpu_and_recorded_from_1ms():
    m = TaskMetrics("wait-job", "n", 0)

    def waits():
        trace.bind("wait-job", "n", 0, m)
        with trace.wait(trace.PUT_WAIT, "task.put_wait", dest="d"):
            time.sleep(0.02)
        with trace.wait(trace.PUT_WAIT, "task.put_wait"):
            pass  # too short to record, still counted
        w0 = time.monotonic()
        with trace.wait(trace.INBOX_WAIT, "task.inbox_wait"):
            t = time.thread_time()
            while time.thread_time() - t < 0.02:  # busy, not waiting
                pass
        wall = time.monotonic() - w0
        trace.unbind()
        return wall

    busy_wall = on_own_thread(waits)
    assert 0.015 < m.account["put_wait"] < 1.0
    # the 20 ms burnt inside are not waiting, whatever a busy machine adds
    assert m.account["inbox_wait"] <= busy_wall - 0.019
    recorded = trace.spans("task.put_wait", job="wait-job")
    assert len(recorded) == 1 and recorded[0].args == {"dest": "d"}


def test_a_span_of_another_threads_work_carries_the_owners_name():
    m = TaskMetrics("owner-job", "agg", 0)

    def owner():
        lane = trace.bind("owner-job", "agg", 0, m)
        with trace.window(777), trace.open_span("agg.close") as tok:
            pass
        return lane, tok

    lane, tok = on_own_thread(owner)

    def worker():
        with trace.wait(trace.DEVICE_WAIT, "agg.fetch", lane=lane):
            time.sleep(0.003)
        tok.end()

    on_own_thread(worker)
    close = trace.spans("agg.close", job="owner-job")
    fetch = trace.spans("agg.fetch", job="owner-job")
    assert [(s.node, s.trace_id) for s in close] == [("agg", 777)]
    assert close[0].t1_ns >= fetch[0].t1_ns > close[0].t0_ns
    assert m.account["device_wait"] == 0.0  # the owner's thread did not wait


# -------------------------------------------------------------- the account


@pytest.fixture(scope="module")
def tumbling_run(tmp_path_factory):
    graph = run_sql(TUMBLING, "span-tumbling", tmp_path_factory.mktemp("t"))
    return "span-tumbling", graph


@pytest.fixture(scope="module")
def sliding_run(tmp_path_factory):
    graph = run_sql(SLIDING, "span-sliding", tmp_path_factory.mktemp("s"))
    return "span-sliding", graph


@pytest.fixture(scope="module")
def mesh_run(tmp_path_factory):
    """The tumbling query with its window state sharded over four devices."""
    graph = run_sql(TUMBLING, "span-mesh", tmp_path_factory.mktemp("m"),
                    settings={"device.mesh-devices": 4})
    return "span-mesh", graph


@pytest.mark.parametrize("run", ["tumbling_run", "mesh_run"])
def test_the_account_adds_up_for_every_task(run, request):
    job, graph = request.getfixturevalue(run)
    acc = accounts(job)
    assert set(acc) == set(graph.nodes)
    for node, a in acc.items():
        measured = a["cpu"] + a["inbox_wait"] + a["put_wait"] + a["device_wait"]
        rest = a["wall"] - measured
        assert rest >= -0.01 * a["wall"] - 1e-3, (node, a)
        assert a["put_wait_in_hook"] <= a["put_wait"] + 1e-9
        assert a["device_wait_in_hook"] <= a["device_wait"] + 1e-9
        assert a["self_time"] >= a["put_wait_in_hook"] + a["device_wait_in_hook"] - 1e-6
        assert a["marks"] >= 2
    if run == "mesh_run":
        # a sharded aggregate waits for the device inside its closes, and
        # its account says so (device_wait was 0 on a task that waited for
        # the device nearly all of its wall: ROADMAP B18)
        agg = next(n for n in acc if "aggregate" in n and acc[n]["device_wait"] > 0)
        closes = trace.spans("agg.close", node=agg, job=job)
        fetches = trace.spans("agg.fetch", node=agg, job=job)
        assert fetches and all(f.args["program"] in ("jit_local_extract", "jit_local_step")
                               for f in fetches)
        assert any(c.t0_ns <= f.t0_ns and f.t1_ns <= c.t1_ns
                   for f in fetches for c in closes)


@pytest.mark.parametrize("run", ["tumbling_run", "sliding_run"])
def test_the_sketch_is_charged_to_the_tasks_that_take_keyed_batches(run, request):
    """``sketch``: the wall a task's thread spent feeding its key sketch, in
    the run loop outside every hook. In every mark, in ``account_over``,
    above 0 on just the tasks whose sketch counted rows, and no fourth wait:
    it lies inside what the three waits leave of the wall."""
    job, graph = request.getfixturevalue(run)
    acc, metrics = accounts(job), registry.job_metrics(job)
    assert all("sketch" in s.args for s in trace.spans("task.account", job=job))
    keyed = {n for n, m in metrics.items() if m.get("sketch_total")}
    assert keyed and any("aggregate" in n for n in keyed)
    assert {n for n, a in acc.items() if a["sketch"] > 0} == keyed < set(graph.nodes)
    for node, a in acc.items():
        waits = a["inbox_wait"] + a["put_wait"] + a["device_wait"]
        assert 0.0 <= a["sketch"] <= a["wall"] - waits + 1e-3, (node, a)
        over = trace.account_over(node, job=job)
        assert over["sketch"] == pytest.approx(a["sketch"])
        assert registry.task(job, node, 0).account["sketch"] >= a["sketch"]


def test_no_sketch_and_no_charge_with_profiling_off(tmp_path):
    graph = run_sql(TUMBLING, "span-unprofiled", tmp_path, events=4_000,
                    settings={"profile.enabled": False})
    for node in graph.nodes:
        m = registry.task("span-unprofiled", node, 0)
        assert m.sketch is None and m.account["sketch"] == 0.0
    assert not trace.spans("task.account", job="span-unprofiled")


def test_account_marks_come_at_least_four_times_a_second(tmp_path):
    run_sql(PASS_THROUGH, "span-marks", tmp_path, events=3_000, rate=2_000)
    by_node = {}
    for s in trace.spans("task.account", job="span-marks"):
        by_node.setdefault(s.node, []).append(s.t0_ns)
    assert by_node
    for node, ts in by_node.items():
        gaps = sorted(b - a for a, b in zip(ts, ts[1:]))
        # due every 0.2 s; a busy machine may hold a thread up now and then
        assert gaps[len(gaps) // 2] < 0.25e9 and gaps[-1] < 1e9, (node, gaps[-3:])


def test_a_slow_sink_reads_as_a_blocked_source(tmp_path):
    _SLOW_SINK_S[0] = 0.01
    try:
        run_sql(PASS_THROUGH, "span-slow-sink", tmp_path, events=12_000,
                settings={"worker.queue-size": 256, "pipeline.source-batch-size": 128})
    finally:
        _SLOW_SINK_S[0] = 0.0
    acc = accounts("span-slow-sink")
    src = next(a for n, a in acc.items() if n.startswith("source"))
    assert src["put_wait"] / src["wall"] > 0.5, src
    waits = trace.spans("task.put_wait", job="span-slow-sink")
    assert waits and all(s.args["dest"] for s in waits)


def test_a_slow_source_reads_as_a_starved_aggregate(tmp_path):
    run_sql(SLIDING, "span-slow-source", tmp_path, events=4_000, rate=2_500)
    acc = accounts("span-slow-source")
    agg = next(a for n, a in acc.items() if "aggregate" in n)
    assert agg["inbox_wait"] / agg["wall"] > 0.5, agg
    src = next(a for n, a in acc.items() if n.startswith("source"))
    assert src["inbox_wait"] / src["wall"] > 0.3, src  # ahead of its schedule
    emits = trace.spans("source.emit", job="span-slow-source")
    assert emits and all(s.args["due_ns"] <= s.t0_ns + 5e6 for s in emits)
    assert [s.args["first_event"] for s in emits] == sorted(s.args["first_event"] for s in emits)


def test_the_new_counters_are_exported(tumbling_run):
    job, _graph = tumbling_run
    text = registry.prometheus_text()
    assert set(ACCOUNT_SERIES) == set(ACCOUNT_KEYS)
    assert f'arroyo_worker_inbox_wait_seconds{{job="{job}"' in text
    for series in ACCOUNT_SERIES.values():
        assert f"# TYPE {series} counter" in text
    metrics = registry.job_metrics(job)
    from arroyo_tpu.obs.profile import job_profile, render_explain

    some = next(m for n, m in metrics.items() if n.startswith("value"))
    assert set(some["account"]) == set(ACCOUNT_KEYS) and some["account"]["inbox_wait"] > 0
    profile = job_profile(metrics)
    nodes = [{"id": n, "op": "x", "parallelism": 1} for n in metrics]
    assert "waits: starved" in render_explain(nodes, [], profile)
    # the watch thread's counter: exported, 0 in a run that met no stall,
    # and in every task.account mark beside table_grows
    assert some["arroyo_worker_device_stalls"] == 0
    assert "arroyo_worker_device_stalls" in text
    assert all(s.args["device_stalls"] == 0 for s in trace.spans("task.account", job=job))


def test_spans_show_in_the_chrome_export(tumbling_run):
    job, _graph = tumbling_run
    chrome = trace.chrome_trace(job, {}, ring_spans=trace.spans(job=job))
    events = [e for e in chrome["traceEvents"] if e["cat"] == "span"]
    names = {e["name"] for e in events}
    assert {"agg.dispatch", "agg.close", "wm.in", "rows.out", "task.account"} <= names
    close = next(e for e in events if e["name"] == "agg.close")
    assert close["ph"] == "X" and close["dur"] > 0 and "trace_id" in close["args"]
    assert abs(close["ts"] - time.time() * 1e6) < 600e6  # wall micros, not monotonic
    assert next(e for e in events if e["name"] == "wm.in")["ph"] == "i"


# ---------------------------------------------------------------- the trail


def first_at(recs, value):
    return next(((t0, t1) for tid, t0, t1 in recs if tid >= value), None)


def check_aggregate(job, node, width, out_value):
    """Every window the aggregate closed on a watermark: wm.in -> agg.close
    (dispatched -> rows on the host) -> rows.out -> wm.out. By the closes,
    one a window whatever the machine's load; the emissions are not: a
    sliding aggregate that finds several closes landed sends their windows
    in one batch under one rows.out mark, the last window's, so a busy
    machine has fewer marks than windows (``r`` is the mark of the batch
    that carried the window: the first at or past its end)."""
    wm_in, wm_out = trace.stamps("wm.in", node, job), trace.stamps("wm.out", node, job)
    close, rows = trace.stamps("agg.close", node, job), trace.stamps("rows.out", node, job)
    assert rows and close and wm_in and wm_out
    checked = 0
    for end in sorted({tid for tid, _a, _b in close}):
        t_in, c = first_at(wm_in, end), first_at(close, end)
        t_out = first_at(wm_out, out_value(end))
        r = first_at(rows, end)
        if t_in is None or t_out is None:
            continue  # closed by the end of the stream, not by a watermark
        assert t_in[0] <= c[0] <= c[1] <= r[0] <= t_out[0], (node, end, t_in, c, r, t_out)
        checked += 1
    return checked


def check_edges(job, graph, values):
    """Along every edge, a watermark leaves before it arrives; inside every
    operator it arrives before it leaves."""
    for nid in graph.nodes:
        ins = trace.crossings("wm.in", nid, values, job)
        outs = trace.crossings("wm.out", nid, values, job)
        for a, b in zip(ins, outs):
            assert a is None or b is None or a <= b, nid
        for e in graph.out_edges(nid):
            down = trace.crossings("wm.in", e.dst, values, job)
            for a, b in zip(outs, down):
                assert a is None or b is None or a <= b, (nid, e.dst)


def test_tumbling_windows_leave_in_causal_order(tumbling_run):
    job, graph = tumbling_run
    aggs = [n for n in graph.nodes if "aggregate" in n]
    assert len(aggs) == 2
    for node in aggs:
        assert check_aggregate(job, node, WIDTH, lambda end: end) >= 5
    join = next(n for n in graph.nodes if n.startswith("join"))
    rows = trace.stamps("rows.out", join, job)
    wm_in, wm_out = trace.stamps("wm.in", join, job), trace.stamps("wm.out", join, job)
    assert rows
    for start in sorted({tid for tid, _a, _b in rows}):
        t_in, t_out = first_at(wm_in, start + 1), first_at(wm_out, start + 1)
        last = max(t0 for tid, t0, _ in rows if tid == start)
        if t_in is not None and t_out is not None:
            assert t_in[0] <= last <= t_out[0], (start, t_in, last, t_out)
        for agg in aggs:  # the join has the window after each aggregate let it go
            left = first_at(trace.stamps("rows.out", agg, job), start + WIDTH)
            assert left[0] <= last
    ends = sorted({tid for tid, _a, _b in trace.stamps("rows.out", aggs[0], job)})
    check_edges(job, graph, ends)


def test_sliding_windows_leave_in_causal_order(sliding_run):
    job, graph = sliding_run
    agg = next(n for n in graph.nodes if "aggregate" in n)
    # 50 bins of 2 s in the stream's 100 s, each closed once; all but the
    # last, which the end of the stream closes, on a watermark
    assert check_aggregate(job, agg, WIDTH, lambda end: end - WIDTH + SLIDE) >= 40
    # one close per bin, named by the window that ends where the bin ends
    ids = [tid for tid, _a, _b in trace.stamps("agg.close", agg, job)]
    assert ids == sorted(ids) and all(tid % SLIDE == 0 for tid in ids)
    check_edges(job, graph, ids)
    # one pane combine a window (the four that start before the stream too),
    # named by the window's end, over before the batch that carried it left
    combines = trace.stamps("agg.combine", agg, job)
    assert [tid for tid, _a, _b in combines] == list(range(SLIDE, 100_000_000 + WIDTH, SLIDE))
    rows = trace.stamps("rows.out", agg, job)
    assert all(t1 <= first_at(rows, end)[0] for end, _t0, t1 in combines)


def test_the_slot_aggregates_spans_nest_in_the_hook(tumbling_run):
    job, graph = tumbling_run
    agg = next(n for n in graph.nodes if "aggregate" in n)
    directory = trace.spans("agg.directory", node=agg, job=job)
    dispatch = trace.spans("agg.dispatch", node=agg, job=job)
    assert len(directory) == len(dispatch) > 0
    for a, b in zip(directory, dispatch):
        assert a.t0_ns <= a.t1_ns <= b.t0_ns <= b.t1_ns
    assert trace.spans("agg.snapshot", job=job) == []  # no checkpoint in this run
    gen = trace.spans("source.generate", job=job)
    assert sum(s.args["rows"] for s in gen) == 2 * 20_000


@pytest.mark.parametrize("run", ["tumbling_run", "sliding_run"])
def test_the_directory_says_what_each_step_was(run, request):
    """``agg.directory`` carries the step's ``rows``, its first-seen groups
    (``misses``) and where they were placed (``on``); the task counts its
    directory steps and those that fell back to Python although the library
    is loaded (in every ``task.account`` mark; 0 here), and ``explain``'s
    ``waits:`` line prints both."""
    from arroyo_tpu import native
    from arroyo_tpu.obs.profile import job_profile, render_explain

    job, graph = request.getfixturevalue(run)
    metrics = registry.job_metrics(job)
    on = "native" if native.available() else "numpy"
    for agg in (n for n in graph.nodes if "aggregate" in n):
        directory = trace.spans("agg.directory", node=agg, job=job)
        dispatch = trace.spans("agg.dispatch", node=agg, job=job)
        assert directory and all(set(s.args) == {"rows", "misses", "on"} for s in directory)
        assert [s.args["rows"] for s in directory] == [s.args["rows"] for s in dispatch]
        assert all(0 <= s.args["misses"] <= s.args["rows"] for s in directory)
        assert {s.args["on"] for s in directory} == {on}
        # every group a close emitted was some step's first-seen group
        closed = sum(s.args["rows"] for s in trace.spans("agg.close", node=agg, job=job))
        assert sum(s.args["misses"] for s in directory) == closed > 0
        assert metrics[agg]["arroyo_worker_directory_steps"] == len(directory)
        assert metrics[agg]["arroyo_worker_directory_fallback_steps"] == 0
    marks = trace.spans("task.account", job=job)
    assert marks and all(s.args["directory_fallback_steps"] == 0 for s in marks)
    assert "arroyo_worker_directory_fallback_steps" in registry.prometheus_text()
    nodes = [{"id": n, "op": "x", "parallelism": 1} for n in metrics]
    lines = render_explain(nodes, [], job_profile(metrics)).splitlines()
    steps = metrics[agg]["arroyo_worker_directory_steps"]
    assert any(ln.strip().startswith("waits:") and f"directory {steps} steps, 0 in Python" in ln
               for ln in lines)


HOP_MAX = SOURCE + """CREATE TABLE out (auction BIGINT, mx BIGINT, ws TIMESTAMP)
WITH (connector = 'span_test_sink', type = 'sink');
INSERT INTO out SELECT auction, mx, window.start FROM (
  SELECT "bid.auction" AS auction, max("bid.price") AS mx,
    hop(interval '2 seconds', interval '10 seconds') AS window
  FROM nexmark WHERE "bid" GROUP BY "bid.auction", window);
"""


def _q5_hot_items() -> str:
    """The benchmark's own q5 text (two first-level sliding aggregates and
    the window's max behind one), its dollar names filled as SOURCE's are."""
    import string

    path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                        "benchmark", "configs", "nexmark-q5-hot-items.sql")
    with open(path) as f:
        text = string.Template(f.read()).substitute(
            seed="7,\n  event_count = {events}", sink="span_test_sink",
            event_rate="{rate}", inter_event_micros=5000, first_event_micros=0)
    return text


# plan -> (its text, why its sliding aggregates' closes cannot slide)
HOP_PLANS = {"q5-hot-items": (_q5_hot_items, ""), "hop-count": (lambda: SLIDING, ""),
             "hop-max": (lambda: HOP_MAX, "max is not retractable")}


@pytest.mark.parametrize("plan", list(HOP_PLANS))
def test_the_pane_closes_say_how_each_window_was_made(plan, tmp_path):
    """Every ``agg.combine`` span says ``on``: ``running``, the last window
    slid by a bin, or ``full``, its bins combined anew; the task's two
    counters are the spans of each kind (in every ``task.account`` mark and
    in ``account_over()``). On the q5 plans ``full`` is exactly the seeding
    close, the first; where the accumulators cannot be retracted it is every
    close. ``explain`` prints the counts on the ``waits:`` line and which the
    aggregate takes, and why not, on the ``table:`` line."""
    from arroyo_tpu import native
    from arroyo_tpu.obs.profile import job_profile, render_explain

    text, why = HOP_PLANS[plan]
    if not why and not native.available():
        why = "the host library is not loaded"
    job = f"span-panes-{plan}"
    graph = run_sql(text(), job, tmp_path)
    metrics = registry.job_metrics(job)
    sliding = [n for n in graph.nodes if graph.nodes[n].op.value == "sliding_aggregate"]
    assert len(sliding) == (2 if plan == "q5-hot-items" else 1)
    nodes = [{"id": n, "op": "x", "parallelism": 1} for n in metrics]
    explained = render_explain(nodes, [], job_profile(metrics))
    for agg in sliding:
        spans = trace.spans("agg.combine", node=agg, job=job)
        # 100 s of stream in 2 s slides, and the four windows that start before it
        assert len(spans) == 20_000 * 5_000 // SLIDE + WIDTH // SLIDE - 1
        assert all(set(s.args) == {"bins", "rows_in", "rows", "on"} for s in spans)
        full = sum(s.args["on"] == "full" for s in spans)
        assert [s.args["on"] for s in spans] == (
            ["full"] * len(spans) if why else ["full"] + ["running"] * (len(spans) - 1))
        m = metrics[agg]
        assert m["arroyo_worker_pane_closes_full"] == full
        assert m["arroyo_worker_pane_closes_running"] == len(spans) - full
        last = trace.spans("task.account", node=agg, job=job)[-1].args
        assert (last["pane_closes_running"], last["pane_closes_full"]) == (len(spans) - full, full)
        over = trace.account_over(agg, job=job)
        assert over["pane_closes_running"] + over["pane_closes_full"] <= len(spans)
        assert m["panes"]["closes"] == (f"full ({why})" if why else "running")
        assert f"closes {len(spans) - full:,} running, {full:,} full" in explained
    assert ("closes: " + (f"full ({why})" if why else "running")) in explained
    assert "arroyo_worker_pane_closes_running" in registry.prometheus_text()


def test_a_step_that_falls_back_to_python_is_counted(monkeypatch):
    """The library is loaded and a step's groups go through
    ``lookup_or_assign`` all the same (its misses span more bins than a
    claim takes; a probe that wrapped): ``on`` says ``numpy`` and the
    fallback counter moves, in the marks and on ``explain``'s line."""
    from arroyo_tpu import native
    from arroyo_tpu.obs.profile import job_profile, render_explain
    from arroyo_tpu.ops.slot_agg import SlotAggregator

    if not native.available():
        pytest.skip("native library unavailable")
    m = TaskMetrics("fallback-job", "agg", 0)
    agg = SlotAggregator(("count",), (np.int64,), cap=4096, batch_cap=256, region_size=16)
    keys = np.arange(200, dtype=np.uint64)
    ones = [np.ones(200, dtype=np.int64)]
    resolve = native.dir_resolve

    def steps():
        lane = trace.bind("fallback-job", "agg", 0, m)
        lane.account(force=True)
        agg.update(keys, np.zeros(200, dtype=np.int32), ones)  # two calls into the library
        agg.update(keys, (np.arange(200) % 100 + 1).astype(np.int32), ones)  # 100 bins
        monkeypatch.setattr(native, "dir_resolve", lambda *a: None)  # a probe wrapped
        agg.update(keys, np.zeros(200, dtype=np.int32), ones)
        monkeypatch.setattr(native, "dir_resolve", resolve)
        lane.account(force=True)
        trace.unbind()

    on_own_thread(steps)
    directory = trace.spans("agg.directory", job="fallback-job")
    assert [(s.args["on"], s.args["misses"]) for s in directory] == [
        ("native", 200), ("numpy", 200), ("numpy", 0)]
    assert m.counters["arroyo_worker_directory_steps"] == 3
    assert m.counters["arroyo_worker_directory_fallback_steps"] == 2
    assert trace.account_over("agg", job="fallback-job")["directory_fallback_steps"] == 2
    profile = job_profile({"agg": dict(m.counters, account=dict(m.account))})
    text = render_explain([{"id": "agg", "op": "x", "parallelism": 1}], [], profile)
    assert "directory 3 steps, 2 in Python" in text
    assert len(agg.extract(0, 200, 200)[0]) == 400


def test_a_checkpoint_reads_the_state_under_agg_snapshot(tmp_path):
    graph = plan_query(SLIDING.format(events=30_000, rate=15_000)).graph
    with cfg.scoped(SMALL):
        engine = Engine(graph, job_id="span-ckpt", storage_url=str(tmp_path / "ck"))
        engine.start()
        time.sleep(0.6)
        engine.checkpoint_and_wait(1)
        engine.stop()
        engine.join(timeout=30)
    snaps = trace.spans("agg.snapshot", job="span-ckpt")
    assert snaps and all(s.t1_ns > s.t0_ns for s in snaps)


# ------------------------------------------- the device trace's clock


def test_annotations_land_in_the_host_plane_on_one_clock(tmp_path):
    import jax.profiler
    from jax.profiler import ProfileData

    def work():
        trace.bind("xplane-job", "agg", 0, TaskMetrics("xplane-job", "agg", 0))
        for _ in range(25):
            with trace.span("agg.dispatch"):
                time.sleep(0.001)
            with trace.wait(trace.INBOX_WAIT, "task.inbox_wait"):
                time.sleep(0.002)
        trace.unbind()

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        on_own_thread(work)
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))[0]
    seen = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("arroyo."):
                    seen.setdefault(e.name, []).append((e.start_ns, e.duration_ns))
    assert set(seen) == {"arroyo.agg.dispatch", "arroyo.task.inbox_wait"}
    for name, events in seen.items():
        ring = trace.spans(name[len("arroyo."):], job="xplane-job")
        assert len(ring) == len(events) == 25
        # the annotation encloses the ring's stamps; a thread held up between
        # the two stamps of one span (a busy machine) is dropped from each end
        offsets = sorted(e[0] - s.t0_ns for e, s in zip(sorted(events), ring))[2:-2]
        assert offsets[-1] - offsets[0] < 1e6, (name, offsets[0], offsets[-1])
        for (start, dur), s in zip(sorted(events), ring):
            assert dur >= (s.t1_ns - s.t0_ns) - 1e5


# ------------------------- what the benchmark's harness reaches in for

# benchmark/harness/probes.py wraps these from outside until a `benchmark`
# issue moves it onto obs.trace.spans(); a rename breaks a harness that no
# other kind of PR may edit
WRAPPED = [
    ("arroyo_tpu.connectors.nexmark", "NexmarkSource.run", ["self", "sctx", "collector"]),
    ("arroyo_tpu.connectors.nexmark", "NexmarkSource._generate", ["self", "numbers"]),
    ("arroyo_tpu.ops.slot_agg", "SlotAggregator._update_chunk",
     ["self", "key_u64", "bins", "vals"]),
    ("arroyo_tpu.ops.slot_agg", "SlotAggregator._spill_update",
     ["self", "keys_i64", "bins_i64", "vals"]),
    ("arroyo_tpu.ops.slot_agg", "SlotAggregator.extract_start",
     ["self", "emit_lo", "emit_hi", "free_below"]),
    ("arroyo_tpu.ops.slot_agg", "SlotAggregator.snapshot", ["self"]),
    ("arroyo_tpu.ops.slot_agg", "SlotExtractHandle.result", ["self"]),
    # the sharded aggregate of a mesh deployment (device.mesh-devices > 1)
    ("arroyo_tpu.parallel.sharded_agg", "ShardedAggregator.update_sharded",
     ["self", "key_i64", "bins", "valid", "vals"]),
    ("arroyo_tpu.parallel.sharded_agg", "ShardedAggregator._drain_spill",
     ["self", "emit_lo", "emit_hi", "free_below"]),
    ("arroyo_tpu.parallel.sharded_agg", "ShardedAggregator.extract_start",
     ["self", "emit_lo", "emit_hi", "free_below"]),
    ("arroyo_tpu.parallel.sharded_agg", "ShardedAggregator.snapshot", ["self"]),
    ("arroyo_tpu.parallel.sharded_agg", "ShardedAggregator.mesh_stats", ["self"]),
    ("arroyo_tpu.parallel.sharded_agg", "_ReadyHandle.result", ["self"]),
    ("arroyo_tpu.operators.collector", "Collector.collect", ["self", "batch"]),
    ("arroyo_tpu.engine.engine", "Engine.trigger_checkpoint", None),
    ("arroyo_tpu.engine.engine", "Engine.build", None),
    ("arroyo_tpu.connectors", "register_sink", None),
    ("arroyo_tpu.native", "require", None),
    ("arroyo_tpu.config", "scoped", None),
]


@pytest.mark.parametrize("module,name,params", WRAPPED, ids=[w[1] for w in WRAPPED])
def test_the_names_the_harness_wraps_still_exist(module, name, params):
    import importlib

    obj = importlib.import_module(module)
    for part in name.split("."):
        obj = getattr(obj, part)
    assert callable(obj)
    if params is not None:
        assert list(inspect.signature(obj).parameters) == params


class _NumpyThatSeesStateReads:
    """``numpy`` for ``parallel/sharded_agg.py``: each call that is handed a
    leaf of the aggregator's device state (what makes a host array of it,
    and so waits for every step queued before) goes on ``events``."""

    def __init__(self, agg, events):
        self._agg, self._events = agg, events

    def __getattr__(self, name):
        import jax

        fn = getattr(np, name)
        if not callable(fn) or isinstance(fn, type):
            return fn

        def seen(*args, **kw):
            leaves = jax.tree_util.tree_leaves(self._agg.state)
            if any(a is leaf for a in args for leaf in leaves):
                self._events.append("state read")
            return fn(*args, **kw)

        return seen


@pytest.mark.parametrize("spilled", [False, True], ids=["table-only", "spill-buffer-in-use"])
def test_a_mesh_close_reads_no_device_state_before_its_extraction_is_queued(
        spilled, monkeypatch):
    """A host read of the table between ``extract_start``'s entry and the
    queuing of ``jit_local_extract`` waits for the steps queued so far and
    lets the other aggregate's steps in ahead of the close: PR 39's first
    tree lost 14-21% of ``q7-mesh4``'s rate to one (``live``, now counted
    inside the extraction). The probe rounds' count is read the same way:
    after the extraction has landed."""
    import jax

    from arroyo_tpu.parallel import ShardedAggregator, make_mesh, sharded_agg

    if len(jax.devices()) < 4:
        pytest.skip("needs multi-device CPU mesh")
    agg = ShardedAggregator(make_mesh(4), ("max",), (np.int64,), cap=64, batch_cap=64,
                            per_dest_cap=64, max_probes=4, emit_cap=64, spill_cap=512)
    keys = np.arange(400 if spilled else 100, dtype=np.uint64) * np.uint64(0x9E3779B97F4A7C15)
    agg.update(keys, np.zeros(len(keys), np.int32), [np.arange(len(keys), dtype=np.int64)])
    events = []
    extract = agg._extract

    def queued(*a, **kw):
        events.append("extraction queued")
        return extract(*a, **kw)

    agg._extract = queued
    monkeypatch.setattr(sharded_agg, "np", _NumpyThatSeesStateReads(agg, events))
    m = TaskMetrics("order-job", "agg", 0)
    trace.bind("order-job", "agg", 0, m)
    try:
        out = agg.extract_start(0, 1, 1).result()
    finally:
        trace.unbind()
    assert len(out[0]) == len(keys)
    # the spill buffers' fill, the rounds and the overflow count are read,
    # each after every extraction of the close
    assert events[0] == "extraction queued" and events.count("state read") >= 3
    last_queued = max(i for i, e in enumerate(events) if e == "extraction queued")
    assert set(events[last_queued + 1:]) == {"state read"}
    close = trace.spans("agg.close", job="order-job")[-1]
    assert close.args["probe_rounds"] == agg.mesh_stats()["probe_rounds"] >= 1
    # the steps behind it that ran narrow behind their exchange, read with the
    # rounds: 100 rows are ~25 a shard, which a rung of this store's ladder
    # (4, 16, 64) holds; of 400 rows the first step's 256 may pass it on a shard
    steps = agg.mesh_stats()["probe_steps"]
    assert close.args["narrow_steps"] == agg.mesh_stats()["narrow_steps"] <= steps
    assert spilled or close.args["narrow_steps"] == steps == 1
    assert 0 < close.args["live"] <= min(len(keys), 4 * 64)


def test_what_the_harness_reads_off_the_objects_is_still_there(tumbling_run):
    from arroyo_tpu.connectors.nexmark import NexmarkSource
    from arroyo_tpu.metrics import TRANSIT_BUCKETS
    from arroyo_tpu.ops.slot_agg import SlotAggregator

    # the schedule's origin is read out of run()'s frame by name
    assert "started" in NexmarkSource.run.__code__.co_varnames
    agg = SlotAggregator(["max"], [np.dtype(np.int64)], cap=4096, region_size=2048)
    for attr in ("cap", "region_size", "batch_cap", "acc_kinds", "acc_dtypes", "state"):
        assert hasattr(agg, attr), attr
    assert callable(agg._read_multi(1, True)) and callable(agg._clear)
    m = TaskMetrics("j", "n", 0)
    assert set(m.self_time) == set(m.self_cpu) and len(m.queue_transit.counts) == \
        len(TRANSIT_BUCKETS) + 1
    assert {"arroyo_worker_messages_recv", "arroyo_worker_messages_sent"} <= set(m.counters)
    job, graph = tumbling_run
    assert graph.in_edges(next(n for n in graph.nodes if n.startswith("join")))


def test_the_span_names_are_frozen():
    assert trace.SPAN_NAMES == (
        "task.inbox_wait", "task.put_wait", "task.account",
        "agg.directory", "agg.dispatch", "agg.spill", "agg.close", "agg.fetch",
        "agg.drain", "agg.snapshot", "agg.grow", "agg.combine", "wf.rank",
        "source.generate", "source.emit", "source.pace",
        "wm.in", "wm.out", "rows.out", "close.wake", "join.prewarm", "join.probe",
        "join.fetch", "device.stall", "watch.tick")
    assert (trace.WATCH_TICK_NS, trace.STALL_NS, trace.STALL_EVENT_NS) == \
        (100_000_000, 1_000_000_000, 60_000_000_000)
    assert (trace.INBOX_WAIT, trace.PUT_WAIT, trace.DEVICE_WAIT) == ACCOUNT_KEYS[:3]
    assert {"name", "t0", "t1", "node"} <= set(inspect.signature(trace.spans).parameters)
    assert trace.Span._fields == ("name", "job", "node", "subtask", "trace_id",
                                  "t0_ns", "t1_ns", "args")
