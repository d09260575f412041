"""Observability plane: epoch-lifecycle tracing, event-time health metrics,
and the controller-side live job view (ISSUE 6).

Covers: the trace recorder capturing a full checkpoint span tree and its
Chrome trace-event export; timeout/wedge diagnostics naming the exact stuck
subtask; the overflow-clamped histogram quantiles; watermark-lag and
sink-latency metrics reaching the prometheus exposition and the per-second
controller snapshot; multi-worker snapshot merging; and the `top`/`trace`
CLIs reading everything back from the controller DB.
"""

from __future__ import annotations

import json
import os
import time

import pytest

import arroyo_tpu
from arroyo_tpu.batch import TIMESTAMP_FIELD, Schema
from arroyo_tpu.expr import Col
from arroyo_tpu.graph import EdgeType, Graph, Node, OpName
from arroyo_tpu.metrics import (
    Histogram,
    merge_job_metrics,
    registry,
)
from arroyo_tpu.obs import trace as obs_trace

SMOKE = os.path.join(os.path.dirname(__file__), "smoke")


# ---------------------------------------------------------------- histograms


def test_histogram_quantile_clamps_overflow():
    h = Histogram((1.0, 2.0, 4.0))
    for v in (0.5, 1.5, 100.0, 200.0, 300.0):
        h.observe(v)
    # p99 lands in the +Inf bucket: clamped to the largest finite bound,
    # never inf (`top` multiplies by 1000 and must not print 'infms')
    assert h.quantile(0.99) == 4.0
    # non-overflow quantiles are untouched
    assert h.quantile(0.2) == 1.0
    empty = Histogram((1.0,))
    assert empty.quantile(0.99) == 0.0


def test_merge_job_metrics_unions_subtasks():
    def snap(sub, sent):
        return {"op": {"per_subtask": {sub: {
            "arroyo_worker_messages_sent": sent,
            "arroyo_worker_messages_recv": 0,
            "backpressure": 0.5 if sub == "1" else 0.1,
            "watermark_lag_seconds": 2.0 if sub == "1" else None,
            "queue_transit_p99_ms": 7.5,
        }}}}

    merged = merge_job_metrics([snap("0", 10), snap("1", 32)])
    m = merged["op"]
    assert set(m["per_subtask"]) == {"0", "1"}
    assert m["subtasks"] == 2
    assert m["arroyo_worker_messages_sent"] == 42
    assert m["backpressure"] == 0.5  # worst subtask wins
    assert m["watermark_lag_seconds"] == 2.0
    # identical snapshots (embedded worker sets share one registry) collapse
    # by label instead of double-counting
    again = merge_job_metrics([snap("0", 10), snap("0", 10)])
    assert again["op"]["arroyo_worker_messages_sent"] == 10


# ------------------------------------------------------------------- tracing


def _graph(tmp_path, n_rows=300, parallelism=1):
    src = tmp_path / "in.json"
    with open(src, "w") as f:
        for i in range(n_rows):
            f.write(json.dumps({"x": i, "_timestamp": i * 1000}) + "\n")
    S = Schema.of([("x", "int64"), (TIMESTAMP_FIELD, "int64")])
    rows: list = []
    g = Graph()
    g.add_node(Node("src", OpName.SOURCE, {
        "connector": "single_file", "path": str(src), "schema": S}, 1))
    g.add_node(Node("wm", OpName.WATERMARK, {
        "expr": Col(TIMESTAMP_FIELD), "interval_micros": 1000}, parallelism))
    g.add_node(Node("sink", OpName.SINK, {
        "connector": "vec", "rows": rows}, parallelism))
    g.add_edge("src", "wm",
               EdgeType.SHUFFLE if parallelism > 1 else EdgeType.FORWARD, S)
    g.add_edge("wm", "sink", EdgeType.FORWARD, S)
    return g, rows


def test_epoch_trace_lifecycle_and_chrome_export(tmp_path, _storage):
    from arroyo_tpu import config as cfg
    from arroyo_tpu.engine.engine import Engine

    cfg.update({"testing.source-read-delay-micros": 2000})
    g, rows = _graph(tmp_path)
    job = "trace-lifecycle"
    obs_trace.recorder.clear_job(job)
    eng = Engine(g, job_id=job)
    eng.start()
    assert eng.checkpoint_and_wait(1, timeout=60)
    eng.stop()
    eng.join(60)

    events = obs_trace.recorder.events(job, 1)
    kinds = {e["event"] for e in events}
    assert {"trigger", "align_start", "snapshot_start", "ack",
            "metadata_durable", "commit_delivered"} <= kinds
    # every task acked; the sink aligned before snapshotting
    acked = {(e["node"], e["subtask"]) for e in events if e["event"] == "ack"}
    assert acked == {("src", 0), ("wm", 0), ("sink", 0)}
    sink = {e["event"]: e["t_us"] for e in events if e["node"] == "sink"}
    assert sink["align_start"] <= sink["snapshot_start"] <= sink["ack"]

    phases = obs_trace.phase_durations(events)
    assert set(phases) == {"align", "snapshot", "ack", "commit"}
    assert all(v >= 0 for v in phases.values())
    assert obs_trace.dominant_phase(phases) in phases

    chrome = obs_trace.chrome_trace(job, {1: events})
    evs = chrome["traceEvents"]
    assert any(e["name"] == "epoch 1" and e["ph"] == "X" for e in evs)
    assert any(e["tid"] == "sink/0" and e["name"] == "snapshot" for e in evs)
    # complete epochs emit only closed spans / instants
    assert all(e["ph"] in ("X", "i") for e in evs)
    json.dumps(chrome)  # must be directly serializable for the API/CLI

    report = obs_trace.timeline_report(job, 1, events)
    assert "metadata_durable" in report and "dominant" in report


def test_checkpoint_timeout_report_names_stuck_subtask(tmp_path, _storage):
    """A dropped/held barrier (chaos `worker` hang fires after the snapshot
    is written, before the barrier is forwarded or acked) wedges the epoch;
    the CheckpointWait timeout attaches a trace timeline naming the exact
    stuck subtask and the downstream subtasks whose barrier never arrived."""
    from arroyo_tpu import config as cfg
    from arroyo_tpu import faults
    from arroyo_tpu.engine.engine import Engine

    cfg.update({"testing.source-read-delay-micros": 3000})
    g, rows = _graph(tmp_path, n_rows=2000)
    job = "trace-stuck"
    obs_trace.recorder.clear_job(job)
    faults.install("worker:hang=4@barrier=1&step=1", seed=3)
    eng = Engine(g, job_id=job)
    try:
        eng.start()
        wait = eng.checkpoint_and_wait(1, timeout=1.5)
        assert wait.outcome == "timeout"
        assert wait.missing  # the hung subtask never acked
        # the report names the hung subtask (snapshot written, never acked)
        # and/or the downstream ones still waiting on its barrier
        assert "stuck:" in wait.report
        assert ("never acked" in wait.report
                or "barrier never arrived" in wait.report
                or "still missing" in wait.report)
        stuck_names = [f"{n}/{s}" for n, s in wait.missing]
        assert any(name in wait.report for name in stuck_names)
        assert wait.report in repr(wait)  # chaos failures print this
    finally:
        faults.clear()
        eng.stop()
        eng.join(60)


# ----------------------------------------------------- event-time health


def test_watermark_lag_and_sink_latency_export(tmp_path, _storage):
    from arroyo_tpu.engine.engine import run_graph

    g, rows = _graph(tmp_path)
    job = "lag-metrics"
    registry.clear_job(job)
    run_graph(g, job_id=job, timeout=60)
    assert len(rows) > 0
    jm = registry.job_metrics(job)
    # the sink saw watermarks (lag = wall now - event time, input stamps
    # are micros near zero => huge positive lag) and observed per-batch
    # end-to-end latency
    assert jm["sink"]["watermark_lag_seconds"] > 0
    assert jm["sink"]["sink_event_latency_p99_s"] > 0
    assert jm["sink"]["per_subtask"]["0"]["watermark_lag_seconds"] > 0
    # non-terminal operators do not record sink latency
    assert jm["wm"]["sink_event_latency_p99_s"] is None
    text = registry.prometheus_text()
    assert f'arroyo_worker_watermark_lag_seconds{{job="{job}",operator="sink"' \
        in text
    assert f'arroyo_worker_sink_event_latency_seconds_count{{job="{job}"' \
        in text


def test_phase_histograms_export(_storage):
    registry.clear_job("phase-job")
    registry.observe_epoch_phases("phase-job", {
        "align": 0.2, "snapshot": 1.1, "ack": 0.01, "commit": 0.002})
    text = registry.prometheus_text()
    assert "# TYPE arroyo_checkpoint_phase_seconds histogram" in text
    assert 'arroyo_checkpoint_phase_seconds_count{job="phase-job",' \
        'phase="snapshot"} 1' in text
    registry.clear_job("phase-job")
    assert "phase-job" not in registry.prometheus_text()


# ------------------------------------------------- controller DB + CLIs


def _sql(tmp_path, name="grouped_aggregates"):
    with open(os.path.join(SMOKE, "queries", f"{name}.sql")) as f:
        sql = f.read()
    out = str(tmp_path / "out.json")
    # single_file sources read from subtask 0 only; at parallelism 2 the
    # other watermark subtask must declare itself Idle or the downstream
    # min-merge (correctly) holds the watermark until EOF and there is no
    # mid-run lag to observe
    sql = sql.replace(
        "event_time_field = 'timestamp'",
        "event_time_field = 'timestamp',\n  'idle-time-ms' = '300'")
    return sql.replace("$input_dir", os.path.join(SMOKE, "inputs")).replace(
        "$output_path", out), out


def test_top_and_trace_from_controller_db(tmp_path, _storage, capsys):
    """Acceptance: a live 2-worker job's controller DB carries nonzero
    watermark lag, throughput, and last-epoch phase durations; `top` and
    `trace` render them, and the API serves the Chrome trace."""
    from arroyo_tpu import cli
    from arroyo_tpu import config as cfg
    from arroyo_tpu.api import ApiServer
    from arroyo_tpu.controller import ControllerServer, Database
    from arroyo_tpu.controller.scheduler import EmbeddedScheduler

    sql, out = _sql(tmp_path)
    db_path = str(tmp_path / "ctl.db")
    db = Database(db_path)
    cfg.update({
        "controller.workers-per-job": 2,
        "checkpoint.interval-ms": 300,
        "testing.source-read-delay-micros": 15000,
    })
    ctl = ControllerServer(db, EmbeddedScheduler()).start()
    api = ApiServer(db, port=0).start()
    try:
        pid = db.create_pipeline("agg", sql, 2)
        jid = db.create_job(pid)
        ctl.wait_for_state(jid, "Running", timeout=60)

        # poll the LIVE job's DB snapshots until every event-time health
        # signal has been observed at least once: nonzero watermark lag,
        # nonzero out-rate, and a completed checkpoint carrying phase
        # durations (a terminal snapshot zeroes the windowed rates, so the
        # conditions accumulate across the run instead of being required
        # of one final sample)
        def _saw(s, key):
            return any((m.get(key) or 0) > 0
                       for m in (s or {}).values() if isinstance(m, dict))

        deadline = time.monotonic() + 90
        snap = ckpt_phases = None
        lag_seen = rate_seen = False
        while time.monotonic() < deadline:
            s = db.get_metrics(jid)
            if s:
                snap = s
            lag_seen = lag_seen or _saw(s, "watermark_lag_seconds")
            rate_seen = rate_seen or _saw(s, "messages_per_sec")
            if ckpt_phases is None:
                ckpt_phases = next(
                    (json.loads(c["phases"]) for c in db.list_checkpoints(jid)
                     if c["state"] == "complete" and c.get("phases")), None)
            if lag_seen and rate_seen and ckpt_phases:
                break
            if db.get_job(jid)["state"] != "Running":
                # drained: the final registry snapshot still carries lag
                s = db.get_metrics(jid)
                lag_seen = lag_seen or _saw(s, "watermark_lag_seconds")
                snap = s or snap
                break
            time.sleep(0.1)
        assert snap, "no metrics snapshot reached the controller DB"
        assert lag_seen, snap
        assert rate_seen, snap
        assert ckpt_phases and set(ckpt_phases) <= {
            "align", "snapshot", "ack", "commit"}, ckpt_phases

        # the live view renders from exactly that DB state
        assert cli.main(["top", jid, "--db", db_path, "--once"]) == 0
        frame = capsys.readouterr().out
        assert "operator" in frame and "wm lag" in frame
        assert "last epoch" in frame and "dominant" in frame

        # trace CLI: chrome export + human report
        assert cli.main(["trace", jid, "--db", db_path]) == 0
        chrome = json.loads(capsys.readouterr().out)
        assert chrome["traceEvents"]
        assert cli.main(["trace", jid, "--db", db_path, "--report"]) == 0
        report = capsys.readouterr().out
        assert "trace (" in report and "metadata_durable" in report

        # API endpoint serves the same trace
        import urllib.request

        with urllib.request.urlopen(
                f"http://127.0.0.1:{api.port}/api/v1/jobs/{jid}/traces",
                timeout=10) as r:
            payload = json.loads(r.read())
        assert payload["traceEvents"]

        ctl.wait_for_state(jid, "Finished", timeout=120)
        # terminal flush: every buffered epoch trace persisted to the DB
        assert db.list_traces(jid)
    finally:
        cfg.update({"controller.workers-per-job": 1,
                    "checkpoint.interval-ms": 10_000,
                    "testing.source-read-delay-micros": 0})
        ctl.stop()
        api.stop()


def test_top_header_renders_evolving_state():
    """`top` on an Evolving job says so (and flags the pending redeploy)
    instead of rendering a bare metrics-less frame."""
    from arroyo_tpu.obs.topview import render

    job = {"id": "j1", "state": "Evolving", "health": "ok", "n_workers": 1,
           "restarts": 0, "checkpoint_epoch": 3,
           "desired_query": "SELECT 1"}
    frame = render(job, None)
    assert "evolving" in frame and "redeploy pending" in frame
    # once the request is consumed the flag drops but the state still shows
    frame = render({**job, "desired_query": None}, None)
    assert "evolving" in frame and "redeploy pending" not in frame
