"""NEXmark Query 5 at the specification's sixty slides to a window (the
deployment ``nexmark-q5-hour``), end to end on the CPU at a small size: the
benchmark cell's own query text through ``plan_query`` into the engine, its
sink's rows and both per-auction aggregates' output held to a plain Python
computation over the connector's own batches (dicts and loops; no code of
``windows/``, ``ops/`` or ``operators/``): on the jax and the numpy backend,
across a checkpoint and a restore in the middle of a window, with a close
wider than a queue and than the extraction's emit buffer, and across a gap
in event time longer than a window; and the pane combine's span, counters
and gauge."""

import json
import os
import string
import time

import numpy as np
import pytest
from test_nexmark_q8 import micros

from arroyo_tpu.batch import TIMESTAMP_FIELD

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
QUERY = os.path.join(REPO, "benchmark", "configs", "nexmark-q5-hour.sql")
INTER = 5_000                                 # 200 events a second of event time
SLIDE, WIDTH = 1_000_000, 60_000_000          # sixty 200-event slides, 12,000 events a window
NB = WIDTH // SLIDE
EVENTS, SEED = 30_000, 43                     # two and a half windows, 150 slides
GAP_AT, GAP = 16_000, 150_000_000             # event 16,000 on comes 150 s later
# conftest's 8,192 slots in 2,048-slot regions hold four bins; sixty and the
# one being filled want their regions without growing the table
SIZES = {"device.table-capacity": 32_768, "device.region-size": 256,
         "engine.coalesce.enabled": False}


def q5_sql(out_path: str, rate: int = 0, events: int = EVENTS) -> str:
    with open(QUERY) as f:
        text = string.Template(f.read()).substitute(
            seed=SEED, sink="$sink", event_rate=rate,
            inter_event_micros=INTER, first_event_micros=0)
    text = text.replace("seed = %d" % SEED, "seed = %d,\n  event_count = %d" % (SEED, events))
    sink = "connector = 'single_file', path = '%s', format = 'json', type = 'sink'" % out_path
    assert "connector = '$sink', type = 'sink'" in text
    return text.replace("connector = '$sink', type = 'sink'", sink)


def the_bids(events: int = EVENTS) -> list[tuple]:
    """(event time, auction) of every bid, from the connector itself."""
    from arroyo_tpu.connectors.nexmark import NexmarkSource

    src = NexmarkSource({"inter_event_micros": INTER, "first_event_micros": 0, "seed": SEED,
                         "columns": ["bid", "bid.auction"]})
    out = []
    for lo in range(0, events, 500):
        b = src._generate(np.arange(lo, min(lo + 500, events)))
        out += [(ts, a) for is_bid, ts, a in zip(np.asarray(b["bid"]).tolist(),
                                                 np.asarray(b[TIMESTAMP_FIELD]).tolist(),
                                                 np.asarray(b["bid.auction"]).tolist()) if is_bid]
    return out


def oracle(bids: list[tuple]) -> tuple[dict, list]:
    """-> per window start {auction: bids} over every window that holds a
    bid, and q5's result rows (window start, auction, bids): the auctions
    with the most bids of their window. A bid at ``ts`` counts in the sixty
    windows that start in (ts - WIDTH, ts] on the slide's grid."""
    per_window: dict = {}
    for ts, a in bids:
        for j in range((ts - WIDTH) // SLIDE + 1, ts // SLIDE + 1):
            per = per_window.setdefault(j * SLIDE, {})
            per[a] = per.get(a, 0) + 1
    rows = []
    for w, per in per_window.items():
        most = max(per.values())
        rows += [(w, a, n) for a, n in per.items() if n == most]
    return per_window, sorted(rows)


@pytest.fixture(scope="module")
def the_oracle():
    return oracle(the_bids())


def tap_sliding(engine, taps: dict) -> None:
    """Every batch a sliding aggregate emits, by the aggregate's node."""
    if not engine.tasks:
        engine.build()
    for (nid, _sub), task in engine.tasks.items():
        if engine.graph.nodes[nid].op.value == "sliding_aggregate":
            collect = task.collector.collect

            def tapped(batch, *a, _collect=collect, _into=taps.setdefault(nid, []), **kw):
                _into.append(batch)
                return _collect(batch, *a, **kw)

            task.collector.collect = tapped


def tapped_counts(batches: list) -> dict:
    """window start -> {auction: bids} as one aggregate emitted them; a
    window emitted again after a restore has to say the same."""
    out: dict = {}
    for b in batches:
        for w, k, v in zip(np.asarray(b["window_start"]).tolist(),
                           np.asarray(b["bid.auction"]).tolist(),
                           np.asarray(b["__agg_0"]).tolist()):
            per = out.setdefault(w, {})
            assert per.get(k, v) == v, (w, k, per.get(k), v)
            per[k] = v
    return out


def sink_rows(path: str) -> list:
    with open(path) as f:
        got = [json.loads(line) for line in f if line.strip()]
    return sorted((micros(r["ws"]), r["auction"], r["num"]) for r in got)


def run_q5(job: str, out: str, settings: dict, events: int = EVENTS) -> dict:
    from arroyo_tpu import config as cfg
    from arroyo_tpu.engine import Engine
    from arroyo_tpu.sql import plan_query

    taps: dict = {}
    with cfg.scoped(dict(SIZES, **settings)):
        engine = Engine(plan_query(q5_sql(out, events=events)).graph, job_id=job)
        tap_sliding(engine, taps)
        engine.run_to_completion(timeout=300)
    assert len(taps) == 2  # the count the join reads, and the one under the maximum
    return taps


def held_to(taps: dict, out: str, per_window: dict, rows: list) -> None:
    for nid, batches in taps.items():
        assert tapped_counts(batches) == per_window, nid
    assert sink_rows(out) == rows


# ------------------------------------------------------ against the oracle


@pytest.fixture(scope="module")
def jax_run(tmp_path_factory):
    """One uninterrupted run on the jax backend, for the comparison and for
    the spans, counters and gauge it leaves."""
    from arroyo_tpu.metrics import registry
    from arroyo_tpu.obs import trace

    tmp = tmp_path_factory.mktemp("q5-hour")
    out = str(tmp / "hot_items.json")
    taps = run_q5("q5-hour-jax", out, {"checkpoint.storage-url": str(tmp / "ck")})
    # read now: the ring keeps the records of a few dozen ended threads only
    records = {node: {name: trace.spans(name, node=node, job="q5-hour-jax")
                      for name in ("agg.combine", "task.account")} for node in taps}
    return {"job": "q5-hour-jax", "taps": taps, "out": out, "records": records,
            "metrics": registry.job_metrics("q5-hour-jax"),
            "prometheus": registry.prometheus_text()}


def test_q5_hour_equals_the_plain_oracle_on_the_device_path(jax_run, the_oracle):
    per_window, rows = the_oracle
    # every window that holds a bid: the 59 that start before the stream too
    assert len(per_window) == EVENTS * INTER // SLIDE + NB - 1 == 209
    held_to(jax_run["taps"], jax_run["out"], per_window, rows)


def test_q5_hour_equals_the_plain_oracle_on_the_numpy_backend(the_oracle, tmp_path):
    out = str(tmp_path / "hot_items.json")
    taps = run_q5("q5-hour-numpy", out, {"device.enabled": False})
    held_to(taps, out, *the_oracle)


@pytest.mark.parametrize("source_batch", [
    pytest.param(512, id="512-row-batches"),
    # batches as wide as a backlog: stages of the step's full 1,024 rows
    pytest.param(4096, id="backlog-width-batches")])
def test_q5_hour_across_a_checkpoint_and_a_restore_inside_a_window(
        source_batch, the_oracle, tmp_path):
    from arroyo_tpu import config as cfg
    from arroyo_tpu.engine import Engine
    from arroyo_tpu.obs import trace
    from arroyo_tpu.sql import plan_query

    per_window, rows = the_oracle
    cfg.update(dict(SIZES, **{"pipeline.source-batch-size": source_batch}))
    out = str(tmp_path / "hot_items.json")
    job = f"q5-hour-restore-{source_batch}"
    # paced, so that the checkpoint falls inside the stream: 3 s of it
    sql = q5_sql(out, rate=10_000)
    taps: dict = {}
    first = Engine(plan_query(sql).graph, job_id=job)
    tap_sliding(first, taps)
    first.start()
    time.sleep(1.5)
    assert first.checkpoint_and_wait(1, timeout=120).outcome == "completed"
    first.stop()
    first.join(timeout=60)
    # the barrier met full windows' worth of bins: most extracted and held
    # on the host, the newest still on the device, and the snapshot wrote both
    before = tapped_counts(next(iter(taps.values())))
    assert NB < len(before) < len(per_window), len(before)
    snaps = trace.spans("agg.snapshot", job=job)
    assert snaps and any(s.args["rows"] > 0 for s in snaps)
    second = Engine(plan_query(sql).graph, job_id=job, restore_epoch=1)
    tap_sliding(second, taps)
    second.run_to_completion(timeout=300)
    held_to(taps, out, per_window, rows)


def test_a_close_wider_than_a_queue_and_than_the_emit_buffer(the_oracle, tmp_path):
    """A window's sixty bins hold more rows than an input edge's queue takes
    and a bin more than one extraction's emit buffer: nothing is cut, every
    window leaves once."""
    from arroyo_tpu.obs import trace

    out = str(tmp_path / "hot_items.json")
    narrow = {"worker.queue-size": 256, "device.emit-capacity": 64}
    taps = run_q5("q5-hour-narrow", out, narrow)
    combines = trace.spans("agg.combine", job="q5-hour-narrow")
    assert max(s.args["rows"] for s in combines) > 4 * narrow["worker.queue-size"]
    # a slide reads two bins: the one coming in and the one going out
    assert max(s.args["rows_in"] for s in combines) / 2 > narrow["device.emit-capacity"]
    held_to(taps, out, *the_oracle)
    for batches in taps.values():
        starts = [w for b in batches for w in np.unique(np.asarray(b["window_start"])).tolist()]
        assert len(starts) == len(set(starts)) == 209 and starts == sorted(starts)


@pytest.mark.parametrize("backend", ["jax", "numpy"])
def test_an_event_time_gap_longer_than_a_window(backend, monkeypatch, tmp_path):
    """From event GAP_AT on the stream is 150 s later: the windows between
    the two stretches hold nothing and none is emitted for them, and those
    on either side of the gap are whole (the fast-forward of the drain)."""
    from arroyo_tpu.connectors.nexmark import NexmarkSource

    generate = NexmarkSource._generate

    def with_a_gap(self, numbers):
        batch = generate(self, numbers)
        batch[TIMESTAMP_FIELD][numbers.astype(np.int64) >= GAP_AT] += GAP
        return batch

    monkeypatch.setattr(NexmarkSource, "_generate", with_a_gap)
    bids = the_bids(24_000)
    assert max(ts for ts, _ in bids) > 24_000 * INTER + GAP - SLIDE
    per_window, rows = oracle(bids)
    # 80 s and 40 s of stream with 150 s between: 139 + 99 windows hold a bid
    assert len(per_window) == 80 + NB - 1 + 40 + NB - 1
    out = str(tmp_path / "hot_items.json")
    taps = run_q5(f"q5-hour-gap-{backend}", out, {"device.enabled": backend == "jax"},
                  events=24_000)
    held_to(taps, out, per_window, rows)


# ------------------------------------------- the pane combine's own record


def test_agg_combine_is_one_span_an_emitted_window(jax_run, the_oracle):
    per_window, _rows = the_oracle
    per_bin: dict = {}  # bin start -> the auctions bid on in it: its rows on the host
    for ts, a in the_bids():
        per_bin.setdefault(ts // SLIDE * SLIDE, set()).add(a)
    for records in jax_run["records"].values():
        spans = records["agg.combine"]
        # one a window, in window order, named by the window's end
        assert [s.trace_id for s in spans] == sorted(w + WIDTH for w in per_window)
        # the first combines its bins anew and seeds the running window;
        # every other is that window slid by a bin (count(*) retracts)
        assert [s.args["on"] for s in spans] == ["full"] + ["running"] * (len(spans) - 1)
        for s in spans:
            start = s.trace_id - WIDTH
            per = per_window[start]
            assert s.args["rows"] == len(per)
            assert 1 <= s.args["bins"] <= NB
            if s.args["on"] == "full":
                assert len(per) <= s.args["rows_in"]
            else:
                # what a slide reads: the bin that came in and the one that went out
                assert s.args["rows_in"] == (len(per_bin.get(start + WIDTH - SLIDE, ()))
                                             + len(per_bin.get(start - SLIDE, ())))
        # a full window's sixty bins hold each auction once a bin it was bid
        # on in: a slide reads two of them
        assert max(s.args["bins"] for s in spans) == NB
        whole = [s for s in spans if s.args["bins"] == NB]
        assert whole and all(s.args["rows_in"] < s.args["rows"] / 4 for s in whole)


def test_the_two_counters_are_the_sums_over_the_spans(jax_run):
    for node, records in jax_run["records"].items():
        spans = records["agg.combine"]
        m = jax_run["metrics"][node]
        assert m["arroyo_worker_window_rows_combined"] == sum(s.args["rows_in"] for s in spans)
        assert m["arroyo_worker_window_rows_emitted"] == sum(s.args["rows"] for s in spans) \
            == m["arroyo_worker_messages_sent"]
        # and the account marks carry both, for a reader to difference
        last = records["task.account"][-1]
        assert last.args["window_rows_emitted"] == m["arroyo_worker_window_rows_emitted"]
        assert last.args["window_rows_combined"] == m["arroyo_worker_window_rows_combined"]


def test_agg_combine_is_the_tasks_own_time_and_no_wait(jax_run):
    """The combine runs on the task's thread inside its hooks: the account
    still adds up with it, and no wait is charged for it."""
    for records in jax_run["records"].values():
        marks = records["task.account"]
        a = {k: marks[-1].args[k] - marks[0].args[k] for k in marks[0].args}
        wall = (marks[-1].t0_ns - marks[0].t0_ns) / 1e9
        waits = a["inbox_wait"] + a["put_wait"] + a["device_wait"]
        assert wall - a["cpu"] - waits >= -0.01 * wall - 1e-3
        combined = sum(s.t1_ns - s.t0_ns for s in records["agg.combine"]
                       if marks[0].t0_ns <= s.t0_ns and s.t1_ns <= marks[-1].t0_ns) / 1e9
        assert 0 < combined <= a["self_time"] + 1e-3
        assert combined <= wall - waits + 1e-3


def test_the_cache_gauge_and_explain_say_what_a_close_costs(jax_run):
    from arroyo_tpu.obs.profile import _annotations, job_profile

    prof = job_profile(jax_run["metrics"])
    for node in jax_run["taps"]:
        m = jax_run["metrics"][node]
        # the stream is over and every window out: nothing is held any more
        assert m["panes"] == {"bins_per_window": NB, "cached_rows": 0, "closes": "running"}
        assert f'arroyo_worker_window_cached_rows{{job="{jax_run["job"]}",operator="{node}"' \
            in jax_run["prometheus"]
        lines = _annotations(prof[node])
        table = next(line for line in lines if line.startswith("table: "))
        assert "bins/window 60, 0 rows of them on the host  closes: running" in table
        assert "slots" in table
        waits = next(line for line in lines if line.startswith("waits: "))
        assert (f"closes combined {m['arroyo_worker_window_rows_combined']:,} rows, "
                f"emitted {m['arroyo_worker_window_rows_emitted']:,}") in waits
        assert f"closes {len(jax_run['records'][node]['agg.combine']) - 1:,} running, 1 full" in waits


def test_the_cache_gauge_counts_the_rows_a_checkpoint_has_to_write():
    """Sixty bins extracted and held: the gauge reads their rows, and a
    window's leaving takes its oldest bin off."""
    from arroyo_tpu.metrics import TaskMetrics
    from arroyo_tpu.obs import trace
    from arroyo_tpu.windows.sliding import SlidingAggregate

    class _Collector:
        def __init__(self):
            self.batches = []

        def collect(self, batch):
            self.batches.append(batch)

        def broadcast(self, signal):
            pass

    op = SlidingAggregate({"width_micros": WIDTH, "slide_micros": SLIDE,
                           "key_fields": [], "aggregates": [("n", "count", None)],
                           "backend": "jax"})
    op.lane_key_fields, op.base_bin, op.next_window, op._target_window = [], 0, 0, 0
    for b in range(NB + 1):
        op._bin_cache[b] = (np.arange(b + 1, dtype=np.uint64), [np.ones(b + 1, dtype=np.int64)])
    metrics = TaskMetrics("gauge-job", "agg", 0)
    trace.bind("gauge-job", "agg", 0, metrics)
    try:
        out = _Collector()
        op._drain(out)
    finally:
        trace.unbind()
    # window 0 left with bins 0..59 (bin 0 evicted); bins 1..60 stay: 2+...+61 rows
    assert metrics.panes == {"bins_per_window": NB, "cached_rows": sum(range(2, NB + 2)),
                             "closes": "running"}
    assert len(out.batches) == 1 and out.batches[0].num_rows == NB
    assert metrics.counters["arroyo_worker_window_rows_combined"] == sum(range(1, NB + 1))
    assert metrics.counters["arroyo_worker_window_rows_emitted"] == NB
