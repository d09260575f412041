"""Upstream Arroyo's first pipeline, the top five auctions of a sliding
minute (the deployment ``nexmark-top5-minute``), end to end on the CPU at a
small size: the benchmark cell's own query text through ``plan_query`` into
the engine, its sink's rows and the per-auction aggregate's output held to a
plain Python computation over the connector's own batches (dicts and loops;
no code of ``windows/``, ``ops/`` or ``operators/``): on the jax and the numpy
backend, across a checkpoint and a restore in the middle of a window, and
across a gap in event time longer than a window; the window function's span
and counters over a whole run; and the configuration's own reference
(``benchmark/configs/nexmark-top5-minute.py``) held to the same computation,
ties at the fifth place included."""

import importlib.util
import json
import os
import string
import time

import numpy as np
import pytest
from test_nexmark_q8 import micros

from arroyo_tpu.batch import TIMESTAMP_FIELD

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(REPO, "benchmark", "configs", "nexmark-top5-minute")
INTER = 10_000                                # 100 events a second of event time
SLIDE, WIDTH = 2_000_000, 60_000_000          # thirty 200-event slides, 6,000 events a window
NB, TOP = WIDTH // SLIDE, 5
EVENTS, SEED = 15_000, 51                     # two and a half windows, 75 slides
GAP_AT, GAP = 8_000, 150_000_000              # event 8,000 on comes 150 s later
# conftest's 8,192 slots in 2,048-slot regions hold four bins; thirty and the
# one being filled want their regions without growing the table
SIZES = {"device.table-capacity": 32_768, "device.region-size": 512,
         "engine.coalesce.enabled": False}


def top5_sql(out_path: str, rate: int = 0, events: int = EVENTS) -> str:
    with open(CONFIG + ".sql") as f:
        text = string.Template(f.read()).substitute(
            seed=SEED, sink="$sink", event_rate=rate,
            inter_event_micros=INTER, first_event_micros=0)
    text = text.replace("seed = %d" % SEED, "seed = %d,\n  event_count = %d" % (SEED, events))
    sink = "connector = 'single_file', path = '%s', format = 'json', type = 'sink'" % out_path
    assert "connector = '$sink', type = 'sink'" in text
    return text.replace("connector = '$sink', type = 'sink'", sink)


def the_events(events: int = EVENTS, seed: int = SEED) -> tuple:
    """(is a bid, event time, the bid's auction) of every event, a column
    each, from the connector itself."""
    from arroyo_tpu.connectors.nexmark import NexmarkSource

    src = NexmarkSource({"inter_event_micros": INTER, "first_event_micros": 0, "seed": seed,
                         "columns": ["bid", "bid.auction"]})
    cols = ([], [], [])
    for lo in range(0, events, 500):
        b = src._generate(np.arange(lo, min(lo + 500, events)))
        for into, name in zip(cols, ("bid", TIMESTAMP_FIELD, "bid.auction")):
            into.append(np.asarray(b[name]))
    return tuple(np.concatenate(c) for c in cols)


def the_bids(events: int = EVENTS) -> list[tuple]:
    is_bid, ts, auction = the_events(events)
    return [(t, a) for b, t, a in zip(is_bid.tolist(), ts.tolist(), auction.tolist()) if b]


def first_five(per: dict) -> list[tuple]:
    """(auction, bids, place) of the five auctions with the most bids, a tie
    to the lower id."""
    ranked = sorted(per.items(), key=lambda kv: (-kv[1], kv[0]))[:TOP]
    return [(a, n, i + 1) for i, (a, n) in enumerate(ranked)]


def oracle(bids: list[tuple]) -> tuple[dict, list]:
    """-> per window start {auction: bids} over every window that holds a
    bid, and the query's result rows (window start, auction, bids, place).
    A bid at ``ts`` counts in the thirty windows that start in
    (ts - WIDTH, ts] on the slide's grid."""
    per_window: dict = {}
    for ts, a in bids:
        for j in range((ts - WIDTH) // SLIDE + 1, ts // SLIDE + 1):
            per = per_window.setdefault(j * SLIDE, {})
            per[a] = per.get(a, 0) + 1
    rows = [(w, *row) for w, per in per_window.items() for row in first_five(per)]
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
    """window start -> {auction: bids} as the aggregate emitted them; a
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
    return sorted((micros(r["ws"]), r["auction"], r["num"], r["row_num"]) for r in got)


def run_top5(job: str, out: str, settings: dict, events: int = EVENTS) -> dict:
    from arroyo_tpu import config as cfg
    from arroyo_tpu.engine import Engine
    from arroyo_tpu.sql import plan_query

    taps: dict = {}
    with cfg.scoped(dict(SIZES, **settings)):
        graph = plan_query(top5_sql(out, events=events)).graph
        ranked = [n for n in graph.nodes.values() if n.op.value == "window_function"]
        assert [n.config.get("limit") for n in ranked] == [TOP]
        engine = Engine(graph, job_id=job)
        tap_sliding(engine, taps)
        engine.run_to_completion(timeout=300)
    assert len(taps) == 1  # the one per-auction count: the bid stream is read once
    return taps


def held_to(taps: dict, out: str, per_window: dict, rows: list) -> None:
    for nid, batches in taps.items():
        assert tapped_counts(batches) == per_window, nid
    assert sink_rows(out) == rows


# ------------------------------------------------------ against the oracle


@pytest.fixture(scope="module")
def jax_run(tmp_path_factory):
    """One uninterrupted run on the jax backend, for the comparison and for
    the spans and counters the window function leaves."""
    from arroyo_tpu.metrics import registry
    from arroyo_tpu.obs import trace

    tmp = tmp_path_factory.mktemp("top5")
    out = str(tmp / "top_auctions.json")
    job = "top5-jax"
    taps = run_top5(job, out, {"checkpoint.storage-url": str(tmp / "ck")})
    # read now: the ring keeps the records of a few dozen ended threads only
    ranks = trace.spans("wf.rank", job=job)
    node = ranks[0].node if ranks else None
    return {"job": job, "taps": taps, "out": out, "ranks": ranks, "node": node,
            "marks": trace.spans("task.account", node=node, job=job),
            "metrics": registry.job_metrics(job)}


def test_top5_equals_the_plain_oracle_on_the_device_path(jax_run, the_oracle):
    per_window, rows = the_oracle
    # every window that holds a bid: the 29 that start before the stream too
    assert len(per_window) == EVENTS * INTER // SLIDE + NB - 1 == 104
    # even the stream's first slide holds five auctions: five rows a window
    assert len(rows) == TOP * len(per_window)
    held_to(jax_run["taps"], jax_run["out"], per_window, rows)


def test_the_oracles_windows_hold_ties_at_the_fifth_place(the_oracle):
    """What ORDER BY num DESC alone would leave to the engine: windows whose
    fifth and sixth auctions have the same count."""
    per_window, _rows = the_oracle
    tied = 0
    for per in per_window.values():
        counts = sorted(per.values(), reverse=True)
        tied += len(counts) > TOP and counts[TOP - 1] == counts[TOP]
    assert tied >= 3, tied


def test_top5_equals_the_plain_oracle_on_the_numpy_backend(the_oracle, tmp_path):
    out = str(tmp_path / "top_auctions.json")
    taps = run_top5("top5-numpy", out, {"device.enabled": False})
    held_to(taps, out, *the_oracle)


def test_top5_across_a_checkpoint_and_a_restore_inside_a_window(the_oracle, tmp_path):
    from arroyo_tpu import config as cfg
    from arroyo_tpu.engine import Engine
    from arroyo_tpu.obs import trace
    from arroyo_tpu.sql import plan_query

    per_window, rows = the_oracle
    cfg.update(SIZES)
    out = str(tmp_path / "top_auctions.json")
    job = "top5-restore"
    # paced, so that the checkpoint falls inside the stream: 3 s of it
    sql = top5_sql(out, rate=5_000)
    taps: dict = {}
    first = Engine(plan_query(sql).graph, job_id=job)
    tap_sliding(first, taps)
    first.start()
    time.sleep(1.5)
    assert first.checkpoint_and_wait(1, timeout=120).outcome == "completed"
    first.stop()
    first.join(timeout=60)
    # the barrier met full windows' worth of bins, and windows already ranked
    before = tapped_counts(next(iter(taps.values())))
    assert NB < len(before) < len(per_window), len(before)
    ranked_before = len(trace.spans("wf.rank", job=job))
    assert 0 < ranked_before < len(per_window)
    second = Engine(plan_query(sql).graph, job_id=job, restore_epoch=1)
    tap_sliding(second, taps)
    second.run_to_completion(timeout=300)
    held_to(taps, out, per_window, rows)


@pytest.mark.parametrize("backend", ["jax", "numpy"])
def test_an_event_time_gap_longer_than_a_window(backend, monkeypatch, tmp_path):
    """From event GAP_AT on the stream is 150 s later: the windows between
    the two stretches hold nothing and none is ranked, and those on either
    side of the gap are whole."""
    from arroyo_tpu.connectors.nexmark import NexmarkSource

    generate = NexmarkSource._generate

    def with_a_gap(self, numbers):
        batch = generate(self, numbers)
        batch[TIMESTAMP_FIELD][numbers.astype(np.int64) >= GAP_AT] += GAP
        return batch

    monkeypatch.setattr(NexmarkSource, "_generate", with_a_gap)
    bids = the_bids(12_000)
    assert max(ts for ts, _ in bids) > 12_000 * INTER + GAP - SLIDE
    per_window, rows = oracle(bids)
    # 80 s and 40 s of stream with 150 s between: 69 + 49 windows hold a bid
    assert len(per_window) == 40 + NB - 1 + 20 + NB - 1
    out = str(tmp_path / "top_auctions.json")
    taps = run_top5(f"top5-gap-{backend}", out, {"device.enabled": backend == "jax"},
                    events=12_000)
    held_to(taps, out, per_window, rows)


# --------------------------------------------- the window function's record


def test_wf_rank_is_one_span_a_window_with_five_rows_out(jax_run, the_oracle):
    per_window, _rows = the_oracle
    spans = jax_run["ranks"]
    # one a window, in window order, named by the window's end as agg.close is
    assert [s.trace_id for s in spans] == sorted(w + WIDTH for w in per_window)
    for s in spans:
        per = per_window[s.trace_id - WIDTH]
        assert s.args == {"rows_in": len(per), "limit": TOP, "partitions": 1,
                          "rows_out": min(TOP, len(per))}
    assert max(s.args["rows_in"] for s in spans) > 20 * TOP


def test_the_two_counters_are_the_sums_over_the_spans(jax_run):
    spans, m = jax_run["ranks"], jax_run["metrics"][jax_run["node"]]
    assert m["arroyo_worker_window_fn_rows_in"] == sum(s.args["rows_in"] for s in spans) \
        == m["arroyo_worker_messages_recv"]
    assert m["arroyo_worker_window_fn_rows_out"] == sum(s.args["rows_out"] for s in spans) \
        == m["arroyo_worker_messages_sent"]
    # and the account marks carry both, for a reader to difference
    last = jax_run["marks"][-1]
    assert last.args["window_fn_rows_in"] == m["arroyo_worker_window_fn_rows_in"]
    assert last.args["window_fn_rows_out"] == m["arroyo_worker_window_fn_rows_out"]
    assert not m.get("late_rows")


def test_explain_says_what_the_ranking_took_in_and_put_out(jax_run):
    from arroyo_tpu.obs.profile import _annotations, job_profile

    m = jax_run["metrics"][jax_run["node"]]
    lines = _annotations(job_profile(jax_run["metrics"])[jax_run["node"]])
    waits = next(line for line in lines if line.startswith("waits: "))
    assert (f"ranked {m['arroyo_worker_window_fn_rows_in']:,} rows, "
            f"{m['arroyo_worker_window_fn_rows_out']:,} out") in waits


# ------------------------------------------ the configuration's own reference


@pytest.fixture(scope="module")
def reference():
    spec = importlib.util.spec_from_file_location("top5_reference", CONFIG + ".py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_reference_equals_the_same_computation_ties_included(reference, the_oracle):
    """The harness hands the reference one whole window of events; here the
    connector's own, window by window of the stream the engine was held to
    (seed 51: its windows hold ties at the fifth place, the test above)."""
    per_window, rows = the_oracle
    is_bid, ts, auction = the_events()
    by_window: dict = {}
    for w, a, n, place in sorted(rows, key=lambda r: (r[0], r[3])):
        by_window.setdefault(w, []).append((a, n, place))
    whole = [w for w in per_window if w >= 0 and w + WIDTH <= EVENTS * INTER]
    assert len(whole) > NB
    for w in whole:
        inside = (ts >= w) & (ts < w + WIDTH)
        window = {"bid": is_bid[inside], "auction": np.where(is_bid[inside], auction[inside], 0)}
        assert reference.rows(window) == by_window[w], w
        partial = reference.partials(window)
        assert list(partial) == [2] and partial[2].dtype == np.int64
        assert partial[2].tolist() == [list(kv) for kv in sorted(per_window[w].items())], w


def test_the_reference_on_a_window_of_few_auctions_and_of_none(reference, monkeypatch):
    window = {"bid": np.array([True, True, False, True, True, True, True]),
              "auction": np.array([7, 9, 0, 7, 8, 9, 3])}
    # 7 and 9 twice (the lower id first), then 3 and 8 once
    assert reference.rows(window) == [(7, 2, 1), (9, 2, 2), (3, 1, 3), (8, 1, 4)]
    assert reference.rows({k: v[:0] for k, v in window.items()}) == []
    assert reference.partials(window)[2].tolist() == [[3, 1], [7, 2], [8, 1], [9, 2]]
    monkeypatch.syspath_prepend(os.path.join(REPO, "benchmark"))
    assert reference.ingested(100) == 92 and reference.ingested(3) == 0
    with open(CONFIG + ".py") as f:
        assert "arroyo_tpu" not in f.read()
