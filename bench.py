#!/usr/bin/env python
"""Round benchmark: the BASELINE.md Nexmark matrix on the TPU backend.

Configs (BASELINE.md "Benchmark configs"):
  q7 — bid stream -> tumbling 10s MAX(price)+COUNT per auction  (primary)
  q5 — bid stream -> sliding 10s/2s COUNT per auction (hot items core)
  q8 — auctions JOIN bids on auction id per tumbling 10s window
       (device-lowered InstantJoin)

Every config runs the full framework (vectorized generator, host engine,
device steps) on the default platform (the real TPU chip under the driver),
asserts EXACT per-window parity against an independent vectorized-numpy
oracle computed from the deterministic generator, and measures p50/p99
watermark-to-emit latency (wall clock from watermark injection at the
watermark operator to row arrival at the sink).

The numpy-backend run of q7 is the CPU baseline proxy for vs_baseline.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "extra"}.
"""

from __future__ import annotations

import gc
import json
import os
import statistics
import sys
import time

import numpy as np

WIDTH = 10_000_000  # 10 s tumbling / sliding width
SLIDE = 2_000_000   # q5 slide


# ---------------------------------------------------------------- graphs


def _source_node(event_count, columns, inter_event=1000):
    from arroyo_tpu.graph import Node, OpName

    return Node("src", OpName.SOURCE, {
        "connector": "nexmark", "event_count": event_count,
        "inter_event_micros": inter_event, "first_event_micros": 0,
        "include_strings": False, "columns": columns}, 1)


def build_q7(rows_sink, backend, event_count, latency_log, arrival_walls):
    from arroyo_tpu.batch import TIMESTAMP_FIELD, Schema
    from arroyo_tpu.expr import Col
    from arroyo_tpu.graph import EdgeType, Graph, Node, OpName

    S = Schema.of([("x", "int64"), (TIMESTAMP_FIELD, "int64")])
    g = Graph()
    g.add_node(_source_node(event_count, ["bid.auction", "bid.price"]))
    g.add_node(Node("bids", OpName.VALUE, {
        "projections": [("auction", Col("bid.auction")), ("price", Col("bid.price"))],
        "filter": Col("bid")}, 1))
    g.add_node(Node("wm", OpName.WATERMARK, {
        "expr": Col(TIMESTAMP_FIELD), "interval_micros": 1_000_000,
        "latency_log": latency_log}, 1))
    g.add_node(Node("key", OpName.KEY, {"keys": [("auction", Col("auction"))]}, 1))
    g.add_node(Node("agg", OpName.TUMBLING_AGGREGATE, {
        "width_micros": WIDTH,
        "key_fields": ["auction"],
        "aggregates": [("max_price", "max", Col("price")), ("bids", "count", None)],
        "input_dtype_of": lambda e: np.dtype(np.int64),
        "backend": backend}, 1))
    g.add_node(Node("sink", OpName.SINK, {
        "connector": "vec", "rows": rows_sink, "columnar": True,
        "arrival_walls": arrival_walls}, 1))
    g.add_edge("src", "bids", EdgeType.FORWARD, S)
    g.add_edge("bids", "wm", EdgeType.FORWARD, S)
    g.add_edge("wm", "key", EdgeType.FORWARD, S)
    g.add_edge("key", "agg", EdgeType.SHUFFLE, S)
    g.add_edge("agg", "sink", EdgeType.FORWARD, S)
    return g


def build_q5(rows_sink, backend, event_count, latency_log, arrival_walls):
    from arroyo_tpu.batch import TIMESTAMP_FIELD, Schema
    from arroyo_tpu.expr import Col
    from arroyo_tpu.graph import EdgeType, Graph, Node, OpName

    S = Schema.of([("x", "int64"), (TIMESTAMP_FIELD, "int64")])
    g = Graph()
    g.add_node(_source_node(event_count, ["bid.auction"]))
    g.add_node(Node("bids", OpName.VALUE, {
        "projections": [("auction", Col("bid.auction"))],
        "filter": Col("bid")}, 1))
    g.add_node(Node("wm", OpName.WATERMARK, {
        "expr": Col(TIMESTAMP_FIELD), "interval_micros": 1_000_000,
        "latency_log": latency_log}, 1))
    g.add_node(Node("key", OpName.KEY, {"keys": [("auction", Col("auction"))]}, 1))
    g.add_node(Node("agg", OpName.SLIDING_AGGREGATE, {
        "width_micros": WIDTH, "slide_micros": SLIDE,
        "key_fields": ["auction"],
        "aggregates": [("bids", "count", None)],
        "input_dtype_of": lambda e: np.dtype(np.int64),
        "backend": backend}, 1))
    g.add_node(Node("sink", OpName.SINK, {
        "connector": "vec", "rows": rows_sink, "columnar": True,
        "arrival_walls": arrival_walls}, 1))
    g.add_edge("src", "bids", EdgeType.FORWARD, S)
    g.add_edge("bids", "wm", EdgeType.FORWARD, S)
    g.add_edge("wm", "key", EdgeType.FORWARD, S)
    g.add_edge("key", "agg", EdgeType.SHUFFLE, S)
    g.add_edge("agg", "sink", EdgeType.FORWARD, S)
    return g


SESSION_GAP = 2_000_000  # qs session gap


def build_qs(rows_sink, backend, event_count, latency_log, arrival_walls):
    """Session windows per bidder (BASELINE config #5 shape): bursty
    per-bidder activity with gaps — COUNT + SUM(price) per session."""
    from arroyo_tpu.batch import TIMESTAMP_FIELD, Schema
    from arroyo_tpu.expr import Col
    from arroyo_tpu.graph import EdgeType, Graph, Node, OpName

    S = Schema.of([("x", "int64"), (TIMESTAMP_FIELD, "int64")])
    g = Graph()
    g.add_node(_source_node(event_count, ["bid.bidder", "bid.price"]))
    g.add_node(Node("bids", OpName.VALUE, {
        "projections": [("bidder", Col("bid.bidder")), ("price", Col("bid.price"))],
        "filter": Col("bid")}, 1))
    g.add_node(Node("wm", OpName.WATERMARK, {
        "expr": Col(TIMESTAMP_FIELD), "interval_micros": 1_000_000,
        "latency_log": latency_log}, 1))
    g.add_node(Node("key", OpName.KEY, {"keys": [("bidder", Col("bidder"))]}, 1))
    g.add_node(Node("agg", OpName.SESSION_AGGREGATE, {
        "gap_micros": SESSION_GAP,
        "key_fields": ["bidder"],
        "aggregates": [("bids", "count", None), ("spend", "sum", Col("price"))],
        "input_dtype_of": lambda e: np.dtype(np.int64)}, 1))
    g.add_node(Node("sink", OpName.SINK, {
        "connector": "vec", "rows": rows_sink, "columnar": True,
        "arrival_walls": arrival_walls}, 1))
    g.add_edge("src", "bids", EdgeType.FORWARD, S)
    g.add_edge("bids", "wm", EdgeType.FORWARD, S)
    g.add_edge("wm", "key", EdgeType.FORWARD, S)
    g.add_edge("key", "agg", EdgeType.SHUFFLE, S)
    g.add_edge("agg", "sink", EdgeType.FORWARD, S)
    return g


def build_q8(rows_sink, backend, event_count, latency_log, arrival_walls):
    """Auctions JOIN bids on auction id within tumbling windows. Denser
    event time (100us) so windows carry join-sized inputs."""
    from arroyo_tpu.batch import TIMESTAMP_FIELD, Schema
    from arroyo_tpu.expr import BinOp, Col, Lit
    from arroyo_tpu.graph import EdgeType, Graph, Node, OpName

    S = Schema.of([("x", "int64"), (TIMESTAMP_FIELD, "int64")])
    win = BinOp("*", BinOp("/", Col(TIMESTAMP_FIELD), Lit(WIDTH)), Lit(WIDTH))
    g = Graph()
    g.add_node(_source_node(event_count, ["auction.id", "bid.auction"],
                            inter_event=100))
    # watermark floored to the window start: join rows are re-stamped with
    # their window start, so a raw event-time watermark would close the
    # current window mid-stream and drop its remaining rows as late
    g.add_node(Node("wm", OpName.WATERMARK, {
        "expr": win, "latency_log": latency_log}, 1))
    # stamp rows with their window start; InstantJoin buckets by timestamp
    g.add_node(Node("auctions", OpName.VALUE, {
        "projections": [("id", Col("auction.id")), (TIMESTAMP_FIELD, win)],
        "filter": Col("auction")}, 1))
    g.add_node(Node("akey", OpName.KEY, {"keys": [("id", Col("id"))]}, 1))
    g.add_node(Node("bids", OpName.VALUE, {
        "projections": [("auction", Col("bid.auction")), (TIMESTAMP_FIELD, win)],
        "filter": Col("bid")}, 1))
    g.add_node(Node("bkey", OpName.KEY, {"keys": [("auction", Col("auction"))]}, 1))
    g.add_node(Node("join", OpName.INSTANT_JOIN, {
        "join_type": "inner",
        "left_names": [("id", "id")],
        "right_names": [("bid_auction", "auction")],
        "backend": backend}, 1))
    g.add_node(Node("sink", OpName.SINK, {
        "connector": "vec", "rows": rows_sink, "columnar": True,
        "include_internal": True,  # the join's window rides _timestamp
        "arrival_walls": arrival_walls}, 1))
    g.add_edge("src", "wm", EdgeType.FORWARD, S)
    g.add_edge("wm", "auctions", EdgeType.FORWARD, S)
    g.add_edge("wm", "bids", EdgeType.FORWARD, S)
    g.add_edge("auctions", "akey", EdgeType.FORWARD, S)
    g.add_edge("bids", "bkey", EdgeType.FORWARD, S)
    g.add_edge("akey", "join", EdgeType.LEFT_JOIN, S)
    g.add_edge("bkey", "join", EdgeType.RIGHT_JOIN, S)
    g.add_edge("join", "sink", EdgeType.FORWARD, S)
    return g


# ---------------------------------------------------------------- oracles


def _gen_events(event_count, columns, inter_event=1000):
    """Exact replay of the deterministic generator (no engine)."""
    from arroyo_tpu.connectors.nexmark import NexmarkSource

    src = NexmarkSource({
        "event_count": event_count, "inter_event_micros": inter_event,
        "first_event_micros": 0, "include_strings": False,
        "columns": columns})
    return src._generate(np.arange(event_count, dtype=np.int64))


def oracle_q7(event_count):
    """(window_start, auction) -> (max_price, count), vectorized."""
    from arroyo_tpu.batch import TIMESTAMP_FIELD

    b = _gen_events(event_count, ["bid.auction", "bid.price"])
    is_bid = np.asarray(b["bid"])
    auc = np.asarray(b["bid.auction"])[is_bid]
    price = np.asarray(b["bid.price"])[is_bid]
    ts = np.asarray(b[TIMESTAMP_FIELD])[is_bid]
    w = (ts // WIDTH) * WIDTH
    group = np.stack([w, auc], axis=1)
    uniq, inv = np.unique(group, axis=0, return_inverse=True)
    mx = np.full(len(uniq), np.iinfo(np.int64).min, dtype=np.int64)
    np.maximum.at(mx, inv, price)
    cnt = np.bincount(inv, minlength=len(uniq))
    return {(int(uniq[i, 0]), int(uniq[i, 1])): (int(mx[i]), int(cnt[i]))
            for i in range(len(uniq))}


def oracle_q5(event_count):
    """(window_start, auction) -> count over sliding 10s/2s windows."""
    from arroyo_tpu.batch import TIMESTAMP_FIELD

    b = _gen_events(event_count, ["bid.auction"])
    is_bid = np.asarray(b["bid"])
    auc = np.asarray(b["bid.auction"])[is_bid]
    ts = np.asarray(b[TIMESTAMP_FIELD])[is_bid]
    sbin = (ts // SLIDE) * SLIDE
    group = np.stack([sbin, auc], axis=1)
    uniq, inv = np.unique(group, axis=0, return_inverse=True)
    cnt = np.bincount(inv, minlength=len(uniq))
    out: dict = {}
    n_bins = WIDTH // SLIDE
    for i in range(len(uniq)):
        sb, a, c = int(uniq[i, 0]), int(uniq[i, 1]), int(cnt[i])
        # slide-bin sb contributes to windows starting sb-(W-S) .. sb
        for k in range(n_bins):
            start = sb - k * SLIDE
            key = (start, a)
            out[key] = out.get(key, 0) + c
    return out


def oracle_qs(event_count):
    """(session_start, bidder) -> (count, spend) with gap-merged sessions."""
    from arroyo_tpu.batch import TIMESTAMP_FIELD

    b = _gen_events(event_count, ["bid.bidder", "bid.price"])
    is_bid = np.asarray(b["bid"])
    bidder = np.asarray(b["bid.bidder"])[is_bid]
    price = np.asarray(b["bid.price"])[is_bid]
    ts = np.asarray(b[TIMESTAMP_FIELD])[is_bid]
    out: dict = {}
    order = np.lexsort((ts, bidder))
    bs, tss, ps = bidder[order], ts[order], price[order]
    i0 = 0
    for i in range(1, len(bs) + 1):
        if i == len(bs) or bs[i] != bs[i - 1] or tss[i] - tss[i - 1] > SESSION_GAP:
            out[(int(tss[i0]), int(bs[i0]))] = (i - i0, int(ps[i0:i].sum()))
            i0 = i
    return out


def oracle_q8(event_count):
    """(window_start, auction_id) -> n_auction_events * n_bid_events."""
    from arroyo_tpu.batch import TIMESTAMP_FIELD

    b = _gen_events(event_count, ["auction.id", "bid.auction"], inter_event=100)
    ts = np.asarray(b[TIMESTAMP_FIELD])
    w = (ts // WIDTH) * WIDTH
    is_a = np.asarray(b["auction"])
    is_b = np.asarray(b["bid"])

    def counts(mask, ids):
        grp = np.stack([w[mask], ids[mask]], axis=1)
        uniq, inv = np.unique(grp, axis=0, return_inverse=True)
        c = np.bincount(inv, minlength=len(uniq))
        return {(int(uniq[i, 0]), int(uniq[i, 1])): int(c[i]) for i in range(len(uniq))}

    na = counts(is_a, np.asarray(b["auction.id"]))
    nb = counts(is_b, np.asarray(b["bid.auction"]))
    return {k: na[k] * nb[k] for k in na.keys() & nb.keys()}


# ---------------------------------------------------------------- running


def run_config(name, build, backend, event_count, batch_size, queue_mult=2):
    from arroyo_tpu import config as cfg
    from arroyo_tpu.engine import run_graph
    from arroyo_tpu.metrics import registry

    # fresh histograms per run: the coalesce breakdown reports THIS rep
    registry.clear_job(f"bench-{name}-{backend}")
    # queue depth sweep (r5, CPU): 2x batch beats 4x on every config
    # (less cache-cold buffering); q8 runs 1x — watermark-to-emit latency
    # is queue-transit bound and the join tolerates the shallower pipeline
    cfg.update({
        "pipeline.source-batch-size": batch_size,
        "device.batch-capacity": batch_size,
        "worker.queue-size": queue_mult * batch_size if backend == "jax" else batch_size,
    })
    rows: list = []
    latency_log: list = []
    arrival_walls: list = []
    g = build(rows, backend, event_count, latency_log, arrival_walls)
    t0 = time.perf_counter()
    run_graph(g, job_id=f"bench-{name}-{backend}", timeout=1800)
    wall = time.perf_counter() - t0
    return wall, rows, latency_log, arrival_walls


def coalesce_breakdown(job_id):
    """Aggregate the instrumentation histograms (emit-batch rows,
    queue-transit seconds, sink end-to-end latency) across every task of
    one job (last rep: run_config clears)."""
    from arroyo_tpu.metrics import (EMIT_ROWS_BUCKETS, SINK_LATENCY_BUCKETS,
                                    TRANSIT_BUCKETS, Histogram, registry)

    em, qt, sk = (Histogram(EMIT_ROWS_BUCKETS), Histogram(TRANSIT_BUCKETS),
                  Histogram(SINK_LATENCY_BUCKETS))
    for t in registry.snapshot():
        if t.job_id != job_id:
            continue
        for agg, h in ((em, t.emit_batch_rows), (qt, t.queue_transit),
                       (sk, t.sink_event_latency)):
            agg.counts = [a + b for a, b in zip(agg.counts, h.counts)]
            agg.count += h.count
            agg.sum += h.sum
    return em, qt, sk


def histogram_summary(h, scale=1.0):
    """Compact JSON-able distribution summary; overflow-bucket quantiles
    are clamped lower bounds flagged with '>' (Histogram.quantile_str)."""
    return {
        "count": h.count,
        "mean": round(h.mean() * scale, 3),
        "p50": h.quantile_str(0.5, scale=scale),
        "p90": h.quantile_str(0.9, scale=scale),
        "p99": h.quantile_str(0.99, scale=scale),
    }


def latency_percentiles(rows, latency_log, arrival_walls, window_end_of):
    """Per-row wall latency from closing-watermark injection to sink
    arrival; rows flushed at end-of-stream (no covering watermark) are
    excluded. Returns (p50_ms, p99_ms, n)."""
    if not latency_log:
        return None, None, 0
    wm_vals = np.array([v for v, _ in latency_log], dtype=np.int64)
    wm_wall = np.array([wl for _, wl in latency_log])
    lats: list[np.ndarray] = []
    for batch, wall in zip(rows, arrival_walls):
        ends = window_end_of(batch)
        idx = np.searchsorted(wm_vals, ends, side="left")
        ok = idx < len(wm_vals)
        if ok.any():
            lats.append(wall - wm_wall[idx[ok]])
    if not lats:
        return None, None, 0
    all_l = np.concatenate(lats) * 1000.0
    return float(np.percentile(all_l, 50)), float(np.percentile(all_l, 99)), len(all_l)


def check_parity_q7(rows, event_count):
    got: dict = {}
    for b in rows:
        ws = np.asarray(b["window_start"])
        auc = np.asarray(b["auction"])
        mx = np.asarray(b["max_price"])
        cnt = np.asarray(b["bids"])
        for i in range(b.num_rows):
            got[(int(ws[i]), int(auc[i]))] = (int(mx[i]), int(cnt[i]))
    want = oracle_q7(event_count)
    assert got == want, (
        f"q7 parity failure: {len(got)} windows vs {len(want)}; "
        f"first diff: {next(iter(set(got.items()) ^ set(want.items())), None)}"
    )
    return sum(c for _m, c in got.values())


def check_parity_q5(rows, event_count):
    got: dict = {}
    for b in rows:
        ws = np.asarray(b["window_start"])
        auc = np.asarray(b["auction"])
        cnt = np.asarray(b["bids"])
        for i in range(b.num_rows):
            got[(int(ws[i]), int(auc[i]))] = got.get((int(ws[i]), int(auc[i])), 0) + int(cnt[i])
    want = oracle_q5(event_count)
    assert got == want, (
        f"q5 parity failure: {len(got)} (window,auction) rows vs {len(want)}; "
        f"first diff: {next(iter(set(got.items()) ^ set(want.items())), None)}"
    )
    return sum(got.values())


def check_parity_qs(rows, event_count):
    got: dict = {}
    for b in rows:
        ws = np.asarray(b["window_start"])
        bd = np.asarray(b["bidder"])
        cnt = np.asarray(b["bids"])
        sp = np.asarray(b["spend"])
        for i in range(b.num_rows):
            got[(int(ws[i]), int(bd[i]))] = (int(cnt[i]), int(sp[i]))
    want = oracle_qs(event_count)
    assert got == want, (
        f"qs parity failure: {len(got)} sessions vs {len(want)}; "
        f"first diff: {next(iter(set(got.items()) ^ set(want.items())), None)}"
    )
    return sum(c for c, _s in got.values())


def check_parity_q8(rows, event_count):
    from arroyo_tpu.batch import TIMESTAMP_FIELD

    got: dict = {}
    for b in rows:
        w = np.asarray(b[TIMESTAMP_FIELD])
        ids = np.asarray(b["id"])
        for i in range(b.num_rows):
            k = (int(w[i]), int(ids[i]))
            got[k] = got.get(k, 0) + 1
    want = oracle_q8(event_count)
    assert got == want, (
        f"q8 parity failure: {len(got)} (window,id) groups vs {len(want)}; "
        f"first diff: {next(iter(set(got.items()) ^ set(want.items())), None)}"
    )
    return sum(got.values())


# ------------------------------------------------------------- load ramp


def run_load_ramp() -> None:
    """``bench.py --load-ramp``: prove the elastic autoscaler closes the
    loop with no operator in it. An impulse source paces a scheduled load
    — BASE events/s for 10 s, then a sustained 4x spike — through a keyed
    windowed aggregate whose per-row cost is a GIL-releasing sleep UDF
    (an external-enrichment stand-in: per-subtask capacity is fixed, so
    added parallelism genuinely adds throughput even on a throttled CPU).
    At the base rate one subtask holds the sink p99 under budget; the
    spike melts it; the autoscaler must detect the pressure, rescale
    through the coordinated drain/restore path, burst through the
    backlog, and bring the *windowed* sink p99 back under budget — all
    with zero rescale API calls. Event timestamps are the scheduled
    emission wall time (impulse rate_phases), so sink latency reads
    directly as "seconds behind schedule"."""
    import time as _time

    import arroyo_tpu
    from arroyo_tpu import config as cfg
    from arroyo_tpu.controller import ControllerServer, Database
    from arroyo_tpu.controller.scheduler import EmbeddedScheduler
    from arroyo_tpu.metrics import SINK_LATENCY_BUCKETS, Histogram, registry
    from arroyo_tpu.udf import register_udf

    arroyo_tpu._load_operators()

    BASE = 6_000          # events/s before the spike
    SPIKE = 4 * BASE      # the 4x traffic spike, sustained
    BASE_SECONDS = 10
    # sleep-modelled per-row enrichment cost: one subtask caps out near
    # 1/60us ~ 16k rows/s, well under the spike and well over the base —
    # the spike NEEDS the rescale, the base must not
    PER_ROW_COST_S = 60e-6
    P99_BUDGET_S = 5.0
    WINDOW_S = 5.0        # sliding window for the p99 readout
    DEADLINE_S = 150.0

    def enrich(x):
        _time.sleep(len(np.asarray(x)) * PER_ROW_COST_S)
        return np.asarray(x, dtype=np.int64)

    register_udf("enrich", enrich, return_dtype="int64", vectorized=True)

    cfg.update({
        "checkpoint.storage-url": "/tmp/arroyo-tpu-bench/ramp-checkpoints",
        "checkpoint.interval-ms": 2000,
        # bigger source batches cut the per-batch Python overhead that
        # would otherwise dominate the sleep-modelled per-row cost
        "pipeline.source-batch-size": 1024,
        "autoscaler.enabled": True,
        "autoscaler.min-parallelism": 1,
        "autoscaler.max-parallelism": 4,
        "autoscaler.up-ticks": 10,
        "autoscaler.up-factor": 4.0,  # one decisive jump for a 4x spike
        "autoscaler.cooldown-s": 5.0,
        "autoscaler.down-ticks": 100_000,  # this run only proves scale-up
        # detection deliberately keys off the SLOW end-latency symptoms
        # (watermark lag / sink p99) with the early-warning queue signals
        # off: the melt must be visible in the p99 readout before the
        # loop reacts, or "returns under budget" proves nothing. A
        # production config would leave backpressure on and act sooner.
        "autoscaler.up-backpressure": 1e12,
        "autoscaler.up-queue-transit-p99-ms": 1e12,
        "autoscaler.up-watermark-lag-s": 4.0,
        "autoscaler.up-sink-latency-p99-s": 6.0,
    })
    import shutil

    shutil.rmtree("/tmp/arroyo-tpu-bench/ramp-checkpoints", ignore_errors=True)

    sql = f"""
CREATE TABLE load (
  counter BIGINT UNSIGNED NOT NULL,
  subtask_index BIGINT UNSIGNED NOT NULL
) WITH (
  connector = 'impulse',
  rate_phases = '{BASE}x{BASE * BASE_SECONDS},{SPIKE}'
);
CREATE TABLE ramp_out (
  start TIMESTAMP, g BIGINT, rows BIGINT, mx BIGINT
) WITH (connector = 'blackhole', type = 'sink');
INSERT INTO ramp_out
SELECT window.start AS start, g, rows, mx FROM (
  SELECT tumble(interval '1 second') AS window,
    CAST(counter % 64 AS BIGINT) AS g,
    count(*) AS rows,
    max(enrich(counter)) AS mx
  FROM load
  GROUP BY window, g
) x;
"""

    def sink_hist(jid):
        h = Histogram(SINK_LATENCY_BUCKETS)
        for t in registry.snapshot():
            if t.job_id == jid and t.sink_event_latency.count:
                h.counts = [a + b for a, b in
                            zip(h.counts, t.sink_event_latency.counts)]
                h.count += t.sink_event_latency.count
                h.sum += t.sink_event_latency.sum
        return h

    def windowed_p99(samples):
        """p99 over roughly the last WINDOW_S of sink arrivals: bucket
        difference between the newest cumulative histogram and the one
        ~WINDOW_S ago (counters are monotone across restores — the
        registry outlives embedded worker sets)."""
        if len(samples) < 2:
            return None
        newest_t, newest = samples[-1]
        base_t, base = samples[0]
        for t, h in samples:
            if newest_t - t >= WINDOW_S:
                base_t, base = t, h
        delta = Histogram(SINK_LATENCY_BUCKETS)
        delta.counts = [a - b for a, b in zip(newest.counts, base.counts)]
        delta.count = newest.count - base.count
        delta.sum = newest.sum - base.sum
        if delta.count < 3:  # sink latency observes once per arriving
            return None      # batch (~1/s per closing window round)
        return delta.quantile(0.99)

    db = Database()
    ctl = ControllerServer(db, EmbeddedScheduler()).start()
    timeline: list[dict] = []
    outcome = {"melted": False, "recovered": False, "recovery_s": None,
               "peak_p99_s": None}
    try:
        pid = db.create_pipeline("load-ramp", sql, 1)
        jid = db.create_job(pid)
        ctl.wait_for_state(jid, "Running", timeout=60)
        t0 = _time.monotonic()
        spike_at = t0 + BASE_SECONDS
        samples: list[tuple[float, Histogram]] = []
        recovered_since = None
        while _time.monotonic() - t0 < DEADLINE_S:
            _time.sleep(0.5)
            now = _time.monotonic()
            samples.append((now, sink_hist(jid)))
            samples = [s for s in samples if now - s[0] <= WINDOW_S + 2.0]
            p99 = windowed_p99(samples)
            jc = ctl.jobs.get(jid)
            par = jc.parallelism if jc is not None else None
            state = db.get_job(jid)["state"]
            timeline.append({
                "t_s": round(now - t0, 1), "p99_s": p99 and round(p99, 3),
                "parallelism": par, "state": state,
            })
            if state in ("Failed", "Finished", "Stopped"):
                break
            if now < spike_at or p99 is None:
                continue
            outcome["peak_p99_s"] = max(outcome["peak_p99_s"] or 0.0, p99)
            if p99 > P99_BUDGET_S:
                outcome["melted"] = True
                recovered_since = None
            elif outcome["melted"]:
                # under budget post-melt; require it to HOLD for a window
                recovered_since = recovered_since or now
                if now - recovered_since >= WINDOW_S:
                    outcome["recovered"] = True
                    outcome["recovery_s"] = round(now - spike_at, 1)
                    break
        evs = db.list_events(jid)
        # graceful stop: a final checkpoint drains the workers so engine
        # threads exit cleanly instead of being killed mid-batch
        db.update_job(jid, desired_stop="checkpoint")
        try:
            ctl.wait_for_state(jid, "Stopped", "Failed", "Finished",
                               timeout=45)
        except Exception:  # lint: waive LR102 — bench teardown only
            pass
    finally:
        ctl.stop()

    autoscale = [e["code"] for e in evs if e["code"].startswith("AUTOSCALE")]
    final_par = next((s["parallelism"] for s in reversed(timeline)
                      if s["parallelism"]), None)
    ok = (outcome["melted"] and outcome["recovered"]
          and "AUTOSCALE_DONE" in autoscale)
    print(json.dumps({
        "metric": "load_ramp_autoscale_recovery_seconds",
        "value": outcome["recovery_s"] if ok else None,
        "unit": "s",
        "vs_baseline": None,
        "extra": {
            "ok": ok,
            "base_rate": BASE, "spike_rate": SPIKE,
            "p99_budget_s": P99_BUDGET_S,
            "peak_p99_s": outcome["peak_p99_s"] and round(outcome["peak_p99_s"], 2),
            "melted": outcome["melted"], "recovered": outcome["recovered"],
            "final_parallelism": final_par,
            "autoscale_events": autoscale,
            "manual_rescale_calls": 0,
            "timeline": timeline,
        },
    }))
    sys.exit(0 if ok else 1)


def run_segment_ab() -> None:
    """--segment-compile-ab: whole-segment compilation A/B (ISSUE 12).

    Runs q5/q7/q8 twice each — segment.compile.enabled on vs off, chaining
    on both times, everything else identical — and emits BENCH_r06.json:
    best-of-reps events/s per mode, the compiled/interpreted ratio, and the
    per-operator cost profile embedded for BOTH modes so the chain's
    per-batch dispatch overhead (its 'process' self-time and us/row) is
    visible before/after. The compiled chain profiles as ONE dispatch site;
    its interpreted twin pays N member hook calls per micro-batch.

    Warm-box caveat (BENCH_r05 note): this container's CPU throttling
    swings absolute ev/s >2x between back-to-back runs — judge the A/B
    ratio only on a warm, unthrottled run, and prefer the embedded
    self-time deltas (CPU-clock based) over wall ev/s when they disagree.
    """
    import arroyo_tpu
    from arroyo_tpu import config as cfg
    from arroyo_tpu.metrics import registry
    from arroyo_tpu.obs.profile import job_profile

    arroyo_tpu._load_operators()
    cfg.update({
        "pipeline.chaining.enabled": True,
        "device.table-capacity": 65536,
        "device.emit-capacity": 8192,
        "checkpoint.storage-url": "/tmp/arroyo-tpu-bench/checkpoints",
    })
    events = int(os.environ.get("ARROYO_BENCH_EVENTS", 2_000_000))
    reps = int(os.environ.get("ARROYO_BENCH_REPS", 5))
    DEV_BS = 65536
    configs = [
        ("q7", build_q7, check_parity_q7, events),
        ("q5", build_q5, check_parity_q5, events // 2),
        ("q8", build_q8, check_parity_q8, events // 4),
    ]
    queue_mult = {"q8": 1}
    out: dict = {"events": events, "reps": reps}
    all_ok = True
    for name, build, parity, n_ev in configs:
        per_mode: dict = {"interpreted": {}, "compiled": {}}
        # run_config clears the job's registry per run, so segment stats
        # accumulate HERE across warmup + every compiled rep — the
        # artifact must show where compilation actually happened (the
        # warmup), not just the final warm-cache rep's zeros
        seg_totals = [0, 0]  # compiles, cache hits

        def take_seg_stats():
            c, h = registry.segment_compile_stats(f"bench-{name}-jax")
            seg_totals[0] += c
            seg_totals[1] += h

        def one(enabled: bool) -> float:
            cfg.update({"segment.compile.enabled": enabled})
            gc.collect()
            wall, rows, _lat, _walls = run_config(
                name, build, "jax", n_ev, DEV_BS, queue_mult.get(name, 2))
            parity(rows, n_ev)
            if enabled:
                take_seg_stats()
            return n_ev / wall

        # warmup both modes: the big device shapes AND the segment-cache
        # entries — including the measured run's REMAINDER batch shape
        # (n_ev % batch), so no rep pays a mid-measurement XLA compile
        for enabled in (False, True):
            cfg.update({"segment.compile.enabled": enabled})
            run_config(name, build, "jax",
                       3 * DEV_BS + (n_ev % DEV_BS or DEV_BS), DEV_BS,
                       queue_mult.get(name, 2))
            if enabled:
                take_seg_stats()
        # PAIRED reps, interpreted/compiled back to back on the same box
        # state: container CPU throttling drifts absolute ev/s >2x across
        # seconds, so unpaired mode blocks measure the throttle, not the
        # change; the per-pair ratio cancels the drift (the PR 5 bench's
        # back-to-back A/B protocol), judged on the median pair
        ratios: list[float] = []
        for r in range(reps):
            eps_i = one(False)
            prof_i = job_profile(registry.job_metrics(f"bench-{name}-jax"))
            eps_c = one(True)
            prof_c = job_profile(registry.job_metrics(f"bench-{name}-jax"))
            ratios.append(eps_c / eps_i)
            print(f"# {name} pair {r}: interpreted {eps_i:,.0f} ev/s, "
                  f"compiled {eps_c:,.0f} ev/s, ratio {eps_c / eps_i:.3f}",
                  file=sys.stderr)
            if eps_i > per_mode["interpreted"].get("events_per_sec", 0):
                per_mode["interpreted"] = {
                    "events_per_sec": round(eps_i, 1), "profile": prof_i}
            if eps_c > per_mode["compiled"].get("events_per_sec", 0):
                per_mode["compiled"] = {
                    "events_per_sec": round(eps_c, 1), "profile": prof_c}
        per_mode["compiled"]["segment_compiles"] = seg_totals[0]
        per_mode["compiled"]["segment_cache_hits"] = seg_totals[1]
        # judged like every ev/s number in this series: on the least-
        # throttled (best) pair — the repo's best-of-N convention for this
        # container's one-sided CPU-throttling noise — with the median as
        # a no-hidden-regression guard (a real slowdown drags BOTH)
        best_pair = max(ratios)
        median = statistics.median(ratios)
        ok = best_pair >= 1.0 and median >= 0.97
        all_ok = all_ok and ok
        print(f"# {name}: compiled/interpreted best pair {best_pair:.3f}, "
              f"median of {len(ratios)} pairs {median:.3f} "
              f"({'OK' if ok else 'REGRESSION'})", file=sys.stderr)
        out[name] = {**per_mode,
                     "pair_ratios": [round(x, 3) for x in ratios],
                     "compiled_over_interpreted": round(best_pair, 3),
                     "pair_ratio_median": round(median, 3),
                     "dispatch_overhead_eliminated": ok}
    payload = {
        "metric": "segment_compile_ab_min_ratio",
        "value": round(min(out[c[0]]["compiled_over_interpreted"]
                           for c in configs), 3),
        "unit": "compiled/interpreted events-per-sec ratio, best of paired "
                "back-to-back reps (>=1 = dispatch overhead eliminated; "
                "pair_ratio_median >= 0.97 guards against a hidden "
                "regression)",
        "platform": os.environ.get("JAX_PLATFORMS", "default"),
        "note": "warm-box caveat: container CPU throttling swings absolute "
                "ev/s >2x run-to-run, so reps pair interpreted/compiled "
                "back to back, the ratio is judged on the least-throttled "
                "pair (the series' best-of-N convention), and the median "
                "is reported alongside; judge absolute ev/s on a warm run "
                "only",
        "extra": out,
    }
    with open("BENCH_r06.json", "w") as f:
        json.dump(payload, f, indent=1)
        f.write("\n")
    print(json.dumps(payload))
    sys.exit(0 if all_ok else 1)


def run_mesh_ab() -> None:
    """--mesh-ab: fused shard_map segment vs host-shuffle mesh A/B
    (ISSUE 20), emitting MULTICHIP_r06.json.

    One pipeline — impulse -> watermark -> key -> tumbling count/sum over
    an 8-way key-sharded aggregate -> vec sink — run two ways, paired back
    to back per rep:

      fused:  the compiled segment runs INSIDE the sharded aggregate's one
              shard_map'd jitted program per micro-batch
              (segment.compile.mesh-fuse on);
      host:   the same compiled segment on host, feeding the aggregate's
              per-batch host bucketing + device all_to_all exchange
              (mesh-fuse off) — the pre-fusion mesh path.

    Both modes' outputs are verified exactly against a closed-form oracle,
    and the artifact embeds the dispatch ledger per mode: segment-level
    fused dispatches MUST equal aggregate-level program executions
    (calls_per_step == 1.0), so 'one jitted call per step' is data in the
    artifact, not prose. Runs on 8 EMULATED host devices
    (--xla_force_host_platform_device_count, set by main() before jax
    starts), so absolute ev/s is a CPU number — judge the ledger and the
    paired ratio, not the wall clock. When fewer than 8
    devices materialize the artifact records skipped=true and exits 0
    (r01-r05 convention)."""
    import tempfile

    import jax

    import arroyo_tpu
    from arroyo_tpu import config as cfg
    from arroyo_tpu.engine import Engine
    from arroyo_tpu.engine.segment import (mesh_dispatch_counts,
                                           reset_mesh_dispatch_counts)
    from arroyo_tpu.parallel import can_make
    from arroyo_tpu.parallel.sharded_agg import (dispatch_counts,
                                                 reset_dispatch_counts)

    n_dev = 8
    if not can_make(n_dev):
        payload = {"n_devices": len(jax.devices()), "rc": 0, "ok": False,
                   "skipped": True,
                   "tail": f"mesh-ab skipped: {len(jax.devices())} devices "
                           f"< {n_dev} (set XLA_FLAGS="
                           f"--xla_force_host_platform_device_count=8)"}
        with open("MULTICHIP_r06.json", "w") as f:
            json.dump(payload, f, indent=1)
            f.write("\n")
        print(json.dumps(payload))
        sys.exit(0)

    arroyo_tpu._load_operators()
    count, width, nkeys = int(os.environ.get("ARROYO_BENCH_EVENTS", 200_000)), 1_000_000, 7
    reps = int(os.environ.get("ARROYO_BENCH_REPS", 3))
    BS = 4096
    cfg.update({
        "checkpoint.storage-url": tempfile.mkdtemp(prefix="arroyo-mesh-ab-"),
        "device.mesh-devices": n_dev,
        "device.table-capacity": 8192, "device.batch-capacity": 2048,
        "device.emit-capacity": 4096, "device.spill-capacity": 4096,
        "device.max-probes": 32,
        "pipeline.chaining.enabled": True,
        "pipeline.source-batch-size": BS,
        "engine.coalesce.max-rows": BS,
        "segment.compile.min-rows": 1,
    })

    from arroyo_tpu.batch import TIMESTAMP_FIELD, Schema
    from arroyo_tpu.expr import BinOp, Col, Lit
    from arroyo_tpu.graph import EdgeType, Graph, Node, OpName

    S = Schema.of([("x", "int64"), (TIMESTAMP_FIELD, "int64")])

    def mk(rows):
        g = Graph()
        g.add_node(Node("src", OpName.SOURCE, {
            "connector": "impulse", "message_count": count,
            "interval_micros": 1000, "start_time_micros": 0,
            "event_rate": 0}, 1))
        g.add_node(Node("wm", OpName.WATERMARK, {"expr": Col(TIMESTAMP_FIELD)}, 1))
        g.add_node(Node("key", OpName.KEY, {
            "keys": [("k", BinOp("%", Col("counter"), Lit(nkeys)))]}, 1))
        g.add_node(Node("agg", OpName.TUMBLING_AGGREGATE, {
            "width_micros": width, "key_fields": ["k"],
            "aggregates": [("cnt", "count", None),
                           ("total", "sum", Col("counter"))],
            "input_dtype_of": lambda e: np.dtype(np.int64),
            "backend": "jax"}, 1))
        g.add_node(Node("sink", OpName.SINK, {"connector": "vec", "rows": rows}, 1))
        g.add_edge("src", "wm", EdgeType.FORWARD, S)
        g.add_edge("wm", "key", EdgeType.FORWARD, S)
        g.add_edge("key", "agg", EdgeType.SHUFFLE, S)
        g.add_edge("agg", "sink", EdgeType.FORWARD, S)
        return g

    want: dict = {}
    for c in range(count):
        w, k = (c * 1000) // width, c % nkeys
        cnt, tot = want.get((w, k), (0, 0))
        want[(w, k)] = (cnt + 1, tot + c)

    def one(fuse: bool, tag: str):
        cfg.update({"segment.compile.mesh-fuse": fuse})
        reset_mesh_dispatch_counts()
        reset_dispatch_counts()
        rows: list = []
        gc.collect()
        eng = Engine(mk(rows), job_id=f"mesh-ab-{tag}")
        t0 = time.perf_counter()
        eng.run_to_completion(timeout=600)
        wall = time.perf_counter() - t0
        got = {(r["window_start"] // width, r["k"]): (r["cnt"], r["total"])
               for r in rows}
        assert got == want, f"mesh-ab {tag}: output diverged from oracle"
        return count / wall, mesh_dispatch_counts(), dispatch_counts()

    # warmup both modes: XLA program compiles + segment cache entries
    # (including the remainder-batch shape) happen here, not mid-rep
    one(False, "warm-host")
    one(True, "warm-fused")

    modes: dict = {"fused": {}, "host": {}}
    ratios: list[float] = []
    ledger_ok = True
    for r in range(reps):
        eps_h, _seg_h, agg_h = one(False, f"host-{r}")
        eps_f, seg_f, agg_f = one(True, f"fused-{r}")
        ratios.append(eps_f / eps_h)
        # the tentpole's proof obligation: every fused segment dispatch is
        # exactly one program execution, and the fused path actually ran
        cps = (agg_f["fused_steps"] / seg_f["fused"]) if seg_f["fused"] else 0.0
        ledger_ok = ledger_ok and seg_f["fused"] > 0 and cps == 1.0 \
            and agg_h["fused_steps"] == 0 and agg_h["host_steps"] > 0
        print(f"# mesh-ab pair {r}: host {eps_h:,.0f} ev/s, fused "
              f"{eps_f:,.0f} ev/s, ratio {eps_f / eps_h:.3f}, fused "
              f"dispatches {seg_f['fused']} (calls/step {cps:.1f})",
              file=sys.stderr)
        if eps_h > modes["host"].get("events_per_sec", 0):
            modes["host"] = {"events_per_sec": round(eps_h, 1),
                             "dispatch": {"segment_fused": 0,
                                          "agg_program_steps": agg_h["fused_steps"],
                                          "agg_host_exchange_steps": agg_h["host_steps"]}}
        if eps_f > modes["fused"].get("events_per_sec", 0):
            modes["fused"] = {"events_per_sec": round(eps_f, 1),
                              "dispatch": {"segment_fused": seg_f["fused"],
                                           "segment_host_commits": seg_f["host"],
                                           "agg_program_steps": agg_f["fused_steps"],
                                           "agg_host_exchange_steps": agg_f["host_steps"],
                                           "calls_per_step": round(cps, 3)}}
    best, median = max(ratios), statistics.median(ratios)
    ok = ledger_ok and best >= 1.0
    tail = (f"mesh-ab OK: 8 devices, fused/host best {best:.3f} (median "
            f"{median:.3f}), {modes['fused']['dispatch']['segment_fused']} "
            f"fused steps at calls/step "
            f"{modes['fused']['dispatch']['calls_per_step']:.1f}, oracle "
            f"exact both modes" if ok else
            f"mesh-ab REGRESSION: ratio best {best:.3f} median {median:.3f} "
            f"ledger_ok={ledger_ok}")
    payload = {
        "n_devices": n_dev, "rc": 0 if ok else 1, "ok": ok, "skipped": False,
        "tail": tail,
        "metric": "mesh_fused_over_host_events_per_sec",
        "value": round(best, 3),
        "unit": "fused/host events-per-sec ratio, best of paired reps on 8 "
                "emulated CPU devices (ledger proves one jitted program "
                "execution per fused micro-batch)",
        "extra": {"events": count, "reps": reps,
                  "pair_ratios": [round(x, 3) for x in ratios],
                  "pair_ratio_median": round(median, 3),
                  "one_call_per_step": ledger_ok, **modes},
    }
    with open("MULTICHIP_r06.json", "w") as f:
        json.dump(payload, f, indent=1)
        f.write("\n")
    print(json.dumps(payload))
    sys.exit(0 if ok else 1)


def main() -> None:
    # --profile: embed the per-operator cost profile (self-time, busy%,
    # state sizes, hot keys — obs/profile.py, same data `explain` renders)
    # under extra.<cfg>.profile so future perf PRs can attribute wins per
    # operator straight from the BENCH_*.json archive. Taken from the LAST
    # rep (run_config clears the registry per rep).
    # --load-ramp: the autoscaler acceptance run (CPU-bound control-loop
    # proof, not a device benchmark) — see run_load_ramp
    if "--load-ramp" in sys.argv[1:]:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        run_load_ramp()
        return
    if "--mesh-ab" in sys.argv[1:]:
        # fused shard_map segment A/B on 8 emulated host devices: force
        # the flags BEFORE any backend init (jax reads XLA_FLAGS once)
        os.environ["JAX_PLATFORMS"] = "cpu"
        _fl = os.environ.get("XLA_FLAGS", "")
        if "host_platform_device_count" not in _fl:
            os.environ["XLA_FLAGS"] = (
                _fl + " --xla_force_host_platform_device_count=8").strip()
        run_mesh_ab()
        return
    if "--segment-compile-ab" in sys.argv[1:]:
        # whole-segment compilation A/B: the win being measured is the
        # collapse of host-side Python dispatch, so CPU is the honest
        # default platform (a TPU run would conflate device lowering)
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        run_segment_ab()
        return
    embed_profile = "--profile" in sys.argv[1:]
    import jax

    # the matrix measures the accelerator: without one it stops, unless a
    # host run was asked for by name (and is then named for what it is)
    asked = os.environ.get("ARROYO_BENCH_PLATFORM")
    if asked:
        jax.config.update("jax_platforms", asked)
    dev = jax.devices()[0]
    platform = dev.platform
    if platform == "cpu" and asked != "cpu":
        sys.exit("bench.py: jax found no accelerator (platform 'cpu'); set "
                 "ARROYO_BENCH_PLATFORM=cpu to measure the host on purpose")
    print(f"# platform {platform} ({dev.device_kind} x{len(jax.devices())})",
          file=sys.stderr)
    import arroyo_tpu
    from arroyo_tpu import config as cfg

    arroyo_tpu._load_operators()
    cfg.update({
        "pipeline.chaining.enabled": True,
        "device.table-capacity": 65536,
        "device.emit-capacity": 8192,
        "checkpoint.storage-url": "/tmp/arroyo-tpu-bench/checkpoints",
    })

    events = int(os.environ.get("ARROYO_BENCH_EVENTS", 2_000_000))
    # same event count as the measured runs: best-of-N on one size vs
    # best-of-N on another was apples-to-pears
    base_events = int(os.environ.get("ARROYO_BENCH_BASELINE_EVENTS", events))
    reps = int(os.environ.get("ARROYO_BENCH_REPS", 3))
    # 65536-row batches amortise the per-step dispatch and host->device
    # copy (picked in round 2; whether it still pays on a directly attached
    # chip is ROADMAP A2's question); the numpy dict-store baseline prefers
    # smaller batches
    DEV_BS, NP_BS = 65536, 8192

    def window_end_tumbling(batch):
        return np.asarray(batch["window_start"]) + WIDTH

    def window_end_q8(batch):
        from arroyo_tpu.batch import TIMESTAMP_FIELD

        return np.asarray(batch[TIMESTAMP_FIELD]) + WIDTH

    def window_end_session(batch):
        return np.asarray(batch["window_end"])

    configs = [
        ("q7", build_q7, check_parity_q7, window_end_tumbling, events),
        ("q5", build_q5, check_parity_q5, window_end_tumbling, events // 2),
        ("q8", build_q8, check_parity_q8, window_end_q8, events // 4),
        ("qs", build_qs, check_parity_qs, window_end_session, events // 4),
    ]
    QUEUE_MULT_DEFAULT = 2
    queue_mult = {"q8": 1}
    # p99 watermark-to-emit budgets (round-4 review); recorded as explicit
    # pass/fail flags rather than assertions so a miss can never zero the
    # round's number the way r03's crash did
    P99_BUDGET_MS = {"q8": 50.0, "qs": 100.0}
    extra: dict = {}
    q7_eps = 0.0
    for name, build, parity, wend, n_ev in configs:
        # warmup must see at least one FULL-size batch: a 50k-event warmup
        # never produces a 65536-row batch, so the real run's first batch
        # would trigger the big-shape compile mid-measurement (slow rep 0)
        run_config(name, build, "jax", 3 * DEV_BS, DEV_BS,
                   queue_mult.get(name, QUEUE_MULT_DEFAULT))
        best_eps, best_lat = 0.0, (None, None)
        worst_p99 = None
        for r in range(reps):
            gc.collect()
            wall, rows, lat_log, walls = run_config(
                name, build, "jax", n_ev, DEV_BS, queue_mult.get(name, QUEUE_MULT_DEFAULT))
            parity(rows, n_ev)
            eps = n_ev / wall
            p50, p99, n_l = latency_percentiles(rows, lat_log, walls, wend)
            print(f"# {name} rep {r}: {n_ev} events in {wall:.2f}s = {eps:,.0f} ev/s; "
                  f"parity OK; p50 {p50 and round(p50, 1)}ms p99 {p99 and round(p99, 1)}ms "
                  f"({n_l} rows)", file=sys.stderr)
            if eps > best_eps:
                best_eps, best_lat = eps, (p50, p99)
            if p99 is not None and (worst_p99 is None or p99 > worst_p99):
                worst_p99 = p99
        em, qt, sk = coalesce_breakdown(f"bench-{name}-jax")
        print(f"# {name} coalesce: {em.count} emitted batches, "
              f"mean {em.mean():,.0f} rows/batch; queue transit "
              f"p50 {qt.quantile_str(0.5, scale=1000)}ms "
              f"p99 {qt.quantile_str(0.99, scale=1000)}ms ({qt.count} transits)",
              file=sys.stderr)
        extra[name] = {
            "events_per_sec": round(best_eps, 1),
            "p50_ms": best_lat[0] and round(best_lat[0], 2),
            "p99_ms": best_lat[1] and round(best_lat[1], 2),
            "coalesce": {
                "emitted_batches": em.count,
                "mean_emit_rows": round(em.mean(), 1),
                "queue_transit_p99_ms": round(qt.quantile(0.99) * 1000, 3),
            },
            # full distribution summaries so the perf trajectory captures
            # latency shapes, not just ev/s (BENCH_*.json archives these)
            "metrics": {
                "emit_batch_rows": histogram_summary(em),
                "queue_transit_ms": histogram_summary(qt, scale=1000),
                "sink_event_latency_s": histogram_summary(sk),
            },
        }
        if embed_profile:
            from arroyo_tpu.metrics import registry as _registry
            from arroyo_tpu.obs.profile import job_profile

            extra[name]["profile"] = job_profile(
                _registry.job_metrics(f"bench-{name}-jax"))
        budget = P99_BUDGET_MS.get(name)
        if budget is not None:
            # judged on the WORST rep: one blown rep is a blown budget; an
            # explicit null marks "p99 not measurable", distinct from pass
            extra[name]["p99_budget_ms"] = budget
            extra[name]["p99_worst_ms"] = worst_p99 and round(worst_p99, 2)
            extra[name]["p99_budget_ok"] = (
                None if worst_p99 is None else bool(worst_p99 <= budget))
        if name == "q7":
            q7_eps = best_eps

    # CPU baseline proxy: q7 on the numpy dict-store backend
    b_eps = 0.0
    for r in range(reps):
        gc.collect()
        wall, rows, _lat, _walls = run_config("q7", build_q7, "numpy", base_events, NP_BS)
        check_parity_q7(rows, base_events)
        print(f"# q7 numpy-baseline rep {r}: {base_events} events in {wall:.2f}s = "
              f"{base_events / wall:,.0f} ev/s", file=sys.stderr)
        b_eps = max(b_eps, base_events / wall)
    extra["q7_numpy_baseline_events_per_sec"] = round(b_eps, 1)

    extra["platform"] = platform
    extra["device_kind"] = dev.device_kind
    extra["device_count"] = len(jax.devices())
    print(json.dumps({
        # only a run on the chip may carry the per-chip name
        "metric": ("nexmark_q7_tumbling_max_events_per_sec_per_chip"
                   if platform == "tpu" else
                   f"nexmark_q7_tumbling_max_events_per_sec_{platform}"),
        "value": round(q7_eps, 1),
        "unit": "events/s",
        "vs_baseline": round(q7_eps / b_eps, 3),
        "extra": extra,
    }))

if __name__ == "__main__":
    main()
