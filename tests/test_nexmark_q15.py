"""NEXmark Query 15 (the bidding statistics report) end to end on the CPU at
a small size: the benchmark cell's own query text
(``benchmark/configs/nexmark-q15-bid-stats.sql``) through ``plan_query``
into the engine, its sink's rows and both first-level aggregates' output
held to a plain Python computation over the connector's own batches (dicts
and sets; no code of ``windows/``, ``ops/`` or ``operators/``): on the jax
and the numpy backend and across a checkpoint and a restore in the middle
of a window. And what the query forced: ``FILTER (WHERE ...)`` on an
aggregate call, and a windowed ``count(DISTINCT <integer>)`` planned onto
the device through the distinct split (``sql/planner.py
_plan_distinct_split``) where the planner used to force the host's lists."""

import json
import os
import string
import time

import numpy as np
import pytest
from test_nexmark_q8 import micros
from test_smoke import SMOKE, load_sql

from arroyo_tpu.batch import TIMESTAMP_FIELD

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
QUERY = os.path.join(REPO, "benchmark", "configs", "nexmark-q15-bid-stats.sql")
INTER, WIDTH = 5_000, 10_000_000   # 2,000 events a 10 s window, 1,840 of them bids
EVENTS, SEED = 12_000, 46          # six windows
# prices are uniform over 100..9,999,999: the first band holds a bid in a thousand
BANDS = (lambda p: p < 10_000, lambda p: 10_000 <= p < 1_000_000, lambda p: p >= 1_000_000)
COLUMNS = ["total_bids", "rank1_bids", "rank2_bids", "rank3_bids",
           "total_bidders", "rank1_bidders", "rank2_bidders", "rank3_bidders",
           "total_auctions", "rank1_auctions", "rank2_auctions", "rank3_auctions"]
NEXMARK = """CREATE TABLE nexmark (
  "bid" BOOLEAN, "bid.auction" BIGINT, "bid.bidder" BIGINT, "bid.price" BIGINT
) WITH (connector = 'nexmark', inter_event_micros = %d, first_event_micros = 0,
  event_rate = 0, event_count = %d, seed = %d);
""" % (INTER, EVENTS, SEED)


def q15_sql(out_path: str, rate: int = 0) -> str:
    with open(QUERY) as f:
        text = string.Template(f.read()).substitute(
            seed=SEED, sink="$sink", event_rate=rate,
            inter_event_micros=INTER, first_event_micros=0)
    text = text.replace("seed = %d" % SEED, "seed = %d,\n  event_count = %d" % (SEED, EVENTS))
    sink = "connector = 'single_file', path = '%s', format = 'json', type = 'sink'" % out_path
    assert "connector = '$sink', type = 'sink'" in text
    return text.replace("connector = '$sink', type = 'sink'", sink)


def the_bids() -> list[tuple]:
    """(window start, auction, bidder, price) of every bid, from the
    connector itself, batch by batch."""
    from arroyo_tpu.connectors.nexmark import NexmarkSource

    names = ["bid", "bid.auction", "bid.bidder", "bid.price"]
    src = NexmarkSource({"inter_event_micros": INTER, "first_event_micros": 0, "seed": SEED,
                         "columns": names})
    out = []
    for lo in range(0, EVENTS, 512):
        b = src._generate(np.arange(lo, min(lo + 512, EVENTS)))
        cols = [np.asarray(b[c]).tolist() for c in [TIMESTAMP_FIELD] + names]
        out += [(ts // WIDTH * WIDTH, a, who, p) for ts, is_bid, a, who, p in zip(*cols) if is_bid]
    return out


def oracle(bids: list[tuple]) -> tuple[dict, dict, list]:
    """-> per window {bidder: [bids, bids in each band]}, the same by
    auction, and the report's rows (window start, twelve integers)."""
    by_bidder: dict = {}
    by_auction: dict = {}
    for w, a, who, p in bids:
        for per, value in ((by_bidder.setdefault(w, {}), who), (by_auction.setdefault(w, {}), a)):
            lanes = per.setdefault(value, [0, 0, 0, 0])
            lanes[0] += 1
            for k, band in enumerate(BANDS):
                lanes[1 + k] += band(p)
    rows = []
    for w in sorted(by_bidder):
        mine = [(a, who, p) for w2, a, who, p in bids if w2 == w]
        picks = [mine] + [[b for b in mine if band(b[2])] for band in BANDS]
        rows.append((w, *[len(x) for x in picks], *[len({b[1] for b in x}) for x in picks],
                     *[len({b[0] for b in x}) for x in picks]))
    return by_bidder, by_auction, rows


@pytest.fixture(scope="module")
def the_oracle():
    return oracle(the_bids())


def tap_first_levels(engine, taps: dict) -> None:
    """Every batch an aggregate with no aggregate upstream emits, by what
    the plan keys it on."""
    if not engine.tasks:
        engine.build()
    for (nid, _sub), task in engine.tasks.items():
        node = engine.graph.nodes[nid]
        if node.op.value.endswith("_aggregate") and node.config["key_fields"]:
            collect = task.collector.collect

            def tapped(batch, *a, _collect=collect,
                       _into=taps.setdefault(tuple(node.config["key_fields"]), []), **kw):
                _into.append(batch)
                return _collect(batch, *a, **kw)

            task.collector.collect = tapped


def tapped_pairs(batches: list, key: str) -> dict:
    """window start -> {value: [n, c0, c1, c2]} as one first level emitted
    them; a window emitted again after a restore has to say the same."""
    out: dict = {}
    for b in batches:
        lanes = [np.asarray(b[f"__agg_{i}"]).tolist() for i in range(4)]
        for i, (w, v) in enumerate(zip(np.asarray(b["window_start"]).tolist(),
                                       np.asarray(b[key]).tolist())):
            row = [lane[i] for lane in lanes]
            assert out.setdefault(w, {}).setdefault(v, row) == row, (w, v)
    return out


def sink_rows(path: str) -> list:
    with open(path) as f:
        got = [json.loads(line) for line in f if line.strip()]
    return sorted((micros(r["ws"]), *[r[c] for c in COLUMNS]) for r in got)


def held_to(taps: dict, out: str, by_bidder: dict, by_auction: dict, rows: list) -> None:
    assert set(taps) == {("bid.bidder",), ("bid.auction",)}
    assert tapped_pairs(taps[("bid.bidder",)], "bid.bidder") == by_bidder
    assert tapped_pairs(taps[("bid.auction",)], "bid.auction") == by_auction
    assert sink_rows(out) == rows


# ------------------------------------------------------ against the oracle


def test_the_oracle_counts_what_the_report_is_about(the_oracle):
    by_bidder, by_auction, rows = the_oracle
    assert len(rows) == EVENTS * INTER // WIDTH == 6
    assert all(r[1] == 1840 == sum(r[2:5]) for r in rows)
    # the bands overlap in their bidders and auctions: no sum of three
    assert any(r[5] < sum(r[6:9]) for r in rows) and any(r[9] < sum(r[10:13]) for r in rows)
    assert sum(r[2] for r in rows) > 0  # some bid falls in the thin first band
    assert all(len(by_bidder[r[0]]) == r[5] and len(by_auction[r[0]]) == r[9] for r in rows)


@pytest.mark.parametrize("backend", ["jax", "numpy"])
def test_q15_end_to_end_equals_the_plain_oracle(backend, the_oracle, tmp_path):
    from arroyo_tpu import config as cfg
    from arroyo_tpu.engine import Engine
    from arroyo_tpu.metrics import registry
    from arroyo_tpu.obs import trace
    from arroyo_tpu.sql import plan_query

    cfg.update({"device.enabled": backend == "jax"})
    out = str(tmp_path / "bid_stats.json")
    job = f"q15-{backend}"
    graph = plan_query(q15_sql(out)).graph
    aggs = {nid: n for nid, n in graph.nodes.items() if n.op.value == "tumbling_aggregate"}
    # two levels a distinct column, and no accumulator forced to the host
    assert sorted((n.config["distinct"]["level"], n.config["distinct"]["column"])
                  for n in aggs.values()) == [(1, "bid.auction"), (1, "bid.bidder"),
                                              (2, "bid.auction"), (2, "bid.bidder")]
    assert all("backend" not in n.config for n in aggs.values())
    engine = Engine(graph, job_id=job)
    taps: dict = {}
    tap_first_levels(engine, taps)
    engine.run_to_completion(timeout=180)
    held_to(taps, out, *the_oracle)
    for nid in aggs:
        op = engine.tasks[(nid, 0)].operator
        store = {"jax": "SlotAggregator", "numpy": "HostAggregator"}[backend]
        assert op.backend == backend and type(op._agg).__name__ == store, nid
        assert "collect" not in op.acc_kinds
    # the pairs each first level closed: its counter, in the account marks too
    by_bidder, by_auction, _rows = the_oracle
    metrics = registry.job_metrics(job)
    for nid, n in aggs.items():
        d = n.config["distinct"]
        want = 0 if d["level"] == 2 else sum(
            len(per) for per in (by_bidder if d["column"] == "bid.bidder" else by_auction).values())
        assert metrics[nid]["arroyo_worker_distinct_pairs"] == want, nid
        marks = trace.spans("task.account", node=nid, job=job)
        assert marks[-1].args["distinct_pairs"] == want
        if d["level"] == 1:
            assert want == metrics[nid]["arroyo_worker_messages_sent"] > 500
            if backend == "jax":
                # a close says how many lanes a row of the table holds: __n, three
                # filtered counts and the key's value
                closes = trace.spans("agg.close", node=nid, job=job)
                assert closes and {c.args["lanes"] for c in closes} == {5}
            assert f'arroyo_worker_distinct_pairs{{job="{job}",operator="{nid}"' \
                in registry.prometheus_text()


def test_q15_across_a_checkpoint_and_a_restore_inside_a_window(the_oracle, tmp_path):
    from arroyo_tpu.engine import Engine
    from arroyo_tpu.obs import trace
    from arroyo_tpu.sql import plan_query

    out = str(tmp_path / "bid_stats.json")
    job = "q15-restore"
    # paced, so that the checkpoint falls inside the stream: 3 s of it
    sql = q15_sql(out, rate=4_000)
    taps: dict = {}
    first = Engine(plan_query(sql).graph, job_id=job)
    tap_first_levels(first, taps)
    first.start()
    time.sleep(1.3)
    assert first.checkpoint_and_wait(1, timeout=120).outcome == "completed"
    first.stop()
    first.join(timeout=60)
    # the barrier fell inside a window: some are out, and the snapshot holds pairs
    before = tapped_pairs(taps[("bid.bidder",)], "bid.bidder")
    assert 0 < len(before) < 6, len(before)
    snaps = trace.spans("agg.snapshot", job=job)
    assert snaps and any(s.args["rows"] > 100 for s in snaps)
    second = Engine(plan_query(sql).graph, job_id=job, restore_epoch=1)
    tap_first_levels(second, taps)
    second.run_to_completion(timeout=300)
    held_to(taps, out, *the_oracle)


@pytest.mark.mesh
def test_q15_on_a_mesh_of_four_devices(the_oracle, tmp_path):
    """``device.mesh-devices: 4`` (four CPU devices here): the split's
    aggregates are keyed aggregates, so the mesh path takes them: sharded
    tables, the same pairs and the same report."""
    import jax

    from arroyo_tpu import config as cfg
    from arroyo_tpu.engine import Engine
    from arroyo_tpu.sql import plan_query

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 virtual devices (conftest sets XLA_FLAGS)")
    cfg.update({"device.mesh-devices": 4, "device.table-capacity": 4096,
                "device.batch-capacity": 512, "device.emit-capacity": 512,
                "device.spill-capacity": 512})
    out = str(tmp_path / "bid_stats.json")
    engine = Engine(plan_query(q15_sql(out)).graph, job_id="q15-mesh4")
    taps: dict = {}
    tap_first_levels(engine, taps)
    engine.run_to_completion(timeout=300)
    held_to(taps, out, *the_oracle)
    stores = {nid: type(task.operator._agg).__name__ for (nid, _sub), task in engine.tasks.items()
              if nid.startswith("agg")}
    assert len(stores) == 4 and set(stores.values()) == {"ShardedAggregator"}, stores


# windows a seeded stream does not hold: (seconds into the window, auction, bidder, price)
ONE_BIDDER = [(1, 11, 7, 500), (2, 12, 7, 50_000), (3, 11, 7, 2_000_000), (4, 13, 7, 9_999),
              (5, 11, 7, 1_000_000)]
NO_BID_IN_THE_FIRST_RANK = [(1, 21, 1, 10_000), (2, 21, 2, 999_999), (3, 22, 1, 1_000_000),
                            (4, 23, 2, 5_000_000), (5, 21, 2, 10_000)]
EVERY_BID_IN_THE_THIRD_RANK = [(1, 31, 3, 1_000_000), (2, 31, 4, 9_999_999), (3, 32, 3, 7_000_000)]


def test_windows_with_one_bidder_with_an_empty_rank_and_with_one_rank_alone(tmp_path):
    """The report's select list over hand-made bids, through the split: a
    window whose bids are one bidder's, one with no bid under 10,000, one
    whose bids are all of a million and more."""
    windows = [ONE_BIDDER, NO_BID_IN_THE_FIRST_RANK, EVERY_BID_IN_THE_THIRD_RANK]
    path = str(tmp_path / "bids.json")
    with open(path, "w") as f:
        for w, bids in enumerate(windows):
            for s, a, who, p in bids:
                f.write(json.dumps({"timestamp": f"2023-10-09T17:00:{10 * w + s:02d}+00:00",
                                    "auction": a, "bidder": who, "price": p}) + "\n")
    with open(QUERY) as f:
        select = f.read().split("FROM (")[1].split("FROM nexmark")[0]
    for column in ("auction", "bidder", "price"):
        select = select.replace(f'"bid.{column}"', column)
    ddl = f"""CREATE TABLE bids (timestamp TIMESTAMP, auction BIGINT, bidder BIGINT, price BIGINT)
      WITH (connector = 'single_file', path = '{path}', format = 'json', type = 'source',
            event_time_field = 'timestamp');"""
    plan, rows = preview_rows(ddl + select + "FROM bids GROUP BY window;", "q15-by-hand")
    assert sum(1 for n in plan.graph.nodes.values()
               if (n.config.get("distinct") or {}).get("level") == 1) == 2
    want = []
    for bids in windows:
        picks = [bids] + [[b for b in bids if band(b[3])] for band in BANDS]
        want.append((*[len(x) for x in picks], *[len({b[2] for b in x}) for x in picks],
                     *[len({b[1] for b in x}) for x in picks]))
    assert want[0][4:8] == (1, 1, 1, 1) and want[1][1::4] == (0, 0, 0)
    assert want[2][0::4] == want[2][3::4] and want[2][1:3] == (0, 0)
    got = sorted((r["window_start"], *[r[c] for c in COLUMNS]) for r in rows)
    assert [g[1:] for g in got] == want


def test_explain_shows_each_split_and_the_waits_line_its_pairs():
    from arroyo_tpu.obs.profile import _annotations, render_explain
    from arroyo_tpu.sql.planner import executed_graph_view

    nodes, edges = executed_graph_view(q15_sql("/dev/null"))
    text = render_explain(nodes, edges, {})
    lines = [line.strip() for line in text.splitlines() if line.strip().startswith("distinct:")]
    firsts = [n for n in nodes if n.get("distinct") and n["distinct"][0]["level"] == 1]
    assert len(firsts) == 2 and len(lines) == 5
    # the plan's own line first, then each level under its node
    assert lines[0] == ("distinct: split on bid.bidder, bid.auction; "
                        "2 keyed aggregates on the device")
    for n in firsts:
        d = n["distinct"][0]
        assert (f"distinct: {d['column']}  lanes __n __c0 __c1 __c2  pairs {n['id']}  "
                f"counts {d['counts']}") in lines
        assert f"distinct: {d['column']}  counts the pairs of {n['id']}" in lines
        assert "_distinct_l1" in n["id"] and d["column"] in n["description"]
    waits = next(a for a in _annotations({"arroyo_worker_distinct_pairs": 25_123})
                 if a.startswith("waits: "))
    assert "distinct pairs closed 25,123" in waits


@pytest.mark.parametrize("select, why", [
    ('SELECT session(interval \'5 seconds\') AS w, count(DISTINCT "bid.bidder") AS d '
     'FROM nexmark WHERE "bid" GROUP BY w', "a session window's state is the host's"),
    ('SELECT "bid.auction" % 5 AS k, count(DISTINCT "bid.bidder") AS d '
     'FROM nexmark WHERE "bid" GROUP BY "bid.auction" % 5',
     "an updating aggregate keeps each value's multiplicity in a host map"),
    ('SELECT tumble(interval \'10 seconds\') AS w, count(DISTINCT "bid.bidder") AS d, '
     'array_agg("bid.price") AS prices FROM nexmark WHERE "bid" GROUP BY w',
     "array_agg() beside it keeps its values in host lists"),
])
def test_explain_says_why_a_count_distinct_stays_on_the_host(select, why):
    from arroyo_tpu.obs.profile import render_explain
    from arroyo_tpu.sql.planner import executed_graph_view

    text = render_explain(*executed_graph_view(NEXMARK + select + ";"), {})
    lines = [line.strip() for line in text.splitlines() if line.strip().startswith("distinct:")]
    assert lines == [f"distinct: collected on the host ({why})"]


# ------------------------------------------- the split against the host path


CARS = """SELECT tumble(interval '20 seconds') AS w, event_type AS et,
  count(DISTINCT %s) AS drivers, count(*) AS events, max(driver_id) AS top,
  avg(driver_id) AS mean, count(DISTINCT driver_id %% 3) AS thirds
FROM cars GROUP BY w, et HAVING count(*) > 1"""


def cars_sql(distinct: str, tmp_path) -> str:
    head = load_sql("count_distinct", str(tmp_path / "unused.json")).split("CREATE TABLE distinct_output")[0]
    return head + CARS % distinct + ";"


def preview_rows(sql: str, job: str) -> tuple:
    from arroyo_tpu.engine import Engine
    from arroyo_tpu.sql import plan_query

    pp = plan_query(sql)
    Engine(pp.graph, job_id=job).run_to_completion(timeout=120)
    return pp, pp.sinks[0].rows


def test_the_split_says_what_the_host_path_says_on_the_same_input(tmp_path):
    """A TEXT group key beside an integer distinct column, and plain
    aggregates riding the chain: the split's rows are the host lists'."""
    split_plan, split = preview_rows(cars_sql("driver_id", tmp_path), "cars-split")
    host_plan, host = preview_rows(cars_sql("CAST(driver_id AS DOUBLE)", tmp_path), "cars-host")
    roles = [n.config.get("distinct") for n in split_plan.graph.nodes.values()
             if n.op.value == "tumbling_aggregate"]
    # a chain a distinct column, joined on the window and the TEXT key
    assert [r["level"] for r in roles] == [1, 2, 1, 2]
    assert [r["column"] for r in roles] == ["driver_id"] * 2 + ["an expression"] * 2
    assert any(n.op.value == "instant_join" for n in split_plan.graph.nodes.values())
    assert roles[0]["lanes"] == ["__n", "__p1", "__p2"]  # max, avg's sum; its count is __n
    host_agg = [n for n in host_plan.graph.nodes.values() if n.op.value == "tumbling_aggregate"]
    assert len(host_agg) == 1 and host_agg[0].config["backend"] == "numpy"
    assert host_agg[0].config["distinct"] == {
        "host": "an expression is float64, not an integer"}

    def key(rows):
        return sorted((r["window_start"], r["et"], r["drivers"], r["events"], r["top"],
                       round(r["mean"], 9), r["thirds"]) for r in rows)

    assert key(split) == key(host) and len(split) > 4
    assert any(r["thirds"] < r["drivers"] < r["events"] for r in split)


def test_the_smoke_query_plans_the_split_and_keeps_its_golden(tmp_path):
    from test_smoke import canon

    from arroyo_tpu.engine import Engine
    from arroyo_tpu.sql import plan_query

    out = str(tmp_path / "out.json")
    graph = plan_query(load_sql("count_distinct", out)).graph
    firsts = [n for n in graph.nodes.values() if (n.config.get("distinct") or {}).get("level") == 1]
    assert [n.config["key_fields"] for n in firsts] == [["event_type", "driver_id"]]
    Engine(graph, job_id="smoke-distinct").run_to_completion(timeout=120)
    with open(out) as f, open(os.path.join(SMOKE, "golden", "count_distinct.json")) as g:
        assert sorted(canon(json.loads(x)) for x in f if x.strip()) \
            == sorted(canon(json.loads(x)) for x in g if x.strip())


LEFT_JOIN = """SELECT p.window AS w, p.driver_id % 2 AS odd, count(*) AS pairs,
  count(DISTINCT d.dropoffs) AS kinds
FROM (
  SELECT tumble(interval '20 seconds') AS window, driver_id, count(*) AS pickups
  FROM cars WHERE event_type = 'pickup' GROUP BY window, driver_id
) p
LEFT JOIN (
  SELECT tumble(interval '20 seconds') AS window, driver_id, count(*) AS dropoffs
  FROM cars WHERE event_type = 'dropoff' AND driver_id % 3 = 0 GROUP BY window, driver_id
) d
ON p.driver_id = d.driver_id AND p.window = d.window
GROUP BY p.window, p.driver_id % 2"""


def test_a_null_counts_toward_count_star_and_not_toward_count_distinct(tmp_path):
    """The padded side of a left join makes the integer NULL: the planner
    keeps the host's lists, says so, and no NULL is a value."""
    head = load_sql("count_distinct", "unused").split("CREATE TABLE distinct_output")[0]
    plan, rows = preview_rows(head + LEFT_JOIN + ";", "cars-null")
    agg = [n for n in plan.graph.nodes.values() if n.config.get("distinct")]
    assert [n.config["distinct"] for n in agg] == [
        {"host": "a column may be NULL behind an outer join"}]
    # the oracle, from the file
    with open(os.path.join(SMOKE, "inputs", "cars.json")) as f:
        cars = [json.loads(line) for line in f if line.strip()]
    picks: dict = {}
    drops: dict = {}
    for c in cars:
        w = micros(c["timestamp"]) // 20_000_000 * 20_000_000
        if c["event_type"] == "pickup":
            picks.setdefault(w, {}).setdefault(c["driver_id"], 0)
        elif c["event_type"] == "dropoff" and c["driver_id"] % 3 == 0:
            per = drops.setdefault(w, {})
            per[c["driver_id"]] = per.get(c["driver_id"], 0) + 1
    want = sorted(
        (w, odd, len(mine), len({drops.get(w, {}).get(d) for d in mine} - {None}))
        for w, per in picks.items() for odd in (0, 1)
        for mine in [[d for d in per if d % 2 == odd]] if mine)
    assert sorted((r["window_start"], r["odd"], r["pairs"], r["kinds"]) for r in rows) == want
    assert any(pairs > kinds > 0 for _w, _odd, pairs, kinds in want)  # NULLs there were


# ----------------------------------------------------- FILTER (WHERE ...)


P = '"bid.price" >= 5000000'
FILTERED = {
    "count": ("count(*) FILTER (WHERE %s)" % P, "sum(CASE WHEN %s THEN 1 ELSE 0 END)" % P),
    "count_arg": ('count("bid.price") FILTER (WHERE %s)' % P,
                  "sum(CASE WHEN %s THEN 1 ELSE 0 END)" % P),
    "sum": ('sum("bid.price") FILTER (WHERE %s)' % P,
            'sum(CASE WHEN %s THEN "bid.price" ELSE 0 END)' % P),
    "min": ('min("bid.price") FILTER (WHERE %s)' % P,
            'min(CASE WHEN %s THEN "bid.price" ELSE 9223372036854775807 END)' % P),
    "max": ('max("bid.price") FILTER (WHERE %s)' % P,
            'max(CASE WHEN %s THEN "bid.price" ELSE 0 END)' % P),
    "avg": ('avg("bid.price") FILTER (WHERE %s)' % P,
            'CAST(sum(CASE WHEN %s THEN "bid.price" ELSE 0 END) AS DOUBLE)'
            ' / sum(CASE WHEN %s THEN 1 ELSE 0 END)' % (P, P)),
    "count_distinct": ('count(DISTINCT "bid.bidder") FILTER (WHERE %s)' % P,
                       'count(DISTINCT CASE WHEN %s THEN "bid.bidder" ELSE NULL END)' % P),
}


@pytest.mark.parametrize("window", ["tumble(interval '10 seconds')", "no window"])
@pytest.mark.parametrize("kind", sorted(FILTERED))
def test_filter_on_an_aggregate_is_its_case_form(kind, window):
    """In a tumbling window (the split, for count(DISTINCT)) and in the
    updating aggregate, grouped by a key every value of which has rows the
    filter passes and rows it drops."""
    filtered, case = FILTERED[kind]
    group = 'GROUP BY "bid.auction" % 5' + ("" if window == "no window" else ", w")
    head = "" if window == "no window" else f"{window} AS w, "
    sql = (NEXMARK + f'SELECT {head}"bid.auction" % 5 AS k, {filtered} AS a, {case} AS b, '
           f'count(*) AS n FROM nexmark WHERE "bid" {group};')
    _plan, rows = preview_rows(sql, f"filter-{kind}-{window[:2]}")
    if window == "no window":
        # the updating aggregate: the last word on each key
        rows = list({r["k"]: r for r in rows if not r.get("_is_retract")}.values())
    assert len(rows) == (5 if window == "no window" else 30)
    for r in rows:
        assert r["a"] == pytest.approx(r["b"]) and 0 < r["a"], r
        assert kind not in ("count", "count_arg") or r["a"] < r["n"]


@pytest.mark.parametrize("sql, says", [
    ('SELECT count(*) FILTER ("bid.price" > 1) FROM nexmark', "expected WHERE"),
    ('SELECT count(*) FILTER (WHERE "bid.price" > 1 FROM nexmark', "expected"),
    ('SELECT lower(\'x\') FILTER (WHERE "bid") FROM nexmark', "lower(): it is not an aggregate"),
    ('SELECT array_agg("bid.price") FILTER (WHERE "bid") AS a, tumble(interval \'1 second\') AS w '
     'FROM nexmark GROUP BY w', "FILTER (WHERE ...) on array_agg() is unsupported"),
    ('SELECT q15_spread("bid.price") FILTER (WHERE "bid") AS a, tumble(interval \'1 second\') AS w '
     'FROM nexmark GROUP BY w', "FILTER (WHERE ...) on q15_spread() is unsupported"),
    ('SELECT count(*) FILTER (WHERE "bid") OVER (PARTITION BY "bid.auction") FROM nexmark',
     "FILTER (WHERE ...) on the window function count() OVER (...) is unsupported"),
    ('SELECT min("bid") FILTER (WHERE "bid") AS a, tumble(interval \'1 second\') AS w '
     'FROM nexmark GROUP BY w', "min() FILTER (WHERE ...) over a bool column is unsupported"),
])
def test_filter_where_it_is_refused(sql, says):
    from arroyo_tpu.sql import plan_query
    from arroyo_tpu.sql.lexer import SqlError
    from arroyo_tpu.udf import register_udaf

    register_udaf("q15_spread", lambda v: float(np.max(v) - np.min(v)))
    with pytest.raises(SqlError) as e:
        plan_query(NEXMARK + sql + ";")
    assert says in str(e.value)


def test_a_filter_parses_into_the_call_and_is_walked():
    from arroyo_tpu.sql.ast import FuncCall, Ident
    from arroyo_tpu.sql.compile import find_aggregates, replace_nodes, walk
    from arroyo_tpu.sql.parser import parse_statements

    (stmt,) = parse_statements("SELECT 1 + count(DISTINCT a) FILTER (WHERE b > 2) AS n FROM t")
    (call,) = find_aggregates(stmt.query.items[0].expr)
    assert call.distinct and call.filter is not None and call.args == (Ident("a"),)
    assert Ident("b") in list(walk(call))
    swapped = replace_nodes(call, [(Ident("b"), Ident("c"))])
    assert isinstance(swapped, FuncCall) and Ident("c") in list(walk(swapped.filter))
    # and a column may still be called filter
    (plain,) = parse_statements("SELECT count(*) filter FROM t")
    assert plain.query.items[0].alias == "filter"


# ------------------------------------------- the manifest (tier-1's copy of
# benchmark/tests/test_q15_readers_on_a_program_without_them.py's two)


def _manifest() -> dict:
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def test_every_per_layer_metric_of_the_benchmark_lists_its_cells():
    """A metric with no ``workloads`` list is asked of every cell that
    reports what it moves, the parent's program under this benchmark
    included: none stands without one."""
    m = _manifest()
    cells = {w["name"] for w in m["workloads"]}
    for x in m["per_layer"]:
        assert isinstance(x.get("workloads"), list) and x["workloads"], x["name"]
        assert set(x["workloads"]) <= cells, x["name"]


def test_the_distinct_pairs_reader_is_asked_of_q15_sat_alone():
    new = [x for x in _manifest()["per_layer"] if x["name"] == "distinct_pairs_per_event"]
    assert len(new) == 1 and new[0]["workloads"] == ["q15-sat"]
    assert new[0]["moves"] == "events_per_s" and new[0]["source"] == "program_counter"
