"""Sliding (hop) window aggregate: overlap semantics, watermark-driven
emission, device vs numpy backends, checkpoint/restore."""

import numpy as np
import pytest

from arroyo_tpu.batch import TIMESTAMP_FIELD, Schema
from arroyo_tpu.engine import Engine, run_graph
from arroyo_tpu.expr import BinOp, Col, Lit
from arroyo_tpu.graph import EdgeType, Graph, Node, OpName

DUMMY = Schema.of([("x", "int64"), (TIMESTAMP_FIELD, "int64")])


def sliding_graph(rows, backend, count=1000, width=1_000_000, slide=250_000,
                  parallelism=1, agg_parallelism=1):
    g = Graph()
    g.add_node(Node("src", OpName.SOURCE, {
        "connector": "impulse", "message_count": count,
        "interval_micros": 1000, "start_time_micros": 0}, parallelism))
    g.add_node(Node("wm", OpName.WATERMARK, {"expr": Col(TIMESTAMP_FIELD)}, parallelism))
    g.add_node(Node("key", OpName.KEY,
                    {"keys": [("k", BinOp("%", Col("counter"), Lit(5)))]}, parallelism))
    g.add_node(Node("agg", OpName.SLIDING_AGGREGATE, {
        "width_micros": width,
        "slide_micros": slide,
        "key_fields": ["k"],
        "aggregates": [("cnt", "count", None), ("total", "sum", Col("counter"))],
        "input_dtype_of": lambda e: np.dtype(np.int64),
        "backend": backend,
    }, agg_parallelism))
    g.add_node(Node("sink", OpName.SINK, {"connector": "vec", "rows": rows}, 1))
    g.add_edge("src", "wm", EdgeType.FORWARD, DUMMY)
    g.add_edge("wm", "key", EdgeType.FORWARD, DUMMY)
    g.add_edge("key", "agg", EdgeType.SHUFFLE, DUMMY)
    g.add_edge("agg", "sink", EdgeType.SHUFFLE, DUMMY)
    return g


def expected_sliding(count=1000, width=1_000_000, slide=250_000, interval=1000,
                     scale=1):
    """counter c: ts=c*interval, key=c%5. Window starting at s covers
    [s, s+width). Windows emitted for any start s=j*slide with data."""
    out = {}
    for c in range(count):
        ts = c * interval
        k = c % 5
        # windows containing ts: starts s with s <= ts < s + width, s = j*slide
        j_hi = ts // slide
        j_lo = (ts - width) // slide + 1
        for j in range(j_lo, j_hi + 1):
            s = j * slide
            cnt, tot = out.get((s, k), (0, 0))
            out[(s, k)] = (cnt + scale, tot + c * scale)
    return out


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_sliding_count_sum(backend):
    rows: list = []
    g = sliding_graph(rows, backend)
    run_graph(g, job_id=f"sw-{backend}", timeout=120)
    got = {(r["window_start"], r["k"]): (r["cnt"], r["total"]) for r in rows}
    exp = expected_sliding()
    assert got == exp
    for r in rows:
        assert r["window_end"] - r["window_start"] == 1_000_000


def test_sliding_parallel():
    rows: list = []
    g = sliding_graph(rows, "numpy", count=2000, parallelism=2, agg_parallelism=2)
    run_graph(g, job_id="swp", timeout=120)
    got = {(r["window_start"], r["k"]): (r["cnt"], r["total"]) for r in rows}
    # two identical sources double every count/sum
    exp = {}
    for (s, k), (c, t) in expected_sliding(2000).items():
        exp[(s, k)] = (c * 2, t * 2)
    assert got == exp


def test_sliding_incremental_emission():
    """Windows close as the watermark passes, across many small batches."""
    from arroyo_tpu.config import update

    update({"pipeline.source-batch-size": 100})
    rows: list = []
    g = sliding_graph(rows, "numpy", count=3000, width=400_000, slide=100_000)
    run_graph(g, job_id="sw-incr", timeout=120)
    got = {(r["window_start"], r["k"]): (r["cnt"], r["total"]) for r in rows}
    assert got == expected_sliding(3000, width=400_000, slide=100_000)


def test_width_must_be_multiple_of_slide():
    from arroyo_tpu.windows.sliding import SlidingAggregate

    with pytest.raises(ValueError):
        SlidingAggregate({
            "width_micros": 1_000_000, "slide_micros": 300_000,
            "aggregates": [("cnt", "count", None)],
        })


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_sliding_checkpoint_restore(backend):
    rows1: list = []
    count, width, slide = 2000, 500_000, 125_000
    g1 = sliding_graph(rows1, backend, count=count, width=width, slide=slide)
    run_graph(g1, job_id=f"sref-{backend}", timeout=120)
    expected = {(r["window_start"], r["k"]): (r["cnt"], r["total"]) for r in rows1}

    rows2: list = []
    g2 = sliding_graph(rows2, backend, count=count, width=width, slide=slide)
    g2.nodes["src"].config["event_rate"] = 2000
    eng = Engine(g2, job_id=f"sckpt-{backend}")
    eng.start()
    assert eng.checkpoint_and_wait(1, timeout=30)
    eng.stop()
    eng.join(timeout=30)

    rows3: list = []
    g3 = sliding_graph(rows3, backend, count=count, width=width, slide=slide)
    eng3 = Engine(g3, job_id=f"sckpt-{backend}", restore_epoch=1)
    eng3.run_to_completion(timeout=120)
    merged = {}
    for r in rows2 + rows3:
        merged[(r["window_start"], r["k"])] = (r["cnt"], r["total"])
    assert merged == expected


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_sliding_mixed_key_transport_restore(backend):
    """Mixed group-by keys: the numeric column rides aggregate-store lanes,
    the string column rides the host KeyDictionary (r5 split) — both must
    survive checkpoint/restore with exact per-window results."""
    from arroyo_tpu.expr import BinOp, Case, Col, Lit

    def graph(rows, event_rate=None):
        g = Graph()
        cfg = {"connector": "impulse", "message_count": 1500,
               "interval_micros": 1000, "start_time_micros": 0}
        if event_rate:
            cfg["event_rate"] = event_rate
        g.add_node(Node("src", OpName.SOURCE, cfg, 1))
        g.add_node(Node("wm", OpName.WATERMARK, {"expr": Col(TIMESTAMP_FIELD)}, 1))
        # key: (counter % 3 as int lane, parity name as dict string)
        parity = Case(((BinOp("==", BinOp("%", Col("counter"), Lit(2)), Lit(0)),
                        Lit("even")),), Lit("odd"))
        g.add_node(Node("key", OpName.KEY, {"keys": [
            ("k", BinOp("%", Col("counter"), Lit(3))), ("p", parity)]}, 1))
        g.add_node(Node("agg", OpName.SLIDING_AGGREGATE, {
            "width_micros": 500_000, "slide_micros": 125_000,
            "key_fields": ["k", "p"],
            "aggregates": [("cnt", "count", None), ("total", "sum", Col("counter"))],
            "input_dtype_of": lambda e: np.dtype(np.int64),
            "backend": backend,
        }, 1))
        g.add_node(Node("sink", OpName.SINK, {"connector": "vec", "rows": rows}, 1))
        g.add_edge("src", "wm", EdgeType.FORWARD, DUMMY)
        g.add_edge("wm", "key", EdgeType.FORWARD, DUMMY)
        g.add_edge("key", "agg", EdgeType.SHUFFLE, DUMMY)
        g.add_edge("agg", "sink", EdgeType.FORWARD, DUMMY)
        return g

    rows1: list = []
    run_graph(graph(rows1), job_id=f"smix-{backend}", timeout=120)
    expected = {(r["window_start"], r["k"], r["p"]): (r["cnt"], r["total"])
                for r in rows1}
    assert expected, "reference run emitted nothing"
    assert {r["p"] for r in rows1} == {"even", "odd"}

    rows2: list = []
    eng = Engine(graph(rows2, event_rate=2000), job_id=f"smix-ck-{backend}")
    eng.start()
    assert eng.checkpoint_and_wait(1, timeout=30)
    eng.stop()
    eng.join(timeout=30)
    rows3: list = []
    eng3 = Engine(graph(rows3), job_id=f"smix-ck-{backend}", restore_epoch=1)
    eng3.run_to_completion(timeout=120)
    merged = {}
    for r in rows2 + rows3:
        merged[(r["window_start"], r["k"], r["p"])] = (r["cnt"], r["total"])
    assert merged == expected


def test_count_distinct_in_a_hop_window_through_the_distinct_split():
    """count(DISTINCT <integer>) [FILTER] in a hop window plans onto the
    device (sql/planner.py _plan_distinct_split): pairs in panes combine by
    adding counts. Against a plain computation over the impulse's counters."""
    from arroyo_tpu.sql import plan_query
    from arroyo_tpu.sql.lexer import SqlError

    select = """SELECT hop(interval '250 milliseconds', interval '1 second') AS w,
      counter %% 3 AS k, count(DISTINCT %s) AS d,
      count(DISTINCT counter %% 7) FILTER (WHERE counter %% 2 = 0) AS even, count(*) AS n
    FROM impulse GROUP BY w, k;"""
    ddl = """CREATE TABLE impulse (counter BIGINT UNSIGNED) WITH (
      connector = 'impulse', message_count = 2000, interval_micros = 1000,
      start_time_micros = 0);"""
    pp = plan_query(ddl + select % "counter % 7")
    ops = [n.op.value for n in pp.graph.nodes.values() if n.config.get("distinct")]
    assert ops == ["sliding_aggregate", "tumbling_aggregate"]  # pairs in panes, counts by window
    Engine(pp.graph, job_id="hop-distinct").run_to_completion(timeout=120)
    want: dict = {}
    for c in range(2000):
        ts = c * 1000
        for j in range((ts - 1_000_000) // 250_000 + 1, ts // 250_000 + 1):
            d, even, n = want.setdefault((j * 250_000, c % 3), (set(), set(), 0))
            d.add(c % 7)
            if c % 2 == 0:
                even.add(c % 7)
            want[(j * 250_000, c % 3)] = (d, even, n + 1)
    got = {(r["window_start"], r["k"]): (r["d"], r["even"], r["n"]) for r in pp.sinks[0].rows}
    assert got == {k: (len(d), len(even), n) for k, (d, even, n) in want.items()}
    assert len(got) == 3 * (2000 // 250 + 3)
    # where the split does not apply the hop window still refuses, and says why
    with pytest.raises(SqlError, match="supported in session and tumbling windows only.*"
                                       "is float64, not an integer"):
        plan_query(ddl + select % "CAST(counter AS DOUBLE)")
