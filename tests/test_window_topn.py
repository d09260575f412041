"""Window top-N: a bound on ``row_number()`` in the SELECT above a window
function is handed down by the planner as the operator's ``limit``, and the
operator then selects each partition's first N rows without ordering the
rest. The plan (which forms of the bound push it, what keeps whole
partitions, ``explain``'s line either way) and the operator (the limited
output equal bit for bit to the whole-partition path followed by the filter;
its state across a checkpoint and a restore; the late barrier; the
``wf.rank`` span and the two counters).

The five tests of ``tests/test_window_fn.py`` build the operator with no
limit and keep the whole-partition path; the smoke goldens of
``window_function.sql`` (``row_num <= 2``) and ``most_active_driver.sql``
(``rn = 1``) now take the limited path, with their goldens unchanged."""

import os

import numpy as np
import pytest

from arroyo_tpu.batch import TIMESTAMP_FIELD, Batch
from arroyo_tpu.expr import Col
from arroyo_tpu.graph import OpName
from arroyo_tpu.metrics import TaskMetrics
from arroyo_tpu.obs import trace
from arroyo_tpu.obs.profile import render_explain
from arroyo_tpu.operators.base import OperatorContext
from arroyo_tpu.operators.window_fn import WindowFunctionOperator
from arroyo_tpu.sql import plan_query
from arroyo_tpu.sql.planner import executed_graph_view
from arroyo_tpu.state.tables import TableManager
from arroyo_tpu.types import TaskInfo, Watermark

# ------------------------------------------------------------------ the plan

TABLES = """
CREATE TABLE nexmark ("bid" BOOLEAN, "bid.auction" BIGINT)
WITH (connector = 'nexmark', event_count = 1000, inter_event_micros = 100,
      first_event_micros = 0, seed = 1);
"""
COUNTS = """SELECT "bid.auction" AS auction, count(*) AS num,
  hop(interval '2 seconds', interval '60 seconds') AS window
  FROM nexmark WHERE "bid" GROUP BY "bid.auction", window"""
RANKED = ("SELECT *, {over} FROM (" + COUNTS + ")")
ROW_NUMBER = ("row_number() OVER (PARTITION BY window ORDER BY num DESC, auction ASC)")


def window_fn_of(select: str) -> dict:
    graph = plan_query(TABLES + select + ";").graph
    nodes = [n for n in graph.nodes.values() if n.op == OpName.WINDOW_FUNCTION]
    assert len(nodes) == 1
    return nodes[0].config


def top(where: str, over: str = ROW_NUMBER + " AS rn") -> dict:
    return window_fn_of(f"SELECT * FROM ({RANKED.format(over=over)}) WHERE {where}")


@pytest.mark.parametrize("where, limit", [
    ("rn <= 5", 5), ("rn < 6", 5), ("5 >= rn", 5), ("6 > rn", 5), ("rn = 1", 1), ("1 = rn", 1),
    ("rn <= 5 AND auction > 1000", 5), ("num > 2 AND rn < 4 AND auction > 1000", 3),
    ("rn <= 5 AND rn <= 3", 3), ("rn < 2 AND rn <= 7", 1),
])
def test_a_bound_on_the_row_number_becomes_the_operators_limit(where, limit):
    cfg = top(where)
    assert cfg["limit"] == limit and "whole" not in cfg["plan"]
    assert [kind for _n, kind, _e in cfg["functions"]] == ["row_number"]
    assert WindowFunctionOperator(cfg).limit == limit


def test_the_bound_passes_through_projections_that_carry_the_column_unchanged():
    inner = RANKED.format(over=ROW_NUMBER + " AS rn")
    cfg = window_fn_of(f"SELECT auction, place FROM (SELECT auction, rn AS place, num "
                       f"FROM ({inner}) WHERE num > 1) WHERE place <= 4")
    assert cfg["limit"] == 4
    # each level may bound it: the least holds
    cfg = window_fn_of(f"SELECT auction FROM (SELECT auction, rn FROM ({inner}) "
                       f"WHERE rn <= 3) WHERE rn <= 9")
    assert cfg["limit"] == 3


@pytest.mark.parametrize("where, over, why", [
    ("rn <= 5", "rank() OVER (PARTITION BY window ORDER BY num DESC) AS rn", "rank()"),
    ("rn <= 5", "dense_rank() OVER (PARTITION BY window ORDER BY num DESC) AS rn", "dense_rank()"),
    ("rn <= 5", ROW_NUMBER + " AS rn, sum(num) OVER (PARTITION BY window ORDER BY num DESC, "
                "auction ASC) AS total", "sum()"),
    ("rn <= 5", ROW_NUMBER + " AS rn, rank() OVER (PARTITION BY window ORDER BY num DESC, "
                "auction ASC) AS rk", "rank()"),
    ("rn <= 5 OR auction = 1000", ROW_NUMBER + " AS rn", "no bound"),
    ("rn <= num", ROW_NUMBER + " AS rn", "no bound"),
    ("rn <= 5", ROW_NUMBER + " + 0 AS rn", "no bound"),
    ("rn <= 0", ROW_NUMBER + " AS rn", "no bound"),
    ("rn < 1", ROW_NUMBER + " AS rn", "no bound"),
    ("rn = 2", ROW_NUMBER + " AS rn", "no bound"),
    ("rn >= 5", ROW_NUMBER + " AS rn", "no bound"),
    ("rn <= 5.0", ROW_NUMBER + " AS rn", "no bound"),
    ("NOT rn <= 5", ROW_NUMBER + " AS rn", "no bound"),
    ("num <= 5", ROW_NUMBER + " AS rn", "no bound"),
])
def test_what_keeps_whole_partitions(where, over, why):
    cfg = top(where, over)
    assert not cfg.get("limit") and why in cfg["plan"]["whole"]
    assert WindowFunctionOperator(cfg).limit == 0


def test_a_column_renamed_through_an_expression_does_not_push_the_bound():
    inner = RANKED.format(over=ROW_NUMBER + " AS rn")
    cfg = window_fn_of(f"SELECT auction FROM (SELECT auction, rn + 0 AS rn FROM ({inner})) "
                       f"WHERE rn <= 5")
    assert not cfg.get("limit")


def test_no_bound_reaches_through_a_join_or_a_second_window_function():
    inner = RANKED.format(over=ROW_NUMBER + " AS rn")
    kept = f"SELECT auction, window, {ROW_NUMBER} AS rn FROM ({COUNTS})"
    cfg = window_fn_of(
        f"SELECT a.auction FROM ({kept}) a JOIN ({COUNTS}) b "
        f"ON a.window = b.window AND a.auction = b.auction WHERE a.rn <= 5")
    assert not cfg.get("limit")
    graph = plan_query(
        TABLES + f"SELECT * FROM (SELECT *, rank() OVER (PARTITION BY window_start "
                 f"ORDER BY auction) AS rk FROM ({inner})) WHERE rn <= 5;").graph
    assert [n.config.get("limit") for n in graph.nodes.values()
            if n.op == OpName.WINDOW_FUNCTION] == [None, None]


def test_explain_says_which_plan_it_took():
    sql = TABLES + f"SELECT * FROM ({RANKED.format(over=ROW_NUMBER + ' AS rn')}) WHERE rn <= 5;"
    text = render_explain(*executed_graph_view(sql), {})
    assert ("top-n: row_number <= 5 per (window_start), ordered by num desc, auction asc"
            in text), text
    sql = TABLES + f"SELECT * FROM ({RANKED.format(over=ROW_NUMBER + ' AS rn')});"
    text = render_explain(*executed_graph_view(sql), {})
    assert ("window function: whole partitions (no bound on its row_number in the "
            "SELECT above)") in text, text
    sql = TABLES + ("SELECT * FROM (" + RANKED.format(
        over="rank() OVER (PARTITION BY window ORDER BY num DESC) AS rn") + ") WHERE rn <= 5;")
    text = render_explain(*executed_graph_view(sql), {})
    assert "window function: whole partitions (rank() beside it needs them)" in text, text


def test_the_benchmarks_own_query_plans_a_top_five():
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "..", "benchmark", "configs", "nexmark-top5-minute.sql")) as f:
        sql = f.read()
    for name, value in (("inter_event_micros", "100"), ("first_event_micros", "0"),
                        ("event_rate", "0"), ("seed", "1"), ("sink", "vec")):
        sql = sql.replace("$" + name, value)
    graph = plan_query(sql).graph
    wf = next(n for n in graph.nodes.values() if n.op == OpName.WINDOW_FUNCTION)
    assert wf.config["limit"] == 5 and wf.config["partition_fields"] == ["window_start"]
    assert wf.config["plan"]["order"] == ["num desc", "auction asc"]
    # one class, one registration: the operator the plan names is the one there was
    assert sum(1 for n in graph.nodes.values() if "window" in n.op.value
               and n.op != OpName.SLIDING_AGGREGATE) == 1


def test_a_limit_beside_a_function_that_needs_whole_partitions_is_refused():
    with pytest.raises(ValueError, match="row_number alone"):
        WindowFunctionOperator({"functions": [("rn", "row_number", None), ("rk", "rank", None)],
                                "limit": 3})


# -------------------------------------------------------------- the operator


class Collected:
    def __init__(self):
        self.batches = []

    def collect(self, b):
        self.batches.append(b)

    def broadcast(self, s):
        pass


def context(storage="/tmp/wf-topn-unused"):
    ti = TaskInfo("j", "wf", "window_function", 0, 1)
    return OperatorContext(ti, None, TableManager(ti, storage))


def run_bucket(cfg: dict, batches: list[Batch]) -> list[Batch]:
    op, ctx, out = WindowFunctionOperator(cfg), context(), Collected()
    for b in batches:
        op.process_batch(b, ctx, out)
    op.handle_watermark(Watermark.event_time(10**9), ctx, out)
    return out.batches


def same_bits(got: Batch, want: Batch) -> None:
    assert list(got.columns) == list(want.columns)
    for name, col in want.columns.items():
        mine = got[name]
        assert mine.dtype == col.dtype and len(mine) == len(col), name
        if col.dtype == object:
            assert mine.tolist() == col.tolist(), name
        else:
            assert mine.tobytes() == col.tobytes(), name


def column(kind: str, rng, n: int, spread: int) -> np.ndarray:
    """``n`` values of dtype ``kind`` out of about ``spread`` distinct ones."""
    draw = rng.integers(0, spread, n)
    if kind == "int64":
        return (draw - spread // 2).astype(np.int64) * 3
    if kind == "int64-ends":
        ends = np.array([np.iinfo(np.int64).min, np.iinfo(np.int64).max, -1, 0, 1], np.int64)
        return ends[draw % len(ends)]
    if kind == "int32":
        return (draw - spread // 2).astype(np.int32)
    if kind == "uint64":
        return draw.astype(np.uint64) * np.uint64(2**61)  # wraps past 2**63: the high half
    if kind == "uint8":
        return (draw % 256).astype(np.uint8)
    if kind == "float64":
        vals = (draw / 7.0 - 2.0).astype(np.float64)
        vals[draw % 11 == 0] = np.nan
        vals[draw % 13 == 0] = -0.0
        return vals
    if kind == "float32":
        return (draw / 3.0).astype(np.float32)
    if kind == "bool":
        return draw % 2 == 0
    if kind == "str":
        words = np.array([None] + [f"w{i:03d}" for i in range(spread)], dtype=object)
        return words[draw % len(words)]
    raise AssertionError(kind)


KINDS = ["int64", "int64-ends", "int32", "uint64", "uint8", "float64", "float32", "bool", "str"]


@pytest.mark.parametrize("partitions", [1, 7], ids=["one-partition", "many-partitions"])
@pytest.mark.parametrize("asc", [False, True], ids=["desc", "asc"])
@pytest.mark.parametrize("kind", KINDS)
def test_limited_equals_whole_partitions_then_the_filter(kind, asc, partitions):
    """Seeded random buckets: a leading ORDER BY key of every dtype
    ``_sortable`` handles, a second key in the other direction, few distinct
    values (heavy ties at the N-th place) and many, N under, at and over a
    partition's rows, the bucket handed over in three batches."""
    for seed, n, spread, limit in [(1, 400, 4, 5), (2, 400, 10_000, 5), (3, 90, 30, 1),
                                   (4, 60, 6, 70), (5, 5, 3, 5), (6, 6, 2, 5), (7, 2000, 50, 17)]:
        rng = np.random.default_rng([seed, partitions, asc])
        cols = {
            TIMESTAMP_FIELD: np.full(n, 500, dtype=np.int64),
            "g": rng.integers(0, partitions, n).astype(np.int64) * 1_000_003,
            "v": column(kind, rng, n, spread),
            "w": rng.integers(0, 9, n).astype(np.int64),
            "payload": np.arange(n, dtype=np.int64)[::-1].copy(),
        }
        whole = Batch(cols)
        parts = [whole.slice(0, n // 3), whole.slice(n // 3, n // 2), whole.slice(n // 2, n)]
        cfg = {"partition_fields": ["g"], "functions": [("rn", "row_number", None)],
               "order_by": [(Col("v"), asc), (Col("w"), not asc)]}
        unlimited = run_bucket(cfg, parts)
        limited = run_bucket(dict(cfg, limit=limit), parts)
        assert len(unlimited) == len(limited) == 1
        want = unlimited[0].filter(unlimited[0]["rn"] <= limit)
        same_bits(limited[0], want)
        assert limited[0].num_rows <= limit * partitions
        assert limited[0]["rn"].max() <= limit


@pytest.mark.parametrize("partition_fields, order_by", [
    ([], [(Col("v"), False)]),                   # no PARTITION BY: the bucket is the partition
    (["g"], []),                                 # no ORDER BY: the rows' own order
    (["g", "h"], [(Col("v"), True)]),            # two partition columns
    (["s"], [(Col("v"), False), (Col("w"), True)]),  # a string partition column
])
def test_limited_equals_whole_partitions_whatever_the_specification(partition_fields, order_by):
    rng = np.random.default_rng(11)
    n = 300
    for groups in (1, 5):
        g = rng.integers(0, groups, n).astype(np.int64)
        b = Batch({TIMESTAMP_FIELD: np.full(n, 7, dtype=np.int64), "g": g, "h": g % 2,
                   "s": np.array([f"p{x}" for x in g], dtype=object),
                   "v": rng.integers(0, 12, n).astype(np.int64),
                   "w": rng.integers(0, 5, n).astype(np.int64)})
        cfg = {"partition_fields": partition_fields, "order_by": order_by,
               "functions": [("rn", "row_number", None), ("again", "row_number", None)]}
        (whole,), (cut,) = run_bucket(cfg, [b]), run_bucket(dict(cfg, limit=4), [b])
        same_bits(cut, whole.filter(whole["rn"] <= 4))
        assert cut["rn"].tolist() == cut["again"].tolist()


def test_buckets_rank_apart_and_a_batch_of_two_windows_is_split():
    cfg = {"partition_fields": ["g"], "order_by": [(Col("v"), False)],
           "functions": [("rn", "row_number", None)], "limit": 2}
    b = Batch({TIMESTAMP_FIELD: np.array([100, 200, 100, 200, 100, 200], dtype=np.int64),
               "g": np.zeros(6, dtype=np.int64), "v": np.array([5, 1, 9, 2, 7, 3], dtype=np.int64)})
    first, second = run_bucket(cfg, [b])
    assert (first["v"].tolist(), first["rn"].tolist()) == ([9, 7], [1, 2])
    assert (second["v"].tolist(), second["rn"].tolist()) == ([3, 2], [1, 2])
    assert first[TIMESTAMP_FIELD].tolist() == [100, 100]


def bucket(ts: int, vs: list[int]) -> Batch:
    n = len(vs)
    return Batch({TIMESTAMP_FIELD: np.full(n, ts, dtype=np.int64),
                  "window_start": np.full(n, ts, dtype=np.int64),
                  "window_end": np.full(n, ts + 60, dtype=np.int64),
                  "v": np.array(vs, dtype=np.int64)})


TOP2 = {"partition_fields": ["window_start"], "order_by": [(Col("v"), False)],
        "functions": [("rn", "row_number", None)], "limit": 2}


def test_a_checkpoint_and_a_restore_with_a_bucket_half_buffered(tmp_path):
    """The state is what it was: the buffered rows in ``input``, the late
    barrier in ``e``; a limit changes neither table nor a byte of them."""
    ti = TaskInfo("j", "wf", "window_function", 0, 1)

    def snapshot(cfg: dict, storage: str):
        tm = TableManager(ti, storage)
        ctx, out = OperatorContext(ti, None, tm), Collected()
        op = WindowFunctionOperator(cfg)
        op.process_batch(bucket(100, [4, 8, 1]), ctx, out)
        op.handle_watermark(Watermark.event_time(150), ctx, out)   # closes 100
        op.process_batch(bucket(200, [5, 9]), ctx, out)            # half of 200
        op.handle_checkpoint(None, ctx, out)
        tm.checkpoint(1, None)
        return out

    whole = {k: v for k, v in TOP2.items() if k != "limit"}
    first = snapshot(TOP2, str(tmp_path / "limited"))
    snapshot(whole, str(tmp_path / "whole"))
    assert [b["v"].tolist() for b in first.batches] == [[8, 4]]

    def files(root: str) -> dict:
        out = {}
        for d, _dirs, names in os.walk(root):
            for name in names:
                with open(os.path.join(d, name), "rb") as f:
                    out[os.path.relpath(os.path.join(d, name), root)] = f.read()
        return out

    limited_files, whole_files = files(str(tmp_path / "limited")), files(str(tmp_path / "whole"))
    assert limited_files and limited_files == whole_files

    op = WindowFunctionOperator(TOP2)
    assert [(t.name, t.kind) for t in op.tables()] == [
        ("input", "expiring_time_key"), ("e", "global_keyed")]
    tm = TableManager(ti, str(tmp_path / "limited"))
    tm.restore(1, op.tables())
    ctx, out = OperatorContext(ti, None, tm), Collected()
    op.on_start(ctx)
    assert op.emitted_before == 150 and list(op.buf) == [200]
    op.process_batch(bucket(200, [7, 2]), ctx, out)                # the other half
    op.process_batch(bucket(100, [99]), ctx, out)                  # behind the barrier
    assert op.late_rows == 1
    op.handle_watermark(Watermark.event_time(250), ctx, out)
    assert [(b["v"].tolist(), b["rn"].tolist()) for b in out.batches] == [([9, 7], [1, 2])]


def test_the_late_barrier_drops_what_a_closed_bucket_would_have_held():
    op, ctx, out = WindowFunctionOperator(TOP2), context(), Collected()
    op.process_batch(bucket(100, [3, 6, 9]), ctx, out)
    op.handle_watermark(Watermark.event_time(101), ctx, out)
    late_and_not = Batch.concat([bucket(100, [50, 60]), bucket(300, [1, 2, 3])])
    op.process_batch(late_and_not, ctx, out)
    op.process_batch(bucket(50, [7]), ctx, out)
    assert op.late_rows == 3
    op.handle_watermark(Watermark.idle(), ctx, out)                # moves nothing
    assert len(out.batches) == 1
    op.on_close(ctx, out)
    assert [b["v"].tolist() for b in out.batches] == [[9, 6], [3, 2]]


@pytest.mark.parametrize("limit", [0, 2])
def test_the_span_and_the_counters_say_what_went_in_and_out(limit):
    job = f"wf-rank-{limit}"
    metrics = TaskMetrics(job, "wf", 0)
    trace.bind(job, "wf", 0, metrics)
    try:
        cfg = dict(TOP2, limit=limit)
        op, ctx, out = WindowFunctionOperator(cfg), context(), Collected()
        op.process_batch(bucket(100, [3, 6, 9, 1]), ctx, out)
        op.process_batch(bucket(200, [5]), ctx, out)
        op.handle_watermark(Watermark.event_time(1000), ctx, out)
    finally:
        trace.unbind()
    spans = trace.spans("wf.rank", job=job)
    rows_out = [min(4, limit or 4), 1]
    assert [(s.trace_id, s.args) for s in spans] == [
        (160, {"rows_in": 4, "limit": limit, "rows_out": rows_out[0], "partitions": 1}),
        (260, {"rows_in": 1, "limit": limit, "rows_out": 1, "partitions": 1})]
    assert all(s.node == "wf" and s.t1_ns >= s.t0_ns for s in spans)
    assert metrics.counters["arroyo_worker_window_fn_rows_in"] == 5
    assert metrics.counters["arroyo_worker_window_fn_rows_out"] == sum(rows_out)
    marks = trace.spans("task.account", job=job)
    assert marks and marks[-1].args["window_fn_rows_in"] == 5
    assert marks[-1].args["window_fn_rows_out"] == sum(rows_out)
    account = trace.account_over("wf", job=job)
    assert account is None or "window_fn_rows_in" in account


def test_a_bucket_without_a_window_is_traced_under_its_timestamp():
    job = "wf-rank-no-window"
    trace.bind(job, "wf", 0, TaskMetrics(job, "wf", 0))
    try:
        cfg = {"partition_fields": [], "order_by": [(Col("v"), True)],
               "functions": [("rn", "row_number", None)], "limit": 1}
        op, ctx, out = WindowFunctionOperator(cfg), context(), Collected()
        op.process_batch(Batch({TIMESTAMP_FIELD: np.array([40, 40], dtype=np.int64),
                                "v": np.array([2, 1], dtype=np.int64)}), ctx, out)
        op.on_close(ctx, out)
    finally:
        trace.unbind()
    (span,) = trace.spans("wf.rank", job=job)
    assert span.trace_id == 40 and span.args["rows_out"] == 1
    assert out.batches[0]["v"].tolist() == [1]
