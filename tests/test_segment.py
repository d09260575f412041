"""Whole-segment XLA compilation (engine/segment.py).

Covers the compile cache (hit on same schema, recompile on schema or
parallelism change), byte-exact equivalence of the compiled and interpreted
paths across the value/key/watermark/window-insert stage kinds, graceful
fallback (plan-time refusal for UDFs, runtime dtype gate, forced trace
failure — never a job failure), the SEGMENT_COMPILED/SEGMENT_FALLBACK
events, the compile metrics, the [compiled] markers in explain/top, and the
chaos axis: a worker crash mid-checkpoint under compiled segments must
restore to byte-exact goldens (carried state round-trips through the
TableManager checkpoint path because the compiled path mutates state
through the members' own methods).
"""

from __future__ import annotations

import numpy as np
import pytest

from tests.test_smoke import QUERIES, assert_outputs, build, load_sql

WIDTH = 10_000_000
SLIDE = 2_000_000


@pytest.fixture(autouse=True)
def _chained(_storage):
    from arroyo_tpu import config as cfg

    # max-delay-ms effectively off: the time-based coalescing flush makes
    # batch BOUNDARIES wall-clock-dependent (a slow first batch — e.g. the
    # XLA compile — shifts them), which reorders rows WITHIN emitted
    # window-close batches run to run on either path. Thresholds-only
    # coalescing is deterministic, so compiled vs interpreted comparisons
    # here can demand bit-identical batches, not just equal multisets.
    # min-rows 0: these tests drive small hand-built batches straight into
    # the compiled path; the production floor routes them interpreted
    cfg.update({"pipeline.chaining.enabled": True,
                "segment.compile.enabled": True,
                "segment.compile.min-rows": 0,
                "engine.coalesce.max-delay-ms": 60_000})
    yield
    cfg.update({"pipeline.chaining.enabled": False,
                "segment.compile.min-rows": 8192,
                "engine.coalesce.max-delay-ms": 5})


def _mini_graph(rows, agg: str, event_count: int = 30_000,
                price_expr=None, filter_expr=None):
    """q7-shaped pipeline: nexmark source -> value(project+filter) ->
    watermark -> key -> tumbling/sliding aggregate -> vec sink. At p=1 the
    whole run fuses into one chain whose traced prefix ends at the window
    insert."""
    from arroyo_tpu.batch import TIMESTAMP_FIELD, Schema
    from arroyo_tpu.expr import Col
    from arroyo_tpu.graph import EdgeType, Graph, Node, OpName

    S = Schema.of([("x", "int64"), (TIMESTAMP_FIELD, "int64")])
    g = Graph()
    g.add_node(Node("src", OpName.SOURCE, {
        "connector": "nexmark", "event_count": event_count,
        "inter_event_micros": 1000, "first_event_micros": 0,
        "include_strings": False, "columns": ["bid.auction", "bid.price"]}, 1))
    g.add_node(Node("bids", OpName.VALUE, {
        "projections": [("auction", Col("bid.auction")),
                        ("price", price_expr or Col("bid.price"))],
        "filter": filter_expr if filter_expr is not None else Col("bid")}, 1))
    g.add_node(Node("wm", OpName.WATERMARK, {
        "expr": Col(TIMESTAMP_FIELD), "interval_micros": 1_000_000}, 1))
    g.add_node(Node("key", OpName.KEY, {"keys": [("auction", Col("auction"))]}, 1))
    agg_cfg = {
        "key_fields": ["auction"],
        "aggregates": [("max_price", "max", Col("price")),
                       ("bids", "count", None)],
        "input_dtype_of": lambda e: np.dtype(np.int64),
        "backend": "numpy",
    }
    if agg == "tumbling":
        agg_cfg["width_micros"] = WIDTH
        op = OpName.TUMBLING_AGGREGATE
    else:
        agg_cfg["width_micros"] = WIDTH
        agg_cfg["slide_micros"] = SLIDE
        op = OpName.SLIDING_AGGREGATE
    g.add_node(Node("agg", op, agg_cfg, 1))
    g.add_node(Node("sink", OpName.SINK, {
        "connector": "vec", "rows": rows, "columnar": True}, 1))
    g.add_edge("src", "bids", EdgeType.FORWARD, S)
    g.add_edge("bids", "wm", EdgeType.FORWARD, S)
    g.add_edge("wm", "key", EdgeType.FORWARD, S)
    g.add_edge("key", "agg", EdgeType.SHUFFLE, S)
    g.add_edge("agg", "sink", EdgeType.FORWARD, S)
    return g


def _run(job_id: str, compile_enabled: bool, **kw) -> list:
    from arroyo_tpu import config as cfg
    from arroyo_tpu.engine import run_graph

    cfg.update({"segment.compile.enabled": compile_enabled})
    rows: list = []
    run_graph(_mini_graph(rows, kw.pop("agg", "tumbling"), **kw),
              job_id=job_id, timeout=300)
    return rows


def _canon(batches) -> list:
    """Batch list as (values, dtype) — byte-level equality surface."""
    return [{k: (np.asarray(v).tolist(), str(np.asarray(v).dtype))
             for k, v in b.columns.items()} for b in batches]


def _segment_events(job_id: str) -> list[dict]:
    from arroyo_tpu.obs.events import recorder

    return [e for e in recorder.events(job_id)
            if e["code"].startswith("SEGMENT_")]


# ------------------------------------------------------------ equivalence


def test_tumbling_compiled_byte_exact():
    interp = _run("seg-tumb-int", False)
    comp = _run("seg-tumb-cmp", True)
    assert _canon(interp) == _canon(comp)
    evs = _segment_events("seg-tumb-cmp")
    assert [e["code"] for e in evs] == ["SEGMENT_COMPILED"]
    assert evs[0]["node"] is not None and evs[0]["subtask"] == 0
    # the traced prefix covers value+wm+key+insert; the sink is the tail
    assert evs[0]["data"]["members"] == 4


def test_sliding_compiled_byte_exact():
    interp = _run("seg-slide-int", False, agg="sliding")
    comp = _run("seg-slide-cmp", True, agg="sliding")
    assert _canon(interp) == _canon(comp)
    assert [e["code"] for e in _segment_events("seg-slide-cmp")] == [
        "SEGMENT_COMPILED"]


def test_compiled_with_arithmetic_and_filter():
    """Projection arithmetic + a comparison filter trace; the filter's row
    drops must match the interpreted path's compaction exactly."""
    from arroyo_tpu.expr import BinOp, Col, Lit

    price = BinOp("+", BinOp("*", Col("bid.price"), Lit(2)), Lit(1))
    filt = BinOp("and", Col("bid"),
                 BinOp(">", Col("bid.price"), Lit(300)))
    interp = _run("seg-expr-int", False, price_expr=price, filter_expr=filt)
    comp = _run("seg-expr-cmp", True, price_expr=price, filter_expr=filt)
    assert _canon(interp) == _canon(comp)
    assert [e["code"] for e in _segment_events("seg-expr-cmp")] == [
        "SEGMENT_COMPILED"]


# ------------------------------------------------------------------ cache


def test_cache_hit_same_schema_and_metrics():
    from arroyo_tpu.engine.segment import segment_cache
    from arroyo_tpu.metrics import registry

    segment_cache.clear()  # earlier tests may have compiled this segment
    _run("seg-cache-a", True)
    compiles_a, hits_a = registry.segment_compile_stats("seg-cache-a")
    assert compiles_a >= 1 and hits_a == 0
    # same segment configs + same schema in a fresh job: the process-wide
    # cache serves the compiled entry — zero new compiles, one hit — and
    # the hit run commits into ITS OWN operator incarnation (a cached plan
    # once drove the dead first-run members: fresh watermark state saw no
    # advance and every window close vanished)
    rows_b = _run("seg-cache-b", True)
    compiles_b, hits_b = registry.segment_compile_stats("seg-cache-b")
    assert compiles_b == 0 and hits_b == 1
    assert _canon(rows_b) == _canon(_run("seg-cache-int", False))
    text = registry.prometheus_text()
    assert 'arroyo_segment_compile_seconds_count{job="seg-cache-a"}' in text
    assert 'arroyo_segment_cache_hits_total{job="seg-cache-b"} 1' in text


def test_recompile_on_schema_change():
    """A dtype change in a traced input column keys a NEW cache entry (a
    stale trace would astype-coerce instead of mis-executing, but the
    contract is recompile-per-schema)."""
    from arroyo_tpu.metrics import registry

    _run("seg-schema-a", True)
    # float prices change the traced input schema of the same segment...
    from arroyo_tpu.expr import Cast, Col

    _run("seg-schema-b", True,
         price_expr=Cast(Col("bid.price"), "float64"))
    # ...which is a different segment config here, so prove the finer
    # point at the runner level: same configs, different batch dtypes
    from arroyo_tpu.engine.segment import _schema_sig

    a = _schema_sig(_batch(auction=np.int64, n=8))
    b = _schema_sig(_batch(auction=np.float64, n=8))
    assert a != b
    assert registry.segment_compile_stats("seg-schema-b")[0] >= 1


def _batch(auction=np.int64, n: int = 8):
    from arroyo_tpu.batch import TIMESTAMP_FIELD, Batch

    return Batch({
        "bid": np.ones(n, dtype=bool),
        "bid.auction": np.arange(n).astype(auction),
        "bid.price": np.arange(n, dtype=np.int64) * 7,
        TIMESTAMP_FIELD: np.arange(n, dtype=np.int64) * 1000,
    })


def _unit_runner(parallelism: int = 1, job_id: str = "seg-unit"):
    """A ChainedOperator (value+key) + SegmentRunner with no engine: the
    cache-key and fallback behaviors are unit-testable on plain batches."""
    import arroyo_tpu
    from arroyo_tpu.engine.segment import runner_for
    from arroyo_tpu.expr import Col
    from arroyo_tpu.graph import OpName
    from arroyo_tpu.metrics import registry
    from arroyo_tpu.operators.base import OperatorContext
    from arroyo_tpu.operators.chained import ChainedOperator
    from arroyo_tpu.types import TaskInfo

    arroyo_tpu._load_operators()
    from arroyo_tpu.engine.segment import segment_marking

    members = [
        (OpName.VALUE.value, {
            "projections": [("auction", Col("bid.auction")),
                            ("price", Col("bid.price"))],
            "filter": Col("bid")}),
        (OpName.KEY.value, {"keys": [("auction", Col("auction"))]}),
    ]
    cfg = {"members": members, "compile": segment_marking(members)}
    assert cfg["compile"] is not None
    chain = ChainedOperator(cfg)
    ti = TaskInfo(job_id, "n1", chain.name(), 0, parallelism)
    ctx = OperatorContext(ti, None, None)
    chain.on_start(ctx)
    metrics = registry.task(job_id, "n1", 0)

    class Sink:
        def __init__(self):
            self.batches: list = []
            self.signals: list = []

        def collect(self, b):
            self.batches.append(b)

        def broadcast(self, s):
            self.signals.append(s)

    sink = Sink()
    runner = runner_for(chain, ctx, metrics)
    assert runner is not None
    return runner, chain, ctx, sink


def test_parallelism_keys_cache():
    """Same member configs at different parallelism use different cache
    keys (the issue's recompile-on-parallelism-change contract)."""
    r1, *_ = _unit_runner(parallelism=1)
    r2, *_ = _unit_runner(parallelism=2)
    assert r1._seg_key != r2._seg_key


def test_unit_compile_and_schema_recompile():
    from arroyo_tpu.engine.segment import segment_cache

    segment_cache.clear()
    runner, chain, ctx, sink = _unit_runner(job_id="seg-unit-a")
    runner.process_batch(_batch(n=10), ctx, sink)
    assert runner._entry is not None and not runner._fallback
    first_entry = runner._entry
    assert len(sink.batches) == 1
    out = sink.batches[0]
    assert list(out.columns) == ["auction", "price", "_timestamp", "_key"]
    # keys match the host hashing exactly (routing determinism)
    from arroyo_tpu.hashing import hash_columns

    assert np.array_equal(out.keys,
                          hash_columns([np.asarray(out["auction"])]))
    # same schema again: entry reused, no re-prepare
    runner.process_batch(_batch(n=10), ctx, sink)
    assert runner._entry is first_entry
    # dtype change: a NEW entry is compiled for the new signature
    runner.process_batch(_batch(auction=np.float64, n=10), ctx, sink)
    assert runner._entry is not first_entry and not runner._fallback
    assert len(sink.batches) == 3


# --------------------------------------------------------------- fallback


def test_plan_marking_refuses_udf():
    """A UDF anywhere in the would-be prefix stops the marking: the chain
    runs interpreted with no compile attempt (and no WARN — plan-time
    refusal is not a runtime degradation)."""
    from arroyo_tpu.engine.segment import segment_marking
    from arroyo_tpu.expr import Col
    from arroyo_tpu.graph import OpName
    from arroyo_tpu.udf import UdfExpr

    udf = UdfExpr(udf_name="f", fn=lambda x: x, vectorized=True,
                  return_dtype="int64", args=(Col("bid.price"),))
    members = [
        (OpName.VALUE.value, {"projections": [("p", udf)], "filter": None}),
        (OpName.KEY.value, {"keys": [("p", Col("p"))]}),
    ]
    assert segment_marking(members) is None


def test_untraceable_udaf_window_stops_prefix():
    """A window whose aggregate is host-resident (count_distinct) ends the
    marked prefix before it: the value/wm/key stages still compile and the
    window runs interpreted behind them."""
    from arroyo_tpu.engine.segment import segment_marking
    from arroyo_tpu.expr import Col
    from arroyo_tpu.graph import OpName

    members = [
        (OpName.VALUE.value, {
            "projections": [("auction", Col("bid.auction"))],
            "filter": Col("bid")}),
        (OpName.WATERMARK.value, {"expr": Col("_timestamp")}),
        (OpName.KEY.value, {"keys": [("auction", Col("auction"))]}),
        (OpName.TUMBLING_AGGREGATE.value, {
            "width_micros": WIDTH, "key_fields": ["auction"],
            "aggregates": [("d", "count_distinct", Col("auction"))]}),
    ]
    marking = segment_marking(members)
    assert marking == {"prefix": 3, "insert": False, "mesh": False,
                       "stop": "window: count_distinct accumulator is "
                               "host-resident"}


def test_runtime_fallback_object_column():
    """Plan-time marking cannot see dtypes; an object column referenced by
    a traced expression falls back at runtime with a SEGMENT_FALLBACK WARN
    and a correct interpreted run — never a failure."""
    from arroyo_tpu.batch import TIMESTAMP_FIELD, Batch

    runner, chain, ctx, sink = _unit_runner(job_id="seg-objcol")
    b = Batch({
        "bid": np.ones(4, dtype=bool),
        "bid.auction": np.array(["a", "b", "a", "c"], dtype=object),
        "bid.price": np.arange(4, dtype=np.int64),
        TIMESTAMP_FIELD: np.arange(4, dtype=np.int64),
    })
    runner.process_batch(b, ctx, sink)
    assert runner._fallback
    evs = _segment_events("seg-objcol")
    assert [e["code"] for e in evs] == ["SEGMENT_FALLBACK"]
    assert evs[0]["level"] == "WARN"
    assert "dtype" in evs[0]["data"]["reason"]
    # the batch still flowed — through the interpreted members
    assert len(sink.batches) == 1
    assert list(sink.batches[0].columns) == [
        "auction", "price", "_timestamp", "_key"]


def test_trace_failure_is_fallback_not_job_failure(monkeypatch):
    """Any exception out of tracing/compilation — not just the anticipated
    gates — degrades the segment, and the job's output is byte-exact."""
    import arroyo_tpu.engine.segment as seg

    seg.segment_cache.clear()

    def boom(plan):
        raise RuntimeError("injected trace failure")

    monkeypatch.setattr(seg, "_trace_fn", boom)
    comp = _run("seg-traceboom", True)
    evs = _segment_events("seg-traceboom")
    assert [e["code"] for e in evs] == ["SEGMENT_FALLBACK"]
    assert "injected trace failure" in evs[0]["data"]["reason"]
    monkeypatch.undo()
    seg.segment_cache.clear()
    interp = _run("seg-traceboom-int", False)
    assert _canon(interp) == _canon(comp)


def test_verification_mismatch_is_fallback(monkeypatch):
    """A traced function whose outputs diverge from the interpreted
    reference must never be committed: the first-batch verification
    catches it and the segment degrades."""
    import arroyo_tpu.engine.segment as seg

    seg.segment_cache.clear()
    real = seg._reference

    def skewed(plan, batch):
        want = real(plan, batch)
        for name, arr in want["cols"].items():
            if np.asarray(arr).dtype.kind in "iu" and len(arr):
                want["cols"][name] = np.asarray(arr) + 1
                break
        return want

    monkeypatch.setattr(seg, "_reference", skewed)
    runner, chain, ctx, sink = _unit_runner(job_id="seg-verify")
    runner.process_batch(_batch(n=10), ctx, sink)
    assert runner._fallback
    evs = _segment_events("seg-verify")
    assert "verification failed" in evs[0]["data"]["reason"]
    assert len(sink.batches) == 1  # interpreted output still flowed


def test_fallback_cached_negatively():
    """The second subtask (or a restored incarnation) of an untraceable
    segment reuses the negative cache entry instead of re-probing."""
    from arroyo_tpu.batch import TIMESTAMP_FIELD, Batch
    from arroyo_tpu.engine.segment import segment_cache
    from arroyo_tpu.metrics import registry

    segment_cache.clear()
    b = Batch({
        "bid": np.ones(4, dtype=bool),
        "bid.auction": np.array(["a", "b", "a", "c"], dtype=object),
        "bid.price": np.arange(4, dtype=np.int64),
        TIMESTAMP_FIELD: np.arange(4, dtype=np.int64),
    })
    r1, c1, ctx1, s1 = _unit_runner(job_id="seg-neg-a")
    r1.process_batch(b, ctx1, s1)
    r2, c2, ctx2, s2 = _unit_runner(job_id="seg-neg-b")
    r2.process_batch(b, ctx2, s2)
    assert r2._fallback
    # negative-cache reuse is NOT a cache hit: the metric counts reuse of
    # COMPILED entries only (and nothing compiled here either)
    assert registry.segment_compile_stats("seg-neg-b") == (0, 0)
    assert [e["code"] for e in _segment_events("seg-neg-b")] == [
        "SEGMENT_FALLBACK"]


def test_vacuous_first_batch_defers_compile():
    """A first batch whose hoisted filter leaves no survivors must NOT
    adopt (or cache) an unverified trace — the traced function never ran,
    so verify-then-trust would be vacuous. The compile retries on the next
    batch with survivors and verifies for real."""
    from arroyo_tpu.batch import TIMESTAMP_FIELD, Batch
    from arroyo_tpu.engine.segment import segment_cache
    from arroyo_tpu.expr import BinOp, Col, Lit
    from arroyo_tpu.graph import OpName
    from arroyo_tpu.metrics import registry
    from arroyo_tpu.operators.base import OperatorContext
    from arroyo_tpu.operators.chained import ChainedOperator
    from arroyo_tpu.types import TaskInfo

    segment_cache.clear()
    from arroyo_tpu.engine.segment import runner_for, segment_marking

    members = [
        (OpName.VALUE.value, {
            "projections": [("p", Col("bid.price"))],
            # selective: only prices > threshold survive
            "filter": BinOp(">", Col("bid.price"), Lit(100))}),
        (OpName.KEY.value, {"keys": [("p", Col("p"))]}),
    ]
    cfg = {"members": members, "compile": segment_marking(members)}
    chain = ChainedOperator(cfg)
    ctx = OperatorContext(TaskInfo("seg-vac", "n1", chain.name(), 0, 1),
                          None, None)
    chain.on_start(ctx)
    runner = runner_for(chain, ctx, registry.task("seg-vac", "n1", 0))

    class Sink:
        batches: list = []

        def collect(self, b):
            Sink.batches.append(b)

        def broadcast(self, s):
            pass

    Sink.batches = []

    def mk(prices):
        n = len(prices)
        return Batch({"bid.price": np.asarray(prices, dtype=np.int64),
                      TIMESTAMP_FIELD: np.arange(n, dtype=np.int64)})

    # every row filtered: hoist selectivity 0 -> traced fn never runs
    runner.process_batch(mk([1, 2, 3, 4]), ctx, Sink())
    assert runner._entry is None and not runner._fallback
    assert Sink.batches == []  # nothing flows on either path
    # next batch has survivors: compile + verify for real, rows flow
    runner.process_batch(mk([1, 200, 300, 2]), ctx, Sink())
    assert runner._entry is not None and not runner._fallback
    assert len(Sink.batches) == 1
    assert np.asarray(Sink.batches[0]["p"]).tolist() == [200, 300]


def test_steady_state_execute_failure_is_fallback(monkeypatch):
    """An execution failure AFTER the verified first batch (e.g. a new
    padded shape failing to XLA-compile) degrades the segment — execute is
    pure, so the batch replays interpreted and the job never fails."""
    import arroyo_tpu.engine.segment as seg

    seg.segment_cache.clear()
    runner, chain, ctx, sink = _unit_runner(job_id="seg-latefail")
    runner.process_batch(_batch(n=10), ctx, sink)
    assert runner._entry is not None

    def boom(self, batch, job_id, observe=True, min_rows=0):
        raise RuntimeError("injected late XLA failure")

    monkeypatch.setattr(seg.CompiledSegment, "execute", boom)
    runner.process_batch(_batch(n=10), ctx, sink)
    assert runner._fallback
    assert len(sink.batches) == 2  # the failing batch still flowed
    evs = _segment_events("seg-latefail")
    assert evs[-1]["code"] == "SEGMENT_FALLBACK"
    assert "injected late XLA failure" in evs[-1]["data"]["reason"]


def test_min_rows_floor_runs_interpreted():
    """Batches below segment.compile.min-rows never pay the jit dispatch:
    they take the interpreted members, and the mixed stream is still
    correct (the floor only picks between verified-equal paths)."""
    from arroyo_tpu import config as cfg

    cfg.update({"segment.compile.min-rows": 64})
    try:
        runner, chain, ctx, sink = _unit_runner(job_id="seg-floor")
        runner.process_batch(_batch(n=8), ctx, sink)
        assert runner._entry is None  # small batch: no compile attempted
        runner.process_batch(_batch(n=128), ctx, sink)
        assert runner._entry is not None  # big batch compiled
        runner.process_batch(_batch(n=8), ctx, sink)  # small again: interp
        assert len(sink.batches) == 3
        from arroyo_tpu.hashing import hash_columns

        for b in sink.batches:
            assert list(b.columns) == ["auction", "price", "_timestamp",
                                       "_key"]
            assert np.array_equal(
                b.keys, hash_columns([np.asarray(b["auction"])]))
    finally:
        cfg.update({"segment.compile.min-rows": 0})


def test_disabled_by_config():
    from arroyo_tpu import config as cfg
    from arroyo_tpu.engine.segment import runner_for

    runner, chain, ctx, sink = _unit_runner(job_id="seg-off")
    cfg.update({"segment.compile.enabled": False})
    assert runner_for(chain, ctx, None) is None


# ----------------------------------------------------------- observability


def test_explain_top_compiled_marker():
    from arroyo_tpu.metrics import merge_job_metrics, registry
    from arroyo_tpu.obs.profile import job_profile, render_explain
    from arroyo_tpu.obs.topview import render

    _run("seg-marker", True)
    metrics = merge_job_metrics([registry.job_metrics("seg-marker")])
    chained_ops = [op for op, m in metrics.items()
                   if m.get("segment_compiled")]
    assert chained_ops, "no operator carries the compiled flag"
    frame = render({"id": "seg-marker", "state": "Finished"}, metrics)
    assert "[compiled]" in frame
    profile = job_profile(metrics)
    text = render_explain(
        [{"id": op, "op": "chained", "parallelism": 1} for op in metrics],
        [], profile, {"id": "seg-marker", "state": "Finished"})
    assert "[compiled]" in text


def test_executed_graph_view_marks_compilable():
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "smoke"))
    try:
        import udfs  # noqa: F401
    finally:
        sys.path.pop(0)
    from arroyo_tpu.sql.planner import executed_graph_view

    sql = load_sql("tumbling_aggregates", "/tmp/seg_view_out.json")
    nodes, _edges = executed_graph_view(sql)
    chained = [n for n in nodes if n["op"] == "chained"]
    assert chained and any(n.get("compilable") for n in chained)


# ----------------------------------------------------- smoke-family sweep


@pytest.mark.parametrize("name", QUERIES)
def test_smoke_families_compiled(name, tmp_path, _storage):
    """Every smoke family runs to byte-exact goldens with compilation ON
    and actively engaged (min-rows floored to 0 by the fixture, so the
    512-row source batches hit the compiled path, not the cost floor).
    Families whose segments cannot trace — string keys, UDFs, sessions —
    exercise the marking/fallback gates and MUST still match goldens."""
    out = str(tmp_path / "out.json")
    eng = build(load_sql(name, out), 1, f"{name}-segcomp")
    eng.run_to_completion(timeout=180)
    assert_outputs(name, out)


# ------------------------------------------------------------- chaos axis


@pytest.mark.chaos
@pytest.mark.parametrize("name", ["tumbling_aggregates", "sliding_window"])
def test_chaos_crash_restore_compiled(name, tmp_path, _storage):
    """Worker crash mid-epoch-2-checkpoint with compiled segments: the
    carried operator state (window partials, late boundaries, watermark
    marks) must round-trip the TableManager checkpoint path and restore to
    byte-exact goldens — the compiled path mutates state only through the
    members' own methods, so this axis proves that claim end to end."""
    from arroyo_tpu import config as cfg
    from arroyo_tpu import faults
    from arroyo_tpu.state.tables import latest_complete_checkpoint

    out = str(tmp_path / "out.json")
    sql = load_sql(name, out)
    job_id = f"{name}-seg-chaos"
    cfg.update({"testing.source-gate-epochs": 2})
    inj = faults.install("worker:crash@barrier=2&step=1", seed=1337)
    try:
        eng = build(sql, 2, job_id)
        eng.start()
        assert eng.checkpoint_and_wait(1, timeout=60), "epoch 1 incomplete"
        with pytest.raises(RuntimeError, match="injected"):
            if eng.checkpoint_and_wait(2, timeout=60):
                raise AssertionError("epoch 2 completed despite crash")
            eng.join(timeout=60)
    finally:
        faults.clear()
        cfg.update({"testing.source-gate-epochs": 0})
    assert inj.fired_log, "crash fault never fired"
    storage_url = cfg.config().get("checkpoint.storage-url")
    assert latest_complete_checkpoint(storage_url, job_id) == 1

    eng2 = build(sql, 2, job_id, restore_epoch=1)
    eng2.run_to_completion(timeout=180)
    # compiled segments genuinely ran across the crash/restore boundary
    # (the pre-agg chain during phase 1, the post-agg chain once windows
    # close after the restore) and never fell back
    evs = _segment_events(job_id)
    assert any(e["code"] == "SEGMENT_COMPILED" for e in evs), \
        "chaos axis ran without a compiled segment"
    assert_outputs(name, out)
