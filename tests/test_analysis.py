"""Static-analysis subsystem tests (arroyo_tpu.analysis).

Three layers:
- plan-analyzer rules: one minimal positive and one negative graph/SQL
  fixture per rule, plus the known-bad pipeline catalog
  (tests/smoke/queries_bad) asserting each file's annotated rule id;
- repo lint rules: AST fixtures per rule + waiver semantics, and the
  gate that this repository itself lints clean;
- determinism: same input -> identical ordered diagnostics.
"""

from __future__ import annotations

import glob
import os
import re

import pytest

import arroyo_tpu
from arroyo_tpu.analysis import (
    AnalysisError,
    Severity,
    analyze_graph,
    check_sql,
    lint_paths,
    lint_source,
)
from arroyo_tpu.batch import TIMESTAMP_FIELD, Schema
from arroyo_tpu.expr import Col
from arroyo_tpu.graph import EdgeType, Graph, Node, OpName

SMOKE = os.path.join(os.path.dirname(__file__), "smoke")
BAD_DIR = os.path.join(SMOKE, "queries_bad")
PKG_DIR = os.path.dirname(os.path.abspath(arroyo_tpu.__file__))


def load_bad(path: str) -> tuple[str, str, str]:
    """-> (sql, mode, rule_id) from a queries_bad file's annotation."""
    with open(path) as f:
        text = f.read()
    m = re.match(r"--\s*(reject|warn):\s*(\S+)", text)
    assert m, f"{path} lacks a '-- reject:/-- warn: <rule>' annotation"
    sql = text.replace("$input_dir", os.path.join(SMOKE, "inputs")).replace(
        "$output_path", "/tmp/qb_out.json")
    return sql, m.group(1), m.group(2)


BAD_FILES = sorted(glob.glob(os.path.join(BAD_DIR, "*.sql")))


def ids_of(diags):
    return {d.rule_id for d in diags}


# ---------------------------------------------------------------- graph kit


def schema(*cols: tuple[str, str], has_keys: bool = False) -> Schema:
    return Schema.of(list(cols) + [(TIMESTAMP_FIELD, "int64")],
                     has_keys=has_keys)


def base_graph(connector: str = "single_file", fmt: str = "json") -> tuple[Graph, Schema]:
    g = Graph()
    s = schema(("a", "int64"), ("b", "int64"))
    g.add_node(Node("src_0", OpName.SOURCE,
                    {"connector": connector, "format": fmt, "schema": s,
                     "path": "/dev/null"}, 1))
    return g, s


def add_sink(g: Graph, src: str, s: Schema, fmt: str = "json") -> None:
    g.add_node(Node("sink_0", OpName.SINK,
                    {"connector": "single_file", "format": fmt, "schema": s,
                     "path": "/tmp/out"}, 1))
    g.add_edge(src, "sink_0", EdgeType.FORWARD, s)


def errors(diags):
    return [d for d in diags if d.severity == Severity.ERROR]


# ------------------------------------------------------------ catalog tests


def _register_smoke_fixtures():
    """Import the smoke suite's UDF/connector fixtures (idempotent): the
    AR008 catalog entry plans a deliberately-broken test connector that
    tests/smoke/udfs.py registers."""
    import sys
    sys.path.insert(0, SMOKE)
    try:
        import udfs  # noqa: F401
    finally:
        sys.path.pop(0)


@pytest.mark.parametrize("path", BAD_FILES, ids=[os.path.basename(p)[:-4] for p in BAD_FILES])
def test_known_bad_catalog(path):
    """Every cataloged bad pipeline produces exactly its annotated
    diagnostic: 'reject' entries fail `check` with that rule id as an
    ERROR, 'warn' entries plan successfully but carry the warning."""
    _register_smoke_fixtures()
    sql, mode, rule = load_bad(path)
    pp, diags = check_sql(sql)
    if mode == "reject":
        errs = errors(diags)
        assert errs, f"{path}: expected rejection, got {diags}"
        assert rule in ids_of(errs), f"{path}: expected {rule}, got {ids_of(errs)}"
    else:
        assert pp is not None and not errors(diags), f"{path}: unexpectedly rejected: {diags}"
        assert rule in ids_of(diags), f"{path}: expected warning {rule}, got {ids_of(diags)}"


def test_all_smoke_families_accepted():
    """The analyzer must not reject any golden-output family."""
    from arroyo_tpu.sql import plan_query

    _register_smoke_fixtures()
    for p in sorted(glob.glob(os.path.join(SMOKE, "queries", "*.sql"))):
        sql = open(p).read().replace("$input_dir", os.path.join(SMOKE, "inputs")) \
            .replace("$output_path", "/tmp/qa_out.json")
        plan_query(sql)  # analyze=True: raises AnalysisError on any ERROR


def test_unaligned_hop_raises_at_plan_time():
    """The satellite guarantee: plan_query (the path every execution
    surface uses) rejects unaligned hop() before anything runs."""
    sql, _mode, rule = load_bad(
        os.path.join(BAD_DIR, "most_active_driver_last_hour_unaligned.sql"))
    from arroyo_tpu.sql import plan_query

    with pytest.raises(AnalysisError) as ei:
        plan_query(sql)
    assert rule in str(ei.value)
    assert ei.value.diagnostics[0].rule_id == rule


# ----------------------------------------------------- plan rules, per-rule


def test_ar001_edge_schema():
    g, s = base_graph()
    g.add_node(Node("value_1", OpName.VALUE,
                    {"projections": [("x", Col("missing"))]}, 1))
    g.add_edge("src_0", "value_1", EdgeType.FORWARD, s)
    add_sink(g, "value_1", schema(("x", "int64")))
    diags = analyze_graph(g)
    assert "AR001" in ids_of(errors(diags))

    g2, s2 = base_graph()
    g2.add_node(Node("value_1", OpName.VALUE,
                     {"projections": [("x", Col("a"))]}, 1))
    g2.add_edge("src_0", "value_1", EdgeType.FORWARD, s2)
    add_sink(g2, "value_1", schema(("x", "int64")))
    assert "AR001" not in ids_of(analyze_graph(g2))


def test_ar001_unnest_column():
    g, s = base_graph()
    g.add_node(Node("unnest_1", OpName.UNNEST,
                    {"column": "gone", "out_name": "v", "out_dtype": "int64"}, 1))
    g.add_edge("src_0", "unnest_1", EdgeType.FORWARD, s)
    add_sink(g, "unnest_1", schema(("v", "int64")))
    assert "AR001" in ids_of(errors(analyze_graph(g)))

    g2, s2 = base_graph()
    g2.add_node(Node("unnest_1", OpName.UNNEST,
                     {"column": "a", "out_name": "v", "out_dtype": "int64"}, 1))
    g2.add_edge("src_0", "unnest_1", EdgeType.FORWARD, s2)
    add_sink(g2, "unnest_1", schema(("v", "int64")))
    assert "AR001" not in ids_of(analyze_graph(g2))


def _sliding_graph(width_us: int, slide_us: int) -> Graph:
    g, s = base_graph()
    g.add_node(Node("agg_1", OpName.SLIDING_AGGREGATE,
                    {"key_fields": [], "aggregates": [("c", "count", None)],
                     "width_micros": width_us, "slide_micros": slide_us}, 1))
    g.add_edge("src_0", "agg_1", EdgeType.FORWARD, s)
    add_sink(g, "agg_1", schema(("c", "int64")))
    return g


def test_ar002_unaligned_hop():
    diags = analyze_graph(_sliding_graph(10_000_000, 3_000_000))
    hits = [d for d in errors(diags) if d.rule_id == "AR002"]
    assert hits and "slide" in hits[0].message and hits[0].hint
    assert "AR002" not in ids_of(analyze_graph(_sliding_graph(10_000_000, 2_000_000)))


def test_ar003_updating_into_window():
    g, s = base_graph(fmt="debezium_json")
    g.add_node(Node("agg_1", OpName.TUMBLING_AGGREGATE,
                    {"key_fields": [], "aggregates": [("c", "count", None)],
                     "width_micros": 1_000_000}, 1))
    g.add_edge("src_0", "agg_1", EdgeType.FORWARD, s)
    add_sink(g, "agg_1", schema(("c", "int64")))
    assert "AR003" in ids_of(errors(analyze_graph(g)))

    g2, s2 = base_graph(fmt="json")
    g2.add_node(Node("agg_1", OpName.TUMBLING_AGGREGATE,
                     {"key_fields": [], "aggregates": [("c", "count", None)],
                      "width_micros": 1_000_000}, 1))
    g2.add_edge("src_0", "agg_1", EdgeType.FORWARD, s2)
    add_sink(g2, "agg_1", schema(("c", "int64")))
    assert "AR003" not in ids_of(analyze_graph(g2))


def _updating_agg_graph(connector: str, ttl: int = 0) -> Graph:
    g, s = base_graph(connector=connector)
    cfg = {"key_fields": [], "aggregates": [("c", "count", None)]}
    if ttl:
        cfg["ttl_micros"] = ttl
    g.add_node(Node("agg_1", OpName.UPDATING_AGGREGATE, cfg, 1))
    g.add_edge("src_0", "agg_1", EdgeType.FORWARD, s)
    add_sink(g, "agg_1", schema(("c", "int64")), fmt="debezium_json")
    return g


def test_ar004_unbounded_state():
    assert "AR004" in ids_of(analyze_graph(_updating_agg_graph("kafka")))
    # a TTL bounds the state; a bounded source bounds it too
    assert "AR004" not in ids_of(analyze_graph(_updating_agg_graph("kafka", ttl=60_000_000)))
    assert "AR004" not in ids_of(analyze_graph(_updating_agg_graph("single_file")))


def test_ar005_retraction_sink():
    g, s = base_graph()
    g.add_node(Node("agg_1", OpName.UPDATING_AGGREGATE,
                    {"key_fields": [], "aggregates": [("c", "count", None)]}, 1))
    g.add_edge("src_0", "agg_1", EdgeType.FORWARD, s)
    add_sink(g, "agg_1", schema(("c", "int64")), fmt="json")
    diags = analyze_graph(g)
    hit = [d for d in diags if d.rule_id == "AR005"]
    assert hit and hit[0].severity == Severity.WARNING

    g2, s2 = base_graph()
    g2.add_node(Node("agg_1", OpName.UPDATING_AGGREGATE,
                     {"key_fields": [], "aggregates": [("c", "count", None)]}, 1))
    g2.add_edge("src_0", "agg_1", EdgeType.FORWARD, s2)
    add_sink(g2, "agg_1", schema(("c", "int64")), fmt="debezium_json")
    assert "AR005" not in ids_of(analyze_graph(g2))


def test_ar006_barrier_reachability():
    # orphan operator: no input edges -> barriers can never reach it
    g, s = base_graph()
    add_sink(g, "src_0", s)
    g.add_node(Node("agg_orphan", OpName.TUMBLING_AGGREGATE,
                    {"key_fields": [], "aggregates": [],
                     "width_micros": 1_000_000}, 1))
    hits = [d for d in errors(analyze_graph(g)) if d.rule_id == "AR006"]
    assert hits and hits[0].site == "agg_orphan"

    # dead source: output never reaches a sink -> warning
    g2, s2 = base_graph()
    add_sink(g2, "src_0", s2)
    g2.add_node(Node("src_dead", OpName.SOURCE,
                     {"connector": "single_file", "schema": s2,
                      "path": "/dev/null"}, 1))
    diags = analyze_graph(g2)
    hits = [d for d in diags if d.rule_id == "AR006"]
    assert hits and hits[0].severity == Severity.WARNING and hits[0].site == "src_dead"

    g3, s3 = base_graph()
    add_sink(g3, "src_0", s3)
    assert "AR006" not in ids_of(analyze_graph(g3))


def _shuffle_graph(key_names: list[str], group_by: list[str],
                   with_key_node: bool = True) -> Graph:
    g, s = base_graph()
    ks = schema(("a", "int64"), ("b", "int64"), has_keys=True)
    up = "src_0"
    if with_key_node:
        g.add_node(Node("key_1", OpName.KEY,
                        {"keys": [(n, Col(n)) for n in key_names]}, 1))
        g.add_edge("src_0", "key_1", EdgeType.FORWARD, s)
        up = "key_1"
    g.add_node(Node("agg_1", OpName.UPDATING_AGGREGATE,
                    {"key_fields": group_by,
                     "aggregates": [("c", "count", None)]}, 2))
    g.add_edge(up, "agg_1", EdgeType.SHUFFLE, ks if with_key_node else s)
    add_sink(g, "agg_1", schema(("c", "int64")), fmt="debezium_json")
    return g


def test_ar007_shuffle_keys():
    assert "AR007" not in ids_of(analyze_graph(_shuffle_graph(["a"], ["a"])))
    # keyed by the wrong column
    diags = analyze_graph(_shuffle_graph(["b"], ["a"]))
    assert "AR007" in ids_of(errors(diags))
    # no key calculation upstream at all
    diags = analyze_graph(_shuffle_graph([], ["a"], with_key_node=False))
    hits = [d for d in errors(diags) if d.rule_id == "AR007"]
    assert hits and "no upstream key calculation" in hits[0].message


# ----------------------------------------------------------- lint, per-rule


def test_lr101_adhoc_retry_sleep():
    bad = (
        "import time\n"
        "def f():\n"
        "    while True:\n"
        "        try:\n"
        "            io()\n"
        "        except OSError:\n"
        "            time.sleep(1.0)\n"
    )
    diags = lint_source(bad, "arroyo_tpu/connectors/x.py")
    assert "LR101" in ids_of(diags)
    good = bad.replace("time.sleep(1.0)", "time.sleep(backoff.next_delay())")
    assert "LR101" not in ids_of(lint_source(good, "arroyo_tpu/connectors/x.py"))
    # the shared layer itself is allowed to sleep
    assert "LR101" not in ids_of(lint_source(bad, "arroyo_tpu/utils/retry.py"))


def test_lr102_swallowed_exception():
    bare = "def f():\n    try:\n        g()\n    except:\n        pass\n"
    assert "LR102" in ids_of(lint_source(bare, "arroyo_tpu/api/x.py"))
    swallowed = "def f():\n    try:\n        g()\n    except Exception:\n        pass\n"
    assert "LR102" in ids_of(lint_source(swallowed, "arroyo_tpu/engine/x.py"))
    # outside the strict layers a broad except-pass is tolerated
    assert "LR102" not in ids_of(lint_source(swallowed, "arroyo_tpu/api/x.py"))
    logged = swallowed.replace("pass", "log.warning('x')")
    assert "LR102" not in ids_of(lint_source(logged, "arroyo_tpu/engine/x.py"))


def test_lr103_unseeded_random():
    bad = "import random\ndef f():\n    return random.uniform(0, 1)\n"
    assert "LR103" in ids_of(lint_source(bad, "arroyo_tpu/operators/x.py"))
    assert "LR103" in ids_of(lint_source(
        "import numpy as np\ndef f():\n    return np.random.rand(4)\n",
        "arroyo_tpu/engine/x.py"))
    # out of scope (e.g. retry jitter) and seeded instances are fine
    assert "LR103" not in ids_of(lint_source(bad, "arroyo_tpu/utils/x.py"))
    seeded = "import random\ndef f(seed):\n    return random.Random(seed).uniform(0, 1)\n"
    assert "LR103" not in ids_of(lint_source(seeded, "arroyo_tpu/operators/x.py"))


def test_lr104_host_sync_hot_path():
    bad = (
        "import jax.numpy as jnp\nimport numpy as np\n"
        "class Op:\n"
        "    def process_batch(self, batch, ctx, collector):\n"
        "        v = jnp.sum(batch.col)\n"
        "        return float(v)\n"
    )
    diags = lint_source(bad, "arroyo_tpu/operators/x.py")
    assert "LR104" in ids_of(diags)
    assert "LR104" in ids_of(lint_source(
        bad.replace("float(v)", "np.asarray(v)"), "arroyo_tpu/operators/x.py"))
    assert "LR104" in ids_of(lint_source(
        "def flush(x):\n    x.block_until_ready()\n", "arroyo_tpu/ops/x.py"))
    # host-side numpy on host values is the normal case — not flagged
    host = (
        "import numpy as np\n"
        "class Op:\n"
        "    def process_batch(self, batch, ctx, collector):\n"
        "        v = batch.col\n"
        "        return np.asarray(v)\n"
    )
    assert "LR104" not in ids_of(lint_source(host, "arroyo_tpu/operators/x.py"))


def test_lr105_folded_into_lr403():
    """LR105 is retired as a standalone rule: its intraprocedural shape
    now fires as LR403 from the concurrency auditor (which lint_paths
    runs alongside these rules); the old id survives only as a waiver
    alias. See tests/test_concurrency_audit.py for the LR403 fixtures."""
    from arroyo_tpu.analysis import CONCURRENCY_RULES
    from arroyo_tpu.analysis.concurrency_audit import (
        audit_concurrency_source,
    )
    from arroyo_tpu.analysis.repo_lint import RULES

    assert "LR105" not in {rid for rid, _sev, _fn in RULES}
    assert "LR403" in CONCURRENCY_RULES
    bad = (
        "import time\n"
        "def f(self):\n"
        "    with self._lock:\n"
        "        time.sleep(1)\n"
    )
    assert "LR403" in {d.rule_id for d in audit_concurrency_source(
        bad, "arroyo_tpu/engine/x.py")}
    # os.path.join / "".join under a lock are not thread joins
    path = (
        "import os\n"
        "def f(self):\n"
        "    with self._lock:\n"
        "        return os.path.join('a', 'b')\n"
    )
    assert "LR403" not in {d.rule_id for d in audit_concurrency_source(
        path, "arroyo_tpu/engine/x.py")}


def test_lr106_fault_site_coverage():
    uncovered = (
        "def write_bytes(path, data):\n"
        "    open(path, 'wb').write(data)\n"
    )
    assert "LR106" in ids_of(lint_source(uncovered, "arroyo_tpu/state/storage.py"))
    covered = (
        "from ..faults import fault_point\n"
        "def _guarded(site, key, fn):\n"
        "    fault_point(site, key=key)\n"
        "    return fn()\n"
        "def write_bytes(path, data):\n"
        "    _guarded('storage.put', path, lambda: None)\n"
    )
    assert "LR106" not in ids_of(lint_source(covered, "arroyo_tpu/state/storage.py"))
    # rule only binds to declared fault-boundary modules
    assert "LR106" not in ids_of(lint_source(uncovered, "arroyo_tpu/utils/x.py"))


def test_lr107_emit_in_loop():
    bad = (
        "def on_close(self, ctx, collector):\n"
        "    for w in self.windows:\n"
        "        collector.collect(self.window_batch(w))\n"
    )
    assert "LR107" in ids_of(lint_source(bad, "arroyo_tpu/operators/x.py"))
    assert "LR107" in ids_of(lint_source(bad, "arroyo_tpu/windows/x.py"))
    # connectors are out of scope: a source's poll loop IS its emit contract
    assert "LR107" not in ids_of(lint_source(bad, "arroyo_tpu/connectors/x.py"))
    fused = (
        "def on_close(self, ctx, collector):\n"
        "    parts = [self.window_cols(w) for w in self.windows]\n"
        "    collector.collect(concat(parts))\n"
    )
    assert "LR107" not in ids_of(lint_source(fused, "arroyo_tpu/operators/x.py"))
    waived = bad.replace(
        "collector.collect(self.window_batch(w))",
        "collector.collect(self.window_batch(w))  "
        "# lint: waive LR107 — windows carry incompatible schemas")
    assert "LR107" not in ids_of(lint_source(waived, "arroyo_tpu/operators/x.py"))


def test_lr108_bare_print():
    bad = (
        "def poll(self):\n"
        "    print('got batch', 42)\n"
    )
    # library code: worker stdout is the JSON-lines control protocol
    assert "LR108" in ids_of(lint_source(bad, "arroyo_tpu/engine/x.py"))
    assert "LR108" in ids_of(lint_source(bad, "arroyo_tpu/connectors/x.py"))
    # CLI entry points own their stdout; scripts and tools live outside the package
    assert "LR108" not in ids_of(lint_source(bad, "arroyo_tpu/cli.py"))
    assert "LR108" not in ids_of(lint_source(bad, "arroyo_tpu/__main__.py"))
    assert "LR108" not in ids_of(lint_source(bad, "tools/profile.py"))
    assert "LR108" not in ids_of(lint_source(bad, "chip_smoke.py"))
    logged = (
        "import logging\n"
        "def poll(self):\n"
        "    logging.getLogger('arroyo_tpu.engine').info('got batch %d', 42)\n"
    )
    assert "LR108" not in ids_of(lint_source(logged, "arroyo_tpu/engine/x.py"))
    waived = bad.replace(
        "print('got batch', 42)",
        "print('got batch', 42)  # lint: waive LR108 — CLI-owned output")
    assert "LR108" not in ids_of(lint_source(waived, "arroyo_tpu/engine/x.py"))


def test_lr109_adhoc_self_timing():
    bad = (
        "import time\n"
        "def process_batch(self, batch, ctx, collector, input_index=0):\n"
        "    t0 = time.perf_counter()\n"
        "    work(batch)\n"
        "    self.total += time.time() - t0\n"
    )
    # self-measurement in operator/window/state code fragments attribution
    for rel in ("arroyo_tpu/operators/x.py", "arroyo_tpu/windows/x.py",
                "arroyo_tpu/state/x.py", "arroyo_tpu/ops/x.py"):
        assert "LR109" in ids_of(lint_source(bad, rel)), rel
    # the engine/profiler layers OWN the stopwatch; connectors poll clocks
    assert "LR109" not in ids_of(lint_source(bad, "arroyo_tpu/engine/x.py"))
    assert "LR109" not in ids_of(lint_source(bad, "arroyo_tpu/obs/profile.py"))
    assert "LR109" not in ids_of(lint_source(bad, "arroyo_tpu/connectors/x.py"))
    # time.sleep is not a clock read (LR101/LR105 cover sleeps)
    sleepy = "import time\ndef handle_tick(self, ctx, c):\n    time.sleep(0.1)\n"
    assert "LR109" not in ids_of(lint_source(sleepy, "arroyo_tpu/operators/x.py"))
    # a justified waiver records WHY a clock read is not self-measurement
    waived = bad.replace(
        "t0 = time.perf_counter()",
        "t0 = time.perf_counter()  # lint: waive LR109 — cache TTL wall clock"
    ).replace(
        "self.total += time.time() - t0",
        "self.total += time.time() - t0  # lint: waive LR109 — cache TTL wall clock")
    assert "LR109" not in ids_of(lint_source(waived, "arroyo_tpu/operators/x.py"))


def test_lr110_logger_in_function():
    bad = (
        "import logging\n"
        "def handle(self):\n"
        "    logging.getLogger('arroyo_tpu.x').warning('boom')\n"
    )
    # per-call named-logger acquisition anywhere in the package
    assert "LR110" in ids_of(lint_source(bad, "arroyo_tpu/controller/x.py"))
    assert "LR110" in ids_of(lint_source(bad, "arroyo_tpu/engine/x.py"))
    # module-level acquisition is the convention — never flagged
    good = (
        "import logging\n"
        "_log = logging.getLogger('arroyo_tpu.x')\n"
        "def handle(self):\n"
        "    _log.warning('boom')\n"
    )
    assert "LR110" not in ids_of(lint_source(good, "arroyo_tpu/controller/x.py"))
    # the bare root logger (logging-INIT code reconfiguring handlers) is exempt
    root = (
        "import logging\n"
        "def init():\n"
        "    logging.getLogger().setLevel(logging.INFO)\n"
    )
    assert "LR110" not in ids_of(lint_source(root, "arroyo_tpu/server_common.py"))
    # outside the package (tools, tests) the rule does not apply
    assert "LR110" not in ids_of(lint_source(bad, "tools/x.py"))
    waived = bad.replace(
        "logging.getLogger('arroyo_tpu.x').warning('boom')",
        "logging.getLogger('arroyo_tpu.x').warning('boom')"
        "  # lint: waive LR110 — dynamic per-job logger name")
    assert "LR110" not in ids_of(lint_source(waived, "arroyo_tpu/controller/x.py"))


def test_lr111_jit_in_hot_path():
    bad = (
        "import jax\n"
        "class Op:\n"
        "    def process_batch(self, batch, ctx, collector, input_index=0):\n"
        "        fn = jax.jit(lambda x: x + 1)\n"
        "        collector.collect(fn(batch))\n"
    )
    # per-batch jit in any operator hot-path method is the retrace bug
    for rel in ("arroyo_tpu/operators/x.py", "arroyo_tpu/windows/x.py",
                "arroyo_tpu/ops/x.py"):
        assert "LR111" in ids_of(lint_source(bad, rel)), rel
    for hot in ("handle_watermark", "handle_tick"):
        variant = bad.replace("process_batch", hot)
        assert "LR111" in ids_of(
            lint_source(variant, "arroyo_tpu/operators/x.py")), hot
    # bare jit()/pjit() names count too (from-imports)
    frm = (
        "from jax import jit\n"
        "class Op:\n"
        "    def process_batch(self, b, ctx, collector, input_index=0):\n"
        "        jit(lambda x: x)(b)\n"
    )
    assert "LR111" in ids_of(lint_source(frm, "arroyo_tpu/windows/x.py"))
    # jit in a once-per-config builder (not a hot-path method) is the
    # sanctioned pattern — slot_agg's _build_slot_jax shape
    good = (
        "import jax\n"
        "def _build(cfg):\n"
        "    return jax.jit(lambda x: x + 1)\n"
        "class Op:\n"
        "    def process_batch(self, b, ctx, collector, input_index=0):\n"
        "        self._fn(b)\n"
    )
    assert "LR111" not in ids_of(lint_source(good, "arroyo_tpu/ops/x.py"))
    # outside operator/window/ops dirs the segment compiler owns jit use
    assert "LR111" not in ids_of(lint_source(bad, "arroyo_tpu/engine/x.py"))
    waived = bad.replace(
        "fn = jax.jit(lambda x: x + 1)",
        "fn = jax.jit(lambda x: x + 1)  # lint: waive LR111 — test fixture")
    assert "LR111" not in ids_of(lint_source(waived, "arroyo_tpu/operators/x.py"))
    # the repo itself must hold the invariant
    from arroyo_tpu.analysis import lint_paths

    assert not [d for d in lint_paths(["arroyo_tpu"])
                if d.rule_id == "LR111"]


def test_waivers():
    bad = (
        "def f():\n"
        "    try:\n"
        "        g()\n"
        "    except Exception:  # lint: waive LR102 — probe failure is expected here\n"
        "        pass\n"
    )
    assert ids_of(lint_source(bad, "arroyo_tpu/engine/x.py")) == set()
    # a waiver without justification does not suppress
    nojust = bad.replace(" — probe failure is expected here", "")
    assert "LR102" in ids_of(lint_source(nojust, "arroyo_tpu/engine/x.py"))
    # a waiver for a different rule does not suppress
    wrong = bad.replace("LR102", "LR105")
    assert "LR102" in ids_of(lint_source(wrong, "arroyo_tpu/engine/x.py"))


# --------------------------------------------------------------- CI gates


def test_lint_fault_sites_in_sync():
    """The linter's literal site list must track faults.SITES exactly."""
    from arroyo_tpu import faults
    from arroyo_tpu.analysis.repo_lint import _DECLARED_FAULT_SITES

    assert set(_DECLARED_FAULT_SITES) == set(faults.SITES)


def test_repo_lints_clean():
    """The CI gate: zero unwaived findings over the whole package."""
    diags = lint_paths([PKG_DIR], root=os.path.dirname(PKG_DIR))
    assert diags == [], "repo lint found:\n" + "\n".join(d.render() for d in diags)


def test_config_reads_are_declared():
    """Every literal key the package reads through ``config().get("a.b")``
    (or ``config().section("a")`` then ``.get("b")``) resolves in
    config._DEFAULTS, so config.py lists every knob the code reads."""
    import ast

    from arroyo_tpu.config import Config, _DEFAULTS

    def is_config(n):
        return (isinstance(n, ast.Call) and isinstance(n.func, ast.Name)
                and n.func.id == "config" and not n.args)

    def literal(call):
        a = call.args[0] if call.args else None
        return a.value if isinstance(a, ast.Constant) and isinstance(a.value, str) else None

    def method(n, name):
        return (isinstance(n, ast.Call) and isinstance(n.func, ast.Attribute)
                and n.func.attr == name)

    declared, missing, seen = Config(_DEFAULTS), [], 0
    for path in sorted(glob.glob(os.path.join(PKG_DIR, "**", "*.py"), recursive=True)):
        with open(path) as f:
            tree = ast.parse(f.read())
        # names bound to config() or to config().section("a") in this file
        prefix = {}
        for n in ast.walk(tree):
            if isinstance(n, ast.Assign) and isinstance(n.targets[0], ast.Name):
                if is_config(n.value):
                    prefix[n.targets[0].id] = ""
                elif method(n.value, "section") and is_config(n.value.func.value) \
                        and literal(n.value):
                    prefix[n.targets[0].id] = literal(n.value) + "."
        for n in ast.walk(tree):
            if not method(n, "get") or literal(n) is None:
                continue
            recv = n.func.value
            if is_config(recv):
                key = literal(n)
            elif isinstance(recv, ast.Name) and recv.id in prefix:
                key = prefix[recv.id] + literal(n)
            else:
                continue
            seen += 1
            if declared.get(key, missing) is missing:
                missing.append(f"{os.path.relpath(path, PKG_DIR)}:{n.lineno} {key}")
    assert seen > 50  # the walk found the reads at all
    assert not missing, "read but not in config._DEFAULTS:\n" + "\n".join(missing)


def test_cli_check_and_lint():
    from arroyo_tpu.cli import main

    bad = os.path.join(BAD_DIR, "unaligned_hop_group_by.sql")
    good = os.path.join(SMOKE, "queries", "select_star.sql")
    # catalog files use harness placeholders; materialize a checkable copy
    import tempfile

    with tempfile.TemporaryDirectory() as td:
        for src, name in ((bad, "bad.sql"), (good, "good.sql")):
            sql = open(src).read().replace("$input_dir", os.path.join(SMOKE, "inputs")) \
                .replace("$output_path", os.path.join(td, "out.json"))
            with open(os.path.join(td, name), "w") as f:
                f.write(sql)
        assert main(["check", os.path.join(td, "bad.sql")]) == 1
        assert main(["check", os.path.join(td, "good.sql")]) == 0
    assert main(["lint", PKG_DIR]) == 0


# ------------------------------------------------------------- determinism


def test_determinism_plan_and_lint():
    """Same input -> byte-identical ordered diagnostics, repeatedly."""
    g = _sliding_graph(10_000_000, 3_000_000)
    # add more findings so ordering is actually exercised
    g.add_node(Node("agg_orphan", OpName.TUMBLING_AGGREGATE,
                    {"key_fields": [], "aggregates": [],
                     "width_micros": 1_000_000}, 1))
    runs = [analyze_graph(g) for _ in range(3)]
    assert runs[0] == runs[1] == runs[2]
    assert len(runs[0]) >= 2
    assert [d.sort_key() for d in runs[0]] == sorted(d.sort_key() for d in runs[0])

    sql, _m, _r = load_bad(os.path.join(BAD_DIR, "dead_memory_branch.sql"))
    d1 = check_sql(sql)[1]
    d2 = check_sql(sql)[1]
    assert d1 == d2 and d1

    src = open(os.path.join(PKG_DIR, "engine", "engine.py")).read()
    assert lint_source(src, "arroyo_tpu/engine/engine.py") == \
        lint_source(src, "arroyo_tpu/engine/engine.py")
