"""Projection push-down into the nexmark scan (connectors/nexmark.py,
sql/planner.py _plan_source): the scan synthesises the columns its table
declares and nothing else, every column it does build is bit for bit the
column of the full generator, the planner is the one that says which, and
the tracing shows it (``cols`` on source.generate, the node's description in
``explain``)."""

import os
import string

import numpy as np
import pytest

import arroyo_tpu
from arroyo_tpu import config as cfg
from arroyo_tpu.batch import TIMESTAMP_FIELD
from arroyo_tpu.connectors import register_sink
from arroyo_tpu.connectors.nexmark import NEXMARK_SCHEMA, NexmarkSource
from arroyo_tpu.engine import Engine
from arroyo_tpu.graph import OpName
from arroyo_tpu.obs import trace
from arroyo_tpu.obs.profile import render_explain
from arroyo_tpu.operators.base import Operator
from arroyo_tpu.sql import plan_query

arroyo_tpu._load_operators()

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ALWAYS = {"person", "auction", "bid", TIMESTAMP_FIELD}
STRINGS = {f.name for f in NEXMARK_SCHEMA.fields if f.dtype == "string"}
BASE = {"inter_event_micros": 100, "first_event_micros": 0}

SUBSETS = {
    "q7": ["bid", "bid.auction", "bid.price"],
    "q5": ["bid", "bid.auction"],
    "price-alone": ["bid.price"],
    "bidder-seller": ["bid.bidder", "auction.seller"],
    "auction-numbers": ["auction.id", "auction.initial_bid", "auction.reserve",
                        "auction.expires", "auction.category", "event_type"],
    "item-name-without-id": ["auction.item_name"],
    "person-strings": ["person.id", "person.name", "person.email_address",
                       "person.city", "person.state"],
    "channel-datetime": ["bid.channel", "bid.datetime"],
    "every-column": NEXMARK_SCHEMA.names(),
}


def _same(a, b) -> bool:
    a, b = np.asarray(a), np.asarray(b)
    if a.dtype != b.dtype or a.shape != b.shape:
        return False
    return a.tolist() == b.tolist() if a.dtype == object else bool(np.array_equal(a, b))


@pytest.mark.parametrize("first_event,rows", [(0, 512), (1_799_990, 257), (10**12 + 3, 50)],
                         ids=["from-0", "mid-stream", "far"])
@pytest.mark.parametrize("seed", [0, 7, 2**31 + 5])
@pytest.mark.parametrize("subset", list(SUBSETS), ids=list(SUBSETS))
def test_a_pruned_scan_builds_the_full_scans_columns_bit_for_bit(subset, seed, first_event, rows):
    numbers = np.arange(first_event, first_event + rows, dtype=np.uint64)
    full = NexmarkSource({**BASE, "seed": seed})._generate(numbers)
    assert set(full.columns) == set(NEXMARK_SCHEMA.names())
    pruned = NexmarkSource({**BASE, "seed": seed, "columns": SUBSETS[subset]})._generate(numbers)
    assert set(pruned.columns) == ALWAYS | set(SUBSETS[subset])
    assert pruned.num_rows == rows
    for name in pruned.columns:
        assert _same(pruned[name], full[name]), name
        assert np.asarray(full[name]).dtype == NEXMARK_SCHEMA.field(name).numpy_dtype(), name


def test_only_the_lanes_a_built_column_reads_are_drawn(monkeypatch):
    from arroyo_tpu.connectors import nexmark

    drawn = []
    rng = nexmark._rng
    monkeypatch.setattr(nexmark, "_rng", lambda n, salt, seed=0: drawn.append(salt) or rng(n, salt, seed))
    numbers = np.arange(512, dtype=np.uint64)
    for columns, salts in [(["bid"], []), (SUBSETS["q7"], [1, 2]), (["bid.price"], [2]),
                           (["bid.channel", "person.state", "auction.reserve"], [3]),
                           (None, [1, 2, 3, 4])]:
        drawn.clear()
        NexmarkSource({**BASE, "columns": columns})._generate(numbers)
        assert sorted(drawn) == salts, columns


@pytest.mark.parametrize("given,built", [
    ({}, set(NEXMARK_SCHEMA.names())),
    ({"include_strings": False}, set(NEXMARK_SCHEMA.names()) - STRINGS),
    # chip_smoke.py, tests/test_segment.py, benchmark/tests/test_stream.py
    ({"include_strings": False, "columns": ["bid.auction", "bid.price"]},
     ALWAYS | {"bid.auction", "bid.price"}),
    ({"include_strings": False, "columns": ["bid.auction", "bid.channel"]},
     ALWAYS | {"bid.auction"}),
    ({"columns": ["bid.channel"]}, ALWAYS | {"bid.channel"}),
    ({"columns": None}, set(NEXMARK_SCHEMA.names())),
], ids=["nothing", "no-strings", "hand-built", "hand-built-string-switched-off",
        "one-string", "columns-none"])
def test_what_a_hand_built_graph_passes_still_means_what_it_meant(given, built):
    batch = NexmarkSource({**BASE, **given})._generate(np.arange(100, dtype=np.uint64))
    assert set(batch.columns) == built


# ------------------------------------------------------------ the planner

SINK = "CREATE TABLE out ({columns}) WITH (connector = 'pushdown_test_sink', type = 'sink');\n"


class _Sink(Operator):
    def __init__(self, cfg_):
        pass

    def process_batch(self, batch, ctx, collector, input_index=0):
        pass


register_sink("pushdown_test_sink")(_Sink)


def _sources(graph):
    return [n for n in graph.nodes.values() if n.op == OpName.SOURCE]


def _benchmark_sql(config: str) -> str:
    with open(os.path.join(ROOT, "benchmark", "configs", config + ".sql")) as f:
        return string.Template(f.read()).substitute(
            seed=7, sink="pushdown_test_sink", event_rate="0",
            inter_event_micros=100, first_event_micros=0)


@pytest.mark.parametrize("config,declared", [
    ("nexmark-q7-highest-bid", ["bid", "bid.auction", "bid.price"]),
    ("nexmark-q7-minute", ["bid", "bid.auction", "bid.price"]),
    ("nexmark-q5-hot-items", ["bid", "bid.auction"]),
])
def test_the_planner_hands_each_scan_of_a_benchmark_query_its_declared_columns(config, declared):
    sources = _sources(plan_query(_benchmark_sql(config)).graph)
    assert len(sources) == 2
    for node in sources:
        assert node.config["columns"] == declared
        assert "include_strings" not in node.config
        assert node.description == f"nexmark:nexmark [{', '.join(declared)}]"
        batch = NexmarkSource(node.config)._generate(np.arange(64, dtype=np.uint64))
        assert set(batch.columns) == ALWAYS | set(declared)
        assert not STRINGS & set(batch.columns)


def test_a_table_declared_without_columns_keeps_all_22():
    sql = ("CREATE TABLE nexmark WITH (connector = 'nexmark', event_count = 100);\n"
           + SINK.format(columns="n BIGINT") + "INSERT INTO out SELECT count(*) FROM nexmark;")
    (node,) = _sources(plan_query(sql).graph)
    assert "columns" not in node.config and node.description == "nexmark:nexmark"
    batch = NexmarkSource(node.config)._generate(np.arange(64, dtype=np.uint64))
    assert set(batch.columns) == set(NEXMARK_SCHEMA.names()) and len(batch.columns) == 22


def test_a_declared_string_column_brings_that_string_and_no_other():
    sql = ('CREATE TABLE nexmark ("bid" BOOLEAN, "bid.channel" TEXT) '
           "WITH (connector = 'nexmark', event_count = 100);\n"
           + SINK.format(columns="channel TEXT")
           + 'INSERT INTO out SELECT "bid.channel" FROM nexmark WHERE "bid";')
    (node,) = _sources(plan_query(sql).graph)
    assert node.config["columns"] == ["bid", "bid.channel"]
    numbers = np.arange(200, dtype=np.uint64)
    batch = NexmarkSource(node.config)._generate(numbers)
    assert STRINGS & set(batch.columns) == {"bid.channel"}
    assert _same(batch["bid.channel"], NexmarkSource({})._generate(numbers)["bid.channel"])


def test_only_a_nexmark_source_is_given_columns(tmp_path):
    sql = ("CREATE TABLE t (a BIGINT) WITH (connector = 'single_file', "
           f"path = '{tmp_path}/in.json', format = 'json', type = 'source');\n"
           + SINK.format(columns="a BIGINT") + "INSERT INTO out SELECT a FROM t;")
    (node,) = _sources(plan_query(sql).graph)
    assert "columns" not in node.config and node.description == "single_file:t"


# ------------------------------------------------------------ the tracing


@pytest.mark.parametrize("config,cols", [("nexmark-q7-highest-bid", 6), ("nexmark-q5-hot-items", 5)])
def test_the_spans_and_explain_show_the_push_down(config, cols, tmp_path):
    """The benchmark's query, 20,000 events: every source.generate span of
    both scans says how many columns it built, and explain names them."""
    sql = _benchmark_sql(config).replace("seed = 7", "seed = 7,\n  event_count = 20000")
    graph = plan_query(sql).graph
    job = f"pushdown-{config}"
    with cfg.scoped({"device.table-capacity": 4096}):
        Engine(graph, job_id=job, storage_url=str(tmp_path / job)).run_to_completion()
    sources = _sources(graph)
    for node in sources:
        spans = trace.spans("source.generate", job=job, node=node.node_id)
        assert sum(s.args["rows"] for s in spans) == 20_000
        assert {s.args["cols"] for s in spans} == {cols}
        assert all(set(s.args) == {"first_event", "rows", "cols"} for s in spans)
    text = render_explain(
        [{"id": n.node_id, "op": n.op.value, "description": n.description,
          "parallelism": n.parallelism} for n in graph.nodes.values()],
        [{"src": e.src, "dst": e.dst} for e in graph.edges], {})
    declared = ", ".join(sources[0].config["columns"])
    for node in sources:
        assert f"-> {node.node_id} [nexmark:nexmark [{declared}] x1]" in text
