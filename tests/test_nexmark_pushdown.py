"""Projection push-down into the nexmark scan (connectors/nexmark.py,
sql/planner.py _plan_source): the scan synthesises the columns its table
declares and nothing else, every column it does build is bit for bit the
column of the full generator, the planner is the one that says which, and
the tracing shows it (``cols`` on source.generate, the node's description in
``explain``)."""

import os
import string
from types import SimpleNamespace

import numpy as np
import pytest

import arroyo_tpu
from arroyo_tpu import config as cfg
from arroyo_tpu.batch import TIMESTAMP_FIELD
from arroyo_tpu.connectors import nexmark as nexmark_mod
from arroyo_tpu.connectors import register_sink
from arroyo_tpu.connectors.nexmark import NEXMARK_SCHEMA, NexmarkSource
from arroyo_tpu.engine import Engine
from arroyo_tpu.graph import OpName
from arroyo_tpu.metrics import TaskMetrics
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
        assert all(set(s.args) == {"first_event", "rows", "cols", "pieces"} for s in spans)
        assert {s.args["pieces"] for s in spans if s.args["rows"] == 512} == {2}
    text = render_explain(
        [{"id": n.node_id, "op": n.op.value, "description": n.description,
          "parallelism": n.parallelism} for n in graph.nodes.values()],
        [{"src": e.src, "dst": e.dst} for e in graph.edges], {})
    declared = ", ".join(sources[0].config["columns"])
    for node in sources:
        assert f"-> {node.node_id} [nexmark:nexmark [{declared}] x1]" in text


# ------------------------------------------------- a batch built in pieces
#
# numpy lets go of the interpreter lock around an inner loop of more than 500
# elements, so ``NexmarkSource.run`` builds a larger batch in pieces no numpy
# call of which sees more (``_build``): the batch it hands on is the one
# ``_generate`` builds over the whole batch's event numbers, bit for bit.

COLUMN_SETS = {"q7": SUBSETS["q7"], "q5": SUBSETS["q5"], "all-22": None}
# from the stream's start; straddling the 50-event epoch at 1,800,000; a
# restored mid-stream offset in no way aligned to a batch or an epoch
OFFSETS = (0, 1_799_977, 73_141)


def _whole(src: NexmarkSource, first: int, rows: int, p: int, sub: int):
    local = np.arange(first, first + rows, dtype=np.uint64)
    return src._generate(local * np.uint64(p) + np.uint64(sub))


@pytest.mark.parametrize("p,sub", [(1, 0), (4, 3)], ids=["p1", "p4"])
@pytest.mark.parametrize("columns", list(COLUMN_SETS), ids=list(COLUMN_SETS))
@pytest.mark.parametrize("rows", [1, 255, 499, 500, 501, 512, 1000, 4096])
def test_a_batch_built_in_pieces_is_the_whole_batch_bit_for_bit(rows, columns, p, sub):
    for seed in (0, 7, 2**31 + 5):
        src = NexmarkSource({**BASE, "seed": seed, "columns": COLUMN_SETS[columns]})
        for first in OFFSETS:
            want = _whole(src, first, rows, p, sub)
            got, pieces = src._build(first, rows, p, sub)
            assert pieces == -(-rows // nexmark_mod._LOCK_KEPT_ROWS)
            assert got.num_rows == rows and list(got.columns) == list(want.columns)
            for name in want.columns:
                assert _same(got[name], want[name]), (name, seed, first)
                assert got[name].flags.c_contiguous, name


class _Offsets(dict):
    insert = dict.__setitem__


def _drive(src: NexmarkSource, batch_size: int, p: int = 1, sub: int = 0, offset: int = 0,
           job: str = "pieces"):
    """``NexmarkSource.run`` to the end of its stream on this thread, its spans
    recorded: -> the batches it handed on, its source.generate spans, the
    offset it left in its table."""
    table = _Offsets({sub: offset} if offset else {})
    ctx = SimpleNamespace(
        task_info=SimpleNamespace(subtask_index=sub, parallelism=p),
        table_manager=SimpleNamespace(global_keyed=lambda _name: table))
    sctx = SimpleNamespace(ctx=ctx, poll_control=lambda: None, start_checkpoint=None)
    batches: list = []
    trace.bind(job, "src", sub, TaskMetrics(job, "src", sub))
    try:
        with cfg.scoped({"pipeline.source-batch-size": batch_size}):
            src.run(sctx, SimpleNamespace(collect=batches.append))
    finally:
        trace.unbind()
    return batches, trace.spans("source.generate", job=job, node="src"), table[sub]


@pytest.mark.parametrize("p,sub,offset", [(1, 0, 0), (4, 1, 0), (1, 0, 1_300), (4, 2, 777)],
                         ids=["p1", "p4", "p1-restored", "p4-restored"])
def test_the_run_loop_hands_on_whole_batches_built_in_pieces(p, sub, offset, monkeypatch):
    """The shipped 512-row batch, an ``event_count`` whose last batch is short,
    subtask ``sub`` of ``p``, from a restored offset: every batch is 512 rows
    (but the last) and equal to ``_generate`` over its event numbers; no call
    of ``_generate`` saw more rows than numpy keeps the lock for, and ``pieces``
    on the span is the number of calls the batch was built in."""
    src = NexmarkSource({**BASE, "seed": 7, "columns": SUBSETS["q7"], "event_count": 10_000})
    seen: list[int] = []
    generate = NexmarkSource._generate
    monkeypatch.setattr(NexmarkSource, "_generate",
                        lambda self, numbers: seen.append(len(numbers)) or generate(self, numbers))
    job = f"pieces-{p}-{sub}-{offset}"
    batches, spans, left = _drive(src, 512, p, sub, offset, job)
    monkeypatch.undo()
    mine = (10_000 - sub + p - 1) // p
    assert left == mine and sum(b.num_rows for b in batches) == mine - offset
    assert {b.num_rows for b in batches[:-1]} == {512}
    assert 0 < batches[-1].num_rows < 512                    # the short last batch
    assert max(seen) <= nexmark_mod._LOCK_KEPT_ROWS == 500
    assert len(spans) == len(batches)
    assert sum(s.args["pieces"] for s in spans) == len(seen)
    assert [s.args["pieces"] for s in spans] == (
        [2] * (len(batches) - 1) + [1 if batches[-1].num_rows <= 500 else 2])
    assert all(set(s.args) == {"first_event", "rows", "cols", "pieces"} for s in spans)
    first = offset
    for b, s in zip(batches, spans):
        assert (s.args["first_event"], s.args["rows"]) == (first, b.num_rows)
        want = _whole(src, first, b.num_rows, p, sub)
        for name in want.columns:
            assert _same(b[name], want[name]), (name, first)
        first += b.num_rows


@pytest.mark.parametrize("batch_size,pieces", [(500, 1), (501, 2), (1000, 2), (1001, 3), (4096, 9)])
def test_pieces_follow_the_batch_size_against_numpys_threshold(batch_size, pieces):
    src = NexmarkSource({**BASE, "columns": SUBSETS["q5"], "event_count": 2 * batch_size})
    batches, spans, _ = _drive(src, batch_size, job=f"pieces-size-{batch_size}")
    assert [b.num_rows for b in batches] == [batch_size] * 2
    assert [s.args["pieces"] for s in spans] == [pieces] * 2


def test_the_unpruned_stream_in_pieced_batches_is_the_stream_from_before_seeds():
    """The digest tests/test_connectors.py holds the generator to (taken before
    it had seeds, columns or pieces), over the same 5,000 events handed on in
    the shipped 512-row batches."""
    import hashlib

    from arroyo_tpu.batch import Batch

    src = NexmarkSource({"event_count": 5000, "inter_event_micros": 1000,
                         "first_event_micros": 0})
    batches, spans, _ = _drive(src, 512, job="pieces-digest")
    assert {s.args["cols"] for s in spans} == {22}
    b = Batch.concat(batches)
    h = hashlib.sha256()
    for name in sorted(b.columns):
        col = np.asarray(b[name])
        if col.dtype == object:
            h.update("\x00".join("" if v is None else str(v) for v in col).encode())
        else:
            h.update(np.ascontiguousarray(col).tobytes())
    assert h.hexdigest() == "a861453f7b7754498896592baaeb83ce1674b197a420d5bf38d43266d7469839"
