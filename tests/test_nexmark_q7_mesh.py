"""NEXmark Query 7 with its window state sharded over four devices
(``device.mesh-devices: 4``, the deployment ``nexmark-q7-mesh4``), end to end
on four of the CPU's virtual devices at a small size: the benchmark cell's
own query text through ``plan_query`` into the engine, across a checkpoint
and a restore, its sink's rows and both first-level aggregates' output held
to a plain Python computation over the connector's own batches (no code of
``parallel/`` or ``ops/``); every aggregate's state on four devices; the
mesh path's spans and counters; ``explain``'s ``mesh:`` line."""

import json
import os
import string
import time

import numpy as np
import pytest
from test_nexmark_q8 import micros

from arroyo_tpu.batch import TIMESTAMP_FIELD

pytestmark = pytest.mark.mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
QUERY = os.path.join(REPO, "benchmark", "configs", "nexmark-q7-mesh4.sql")
INTER, WIDTH = 5_000, 10_000_000   # 2,000 events a 10 s window, 1,840 of them bids
EVENTS, SEED = 16_000, 39          # eight windows
SHARDS, BATCH_CAP = 4, 1024        # conftest's device.batch-capacity
MESH = {"device.mesh-devices": SHARDS, "engine.coalesce.enabled": False}


def q7_sql(out_path: str, rate: int = 0) -> str:
    with open(QUERY) as f:
        text = string.Template(f.read()).substitute(
            seed=SEED, sink="$sink", event_rate=rate,
            inter_event_micros=INTER, first_event_micros=0)
    text = text.replace("seed = %d" % SEED, "seed = %d,\n  event_count = %d" % (SEED, EVENTS))
    sink = "connector = 'single_file', path = '%s', format = 'json', type = 'sink'" % out_path
    assert "connector = '$sink', type = 'sink'" in text
    return text.replace("connector = '$sink', type = 'sink'", sink)


def the_bids() -> list[tuple]:
    """(window start, auction, price) of every bid, from the connector itself."""
    from arroyo_tpu.connectors.nexmark import NexmarkSource

    src = NexmarkSource({"inter_event_micros": INTER, "first_event_micros": 0, "seed": SEED,
                         "columns": ["bid", "bid.auction", "bid.price"]})
    out = []
    for lo in range(0, EVENTS, 512):
        b = src._generate(np.arange(lo, min(lo + 512, EVENTS)))
        for is_bid, ts, a, p in zip(np.asarray(b["bid"]).tolist(),
                                    np.asarray(b[TIMESTAMP_FIELD]).tolist(),
                                    np.asarray(b["bid.auction"]).tolist(),
                                    np.asarray(b["bid.price"]).tolist()):
            if is_bid:
                out.append((ts // WIDTH * WIDTH, a, p))
    return out


def oracle(bids: list[tuple]) -> tuple[dict, dict, list, int]:
    """-> per window {auction: highest price}, per window the highest
    price, the result rows (window, auction, price) of q7, and how many
    bids there were. Dicts and loops, nothing else."""
    per_auction: dict = {}
    for w, a, p in bids:
        per = per_auction.setdefault(w, {})
        per[a] = max(per.get(a, p), p)
    top = {w: max(per.values()) for w, per in per_auction.items()}
    rows = sorted((w, a, p) for w, per in per_auction.items() for a, p in per.items()
                  if p == top[w])
    return per_auction, top, rows, len(bids)


def tap_aggregates(engine, taps: dict) -> None:
    """Every batch a tumbling aggregate emits, by the aggregate's keys."""
    if not engine.tasks:
        engine.build()
    for (nid, _sub), task in engine.tasks.items():
        node = engine.graph.nodes[nid]
        if node.op.value == "tumbling_aggregate":
            into = taps.setdefault(tuple(node.config.get("key_fields") or ()), [])
            collect = task.collector.collect

            def tapped(batch, *a, _collect=collect, _into=into, **kw):
                _into.append(batch)
                return _collect(batch, *a, **kw)

            task.collector.collect = tapped


def tapped_maxima(batches: list, key: str = None) -> dict:
    """window start -> {key: max} (or -> max for the one-key aggregate), as
    the aggregate emitted them; a window emitted again after the restore
    has to say the same."""
    out: dict = {}
    for b in batches:
        ws = np.asarray(b["window_start"]).tolist()
        vals = np.asarray(b["__agg_0"]).tolist()
        keys = np.asarray(b[key]).tolist() if key else [None] * len(ws)
        for w, k, v in zip(ws, keys, vals):
            per = out.setdefault(w, {})
            assert per.get(k, v) == v, (w, k, per.get(k), v)
            per[k] = v
    return out if key else {w: per[None] for w, per in out.items()}


def sharded_aggregates(engine) -> list:
    from arroyo_tpu.parallel import ShardedAggregator

    aggs = [getattr(t.operator, "_agg", None) for t in engine.tasks.values()]
    return [a for a in aggs if isinstance(a, ShardedAggregator)]


@pytest.fixture(scope="module")
def the_oracle():
    return oracle(the_bids())


@pytest.mark.parametrize("source_batch", [
    # a step a 512-row batch (471 bids of a room of 4,096), and batches as
    # wide as a backlog (3,768 bids: steps of the stage's full 1,024 rows)
    pytest.param(512, id="512-row-batches"),
    pytest.param(SHARDS * BATCH_CAP, id="backlog-width-batches")])
def test_q7_on_the_mesh_equals_the_plain_oracle_across_a_restore(
        source_batch, the_oracle, tmp_path):
    from arroyo_tpu import config as cfg
    from arroyo_tpu.engine import Engine
    from arroyo_tpu.obs import trace
    from arroyo_tpu.sql import plan_query

    per_auction, top, rows, bids = the_oracle
    assert len(per_auction) == EVENTS * INTER // WIDTH == 8 and len(rows) >= 8
    cfg.update(dict(MESH, **{"pipeline.source-batch-size": source_batch}))
    out = str(tmp_path / "highest_bids.json")
    job = f"q7-mesh-{source_batch}"
    # paced, so that the checkpoint falls inside the stream: 2 s of it
    sql = q7_sql(out, rate=8_000)
    taps: dict = {}
    first = Engine(plan_query(sql).graph, job_id=job)
    tap_aggregates(first, taps)
    first.start()
    time.sleep(0.5)
    assert first.checkpoint_and_wait(1, timeout=120).outcome == "completed"
    on_four = sharded_aggregates(first)
    first.stop()
    first.join(timeout=60)
    # both window aggregates are sharded, every leaf of their state over
    # four distinct devices
    import jax

    assert len(on_four) == 2
    for agg in on_four:
        for leaf in jax.tree_util.tree_leaves(agg.state):
            assert len(leaf.devices()) == SHARDS, leaf.sharding
    second = Engine(plan_query(sql).graph, job_id=job, restore_epoch=1)
    tap_aggregates(second, taps)
    second.run_to_completion(timeout=180)

    assert set(taps) == {("bid.auction",), ()}
    assert tapped_maxima(taps[("bid.auction",)], "bid.auction") == per_auction
    assert tapped_maxima(taps[()]) == top
    with open(out) as f:
        got = [json.loads(line) for line in f if line.strip()]
    assert sorted((micros(r["ws"]), r["auction"], r["price"]) for r in got) == rows
    # every step said how full it was, and the two incarnations' steps
    # carried each bid at least once (what followed the checkpoint, twice)
    steps = [s for s in trace.spans("agg.dispatch", job=job) if s.args.get("room")]
    assert steps and all(s.args["room"] == s.args["shards"] * BATCH_CAP == SHARDS * BATCH_CAP
                         and 0 < s.args["rows"] <= s.args["room"] for s in steps)
    assert sum(s.args["rows"] for s in steps) >= 2 * bids  # two aggregates
    # the stage hands over at most device.batch-capacity rows, a quarter of
    # the step's room (ROADMAP A3: fill the step); a batch as wide as a
    # backlog is cut into steps of exactly that width
    assert max(s.args["rows"] for s in steps) <= BATCH_CAP
    full = sum(1 for s in steps if s.args["rows"] == BATCH_CAP)
    if source_batch == 512:
        assert full < len(steps) / 2
    else:
        assert full > len(steps) / 2


@pytest.fixture(scope="module")
def mesh_run(tmp_path_factory):
    """One uninterrupted run with a checkpoint in it, for the spans."""
    from arroyo_tpu import config as cfg
    from arroyo_tpu.engine import Engine
    from arroyo_tpu.metrics import registry
    from arroyo_tpu.sql import plan_query

    tmp = tmp_path_factory.mktemp("mesh")
    job = "q7-mesh-spans"
    settings = dict(MESH, **{
        "checkpoint.storage-url": str(tmp / "ck"), "device.table-capacity": 8192,
        "device.batch-capacity": BATCH_CAP, "device.emit-capacity": 1024,
        "device.max-probes": 32})
    with cfg.scoped(settings):
        engine = Engine(plan_query(q7_sql(str(tmp / "out.json"), rate=8_000)).graph, job_id=job)
        engine.start()
        time.sleep(0.5)
        assert engine.checkpoint_and_wait(1, timeout=120).outcome == "completed"
        engine.join(timeout=180)
        metrics = registry.job_metrics(job)
    return job, engine.graph, metrics


def test_the_mesh_path_records_its_steps_closes_and_snapshots(mesh_run, the_oracle):
    from arroyo_tpu.obs import trace

    job, graph, _metrics = mesh_run
    per_auction, _top, _rows, bids = the_oracle
    aggs = sorted(n for n in graph.nodes if "aggregate" in n)
    assert len(aggs) == 2
    for node in aggs:
        steps = trace.spans("agg.dispatch", node=node, job=job)
        assert steps and all(
            set(s.args) == {"rows", "batches", "rows_in", "made", "shards", "room", "lane_bytes"}
            and s.args["made"] == "numpy"  # on a mesh the hook stays in numpy (PR 53)
            for s in steps)
        # every row the aggregate ingested is in one step's ``rows``: on a
        # mesh the keyless aggregate stages rows too (PR 52: partials there
        # cost q7-mesh4 a seventh of its rate), so ``rows_in`` says the same
        assert sum(s.args["rows"] for s in steps) == bids
        assert all(s.args["rows"] == s.args["rows_in"] for s in steps)
        assert {(s.args["shards"], s.args["room"]) for s in steps} == \
            {(SHARDS, SHARDS * BATCH_CAP)}
        assert all(s.args["batches"] >= 1 and s.t1_ns >= s.t0_ns for s in steps)
        assert trace.spans("agg.directory", node=node, job=job) == []  # no host directory
        closes = trace.spans("agg.close", node=node, job=job)
        assert closes and all(s.args["cap"] == SHARDS * 8192 for s in closes)
        assert all(0 < s.args["rows"] <= s.args["live"] <= s.args["cap"] for s in closes)
        snaps = trace.spans("agg.snapshot", node=node, job=job)
        assert len(snaps) == 1 and snaps[0].args["cap"] == SHARDS * 8192
        assert snaps[0].args["rows"] <= snaps[0].args["live"] and snaps[0].t1_ns > snaps[0].t0_ns
    # the per-auction aggregate's closes read a window's keys each
    keyed = max(aggs, key=lambda n: sum(
        s.args["rows"] for s in trace.spans("agg.close", node=n, job=job)))
    read = sorted(s.args["rows"] for s in trace.spans("agg.close", node=keyed, job=job))
    assert sum(read) == sum(len(per) for per in per_auction.values())
    # a close's trace_id is its window's end, as on one chip
    ends = {s.trace_id for s in trace.spans("agg.close", node=keyed, job=job)}
    assert ends <= {w + WIDTH for w in per_auction}


def test_closes_and_snapshots_say_the_probe_rounds_their_steps_ran(mesh_run):
    """The rounds of the merge's probe loop, counted on the device and read
    where a close or a snapshot has the state on the host anyway: each read
    is a span arg, their sum the counter, the counter a part of ``explain``."""
    from arroyo_tpu.metrics import registry
    from arroyo_tpu.obs import trace
    from arroyo_tpu.obs.profile import job_profile, render_explain

    job, graph, metrics = mesh_run
    aggs = sorted(n for n in graph.nodes if "aggregate" in n)
    per_step = {}
    for node in aggs:
        reads = (trace.spans("agg.close", node=node, job=job)
                 + trace.spans("agg.snapshot", node=node, job=job))
        assert reads and all(s.args["probe_rounds"] >= 0 for s in reads)
        mesh = metrics[node]["mesh"]
        # and the steps that ran behind their exchange at a narrow width: all
        # of them, a step here carrying a few hundred rows at most
        assert mesh["narrow_steps"] == sum(s.args["narrow_steps"] for s in reads)
        assert mesh["narrow_steps"] == mesh["probe_steps"]
        assert mesh["probe_rounds"] == sum(s.args["probe_rounds"] for s in reads) > 0
        assert mesh["max_probes"] == 32 and 0 < mesh["probe_steps"] <= mesh["host_steps"]
        per_step[node] = mesh["probe_rounds"] / mesh["probe_steps"]
        # every step ran a round at least (no step is empty), and a table a
        # few percent full places its rows in a handful, not in the bound's 32
        assert 1 <= per_step[node] <= 6
    # the one-key maximum merges one row a bin; a window's auctions contend
    keyed = max(aggs, key=lambda n: sum(
        s.args["rows"] for s in trace.spans("agg.close", node=n, job=job)))
    assert min(per_step.values()) == 1.0 < per_step[keyed]
    nodes = [{"id": n, "op": "x", "parallelism": 1} for n in metrics]
    text = render_explain(nodes, [], job_profile(metrics))
    lines = [l for l in text.splitlines() if "mesh:" in l]
    assert len(lines) == 2
    for node, line in zip(aggs, lines):
        steps = metrics[node]["mesh"]["probe_steps"]
        assert (f"probe rounds {per_step[node]:.1f} a step of 32, "
                f"{steps:,} of {steps:,} steps narrow") in line, line
    assert f'arroyo_mesh_probe_rounds_total{{job="{job}",operator="{keyed}"' in \
        registry.prometheus_text()


def test_the_steps_counters_and_explain_say_mesh_and_why_not_fused(mesh_run):
    from arroyo_tpu.obs import trace
    from arroyo_tpu.obs.profile import job_profile, render_explain

    job, graph, metrics = mesh_run
    agg = next(n for n in sorted(graph.nodes) if "aggregate" in n)
    m = metrics[agg]
    steps = trace.spans("agg.dispatch", node=agg, job=job)
    assert m["arroyo_worker_steps_dispatched"] == len(steps)
    assert m["arroyo_worker_batches_staged"] == sum(s.args["batches"] for s in steps)
    assert m["mesh"]["shards"] == SHARDS and m["mesh"]["host_steps"] == len(steps)
    assert m["mesh"]["fused_steps"] == 0 and m["mesh"]["overflow_rows"] == 0
    assert m["table"]["capacity"] == SHARDS * 8192
    nodes = [{"id": n, "op": "x", "parallelism": 1} for n in metrics]
    text = render_explain(nodes, [], job_profile(metrics))
    line = next(l for l in text.splitlines() if "mesh:" in l)
    assert "mesh: 4 shards, host prefix (fused program refused: the aggregate is in no " \
           "chained run (pipeline.chaining.enabled))" in line
    assert f"steps {len(steps)} of " in text and "over 4 shards" in text


@pytest.mark.parametrize("where,says", [
    pytest.param(True, "host prefix (fused program refused: a filter behind the chain's "
                       "first member", id="a-WHERE-refuses-the-fused-program"),
    pytest.param(False, "mesh: 4 shards, fused", id="no-WHERE-fuses")])
def test_explain_says_which_mesh_path_a_chained_job_took(where, says, tmp_path):
    """B12: chained and compiled, the planner's watermark -> filter -> key
    order keeps the fused mesh program off any query with a WHERE."""
    from arroyo_tpu import config as cfg
    from arroyo_tpu.engine import Engine
    from arroyo_tpu.metrics import registry
    from arroyo_tpu.obs.profile import job_profile, render_explain
    from arroyo_tpu.sql import plan_query

    sql = """CREATE TABLE nexmark ("bid" BOOLEAN, "bid.auction" BIGINT, "bid.price" BIGINT)
    WITH (connector = 'nexmark', inter_event_micros = 5000, first_event_micros = 0,
          event_count = 8000, seed = 7);
    CREATE TABLE out (auction BIGINT, mx BIGINT, ws TIMESTAMP)
    WITH (connector = 'single_file', path = '%s', format = 'json', type = 'sink');
    INSERT INTO out SELECT auction, mx, window.start FROM (
      SELECT "bid.auction" AS auction, max("bid.price") AS mx,
        tumble(interval '10 seconds') AS window
      FROM nexmark %s GROUP BY "bid.auction", window);
    """ % (tmp_path / "out.json", 'WHERE "bid"' if where else "")
    job = f"mesh-explain-{int(where)}"
    cfg.update(dict(MESH, **{
        "pipeline.chaining.enabled": True, "segment.compile.min-rows": 1,
        "pipeline.source-batch-size": 256, "device.spill-capacity": 1024}))
    Engine(plan_query(sql).graph, job_id=job).run_to_completion(timeout=180)
    metrics = registry.job_metrics(job)
    nodes = [{"id": n, "op": "x", "parallelism": 1} for n in metrics]
    text = render_explain(nodes, [], job_profile(metrics))
    assert says in text, text


def test_the_mesh_programs_are_compiled_when_the_job_is_built_not_in_the_stream(tmp_path):
    """``Engine.build`` prepares every window aggregate on the mesh: the
    sharded store exists and its step and extraction have run once on no
    rows before a task starts, so the stream never waits for their compile
    (minutes on a cold cache, the barriers of that time queued behind it);
    the first batch finds the store built for the lanes it asks for, and
    the run compiles neither program again."""
    from arroyo_tpu import config as cfg
    from arroyo_tpu.engine import Engine, construct_operator
    from arroyo_tpu.parallel import ShardedAggregator
    from arroyo_tpu.sql import plan_query

    cfg.update(MESH)
    graph = plan_query(q7_sql(str(tmp_path / "out.json"))).graph
    # an operator built to be looked at (analysis/) compiles nothing
    node = next(n for n in graph.nodes.values() if n.op.value == "tumbling_aggregate")
    assert construct_operator(node.op, dict(node.config))._agg is None
    engine = Engine(graph, job_id="q7-mesh-prepared")
    engine.build()
    built = sharded_aggregates(engine)
    assert len(built) == 2 and all(isinstance(a, ShardedAggregator) for a in built)
    assert sorted(len(a.acc_kinds) for a in built) == [1, 2]  # the key's lane is there
    for agg in built:
        assert agg._step._cache_size() == 1 and agg._extract._cache_size() == 1
        assert agg.host_steps == 0 and agg.mesh_stats()["probe_rounds"] == 0
        assert not np.asarray(agg.state[2]).any() and not np.asarray(agg.state[-1]).any()
    engine.run_to_completion(timeout=180)
    assert [id(a) for a in sharded_aggregates(engine)] == [id(a) for a in built]
    for agg in built:
        assert agg.host_steps > 0
        assert agg._step._cache_size() == 1 and agg._extract._cache_size() == 1


def test_a_store_prepared_for_other_lanes_than_the_first_batch_asks_for_is_dropped(tmp_path):
    from arroyo_tpu import config as cfg
    from arroyo_tpu.batch import Batch
    from arroyo_tpu.engine import construct_operator
    from arroyo_tpu.sql import plan_query

    cfg.update(MESH)
    graph = plan_query(q7_sql(str(tmp_path / "out.json"))).graph
    node = next(n for n in graph.nodes.values()
                if n.op.value == "tumbling_aggregate" and n.config["key_fields"])
    op = construct_operator(node.op, dict(node.config))
    op.prepare()
    prepared = op._agg
    assert prepared is not None and len(prepared.acc_kinds) == 2
    key = node.config["key_fields"][0]
    op._setup_key_transport(Batch({key: np.array(["a", "b"], dtype=object)}))
    assert op._agg is None and op.dict_key_fields == [key]  # a string key: no lane
    again = construct_operator(node.op, dict(node.config))
    again.prepare()
    again._setup_key_transport(Batch({key: np.arange(2, dtype=np.int64)}))
    assert again._agg is not None and again._aggregator() is again._agg
