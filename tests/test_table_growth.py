"""A slot table that runs out of regions grows (ops/slot_agg.py _grow): the
directory in place, the device state padded, the new capacity's programs
built and warmed, closes in flight untouched, snapshots restored at any
size, nothing compiled afterwards; past the ceiling rows spill as before.
Held to the numpy backend, and from SQL text to the benchmark's plain
reference of NEXmark q7 at the one-minute window."""

import importlib.util
import json
import os
import time

import numpy as np
import pytest

from arroyo_tpu import config as cfg
from arroyo_tpu.engine import Engine
from arroyo_tpu.metrics import TaskMetrics, registry
from arroyo_tpu.obs import trace
from arroyo_tpu.obs.events import recorder as events
from arroyo_tpu.ops import HostAggregator, slot_agg
from arroyo_tpu.ops.slot_agg import BinSlotDirectory, SlotAggregator
from arroyo_tpu.sql import plan_query

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
KW = dict(cap=64, batch_cap=64, region_size=16)
LANES = [
    (("count", "sum"), (np.int64, np.int64)),
    (("min", "max"), (np.int64, np.int64)),
    (("sum", "min", "max"), (np.float64, np.float64, np.float64)),
    (("max", "count"), (np.int32, np.int32)),
    (("sum", "max"), (np.float32, np.float32)),
]
LANE_IDS = ["count-sum-i64", "min-max-i64", "sum-min-max-f64", "max-count-i32", "sum-max-f32"]

# every backend compile jax makes, as benchmark/harness/probes.py CompileLog
# counts them (a listener cannot be taken off again: one for the module)
_COMPILES: list[str] = []


@pytest.fixture(scope="module", autouse=True)
def _compile_log():
    import jax.monitoring

    def on_duration(event, _seconds, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            _COMPILES.append(str(kw.get("fun_name")))

    jax.monitoring.register_event_duration_secs_listener(on_duration)


def _pair(kinds=("count", "sum"), dtypes=(np.int64, np.int64), **kw):
    args = {**KW, **kw}
    return SlotAggregator(kinds, dtypes, **args), HostAggregator(kinds, dtypes)


def _table(keys, bins, accs):
    return {(int(k), int(b)): tuple(float(a[i]) for a in accs)
            for i, (k, b) in enumerate(zip(keys.tolist(), bins.tolist()))}


def _feed(aggs, kinds, keys, bin_, rng):
    n = len(keys)
    vals = rng.integers(1, 1000, n)
    ins = [np.ones(n, dtype=np.int64) if k == "count" else vals for k in kinds]
    for a in aggs:
        a.update(keys.astype(np.uint64), np.full(n, bin_, dtype=np.int32), ins)


# ------------------------------------------------------------ the aggregate


@pytest.mark.parametrize("kinds,dtypes", LANES, ids=LANE_IDS)
def test_growths_with_a_close_in_flight_match_the_numpy_backend(kinds, dtypes):
    """Bin 0 is closed (dispatched, not read), then bins 1 and 2 outgrow
    the table three times, then bin 0's rows are read: what it would have
    delivered. Every later close is exact too, nothing spills."""
    rng = np.random.default_rng(5)
    jx, ora = _pair(kinds, dtypes)
    _feed((jx, ora), kinds, np.arange(40), 0, rng)
    _feed((jx, ora), kinds, np.arange(20, 50), 0, rng)
    in_flight = jx.extract_start(0, 1, 1)
    want0 = _table(*ora.extract(0, 1, 1))
    for lo in (0, 60, 120, 180):
        _feed((jx, ora), kinds, np.arange(lo, lo + 60), 1, rng)
        _feed((jx, ora), kinds, np.arange(lo, lo + 30), 2, rng)
    assert jx.cap == 8 * KW["cap"] and not jx.spill  # 360 groups: three growths
    assert _table(*in_flight.result()) == want0 and len(want0) == 50
    _feed((jx, ora), kinds, np.arange(100, 160), 1, rng)  # keys that own slots
    assert _table(*jx.extract(1, 2, 2)) == _table(*ora.extract(1, 2, 2))
    assert _table(*jx.extract(2, 3, 3)) == _table(*ora.extract(2, 3, 3))
    assert jx.directory.live_slots() == 0
    assert len(jx.directory.free_regions) == jx.cap // KW["region_size"]


@pytest.mark.parametrize("kinds,dtypes", LANES[:3], ids=LANE_IDS[:3])
def test_a_snapshot_at_a_grown_size_restores_into_a_fresh_aggregate(kinds, dtypes, monkeypatch):
    """The fresh aggregate starts at the initial capacity and grows on the
    way in, once, to the capacity the snapshot needs (one pad, one warm-up);
    a snapshot from before the growth restores into the grown one."""
    rng = np.random.default_rng(6)
    jx, ora = _pair(kinds, dtypes)
    _feed((jx, ora), kinds, np.arange(30), 3, rng)
    before = jx.snapshot()
    before_want = _table(*ora.snapshot())
    _feed((jx, ora), kinds, np.arange(200), 3, rng)
    _feed((jx, ora), kinds, np.arange(100), 4, rng)
    assert jx.cap > KW["cap"]
    snap = jx.snapshot()
    assert _table(*snap) == _table(*ora.snapshot()) and len(snap[0]) == 300
    fresh, _ = _pair(kinds, dtypes)
    warmed = []
    monkeypatch.setattr(SlotAggregator, "_warm", lambda self, _w=SlotAggregator._warm:
                        warmed.append(self.cap) or _w(self))
    fresh.restore(*snap)
    # 13 regions of bin 3 and 7 of bin 4: 512 slots, reached in one growth
    assert fresh.cap == 512 and warmed == [512] and not fresh.spill
    _feed((fresh, ora), kinds, np.arange(150, 250), 3, rng)
    assert _table(*fresh.extract(3, 5, 5)) == _table(*ora.extract(3, 5, 5))
    grown = jx.cap
    jx.restore(*before)
    assert jx.cap == grown and _table(*jx.extract(3, 4, 4)) == before_want


def test_nothing_compiles_after_a_growth():
    """Steps (full and padded batches), closes of every region-count bucket
    with and without clearing, a clear alone, snapshots, a restore's merge
    steps and point reads: after the growth that warmed them, no backend
    compile."""
    rng = np.random.default_rng(7)
    kinds = ("count", "max")
    jx, ora = _pair(kinds, (np.int64, np.int64), cap=256, region_size=16)
    jx.read_slots(np.arange(3))  # a point-read bucket met before the growth
    _feed((jx, ora), kinds, np.arange(260), 0, rng)
    assert jx.cap == 512
    mark = len(_COMPILES)
    assert _table(*jx.extract(0, 1, 1)) == _table(*ora.extract(0, 1, 1))
    for bin_, n in ((1, 1), (2, 17), (3, 40), (4, 100), (5, 200)):  # 1, 2, 4, 8, 16 regions
        for lo in range(0, n, 64):
            _feed((jx, ora), kinds, np.arange(lo, min(lo + 64, n)), bin_, rng)
    assert _table(*jx.snapshot()) == _table(*ora.snapshot())
    assert _table(*jx.scan_range(3, 5)) == _table(*ora.scan_range(3, 5))  # no clearing
    for bin_ in range(1, 6):
        assert _table(*jx.extract(bin_, bin_ + 1, bin_ + 1)) == \
            _table(*ora.extract(bin_, bin_ + 1, bin_ + 1))
    _feed((jx, ora), kinds, np.arange(70), 6, rng)
    jx.free_bins_below(7)  # the clear alone
    ora.free_bins_below(7)
    _feed((jx, ora), kinds, np.arange(20), 8, rng)
    jx.read_slots(np.arange(5))
    jx.restore(*jx.snapshot())  # the merge step, at the grown capacity
    assert _table(*jx.extract(8, 9, 9)) == _table(*ora.extract(8, 9, 9))
    assert _COMPILES[mark:] == [] and jx.cap == 512


def test_past_the_ceiling_rows_spill_as_before(monkeypatch):
    """The ceiling is a share of the device's memory, all lanes counted.
    A backend that reports none is taken to have _UNREPORTED_MEMORY_BYTES:
    here enough for one doubling."""
    lane_bytes = 16  # count + sum, int64
    monkeypatch.setattr(slot_agg, "_UNREPORTED_MEMORY_BYTES",
                        int(2 * KW["cap"] * lane_bytes / slot_agg._TABLE_MEMORY_SHARE))
    rng = np.random.default_rng(8)
    jx, ora = _pair()
    assert jx._ceiling() == 2 * KW["cap"]
    _feed((jx, ora), ("count", "sum"), np.arange(200), 0, rng)
    _feed((jx, ora), ("count", "sum"), np.arange(200), 0, rng)
    assert jx.cap == 2 * KW["cap"] and len(jx.spill) == 200 - 2 * KW["cap"]
    assert _table(*jx.snapshot()) == _table(*ora.snapshot())
    assert _table(*jx.extract(0, 1, 1)) == _table(*ora.extract(0, 1, 1))
    assert not jx.spill


@pytest.mark.parametrize("host_bytes,want", [
    (1 << 50, int(16 * 2**30 * slot_agg._TABLE_MEMORY_SHARE) // 8),
    (40 * 2**30, int(40 * 2**30 * slot_agg._TABLE_MEMORY_SHARE)
     // slot_agg._DIRECTORY_BYTES_PER_SLOT),
], ids=["the-device-binds", "the-host-binds"])
def test_the_ceiling_follows_the_memory_the_device_and_the_host_report(
        monkeypatch, host_bytes, want):
    """All lanes counted on the device, the directory's 112 bytes a slot on
    the host; the smaller of the two, and read once."""
    jx, _ = _pair(("max",), (np.int64,))
    asked = []

    class Device:
        def memory_stats(self):
            asked.append(1)
            return {"bytes_limit": 16 * 2**30}

    monkeypatch.setattr(type(jx.state[0]), "devices", lambda self: {Device()})
    monkeypatch.setattr(slot_agg, "_host_memory_bytes", lambda: host_bytes)
    assert jx._ceiling() == want == jx._ceiling() and len(asked) == 1


# ------------------------------------------------------------ the directory


@pytest.mark.parametrize("native_path", [True, False], ids=["native", "numpy"])
def test_the_directory_grows_in_place(native_path, monkeypatch):
    """Assigned slots keep their numbers, closed bins fall out of the
    rebuilt table, and both resolve paths find every open group again."""
    from arroyo_tpu import native

    if native_path and not native.available():
        pytest.skip("native library unavailable")
    if not native_path:
        monkeypatch.setattr(native, "dir_resolve", lambda *a, **k: None)
    jx, _ = _pair(("max",), (np.int64,))
    keys = np.arange(30, dtype=np.uint64)
    _ks, _b, closed, _left = jx._resolve_slots(keys, np.zeros(30, dtype=np.int64))
    _ks, _b, open_, _left = jx._resolve_slots(keys, np.ones(30, dtype=np.int64))
    jx.extract(0, 1, 1)
    d = jx.directory
    entries = int((d.hslot >= 0).sum())
    d.grow(256)
    assert (d.cap, d.n_regions, len(d.slot_keys), len(d.region_fill)) == (256, 16, 256, 16)
    assert d.hcap == 1 << (256).bit_length() + 1 and len(d.hslot) == d.hcap
    assert int((d.hslot >= 0).sum()) == 30 < entries  # bin 0's entries are gone
    assert sorted(d.free_regions) == [0, 1, *range(4, 16)] and d.free_regions[-1] < 4
    _ks, _b, again, _left = jx._resolve_slots(keys, np.ones(30, dtype=np.int64))
    assert again.tolist() == open_.tolist()
    _ks, _b, new, _left = jx._resolve_slots(np.arange(30, 230, dtype=np.uint64),
                                            np.ones(200, dtype=np.int64))
    assert (new >= 0).all() and len(set(new.tolist()) | set(open_.tolist())) == 230


def test_insert_keeps_groups_that_share_a_position():
    d = BinSlotDirectory(cap=32, region_size=16)
    codes = np.arange(20, dtype=np.uint64) * np.uint64(d.hcap)  # all hash to position 0
    slots = d.lookup_or_assign(codes, np.arange(20, dtype=np.int64), np.zeros(20, dtype=np.int64))
    d.grow(64)
    # the codes of a rebuilt table are the real ones: resolve by identity
    agg_codes = slot_agg.splitmix64(np.arange(20, dtype=np.uint64))
    got = d.lookup_or_assign(agg_codes, np.arange(20, dtype=np.int64),
                             np.zeros(20, dtype=np.int64))
    assert got.tolist() == slots.tolist()


# ------------------------------------------------- spans, counters, gauges


def test_a_growth_is_on_the_ring_the_counters_and_the_job_feed():
    def run():
        m = TaskMetrics("grow-job", "agg_1", 0)
        trace.bind("grow-job", "agg_1", 0, m)
        rng = np.random.default_rng(9)
        jx, ora = _pair()
        _feed((jx, ora), ("count", "sum"), np.arange(100), 0, rng)
        handle = jx.extract_start(0, 1, 1)
        handle.result()
        jx.snapshot()
        trace.unbind()
        return m

    box = []
    import threading

    t = threading.Thread(target=lambda: box.append(run()))
    t.start()
    t.join(120)
    m = box[0]
    assert m.counters["arroyo_worker_table_grows"] == 1
    assert m.table == {"capacity": 128, "live_slots": 0}
    (grow,) = trace.spans("agg.grow", job="grow-job")
    assert grow.args["cap_before"] == 64 and grow.args["cap_after"] == 128
    assert grow.args["live"] == 64 and grow.args["pad_bytes"] == (64 + 128) * 16
    assert grow.args["warmed"] == 2 + 2 * 4 + 1 and grow.t1_ns > grow.t0_ns
    (close,) = trace.spans("agg.close", job="grow-job")
    assert close.args == {"rows": 100, "lanes": 2, "live": 100, "cap": 128}
    assert trace.spans("agg.snapshot", job="grow-job")[-1].args == {
        "rows": 0, "live": 0, "cap": 128}
    marks = trace.spans("task.account", job="grow-job")
    assert marks[-1].args["table_grows"] == 1
    (ev,) = [e for e in events.events("grow-job") if e["code"] == "TABLE_GROWN"]
    assert ev["node"] == "agg_1" and ev["data"] == {
        "capacity_before": 64, "capacity_after": 128, "live_slots": 64}
    text = registry.prometheus_text()
    assert "# TYPE arroyo_worker_table_grows counter" in text
    assert "# TYPE arroyo_worker_table_capacity gauge" in text


def test_explain_and_top_show_the_table():
    from arroyo_tpu.obs.profile import _annotations, job_profile
    from arroyo_tpu.obs.topview import render

    m = registry.task("table-view", "tumbling_aggregate_3", 0)
    m.table = {"capacity": 131072, "live_slots": 81392}
    m.add("arroyo_worker_table_grows")
    metrics = registry.job_metrics("table-view")
    assert metrics["tumbling_aggregate_3"]["table"] == m.table
    prof = job_profile(metrics)["tumbling_aggregate_3"]
    assert "table: 81,392 of 131,072 slots (62.1%)  grown 1x" in _annotations(prof)
    frame = render({"id": "table-view", "state": "Running"}, metrics)
    assert "81,392/131,072 +1" in frame
    text = registry.prometheus_text()
    label = 'job="table-view",operator="tumbling_aggregate_3",subtask="0"'
    assert f"arroyo_worker_table_capacity{{{label}}} 131072" in text
    assert f"arroyo_worker_table_live_slots{{{label}}} 81392" in text
    registry.clear_job("table-view")


# ------------------------------------------------------------ from SQL text

SOURCE = """CREATE TABLE nexmark ("bid" BOOLEAN, "bid.auction" BIGINT, "bid.price" BIGINT)
WITH (connector = 'nexmark', inter_event_micros = 5000, first_event_micros = 0,
      event_count = {events}, event_rate = 0, seed = {seed});
"""
SINK = """CREATE TABLE out ({columns}, ws TIMESTAMP)
WITH (connector = 'single_file', path = '{path}', format = 'json', type = 'sink');
"""
SLIDING = """INSERT INTO out SELECT auction, num, window.start FROM (
  SELECT "bid.auction" AS auction, count(*) AS num,
    hop(interval '2 seconds', interval '10 seconds') AS window
  FROM nexmark WHERE "bid" GROUP BY "bid.auction", window);
"""
SMALL = {"device.table-capacity": 1024, "device.region-size": 128,
         "pipeline.source-batch-size": 512}


def _rows(path: str, columns: list[str]) -> list[tuple]:
    with open(path) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    return sorted(tuple(r[c] for c in columns) + (r["ws"],) for r in rows)


def _grows(job: str) -> dict:
    return {n: m["arroyo_worker_table_grows"] for n, m in registry.job_metrics(job).items()
            if m["arroyo_worker_table_grows"]}


def test_the_sliding_operator_grows(tmp_path):
    """Five bins an event: a small table outgrown from SQL text gives the
    rows a table that never grows gives."""
    out = {}
    for name, settings in (("grown", SMALL), ("roomy", dict(SMALL, **{
            "device.table-capacity": 65536, "device.region-size": 2048}))):
        path = str(tmp_path / f"{name}.json")
        sql = (SOURCE.format(events=20_000, seed=3)
               + SINK.format(columns="auction BIGINT, num BIGINT", path=path) + SLIDING)
        with cfg.scoped(settings):
            Engine(plan_query(sql).graph, job_id=f"slide-{name}",
                   storage_url=str(tmp_path / name)).run_to_completion()
        out[name] = _rows(path, ["auction", "num"])
    assert out["grown"] == out["roomy"] and len(out["grown"]) > 5_000
    # once or twice: how many bins are open at once depends on when closes land
    assert sum(_grows("slide-grown").values()) >= 1 and not _grows("slide-roomy")
    codes = [e["code"] for e in events.events("slide-grown")]
    assert codes.count("TABLE_GROWN") == sum(_grows("slide-grown").values())


@pytest.mark.parametrize("aggs", [
    [("n", "count", None), ("total", "sum", "v")],
    [("total", "sum", "v")],
], ids=["count-sum", "sum-alone"])
def test_the_updating_aggregate_grows_and_compacts_into_the_capacity_it_needs(aggs, tmp_path):
    """400 keys outgrow a 64-slot table (to 512); half of them go idle past
    the TTL, which is a quarter of the table dead: the compaction restores
    the 200 live keys into a fresh table that grows once, to the 256 slots
    they need, not doubling by doubling. Equal to the host path throughout."""
    from arroyo_tpu.batch import KEY_FIELD, TIMESTAMP_FIELD, Batch
    from arroyo_tpu.expr import Col
    from arroyo_tpu.hashing import hash_columns
    from arroyo_tpu.operators.base import OperatorContext
    from arroyo_tpu.operators.updating_aggregate import UpdatingAggregate, merge_updating_rows
    from arroyo_tpu.state.tables import TableManager
    from arroyo_tpu.types import TaskInfo

    class Collector:
        def __init__(self):
            self.batches = []

        def collect(self, b):
            self.batches.append(b)

        def broadcast(self, s):
            pass

    warmed = []

    def run(backend):
        op = UpdatingAggregate({
            "key_fields": ["k"],
            "aggregates": [(n, k, Col(e) if e else None) for n, k, e in aggs],
            "input_dtype_of": lambda e: np.dtype(np.int64),
            "ttl_micros": 30_000_000, "backend": backend})
        assert op.device_mode == (backend == "jax")
        ti = TaskInfo("upd-grow", "agg", "agg", 0, 1)
        ctx, col = OperatorContext(ti, None, TableManager(ti, str(tmp_path / backend))), Collector()
        rng = np.random.default_rng(41)
        caps = []
        for step in range(9):
            ks = np.arange(400 if step < 4 else 200, dtype=np.int64)
            vs = rng.integers(1, 100, size=len(ks)).astype(np.int64)
            op.process_batch(Batch({
                "k": ks, "v": vs, KEY_FIELD: hash_columns([ks]),
                TIMESTAMP_FIELD: np.full(len(ks), step * 10_000_000, dtype=np.int64)}), ctx, col)
            mark = len(warmed)
            op.handle_tick(ctx, col)
            if op._dev is not None:
                caps.append((op._dev.cap, warmed[mark:], len(op._dev.spill)))
        op.on_close(ctx, col)
        rows = merge_updating_rows([r for b in col.batches for r in b.to_pylist()])
        return sorted(tuple(r[c] for c in ["k"] + [a[0] for a in aggs]) for r in rows), caps

    warm = SlotAggregator._warm
    SlotAggregator._warm = lambda self: warmed.append(self.cap) or warm(self)
    try:
        with cfg.scoped({"device.table-capacity": 64, "device.region-size": 16,
                         "device.batch-capacity": 64}):
            host, _ = run("numpy")
            dev, caps = run("jax")
    finally:
        SlotAggregator._warm = warm
    assert dev == host and len(dev) == 200
    # grown to 512 under 400 keys; the tick that evicts the idle 200
    # compacts: a fresh table, one growth to 256, nothing spilled anywhere
    assert caps[3] == (512, [], 0) and (256, [256], 0) in caps and caps[-1] == (256, [], 0)


def _module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_q7_at_the_one_minute_window_is_the_plain_reference_across_growth_and_restore(tmp_path):
    """The benchmark's q7-minute-sat scaled down: its SQL text, a table that
    starts at 512 slots and grows twice under 700-1,500 auctions a window, a
    checkpoint -> stop -> restore in the stream (the restored aggregate
    grows again on the way in), against benchmark/configs/nexmark-q7-minute.py
    over the benchmark's own copy of the generator. 0 rows spilled."""
    bench = os.path.join(ROOT, "benchmark")
    reference = _module(os.path.join(bench, "configs", "nexmark-q7-minute.py"), "q7_minute_ref")
    stream = _module(os.path.join(bench, "harness", "stream.py"), "q7_minute_stream")
    with open(os.path.join(bench, "configs", "nexmark-q7-minute.sql")) as f:
        text = f.read()
    events_n, seed, inter, width = 54_000, 11, 5_000, 60_000_000
    path = str(tmp_path / "q7.json")
    head, insert = text.split("INSERT INTO", 1)
    assert "interval '1 minute'" in insert
    sql = (SOURCE.format(events=events_n, seed=seed)
           + SINK.format(columns="auction BIGINT, price BIGINT", path=path).replace(
               "TABLE out", "TABLE highest_bids") + "INSERT INTO" + insert)
    spilled = []
    spill = SlotAggregator._spill_update

    def counted(self, keys_i64, bins_i64, vals):
        spilled.append(len(keys_i64))
        return spill(self, keys_i64, bins_i64, vals)

    SlotAggregator._spill_update = counted
    try:
        with cfg.scoped(dict(SMALL, **{"device.table-capacity": 512,
                                       "device.region-size": 64})):
            first = Engine(plan_query(sql).graph, job_id="q7-minute",
                           storage_url=str(tmp_path / "ck"))
            first.start()
            deadline = time.monotonic() + 120
            while (registry.task("q7-minute", _source(first), 0).counters[
                    "arroyo_worker_messages_sent"] < 30_000):
                assert time.monotonic() < deadline
                time.sleep(0.002)
            assert first.checkpoint_and_wait(1, timeout=120).outcome == "completed"
            first.stop()
            first.join(timeout=120)
            grown_before = _grows("q7-minute")
            registry.clear_job("q7-minute")
            Engine(plan_query(sql).graph, job_id="q7-minute", restore_epoch=1,
                   storage_url=str(tmp_path / "ck")).run_to_completion()
    finally:
        SlotAggregator._spill_update = spill
    per_window = width // inter
    want = []
    for lo in range(0, events_n, per_window):
        window = stream.generate(lo, min(lo + per_window, events_n), seed)
        ws = np.datetime64(lo * inter, "us")
        want += [(a, p, str(ws)) for a, p in reference.rows(window)]
    got = [(a, p, str(np.datetime64(ws.rstrip("Z"), "us"))) for a, p, ws in
           _rows(path, ["auction", "price"])]
    assert got == sorted(want) and len(want) >= events_n // per_window
    assert not spilled
    # the per-auction aggregate grew twice before the checkpoint, and the
    # restored one, which starts at 512 slots again, on the way in
    assert max(grown_before.values()) >= 2 and max(_grows("q7-minute").values()) >= 1


def _source(engine) -> str:
    return next(n for n in engine.graph.nodes if n.startswith("source"))
