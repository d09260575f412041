"""Join operators: windowed instant join (inner/left/right/full), updating
join with retractions, lookup join caching."""

from types import SimpleNamespace

import numpy as np
import pytest

from arroyo_tpu.batch import Batch, TIMESTAMP_FIELD
from arroyo_tpu.hashing import hash_columns
from arroyo_tpu.operators.base import OperatorContext
from arroyo_tpu.operators.joins import InstantJoin, JoinWithExpiration, LookupJoin
from arroyo_tpu.operators.updating_aggregate import IS_RETRACT_FIELD, merge_updating_rows
from arroyo_tpu.state.tables import TableManager
from arroyo_tpu.types import TaskInfo, Watermark


class FakeCollector:
    def __init__(self):
        self.batches = []

    def collect(self, b):
        self.batches.append(b)

    def broadcast(self, s):
        pass


def rows_of(col):
    out = []
    for b in col.batches:
        out.extend(b.to_pylist())
    return out


def two_input_ctx(name="join", storage="/tmp/join-unused"):
    """Context where input 0 -> edge 0 (left), input 1 -> edge 1 (right)."""
    ti = TaskInfo("j", name, name, 0, 1)
    return OperatorContext(
        ti, None, TableManager(ti, storage), in_edge_of_input=lambda i: (i, 0)
    )


def kb(ts, keys, vals, vname="v", retracts=None):
    k = np.array(keys, dtype=np.int64)
    cols = {
        TIMESTAMP_FIELD: np.array(ts, dtype=np.int64),
        "id": k,
        vname: np.array(vals, dtype=object),
        "_key": hash_columns([k]),
    }
    if retracts is not None:
        cols[IS_RETRACT_FIELD] = np.array(retracts, dtype=bool)
    return Batch(cols)


def make_instant(jt="inner"):
    op = InstantJoin({
        "join_type": jt,
        "left_names": [("lid", "id"), ("lv", "v")],
        "right_names": [("rid", "id"), ("rv", "v")],
    })
    return op, two_input_ctx(), FakeCollector()


def test_instant_inner_join():
    op, ctx, col = make_instant()
    op.process_batch(kb([100, 100], [1, 2], ["a", "b"]), ctx, col, input_index=0)
    op.process_batch(kb([100, 100, 100], [2, 2, 3], ["x", "y", "z"]), ctx, col, input_index=1)
    op.handle_watermark(Watermark.event_time(50), ctx, col)
    assert rows_of(col) == []  # bucket 100 not closed yet
    op.handle_watermark(Watermark.event_time(101), ctx, col)
    rows = sorted(rows_of(col), key=lambda r: (r["lid"], r["rv"]))
    assert [(r["lid"], r["lv"], r["rid"], r["rv"]) for r in rows] == [
        (2, "b", 2, "x"), (2, "b", 2, "y"),
    ]


def test_instant_outer_joins():
    for jt, expected in [
        ("left", {(1, "a", None, None), (2, "b", 2, "x")}),
        ("right", {(2, "b", 2, "x"), (None, None, 3, "z")}),
        ("full", {(1, "a", None, None), (2, "b", 2, "x"), (None, None, 3, "z")}),
    ]:
        op, ctx, col = make_instant(jt)
        op.process_batch(kb([100, 100], [1, 2], ["a", "b"]), ctx, col, input_index=0)
        op.process_batch(kb([100, 100], [2, 3], ["x", "z"]), ctx, col, input_index=1)
        op.on_close(ctx, col)
        got = {(r["lid"], r["lv"], r["rid"], r["rv"]) for r in rows_of(col)}
        assert got == expected, jt


def test_instant_join_buckets_by_timestamp():
    """Rows in different time buckets never join."""
    op, ctx, col = make_instant()
    op.process_batch(kb([100], [1], ["a"]), ctx, col, input_index=0)
    op.process_batch(kb([200], [1], ["b"]), ctx, col, input_index=1)
    op.on_close(ctx, col)
    assert rows_of(col) == []


def test_instant_join_checkpoint_restore(tmp_path):
    storage = str(tmp_path / "ij")
    cfg = {
        "join_type": "inner",
        "left_names": [("lid", "id"), ("lv", "v")],
        "right_names": [("rid", "id"), ("rv", "v")],
    }
    ti = TaskInfo("j", "join", "instant_join", 0, 1)
    tm = TableManager(ti, storage)
    ctx = OperatorContext(ti, None, tm, in_edge_of_input=lambda i: (i, 0))
    op = InstantJoin(cfg)
    col = FakeCollector()
    op.process_batch(kb([100], [1], ["a"]), ctx, col, input_index=0)
    op.handle_checkpoint(None, ctx, col)
    tm.checkpoint(1, None)

    op2 = InstantJoin(cfg)
    tm2 = TableManager(ti, storage)
    tm2.restore(1, op2.tables())
    ctx2 = OperatorContext(ti, None, tm2, in_edge_of_input=lambda i: (i, 0))
    col2 = FakeCollector()
    op2.on_start(ctx2)
    op2.process_batch(kb([100], [1], ["z"]), ctx2, col2, input_index=1)
    op2.on_close(ctx2, col2)
    rows = rows_of(col2)
    assert len(rows) == 1 and rows[0]["lv"] == "a" and rows[0]["rv"] == "z"


# ---------------------------------------------------------------- updating


def make_updating(jt="inner"):
    op = JoinWithExpiration({
        "join_type": jt,
        "left_names": [("lid", "id"), ("lv", "v")],
        "right_names": [("rid", "id"), ("rv", "v")],
    })
    return op, two_input_ctx("exp_join"), FakeCollector()


def test_updating_inner_join_append_only():
    op, ctx, col = make_updating()
    op.process_batch(kb([0], [1], ["a"]), ctx, col, input_index=0)
    assert rows_of(col) == []  # no match yet
    op.process_batch(kb([1], [1], ["x"]), ctx, col, input_index=1)
    rows = rows_of(col)
    assert len(rows) == 1
    assert rows[0]["lv"] == "a" and rows[0]["rv"] == "x"
    assert rows[0][IS_RETRACT_FIELD] is False
    # second left row joins existing right
    op.process_batch(kb([2], [1], ["b"]), ctx, col, input_index=0)
    final = merge_updating_rows(rows_of(col))
    assert len(final) == 2


def test_updating_left_join_null_then_match():
    op, ctx, col = make_updating("left")
    op.process_batch(kb([0], [1], ["a"]), ctx, col, input_index=0)
    rows = rows_of(col)
    # immediate (left, null) emission
    assert len(rows) == 1 and rows[0]["rv"] is None and not rows[0][IS_RETRACT_FIELD]
    op.process_batch(kb([1], [1], ["x"]), ctx, col, input_index=1)
    rows = rows_of(col)
    # nulls retracted, matched pair appended
    assert len(rows) == 3
    assert rows[1][IS_RETRACT_FIELD] is True and rows[1]["rv"] is None
    assert rows[2][IS_RETRACT_FIELD] is False and rows[2]["rv"] == "x"
    final = merge_updating_rows(rows)
    assert final == [{"lid": 1, "lv": "a", "rid": 1, "rv": "x"}]


def test_updating_join_retract_last_match_restores_nulls():
    op, ctx, col = make_updating("left")
    op.process_batch(kb([0], [1], ["a"]), ctx, col, input_index=0)
    op.process_batch(kb([1], [1], ["x"]), ctx, col, input_index=1)
    # retract the right row: pair retracted, (left, null) re-emitted
    op.process_batch(kb([2], [1], ["x"], retracts=[True]), ctx, col, input_index=1)
    final = merge_updating_rows(rows_of(col))
    assert final == [{"lid": 1, "lv": "a", "rid": None, "rv": None}]


def test_updating_full_join():
    op, ctx, col = make_updating("full")
    op.process_batch(kb([0], [1], ["a"]), ctx, col, input_index=0)
    op.process_batch(kb([1], [2], ["x"]), ctx, col, input_index=1)
    final = sorted(
        merge_updating_rows(rows_of(col)),
        key=lambda r: (r["lid"] is None, r["lid"] or 0),
    )
    assert final == [
        {"lid": 1, "lv": "a", "rid": None, "rv": None},
        {"lid": None, "lv": None, "rid": 2, "rv": "x"},
    ]


def test_updating_join_ttl_expiry():
    op, ctx, col = make_updating()
    op.ttl = 1000
    op.process_batch(kb([0], [1], ["a"]), ctx, col, input_index=0)
    op.handle_watermark(Watermark.event_time(5000), ctx, col)  # expire left row
    op.process_batch(kb([5000], [1], ["x"]), ctx, col, input_index=1)
    assert rows_of(col) == []  # expired row no longer joins


def test_updating_join_checkpoint_restore(tmp_path):
    storage = str(tmp_path / "uj")
    cfg = {
        "join_type": "left",
        "left_names": [("lid", "id"), ("lv", "v")],
        "right_names": [("rid", "id"), ("rv", "v")],
    }
    ti = TaskInfo("j", "exp_join", "join_with_expiration", 0, 1)
    tm = TableManager(ti, storage)
    ctx = OperatorContext(ti, None, tm, in_edge_of_input=lambda i: (i, 0))
    op = JoinWithExpiration(cfg)
    col = FakeCollector()
    op.process_batch(kb([0], [1], ["a"]), ctx, col, input_index=0)  # emits (a, null)
    op.handle_checkpoint(None, ctx, col)
    tm.checkpoint(1, None)

    op2 = JoinWithExpiration(cfg)
    tm2 = TableManager(ti, storage)
    tm2.restore(1, op2.tables())
    ctx2 = OperatorContext(ti, None, tm2, in_edge_of_input=lambda i: (i, 0))
    col2 = FakeCollector()
    op2.on_start(ctx2)
    op2.process_batch(kb([1], [1], ["x"]), ctx2, col2, input_index=1)
    rows = rows_of(col2)
    # null_emitted survived the restore: nulls retracted before the append
    assert len(rows) == 2
    assert rows[0][IS_RETRACT_FIELD] is True and rows[0]["rv"] is None
    assert rows[1][IS_RETRACT_FIELD] is False and rows[1]["rv"] == "x"


# ---------------------------------------------------------------- lookup


class DictLookup:
    def __init__(self, table):
        self.table = table
        self.calls = 0

    def lookup(self, keys):
        self.calls += 1
        return {k: self.table.get(k) for k in keys}


def _lookup_drain(op, ctx, col):
    """Async lookups emit in order; a barrier force-drains everything
    (watermarks queue behind batches instead of blocking)."""
    op.handle_checkpoint(None, ctx, col)


def test_lookup_join_left_and_cache():
    conn = DictLookup({1: {"name": "one"}, 2: {"name": "two"}})
    from arroyo_tpu.expr import Col

    op = LookupJoin({
        "connector": conn,
        "key_exprs": [Col("id")],
        "right_names": [("name", "name")],
        "join_type": "left",
    })
    ctx = two_input_ctx("lookup")
    col = FakeCollector()
    op.process_batch(kb([0, 1, 2], [1, 2, 9], ["a", "b", "c"]), ctx, col)
    _lookup_drain(op, ctx, col)
    rows = rows_of(col)
    assert [r["name"] for r in rows] == ["one", "two", None]
    assert conn.calls == 1
    op.process_batch(kb([3], [1], ["d"]), ctx, col)
    _lookup_drain(op, ctx, col)
    assert conn.calls == 1  # cache hit


def test_lookup_join_inner_filters_missing():
    conn = DictLookup({1: {"name": "one"}})
    from arroyo_tpu.expr import Col

    op = LookupJoin({
        "connector": conn,
        "key_exprs": [Col("id")],
        "right_names": [("name", "name")],
        "join_type": "inner",
    })
    ctx = two_input_ctx("lookup")
    col = FakeCollector()
    op.process_batch(kb([0, 1], [1, 9], ["a", "b"]), ctx, col)
    _lookup_drain(op, ctx, col)
    rows = rows_of(col)
    assert len(rows) == 1 and rows[0]["v"] == "a" and rows[0]["name"] == "one"


def test_lookup_join_watermark_rides_pending_queue():
    """A watermark arriving while fetches are in flight must broadcast
    AFTER the batches that preceded it, without blocking the task thread
    for the whole fetch latency."""
    import time

    from arroyo_tpu.expr import Col
    from arroyo_tpu.types import SignalKind, Watermark

    class SlowLookup:
        def lookup(self, keys):
            time.sleep(0.05)
            return {k: {"name": f"n{k}"} for k in keys}

    class OrderCollector(FakeCollector):
        def __init__(self):
            super().__init__()
            self.events = []

        def collect(self, b):
            super().collect(b)
            self.events.append("batch")

        def broadcast(self, s):
            if s.kind == SignalKind.WATERMARK:
                self.events.append("wm")

    op = LookupJoin({
        "connector": SlowLookup(),
        "key_exprs": [Col("id")],
        "right_names": [("name", "name")],
        "join_type": "left",
    })
    ctx = two_input_ctx("lookup")
    col = OrderCollector()
    t0 = time.perf_counter()
    op.process_batch(kb([0], [1], ["a"]), ctx, col)
    out = op.handle_watermark(Watermark.event_time(10), ctx, col)
    queued_fast = time.perf_counter() - t0 < 0.04  # did not block on the fetch
    assert out is None and queued_fast  # held behind the in-flight batch
    op.process_batch(kb([1], [2], ["b"]), ctx, col)
    _lookup_drain(op, ctx, col)
    assert col.events == ["batch", "wm", "batch"]


def test_lookup_join_async_sustains_slow_source():
    """A 50ms-latency lookup source must overlap fetches across batches
    (VERDICT r4 weak #4): 12 batches of all-new keys would serialize to
    ~600ms; the pipelined path must land well under half that while
    preserving input order and exact results."""
    import time

    class SlowLookup:
        def __init__(self):
            self.calls = 0

        def lookup(self, keys):
            self.calls += 1
            time.sleep(0.05)
            return {k: {"name": f"n{k}"} for k in keys}

    from arroyo_tpu.expr import Col

    conn = SlowLookup()
    op = LookupJoin({
        "connector": conn,
        "key_exprs": [Col("id")],
        "right_names": [("name", "name")],
        "join_type": "left",
        "max_concurrency": 16,
    })
    ctx = two_input_ctx("lookup")
    col = FakeCollector()
    n_batches, per = 12, 4
    t0 = time.perf_counter()
    for b in range(n_batches):
        ids = [b * per + j for j in range(per)]
        op.process_batch(kb(ids, ids, [f"v{c}" for c in ids]), ctx, col)
    _lookup_drain(op, ctx, col)
    elapsed = time.perf_counter() - t0
    assert elapsed < 0.3, f"lookups serialized: {elapsed:.2f}s for 12x50ms"
    rows = rows_of(col)
    assert len(rows) == n_batches * per
    # strict input order and exact join results
    assert [r["id"] for r in rows] == list(range(n_batches * per))
    assert all(r["name"] == f"n{r['id']}" for r in rows)
    assert conn.calls == n_batches


def test_device_join_probe_matches_numpy():
    """Device sort/search join phase (ops/join_probe.py) must yield exactly
    the host _hash_join_indices pairs, including duplicate keys on both
    sides and sentinel-adjacent values."""
    import numpy as np
    from arroyo_tpu.operators.joins import _hash_join_indices
    from arroyo_tpu.ops.join_probe import device_join_start

    rng = np.random.default_rng(13)
    for n_l, n_r in ((5, 3), (100, 700), (1000, 1000), (0, 50), (50, 0)):
        lk = rng.integers(0, 40, size=n_l).astype(np.int64)
        rk = rng.integers(0, 40, size=n_r).astype(np.int64)
        if n_l > 4:
            lk[-1] = np.iinfo(np.int64).max  # collide with the pad sentinel
        want_li, want_ri = _hash_join_indices(lk, rk)
        got_li, got_ri = device_join_start(lk, rk).result()
        want = sorted(zip(want_li.tolist(), want_ri.tolist()))
        got = sorted(zip(got_li.tolist(), got_ri.tolist()))
        assert got == want, (n_l, n_r)


@pytest.mark.parametrize("n_r", [1, 2, 64, 65, 300])
@pytest.mark.parametrize("n_l", [1, 100, 5000])
def test_a_counted_build_side_gives_the_searched_pairs(n_l, n_r):
    """A build side of one smallest bucket (64 rows; q7's and q5's one global
    row) is counted, a larger one searched (ops/join_probe.py ``probe``):
    either way the pairs of the host join, with duplicate keys on both sides,
    probe keys below, between and above every build key, and the pad sentinel
    itself among the probe keys."""
    import numpy as np
    from arroyo_tpu.operators.joins import _hash_join_indices
    from arroyo_tpu.ops.join_probe import _SMALLEST_BUCKET, device_join_start

    assert _SMALLEST_BUCKET == 64
    rng = np.random.default_rng(n_l * 1000 + n_r)
    rk = rng.integers(-20, 20, size=n_r).astype(np.int64) * 3
    lk = rng.integers(-70, 70, size=n_l).astype(np.int64)
    lk[-1] = np.iinfo(np.int64).max
    if n_l > 2:
        lk[0], lk[1] = np.iinfo(np.int64).min, rk[0]
    want_li, want_ri = _hash_join_indices(lk, rk)
    got_li, got_ri = device_join_start(lk, rk).result()
    assert sorted(zip(got_li.tolist(), got_ri.tolist())) == \
        sorted(zip(want_li.tolist(), want_ri.tolist()))
    assert len(want_li) > 0 or n_l == 1


def test_instant_join_device_backend_end_to_end():
    """InstantJoin on the device backend (join-min-rows forced to 0 so every
    window takes the device path), with pipelined emission across several
    windows + watermarks, matches the numpy backend exactly."""
    from arroyo_tpu import config as cfg

    # force-device-join forces the device dispatch even though the test jax
    # platform IS the host cpu (where the adaptive gate prefers numpy)
    cfg.update({"device.join-min-rows": 0, "device.force-device-join": True})
    rng = np.random.default_rng(23)

    def run(backend):
        op = InstantJoin({
            "join_type": "full",
            "left_names": [("lid", "id"), ("lv", "v")],
            "right_names": [("rid", "id"), ("rv", "v")],
            "backend": backend,
        })
        ctx, col = two_input_ctx(), FakeCollector()
        for t in (100, 200, 300, 400):
            nl, nr = int(rng.integers(5, 60)), int(rng.integers(5, 60))
            lkeys = rng.integers(0, 12, size=nl).tolist()
            rkeys = rng.integers(0, 12, size=nr).tolist()
            op.process_batch(kb([t] * nl, lkeys, [f"l{t}_{i}" for i in range(nl)]),
                             ctx, col, input_index=0)
            op.process_batch(kb([t] * nr, rkeys, [f"r{t}_{i}" for i in range(nr)]),
                             ctx, col, input_index=1)
            op.handle_watermark(Watermark.event_time(t + 1), ctx, col)
        op.on_close(ctx, col)
        return sorted(
            repr((r["lid"], r["lv"], r["rid"], r["rv"], r[TIMESTAMP_FIELD]))
            for r in rows_of(col)
        )

    # same rng stream for both backends
    rng = np.random.default_rng(23)
    rows_np = run("numpy")
    rng = np.random.default_rng(23)
    rows_dev = run("jax")
    assert rows_dev == rows_np
    assert len(rows_dev) > 100


# --------------------- the next probe size, compiled before a close needs it
#
# The device probe pads each side to a power of two and compiles once per
# pair of sizes, on a chip for seconds. A probe that fills more than half of
# a bucket has a fetch worker run the next pair on scratch arrays, once per
# pair and process (ops/join_probe.py next_pairs / prewarm, operators/joins.py
# _prewarm), so the close that first needs it does not wait on the compiler.

PREWARMED = "arroyo_worker_join_probes_prewarmed"
GAVE_UP = "arroyo_worker_join_prewarms_failed"


@pytest.fixture
def device_join(request, monkeypatch):
    """The device path forced on the CPU backend as the device-join tests
    above do, no bucket pair met yet in this process, the test's thread bound
    as the join's task, and every warm-up noted: (pair, thread name)."""
    import threading

    from arroyo_tpu import config as cfg
    from arroyo_tpu.metrics import TaskMetrics
    from arroyo_tpu.obs import trace
    from arroyo_tpu.ops import join_probe

    cfg.update({"device.join-min-rows": 0, "device.force-device-join": True})
    monkeypatch.setattr(join_probe, "_pairs_met", set())
    warm, asked = join_probe.prewarm, []

    def noting(pair):
        asked.append((pair, threading.current_thread().name))
        warm(pair)

    monkeypatch.setattr(join_probe, "prewarm", noting)
    job = f"prewarm-{request.node.name}"
    metrics = TaskMetrics(job, "join", 0)
    trace.bind(job, "join", 0, metrics)
    yield SimpleNamespace(job=job, metrics=metrics, asked=asked)
    trace.unbind()



def _settled(metrics, n: int) -> None:
    """Wait for n warm-ups to have ended, one way or the other."""
    import time

    limit = time.monotonic() + 60
    while metrics.counters[PREWARMED] + metrics.counters[GAVE_UP] < n:
        assert time.monotonic() < limit, dict(metrics.counters)
        time.sleep(0.005)


def _close_window(op, ctx, col, t: int, n_l: int, n_r: int) -> None:
    """One window of n_l left and n_r right rows; keys 0.. on both sides, so
    the first min(n_l, n_r) keys match."""
    op.process_batch(kb([t] * n_l, range(n_l), [f"l{t}_{i}" for i in range(n_l)]),
                     ctx, col, input_index=0)
    op.process_batch(kb([t] * n_r, range(n_r), [f"r{t}_{i}" for i in range(n_r)]),
                     ctx, col, input_index=1)
    op.handle_watermark(Watermark.event_time(t + 1), ctx, col)


@pytest.mark.parametrize("n_l,n_r,pairs", [
    (40, 3, [(128, 64)]),                      # the left side over half of 64
    (3, 40, [(64, 128)]),
    (40, 40, [(64, 128), (128, 64), (128, 128)]),
    (33, 1, [(128, 64)]),
    (32, 32, []),                              # half is not more than half
    (20, 3, []),
    (14_500, 1, [(32_768, 64)]),               # q7 and q5 at 10 s
], ids=lambda v: str(v).replace(" ", ""))
def test_a_side_over_half_its_bucket_names_the_next_pair_once(n_l, n_r, pairs, monkeypatch):
    from arroyo_tpu.ops import join_probe

    monkeypatch.setattr(join_probe, "_pairs_met", set())
    assert join_probe.next_pairs(n_l, n_r) == pairs
    assert join_probe.next_pairs(n_l, n_r) == []             # once per process and pair


@pytest.mark.parametrize("sizes,asked", [
    # q7 at the minute, the per-auction side of windows 0-6 (seed 7) and where it
    # tends: the first close asks for the size the third needs, the third for one
    # no window reaches, and nothing is asked once the bucket holds
    ([35_994, 59_600, 69_211, 74_679, 77_384, 80_002, 81_392, 87_000, 91_000],
     {0: [(131_072, 64)], 2: [(262_144, 64)]}),
    # q7 and q5 at 10 s: towards ~15,300 keys, under the 16,384 of their bucket
    ([7_000, 10_000, 12_500, 14_000, 14_800, 15_100, 15_300, 15_250, 15_300],
     {0: [(16_384, 64)], 1: [(32_768, 64)]}),
    # a side that doubles at every close is followed one size ahead
    ([100, 200, 400, 800], {0: [(256, 64)], 1: [(512, 64)], 2: [(1024, 64)],
                            3: [(2048, 64)]}),
    # one that shrinks or stands still under half asks for nothing
    ([30, 30, 20, 25], {}),
], ids=["minute", "ten-seconds", "doubling", "still"])
def test_what_a_join_asks_for_close_by_close(sizes, asked, monkeypatch):
    from arroyo_tpu.ops import join_probe

    monkeypatch.setattr(join_probe, "_pairs_met", set())
    got = {}
    for i, n in enumerate(sizes):
        join_probe._pairs_met.add((join_probe._bucket(n), 64))   # as device_join_start does
        pairs = join_probe.next_pairs(n, 1)
        if pairs:
            got[i] = pairs
    assert got == asked


def test_a_probe_over_half_its_bucket_is_warmed_once_on_a_fetch_worker(device_join):
    import threading

    from arroyo_tpu.obs import trace
    from arroyo_tpu.obs.profile import _annotations

    op, ctx, col = make_instant()
    op.backend = "jax"
    _close_window(op, ctx, col, 100, 40, 3)
    _settled(device_join.metrics, 1)
    assert [pair for pair, _ in device_join.asked] == [(128, 64)]
    # on a fetch worker, not the join task's thread
    worker = device_join.asked[0][1]
    assert worker.startswith("arroyo-prefetch-") and worker != threading.current_thread().name
    # the same sizes again, and a second join of the same process: nothing more
    _close_window(op, ctx, col, 200, 41, 3)
    other, ctx2, col2 = make_instant()
    other.backend = "jax"
    _close_window(other, ctx2, col2, 100, 40, 3)
    # a window that passes the bucket finds (128, 64) compiled and asks for the next
    _close_window(op, ctx, col, 300, 100, 3)
    _settled(device_join.metrics, 2)
    op.on_close(ctx, col)
    other.on_close(ctx2, col2)
    assert [pair for pair, _ in device_join.asked] == [(128, 64), (256, 64)]
    assert device_join.metrics.counters[PREWARMED] == 2
    assert device_join.metrics.counters[GAVE_UP] == 0
    spans = trace.spans("join.prewarm", job=device_join.job)
    assert sorted((s.node, s.args["left"], s.args["right"]) for s in spans) == [
        ("join", 128, 64), ("join", 256, 64)]
    assert all(s.t1_ns > s.t0_ns and set(s.args) == {"left", "right"} for s in spans)
    assert len(rows_of(col)) == 3 * 3 and len(rows_of(col2)) == 3
    lines = _annotations({"busy_pct": 1.0, **device_join.metrics.counters})
    assert any(ln.startswith("waits:") and ln.endswith("probes prewarmed 2") for ln in lines)


def test_a_probe_under_half_its_bucket_warms_nothing(device_join):
    op, ctx, col = make_instant()
    op.backend = "jax"
    for t in (100, 200, 300):
        _close_window(op, ctx, col, t, 20, 3)
    op.on_close(ctx, col)
    assert device_join.asked == []
    assert device_join.metrics.counters[PREWARMED] == device_join.metrics.counters[GAVE_UP] == 0
    assert len(rows_of(col)) == 9


def _four_windows(join_type: str):
    op = InstantJoin({"join_type": join_type, "backend": "jax",
                      "left_names": [("lid", "id"), ("lv", "v")],
                      "right_names": [("rid", "id"), ("rv", "v")]})
    ctx, col = two_input_ctx(), FakeCollector()
    for t, n_l, n_r in ((100, 40, 3), (200, 70, 50), (300, 200, 90), (400, 10, 300)):
        _close_window(op, ctx, col, t, n_l, n_r)
    op.on_close(ctx, col)
    return sorted(repr((r["lid"], r["lv"], r["rid"], r["rv"], r[TIMESTAMP_FIELD]))
                  for r in rows_of(col))


@pytest.mark.parametrize("join_type", ["inner", "full"])
def test_rows_out_are_the_same_with_the_warm_up_patched_out(join_type, device_join, monkeypatch):
    from arroyo_tpu.operators import joins

    with_warm_up = _four_windows(join_type)
    _settled(device_join.metrics, 8)
    assert len(device_join.asked) == 8
    monkeypatch.setattr(joins, "_prewarm", lambda pairs: None)
    assert _four_windows(join_type) == with_warm_up
    assert len(with_warm_up) >= 3 + 50 + 90 + 10


def test_warm_ups_compile_one_at_a_time_and_hold_no_close_back(device_join, monkeypatch):
    """Three pairs named by one close and one by the next take one fetch
    worker between them, in turn (a compile is seconds long on a chip and the
    pool's other workers are the closes' own): while the first is held open
    the join's own probes land and both windows leave."""
    import threading

    from arroyo_tpu.ops import join_probe

    gate, lock, running, most = threading.Event(), threading.Lock(), [0], [0]
    noting = join_probe.prewarm

    def held(pair):
        with lock:
            running[0] += 1
            most[0] = max(most[0], running[0])
        assert gate.wait(60)
        noting(pair)
        with lock:
            running[0] -= 1

    monkeypatch.setattr(join_probe, "prewarm", held)
    op, ctx, col = make_instant()
    op.backend = "jax"
    try:
        _close_window(op, ctx, col, 100, 40, 40)       # (64, 128), (128, 64), (128, 128)
        _close_window(op, ctx, col, 200, 70, 3)        # (256, 64)
        op.on_close(ctx, col)
        assert len(rows_of(col)) == 40 + 3
        assert most[0] == 1 and device_join.metrics.counters[PREWARMED] == 0
    finally:
        gate.set()
    _settled(device_join.metrics, 4)
    assert most[0] == 1
    assert [pair for pair, _ in device_join.asked] == [(64, 128), (128, 64), (128, 128), (256, 64)]
    assert len({worker for _, worker in device_join.asked}) == 1


@pytest.mark.parametrize("fault", ["compile-fails", "pool-closed"])
def test_a_warm_up_that_cannot_run_is_counted_and_never_reaches_the_task(
        fault, device_join, monkeypatch):
    from arroyo_tpu.ops import join_probe, prefetch

    if fault == "compile-fails":
        def broken(pair):
            raise RuntimeError("XLA: out of memory while compiling")

        monkeypatch.setattr(join_probe, "prewarm", broken)
    else:
        pool = prefetch.shared_prefetcher()

        class Closed:
            """The pool, taking the probe's own fetch and no new work."""

            def submit(self, fn, on_done=None, program=None):
                if getattr(fn, "__name__", "") == "_warm_queued":
                    raise RuntimeError("cannot schedule new futures after shutdown")
                return pool.submit(fn, on_done)

        monkeypatch.setattr(prefetch, "shared_prefetcher", lambda: Closed())
    op, ctx, col = make_instant()
    op.backend = "jax"
    _close_window(op, ctx, col, 100, 40, 3)
    _settled(device_join.metrics, 1)
    _close_window(op, ctx, col, 200, 40, 3)        # asked once, failed or not
    op.on_close(ctx, col)
    assert device_join.metrics.counters[GAVE_UP] == 1
    assert device_join.metrics.counters[PREWARMED] == 0
    assert len(rows_of(col)) == 6
    # and said: nothing else tells an operator why a close later waited
    from arroyo_tpu.obs.events import recorder

    said = [e for e in recorder.events(device_join.job) if e["code"] == "JOIN_PREWARM_FAILED"]
    assert [(e["level"], e["node"], e["data"]["left"], e["data"]["right"]) for e in said] == [
        ("WARN", "join", 128, 64)]
    assert ("out of memory" if fault == "compile-fails" else "shutdown") in said[0]["data"]["error"]


def test_pairs_are_handed_out_once_under_contention():
    """More threads than cores asking for the same pairs under a shortened
    switch interval: each pair is handed out once in all."""
    import sys
    import threading

    from arroyo_tpu.ops import join_probe

    threads, rounds = 32, 200
    handed: list = []
    start = threading.Barrier(threads)

    def work():
        start.wait(10)
        for k in range(rounds):
            handed.extend(join_probe.next_pairs(40 << (k % 8), 3))

    before, interval = set(join_probe._pairs_met), sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        join_probe._pairs_met.clear()
        pool = [threading.Thread(target=work, daemon=True) for _ in range(threads)]
        for t in pool:
            t.start()
        for t in pool:
            t.join(60)
        assert not any(t.is_alive() for t in pool)
    finally:
        sys.setswitchinterval(interval)
        join_probe._pairs_met.clear()
        join_probe._pairs_met.update(before)
    assert sorted(handed) == [(128 << k, 64) for k in range(8)]


def test_the_job_profile_carries_the_warm_up_counters_explain_prints():
    """``explain`` renders from the job profile: the counters of the warm-ups
    on its ``waits:`` line have to be in it."""
    from arroyo_tpu.obs.profile import _annotations, job_profile

    counters = {PREWARMED: 2, GAVE_UP: 1, "arroyo_worker_table_grows": 0}
    prof = job_profile({"join": {"busy_pct": 1.0, **counters}})["join"]
    assert {k: prof.get(k) for k in counters} == {**counters, "arroyo_worker_table_grows": None}
    assert "waits: probes prewarmed 2, 1 failed" in _annotations(prof)


# ------------------------------- q8's shapes: two wide sides, searched, counted
#
# NEXmark q8 joins a window's 2,000 persons to its distinct sellers: ~3,600
# in the stream's third window (bucket 4,096), 4,100-5,950 from the fourth on
# (bucket 8,192), a few hundred in common early and a handful late. Every such
# window has both sides over device.join-min-rows, so the searched program
# (argsort of the build side, two searchsorted scans) runs at every close; a
# window under it probes with numpy on the join's thread, and says so.

PROBED_DEVICE = "arroyo_worker_join_probes_device"
PROBED_HOST = "arroyo_worker_join_probes_host"


@pytest.mark.parametrize("n_l,n_r,common,caps", [
    pytest.param(2000, 3600, 300, (2048, 4096), id="persons-x-3600-sellers"),
    pytest.param(2000, 5900, 400, (2048, 8192), id="persons-x-5900-sellers"),
    pytest.param(3600, 2000, 300, (4096, 2048), id="3600-sellers-x-persons"),
    pytest.param(5900, 2000, 400, (8192, 2048), id="5900-sellers-x-persons"),
    pytest.param(2000, 5900, 0, (2048, 8192), id="no-match"),
])
def test_the_searched_probe_gives_the_host_pairs_at_q8s_shapes(n_l, n_r, common, caps):
    from arroyo_tpu.ops.join_probe import bucket_pair, device_join_start, host_join_indices

    rng = np.random.default_rng(n_l + n_r + common)
    # unique keys a side, as a per-key aggregate emits them; `common` shared
    pool = rng.permutation(np.arange(1000, 1000 + 4 * (n_l + n_r), dtype=np.int64))
    shared, rest = pool[:common], pool[common:]
    lk = rng.permutation(np.concatenate([shared, rest[:n_l - common]]))
    rk = rng.permutation(np.concatenate([shared, rest[n_l:n_l + n_r - common]]))
    assert len(lk) == n_l and len(rk) == n_r and bucket_pair(n_l, n_r) == caps
    want_li, want_ri = host_join_indices(lk, rk)
    got_li, got_ri = device_join_start(lk, rk).result()
    assert sorted(zip(got_li.tolist(), got_ri.tolist())) == \
        sorted(zip(want_li.tolist(), want_ri.tolist()))
    assert len(got_li) == common and (lk[got_li] == rk[got_ri]).all()


@pytest.mark.parametrize("min_rows,on,caps", [
    pytest.param(0, "device", (64, 128), id="device"),
    pytest.param(2048, "host", (0, 0), id="under-join-min-rows"),
])
def test_a_probe_is_a_span_and_a_count_on_either_path(min_rows, on, caps, device_join):
    """One ``join.probe`` span and one count a window with both sides, under
    the join's task, ``trace_id`` the window's start, from the dispatch to the
    pairs on the host; a window with one side alone probes nothing."""
    from arroyo_tpu import config as cfg
    from arroyo_tpu.obs import trace
    from arroyo_tpu.obs.profile import _annotations, job_profile

    cfg.update({"device.join-min-rows": min_rows})
    op, ctx, col = make_instant("full")
    op.backend = "jax"
    _close_window(op, ctx, col, 100, 40, 70)
    op.process_batch(kb([200] * 5, range(5), ["x"] * 5), ctx, col, input_index=0)
    op.handle_watermark(Watermark.event_time(201), ctx, col)
    _close_window(op, ctx, col, 300, 41, 90)
    op.on_close(ctx, col)
    # the first device probe (40 of 64, 70 of 128) names three pairs: leave no
    # warm-up running into the next test
    _settled(device_join.metrics, 3 if on == "device" else 0)
    counters = device_join.metrics.counters
    other = PROBED_HOST if on == "device" else PROBED_DEVICE
    assert counters[PROBED_DEVICE if on == "device" else PROBED_HOST] == 2 and counters[other] == 0
    spans = trace.spans("join.probe", job=device_join.job)
    assert [(s.node, s.trace_id, s.args) for s in spans] == [
        ("join", 100, dict(left=40, right=70, l_cap=caps[0], r_cap=caps[1], pairs=40, on=on)),
        ("join", 300, dict(left=41, right=90, l_cap=caps[0], r_cap=caps[1], pairs=41, on=on))]
    assert all(s.t1_ns > s.t0_ns for s in spans)
    # full join: 70 + 5 + 90 rows
    assert len(rows_of(col)) == 70 + 5 + 90
    # the task.account marks carry both counters, for a reader to difference
    trace.current().account(force=True)
    mark = trace.spans("task.account", job=device_join.job)[-1].args
    assert (mark["join_probes_device"], mark["join_probes_host"]) == (
        (2, 0) if on == "device" else (0, 2))
    prof = job_profile({"join": {"busy_pct": 1.0, **counters}})["join"]
    want = "probes 2 on device, 0 on host" if on == "device" else "probes 0 on device, 2 on host"
    assert any(ln.startswith("waits:") and want in ln for ln in _annotations(prof))


def test_a_fused_close_counts_every_window_it_probes():
    """Several windows closed by one watermark on a host-probe backend are
    probed in one call (``_fused_close``): one span, a count a window."""
    from arroyo_tpu.metrics import TaskMetrics
    from arroyo_tpu.obs import trace

    metrics = TaskMetrics("fused-probe", "join", 0)
    trace.bind("fused-probe", "join", 0, metrics)
    try:
        op, ctx, col = make_instant()
        op.backend = "numpy"
        for t in (100, 200, 300):
            op.process_batch(kb([t] * 4, range(4), ["l"] * 4), ctx, col, input_index=0)
            op.process_batch(kb([t] * 6, range(6), ["r"] * 6), ctx, col, input_index=1)
        op.handle_watermark(Watermark.event_time(301), ctx, col)
    finally:
        trace.unbind()
    assert metrics.counters[PROBED_HOST] == 3 and metrics.counters[PROBED_DEVICE] == 0
    (span,) = trace.spans("join.probe", job="fused-probe")
    assert span.args == dict(left=12, right=18, l_cap=0, r_cap=0, on="host", pairs=12, windows=3)
    assert len(rows_of(col)) == 12


def test_the_job_profile_carries_the_close_counters_explain_prints():
    """ROADMAP C13: ``explain`` renders its ``waits:`` line from the job
    profile, so the counters of the completion wake have to be in it."""
    from arroyo_tpu.obs.profile import _annotations, job_profile

    counters = {"arroyo_worker_closes_on_wake": 16, "arroyo_worker_closes_on_input": 1}
    prof = job_profile({"agg": {"busy_pct": 1.0, **counters}})["agg"]
    assert {k: prof.get(k) for k in counters} == counters
    assert "waits: closes 16 on wake, 1 on input" in _annotations(prof)
