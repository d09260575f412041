"""Micro-batch coalescing correctness suite (ISSUE 5).

The coalescing layer may only change BATCH BOUNDARIES, never content or
signal ordering: goldens must stay byte-exact with coalescing on/off at any
row/byte/delay setting, signals must flush pending rows ahead of themselves,
checkpoint/restore must stay exact with rows buffered mid-stream, and the
fused multi-window join close must emit exactly the per-window groups.
"""

from __future__ import annotations

import threading

import numpy as np
import pytest

from arroyo_tpu.batch import TIMESTAMP_FIELD, Batch
from arroyo_tpu.types import Signal, SignalKind, Watermark

from test_smoke import assert_outputs, build, load_sql


# ------------------------------------------------------------- unit layer


class RecordingDest:
    """Duck-types TaskInbox.put; remembers arrival order."""

    def __init__(self):
        self.items = []

    def put(self, input_index, item):
        self.items.append(item)


def make_collector(**over):
    from arroyo_tpu import config as cfg
    from arroyo_tpu.graph import EdgeType
    from arroyo_tpu.operators.collector import Collector, OutEdge

    cfg.update({f"engine.coalesce.{k}": v for k, v in over.items()})
    dest = RecordingDest()
    col = Collector([OutEdge(EdgeType.FORWARD, [dest], [0])], 0)
    return col, dest


def b(n: int, base: int = 0) -> Batch:
    return Batch({
        "x": np.arange(base, base + n, dtype=np.int64),
        TIMESTAMP_FIELD: np.full(n, 1000, dtype=np.int64),
    })


def test_signal_flushes_pending_rows_first(_storage):
    col, dest = make_collector(**{"max-rows": 1000, "max-delay-ms": 10_000})
    col.collect(b(3))
    col.collect(b(2, base=3))
    assert dest.items == []  # buffered: below every threshold
    col.broadcast(Signal.watermark_of(Watermark.event_time(5)))
    assert len(dest.items) == 2
    assert isinstance(dest.items[0], Batch)  # rows precede the signal
    assert dest.items[0].num_rows == 5
    assert np.array_equal(dest.items[0]["x"], np.arange(5))
    assert isinstance(dest.items[1], Signal)
    assert dest.items[1].kind == SignalKind.WATERMARK


def test_row_threshold_flush_and_big_batch_passthrough(_storage):
    col, dest = make_collector(**{"max-rows": 4})
    col.collect(b(2))
    col.collect(b(2, base=2))
    assert len(dest.items) == 1 and dest.items[0].num_rows == 4
    big = b(100)
    col.collect(big)  # >= max-rows with nothing pending: no copy at all
    assert dest.items[1] is big


def test_schema_change_flushes_before_concat(_storage):
    col, dest = make_collector(**{"max-rows": 1000, "max-delay-ms": 10_000})
    col.collect(b(2))
    other = Batch({"y": np.ones(3), TIMESTAMP_FIELD: np.zeros(3, dtype=np.int64)})
    col.collect(other)
    assert len(dest.items) == 1 and "x" in dest.items[0]
    col.flush()
    assert len(dest.items) == 2 and "y" in dest.items[1]


def test_time_based_flush(_storage):
    col, dest = make_collector(**{"max-rows": 1000, "max-delay-ms": 5})
    col.collect(b(2))
    col.flush_expired(col._pending_since + 0.001)
    assert dest.items == []  # not expired yet
    col.flush_expired(col._pending_since + 0.006)
    assert len(dest.items) == 1 and dest.items[0].num_rows == 2


def test_coalescing_disabled_is_passthrough(_storage):
    col, dest = make_collector(enabled=False)
    small = b(1)
    col.collect(small)
    assert dest.items == [small]


def test_emit_and_transit_histograms_exported(_storage):
    from arroyo_tpu.engine.queues import TaskInbox
    from arroyo_tpu.metrics import registry

    col, dest = make_collector(**{"max-rows": 4})
    tm = registry.task("co-job", "op", 0)
    col.metrics = tm
    col.collect(b(5))
    assert tm.emit_batch_rows.count == 1 and tm.emit_batch_rows.sum == 5
    inbox = TaskInbox(1, 100)
    inbox.metrics = tm
    inbox.put(0, b(3))
    inbox.get(timeout=1)
    assert tm.queue_transit.count == 1
    text = registry.prometheus_text()
    assert "arroyo_worker_emit_batch_rows_bucket" in text
    assert "arroyo_worker_queue_transit_seconds_count" in text
    registry.clear_job("co-job")


# ------------------------------------------------- golden on/off equivalence

COALESCE_FAMILIES = ["tumbling_aggregates", "sliding_window", "updating_aggregate"]
SETTINGS = [
    pytest.param({"enabled": False}, id="off"),
    # everything buffers until a signal: the pure ordering-correctness axis
    pytest.param({"max-rows": 1_000_000, "max-bytes": 1 << 30,
                  "max-delay-ms": 50}, id="aggressive"),
    # constant flushing: the threshold-boundary axis
    pytest.param({"max-rows": 64, "max-bytes": 2048, "max-delay-ms": 1},
                 id="tiny"),
]


@pytest.mark.parametrize("settings", SETTINGS)
@pytest.mark.parametrize("name", COALESCE_FAMILIES)
def test_goldens_exact_across_coalesce_settings(name, settings, tmp_path, _storage):
    from arroyo_tpu import config as cfg

    cfg.update({f"engine.coalesce.{k}": v for k, v in settings.items()})
    out = str(tmp_path / "out.json")
    eng = build(load_sql(name, out), 1, f"{name}-co")
    eng.run_to_completion(timeout=180)
    assert_outputs(name, out)


def test_checkpoint_restore_exact_with_aggressive_coalescing(tmp_path, _storage):
    """Barriers must align and snapshots stay byte-exact while rows are
    held in collectors mid-stream (the flush-on-broadcast rule e2e)."""
    from arroyo_tpu import config as cfg

    name = "tumbling_aggregates"
    cfg.update({"engine.coalesce.max-rows": 1_000_000,
                "engine.coalesce.max-bytes": 1 << 30,
                "engine.coalesce.max-delay-ms": 50,
                "testing.source-gate-epochs": 2})
    out = str(tmp_path / "out.json")
    sql = load_sql(name, out)
    try:
        eng = build(sql, 2, f"{name}-co-ckpt")
        eng.start()
        assert eng.checkpoint_and_wait(1, timeout=60)
        assert eng.checkpoint_and_wait(2, timeout=60, then_stop=True)
        eng.join(timeout=120)
    finally:
        cfg.update({"testing.source-gate-epochs": 0})
    eng2 = build(sql, 2, f"{name}-co-ckpt", restore_epoch=2)
    eng2.run_to_completion(timeout=180)
    assert_outputs(name, out)


@pytest.mark.chaos
def test_chaos_crash_mid_checkpoint_with_coalescing(tmp_path, _storage):
    """Chaos axis under aggressive coalescing: worker crash after epoch-2
    state lands but before completion; recovery from epoch 1 must still
    reproduce the goldens byte-exact with rows buffered in collectors."""
    from arroyo_tpu import config as cfg
    from arroyo_tpu import faults
    from arroyo_tpu.state.tables import latest_complete_checkpoint

    name = "sliding_window"
    out = str(tmp_path / "out.json")
    sql = load_sql(name, out)
    job_id = f"{name}-co-chaos"
    cfg.update({"engine.coalesce.max-rows": 1_000_000,
                "engine.coalesce.max-delay-ms": 50,
                "testing.source-gate-epochs": 2})
    inj = faults.install("worker:crash@barrier=2&step=1", seed=1337)
    try:
        eng = build(sql, 2, job_id)
        eng.start()
        assert eng.checkpoint_and_wait(1, timeout=60)
        with pytest.raises(RuntimeError, match="injected"):
            if eng.checkpoint_and_wait(2, timeout=60):
                raise AssertionError("epoch 2 completed despite injected crash")
            eng.join(timeout=60)
    finally:
        faults.clear()
        cfg.update({"testing.source-gate-epochs": 0})
    assert inj.fired_log, "crash fault never fired"
    storage_url = cfg.config().get("checkpoint.storage-url")
    assert latest_complete_checkpoint(storage_url, job_id) == 1
    eng2 = build(sql, 2, job_id, restore_epoch=1)
    eng2.run_to_completion(timeout=180)
    assert_outputs(name, out)


# ------------------------------------------------ fused multi-window close


class FakeCollector:
    def __init__(self):
        self.batches = []

    def collect(self, batch):
        self.batches.append(batch)

    def broadcast(self, signal):
        pass


def _join_rows(col):
    rows = []
    for bt in col.batches:
        rows.extend(bt.to_pylist())
    return sorted(
        repr((r[TIMESTAMP_FIELD], r["lid"], r["lv"], r["rid"], r["rv"]))
        for r in rows
    )


def _feed_windows(op, ctx, col, rng):
    from test_joins import kb

    for t in (100, 200, 300, 400):
        nl, nr = int(rng.integers(3, 40)), int(rng.integers(3, 40))
        op.process_batch(
            kb([t] * nl, rng.integers(0, 9, nl).tolist(),
               [f"l{t}_{i}" for i in range(nl)]), ctx, col, input_index=0)
        op.process_batch(
            kb([t] * nr, rng.integers(0, 9, nr).tolist(),
               [f"r{t}_{i}" for i in range(nr)]), ctx, col, input_index=1)


@pytest.mark.parametrize("jt", ["inner", "left", "right", "full"])
def test_fused_multi_window_close_matches_per_window(jt, _storage):
    """One watermark closing N windows (fused path) must emit exactly the
    (window, key) groups that N per-window watermarks emit, in one batch per
    match category where per-window closes emit at least one per window."""
    from test_joins import two_input_ctx

    from arroyo_tpu.operators.joins import InstantJoin

    def run(close_per_window: bool):
        op = InstantJoin({
            "join_type": jt,
            "left_names": [("lid", "id"), ("lv", "v")],
            "right_names": [("rid", "id"), ("rv", "v")],
            "backend": "numpy",
        })
        ctx, col = two_input_ctx(), FakeCollector()
        rng = np.random.default_rng(41)
        _feed_windows(op, ctx, col, rng)
        if close_per_window:
            for t in (101, 201, 301, 401):
                op.handle_watermark(Watermark.event_time(t), ctx, col)
        else:
            op.handle_watermark(Watermark.event_time(401), ctx, col)
        op.on_close(ctx, col)
        return _join_rows(col), len(col.batches)

    (per_window, n_per_window), (fused, n_fused) = run(True), run(False)
    assert per_window == fused, jt
    # matched, left-only, right-only: the categories this join type emits
    categories = {"inner": 1, "left": 2, "right": 2, "full": 3}[jt]
    assert n_fused <= categories < 4 <= n_per_window, (jt, n_fused, n_per_window)


def test_fused_close_on_stream_end(_storage):
    """on_close with several buffered windows takes the fused path and
    emits the same groups as watermark-driven closes."""
    from test_joins import two_input_ctx

    from arroyo_tpu.operators.joins import InstantJoin

    def run(with_watermarks: bool):
        op = InstantJoin({
            "join_type": "inner",
            "left_names": [("lid", "id"), ("lv", "v")],
            "right_names": [("rid", "id"), ("rv", "v")],
            "backend": "numpy",
        })
        ctx, col = two_input_ctx(), FakeCollector()
        rng = np.random.default_rng(42)
        _feed_windows(op, ctx, col, rng)
        if with_watermarks:
            for t in (101, 201, 301, 401):
                op.handle_watermark(Watermark.event_time(t), ctx, col)
        op.on_close(ctx, col)
        return _join_rows(col)

    assert run(False) == run(True)
    # the fused path really was taken: everything emitted in few batches
    op_rows = run(False)
    assert len(op_rows) > 0


@pytest.mark.parametrize("coalesce", [False, True])
@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_fused_sliding_drain_emits_one_batch_per_round(backend, coalesce, _storage):
    """A watermark that closes ten slide bins emits them as one batch, not
    ten: three 1,000-row source batches and the end of the stream close 33
    windows in at most four emitted batches, with the collector's own
    coalescing off (the operator's fusing alone) and on."""
    from test_sliding import expected_sliding, sliding_graph

    from arroyo_tpu import config as cfg
    from arroyo_tpu.engine import run_graph

    cfg.update({"engine.coalesce.enabled": coalesce,
                "pipeline.source-batch-size": 1000})
    batches: list = []
    g = sliding_graph(batches, backend, count=3000, width=400_000, slide=100_000)
    g.nodes["sink"].config["columnar"] = True
    run_graph(g, job_id=f"sw-fused-{backend}-{int(coalesce)}", timeout=120)
    got = {}
    for bt in batches:
        for r in bt.to_pylist():
            got[(r["window_start"], r["k"])] = (r["cnt"], r["total"])
    assert got == expected_sliding(3000, width=400_000, slide=100_000)
    assert len({ws for ws, _k in got}) == 33
    assert len(batches) <= 4, [bt.num_rows for bt in batches]


# ------------------------------------------------ data plane frame coalescing


def test_network_frame_coalescing_preserves_order(_storage):
    """Many small data frames + a signal over the coalescing send buffer:
    one write carries them all, receiver sees identical frames in order."""
    from arroyo_tpu import config as cfg
    from arroyo_tpu.engine.network import NetworkManager, RemoteDest
    from arroyo_tpu.native import available

    if not available():
        pytest.skip("native library unavailable")
    cfg.update({"engine.coalesce.max-delay-ms": 20})
    rx, tx = NetworkManager(), NetworkManager()
    peers = {0: ("127.0.0.1", rx.port), 1: ("127.0.0.1", tx.port)}
    rx.set_peers(peers)
    tx.set_peers(peers)
    got = []
    done = threading.Event()

    class Inbox:
        def put(self, idx, item):
            got.append((idx, item))
            if isinstance(item, Signal):
                done.set()

    quad = (0, 0, 1, 0)
    rx.register_receiver(quad, Inbox(), 7)
    rx.start()
    tx.start()
    dest = RemoteDest(tx, 0, quad)
    for i in range(10):
        dest.put(0, b(3, base=i * 3))
    dest.put(0, Signal.watermark_of(Watermark.event_time(99)))
    assert done.wait(timeout=10), "signal never arrived"
    try:
        assert len(got) == 11
        assert all(idx == 7 for idx, _ in got)
        for i in range(10):
            item = got[i][1]
            assert isinstance(item, Batch) and item.num_rows == 3
            assert np.array_equal(item["x"], np.arange(i * 3, i * 3 + 3))
        assert isinstance(got[10][1], Signal)
    finally:
        tx.close()
        rx.close()
