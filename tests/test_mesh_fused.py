"""Fused mesh execution: the compiled segment runs INSIDE the sharded
aggregate's shard_map'd program (engine/segment.py mesh path).

With device.mesh-devices > 1 and a mesh-markable segment, each micro-batch
is ONE jitted shard_map dispatch: the traced prefix (projections, key
hashing, watermark taps) runs per-shard and feeds owner bucketing →
all_to_all → sort_reduce/probe_merge without rows ever round-tripping to
the host between projection and state update. These tests prove the three
load-bearing claims on 8 emulated CPU devices:

 - engagement is real (module dispatch counters, not vibes: exactly one
   fused program execution per post-verification micro-batch);
 - output is byte-exact against the same golden files the host path is
   held to, including through checkpoint -> crash -> restore chaos for
   the tumbling AND sliding families;
 - checkpoints are canonical (placement-independent), so a restore onto
   a DIFFERENT mesh width (4 -> 8) replays exactly.
"""

from __future__ import annotations

import pytest

from test_smoke import (CHAOS_SEED, assert_fsck_clean, assert_outputs, build,
                        load_sql)

pytestmark = pytest.mark.mesh


def _mesh_devices():
    import jax

    return len(jax.devices())


@pytest.fixture
def _fused_cfg(_storage):
    """Mesh-fused segment config: 8-way mesh, chaining on, compile floor
    dropped to 1 row (smoke batches are far below the production 8192
    floor), and source/coalesce caps small enough that a run spans several
    micro-batches — the first is host-verified, so a single-batch run
    could never prove the fused path executed."""
    from arroyo_tpu import config as cfg
    from arroyo_tpu.engine.segment import reset_mesh_dispatch_counts
    from arroyo_tpu.parallel.sharded_agg import reset_dispatch_counts

    if _mesh_devices() < 8:
        pytest.skip("needs 8 virtual devices (conftest sets XLA_FLAGS)")
    cfg.update({
        "device.mesh-devices": 8, "device.table-capacity": 2048,
        "device.batch-capacity": 512, "device.emit-capacity": 512,
        "device.spill-capacity": 512, "device.max-probes": 32,
        "segment.compile.min-rows": 1,
        "pipeline.chaining.enabled": True,
        "pipeline.source-batch-size": 256,
        "engine.coalesce.max-rows": 256,
    })
    reset_mesh_dispatch_counts()
    reset_dispatch_counts()
    yield
    cfg.update({"device.mesh-devices": 0,
                "pipeline.chaining.enabled": False})


def assert_fused_engaged():
    """The engagement proof: at least one micro-batch ran as the fused
    shard_map program, and every such segment-level dispatch was exactly
    one aggregate-level program execution (no hidden host exchange)."""
    from arroyo_tpu.engine.segment import mesh_dispatch_counts
    from arroyo_tpu.parallel.sharded_agg import dispatch_counts

    seg = mesh_dispatch_counts()
    agg = dispatch_counts()
    assert seg["fused"] > 0, f"fused path never engaged: {seg} / {agg}"
    assert agg["fused_steps"] == seg["fused"], (
        f"fused dispatch mismatch (segment {seg} vs aggregate {agg}): "
        f"a fused batch must be exactly one program execution")


@pytest.mark.parametrize(
    "name", ["tumbling_aggregates", "grouped_aggregates", "sliding_window"])
def test_mesh_fused_golden(name, _fused_cfg, tmp_path):
    """Each window family through the fused program at parallelism 1 (mesh
    replaces host data-parallelism): goldens byte-exact, engagement real."""
    out = str(tmp_path / "out.json")
    eng = build(load_sql(name, out), 1, f"fused-mesh-{name}")
    eng.run_to_completion(timeout=180)
    assert_fused_engaged()
    assert_outputs(name, out)


@pytest.mark.chaos
@pytest.mark.parametrize("name", ["tumbling_aggregates", "sliding_window"])
def test_mesh_fused_chaos_crash_mid_checkpoint(name, _fused_cfg, tmp_path):
    """The smoke suite's worst-case chaos point, on the fused path: crash
    after epoch-2 state files land but before the epoch completes. The
    torn epoch must be ignored, and a restore from epoch 1 — which
    re-fuses on the recompiled (cache-hit) segment — must reproduce the
    host-path goldens byte-exact."""
    from arroyo_tpu import config as cfg
    from arroyo_tpu import faults
    from arroyo_tpu.state.tables import latest_complete_checkpoint

    out = str(tmp_path / "out.json")
    sql = load_sql(name, out)
    job_id = f"mesh-chaos-{name}"
    cfg.update({"testing.source-gate-epochs": 2})
    inj = faults.install("worker:crash@barrier=2&step=1", seed=CHAOS_SEED)
    try:
        eng = build(sql, 1, job_id)
        eng.start()
        assert eng.checkpoint_and_wait(1, timeout=60), "epoch 1 did not complete"
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

    eng2 = build(sql, 1, job_id, restore_epoch=1)
    eng2.run_to_completion(timeout=180)
    assert_fused_engaged()
    assert_outputs(name, out)
    assert_fsck_clean(job_id)


def test_mesh_resize_restore_4_to_8(_fused_cfg, tmp_path):
    """Mesh-width elasticity: checkpoint on a 4-device mesh, restore onto
    8 devices. The snapshot is canonical (owner placement is never
    persisted), so the wider mesh re-shards it through the same rescale
    merge path a parallelism change takes — output stays byte-exact."""
    from arroyo_tpu import config as cfg

    name = "tumbling_aggregates"
    out = str(tmp_path / "out.json")
    sql = load_sql(name, out)
    job_id = "mesh-resize"
    cfg.update({"device.mesh-devices": 4,
                "testing.source-gate-epochs": 2})
    try:
        eng = build(sql, 1, job_id)
        eng.start()
        assert eng.checkpoint_and_wait(1, timeout=60), "epoch 1 did not complete"
        eng.stop()
        eng.join(timeout=60)
    finally:
        cfg.update({"testing.source-gate-epochs": 0})

    cfg.update({"device.mesh-devices": 8})
    eng2 = build(sql, 1, job_id, restore_epoch=1)
    eng2.run_to_completion(timeout=180)
    assert_fused_engaged()
    assert_outputs(name, out)
    assert_fsck_clean(job_id)
