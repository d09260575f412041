"""Direct unit tests for the slot-directory aggregator (ops/slot_agg.py):
spill tier, region lifecycle, collision detection, and differential checks
against the dict-based numpy oracle under random interleaved streams.

A table that runs out of regions grows (tests/test_table_growth.py); the
host spill tier is what is left past the ceiling, so the spill tests here
hold the ceiling at the capacity the table starts with (``at_ceiling``)."""

import numpy as np
import pytest

from arroyo_tpu.ops.slot_agg import BinSlotDirectory, SlotAggregator

KW = dict(cap=64, batch_cap=64, emit_cap=64, region_size=16)


def _mk(backend="jax", kinds=("count", "sum"), dtypes=(np.int64, np.int64), **kw):
    args = {**KW, **kw}
    return SlotAggregator(kinds, dtypes, backend=backend, **args)


def _table(keys, bins, accs):
    return {
        (int(k), int(b)): tuple(float(a[i]) for a in accs)
        for i, (k, b) in enumerate(zip(keys.tolist(), bins.tolist()))
    }


# --------------------------------------------------------------- spill tier


@pytest.fixture
def at_ceiling(monkeypatch):
    """The table may not grow: what finds no free region spills to the host
    store, the last resort."""
    monkeypatch.setattr(SlotAggregator, "_ceiling", lambda self: self.cap)


def test_spill_tier_overflow_to_host_round_trip(at_ceiling):
    """More distinct (bin, key) groups than device slots: the surplus lands
    in the host spill store and window closes still emit exact results."""
    agg = _mk()
    ora = _mk(backend="numpy")
    n_keys = 200  # 200 groups in one bin >> cap=64
    keys = np.arange(n_keys, dtype=np.uint64)
    ones = np.ones(n_keys, dtype=np.int64)
    vals = np.arange(n_keys, dtype=np.int64)
    for a in (agg, ora):
        a.update(keys, np.zeros(n_keys, dtype=np.int32), [ones, vals])
        a.update(keys, np.zeros(n_keys, dtype=np.int32), [ones, vals])
    assert len(agg.spill) == n_keys - KW["cap"]  # surplus spilled, no error
    k, b, accs = agg.extract(0, 1, 1)
    ok, ob, oaccs = ora.extract(0, 1, 1)
    assert _table(k, b, accs) == _table(ok, ob, oaccs)
    assert len(k) == n_keys
    # spill entries for the closed bin are gone
    assert not agg.spill


def test_snapshot_with_live_spill_entries(at_ceiling):
    """snapshot() must include spill-tier entries (checkpoint correctness
    when the device table overflowed to host)."""
    agg = _mk()
    n_keys = 100
    keys = np.arange(n_keys, dtype=np.uint64)
    ones = np.ones(n_keys, dtype=np.int64)
    agg.update(keys, np.zeros(n_keys, dtype=np.int32), [ones, ones * 3])
    assert agg.spill  # overflowed
    sk, sb, saccs = agg.snapshot()
    assert len(sk) == n_keys
    got = _table(sk, sb, saccs)
    assert got == {(k, 0): (1.0, 3.0) for k in range(n_keys)}
    # snapshot is non-destructive: spill still live, extract still exact
    assert agg.spill
    k, b, accs = agg.extract(0, 1, 1)
    assert _table(k, b, accs) == got


def test_restore_merges_partial_counts():
    """Restore must scatter the snapshotted partial counts (merge mode), not
    +1 per restored row: a checkpoint taken after several updates of the
    same keys carries counts > 1, and a regression that routes restore
    through the constant-increment hot step would floor them back to 1."""
    agg = _mk()
    keys = np.arange(8, dtype=np.uint64)
    ones = np.ones(8, dtype=np.int64)
    vals = np.arange(8, dtype=np.int64)
    for _ in range(3):  # counts reach 3, sums reach 3*vals
        agg.update(keys, np.zeros(8, dtype=np.int32), [ones, vals])
    sk, sb, saccs = agg.snapshot()

    fresh = _mk()
    fresh.restore(sk, sb, saccs)
    k, b, accs = fresh.extract(0, 1, 1)
    assert _table(k, b, accs) == {
        (i, 0): (3.0, float(3 * i)) for i in range(8)
    }
    # and post-restore updates keep counting from the restored partials
    fresh2 = _mk()
    fresh2.restore(sk, sb, saccs)
    fresh2.update(keys, np.zeros(8, dtype=np.int32), [ones, vals])
    k2, b2, accs2 = fresh2.extract(0, 1, 1)
    assert _table(k2, b2, accs2) == {
        (i, 0): (4.0, float(4 * i)) for i in range(8)
    }


def test_spill_restore_round_trip(at_ceiling):
    """snapshot -> restore into a fresh aggregator -> identical output
    (restore itself may spill again; that must be transparent)."""
    agg = _mk()
    n_keys = 150
    keys = np.arange(n_keys, dtype=np.uint64)
    ones = np.ones(n_keys, dtype=np.int64)
    vals = (np.arange(n_keys) * 7).astype(np.int64)
    agg.update(keys, np.zeros(n_keys, dtype=np.int32), [ones, vals])
    sk, sb, saccs = agg.snapshot()

    fresh = _mk()
    fresh.restore(sk, sb, saccs)
    k, b, accs = fresh.extract(0, 1, 1)
    assert _table(k, b, accs) == _table(sk, sb, saccs)


# ------------------------------------------------------------ region reuse


def test_region_exhaustion_and_reuse_after_close(at_ceiling):
    d_regions = KW["cap"] // KW["region_size"]
    agg = _mk()
    d = agg.directory
    assert len(d.free_regions) == d_regions
    # fill the whole table with bin 0
    keys = np.arange(KW["cap"], dtype=np.uint64)
    ones = np.ones(KW["cap"], dtype=np.int64)
    agg.update(keys, np.zeros(KW["cap"], dtype=np.int32), [ones, ones])
    assert len(d.free_regions) == 0
    assert sorted(d.bin_regions) == [0]
    # new bin's groups must spill (no regions left)
    agg.update(keys[:8], np.ones(8, dtype=np.int32), [ones[:8], ones[:8]])
    assert len(agg.spill) == 8
    # close bin 0 -> all regions return to the free list
    k, b, accs = agg.extract(0, 1, 1)
    assert len(k) == KW["cap"]
    assert len(d.free_regions) == d_regions
    assert 0 not in d.bin_regions
    # bin 1 can now claim fresh regions; cleared slots hold identities
    agg.update(keys[:8], np.ones(8, dtype=np.int32), [ones[:8], ones[:8]])
    k2, b2, accs2 = agg.extract(1, 2, 2)
    got = _table(k2, b2, accs2)
    # spilled first update (1,1) merged with the post-close device update (1,1)
    assert got == {(k, 1): (2.0, 2.0) for k in range(8)}


def test_closed_boundary_blocks_stale_directory_hits():
    """After a close, a key from the closed bin re-appearing (late data path
    upstream allows this for new bins) must claim a fresh slot, not the stale
    directory entry."""
    agg = _mk()
    keys = np.arange(4, dtype=np.uint64)
    ones = np.ones(4, dtype=np.int64)
    agg.update(keys, np.zeros(4, dtype=np.int32), [ones, ones])
    agg.extract(0, 1, 1)  # closes bin 0, boundary=1
    assert agg.directory.boundary == 1
    agg.update(keys, np.full(4, 5, dtype=np.int32), [ones, ones * 9])
    k, b, accs = agg.extract(5, 6, 6)
    assert _table(k, b, accs) == {(k, 5): (1.0, 9.0) for k in range(4)}


# --------------------------------------------------------------- collision


def test_directory_code_collision_raises():
    d = BinSlotDirectory(cap=64, region_size=16)
    code = np.array([12345], dtype=np.uint64)
    d.lookup_or_assign(code, np.array([1], dtype=np.int64), np.array([0], dtype=np.int64))
    # same 64-bit code, different key identity -> must be detected
    with pytest.raises(RuntimeError, match="collision"):
        d.lookup_or_assign(code, np.array([2], dtype=np.int64), np.array([0], dtype=np.int64))


# ------------------------------------------------------------- differential


@pytest.mark.parametrize("kinds,dtypes", [
    (("count", "sum"), (np.int64, np.int64)),
    (("min", "max"), (np.int64, np.int64)),
    (("sum",), (np.float64,)),
])
@pytest.mark.parametrize("table", ["at_ceiling", "grows"])
def test_random_stream_differential_with_closes(kinds, dtypes, table, request):
    """Interleaved updates + incremental closes, small table forcing constant
    region churn and, at its ceiling, spill (free to grow, it grows); jax
    path must match the numpy oracle exactly."""
    if table == "at_ceiling":
        request.getfixturevalue("at_ceiling")
    rng = np.random.default_rng(3)
    jx = _mk(kinds=kinds, dtypes=dtypes)
    ora = _mk(backend="numpy", kinds=kinds, dtypes=dtypes)
    got, want = {}, {}
    for step in range(24):
        n = 120
        keys = rng.integers(0, 90, n).astype(np.uint64)  # 90 keys/bin > cap=64
        bins = rng.integers(step // 4, step // 4 + 2, n).astype(np.int32)
        vals = rng.integers(1, 100, n).astype(np.int64)
        ins = [np.ones(n, dtype=np.int64) if k == "count" else vals for k in kinds]
        jx.update(keys, bins, ins)
        ora.update(keys, bins, ins)
        if step % 4 == 3:
            close = step // 4 + 1
            for agg, out in ((jx, got), (ora, want)):
                k, b, accs = agg.extract(0, close, close)
                t = _table(k, b, accs)
                assert not (set(t) & set(out)), "duplicate (key,bin) emitted"
                out.update(t)
    for agg, out in ((jx, got), (ora, want)):
        k, b, accs = agg.extract(0, 1 << 30, 1 << 30)
        out.update(_table(k, b, accs))
    assert got == want
    assert (jx.cap == KW["cap"]) == (table == "at_ceiling")


def test_scan_range_nondestructive_with_spill(at_ceiling):
    agg = _mk()
    n_keys = 100
    keys = np.arange(n_keys, dtype=np.uint64)
    ones = np.ones(n_keys, dtype=np.int64)
    agg.update(keys, np.zeros(n_keys, dtype=np.int32), [ones, ones])
    t1 = _table(*agg.scan_range(0, 1))
    t2 = _table(*agg.scan_range(0, 1))
    assert t1 == t2 and len(t1) == n_keys
    assert agg.spill  # scan must not consume spill entries


def test_native_dir_resolve_matches_numpy_fallback():
    """The C++ ah_dir_resolve fast path and the pure-numpy unique+probe path
    must produce identical aggregation results (same directory semantics,
    including claims after closes raising the boundary)."""
    from arroyo_tpu import config as cfg
    from arroyo_tpu import native

    if not native.available():
        pytest.skip("native library unavailable")
    rng = np.random.default_rng(11)
    streams = [
        (rng.integers(0, 90, 120).astype(np.uint64),
         rng.integers(s // 4, s // 4 + 2, 120).astype(np.int32),
         rng.integers(1, 100, 120).astype(np.int64))
        for s in range(24)
    ]

    def run(disable_native):
        saved = native._lib, native._lib_failed
        saved_enabled = cfg.config().get("native.enabled", True)
        try:
            if disable_native:
                cfg.update({"native.enabled": False})
                native._lib = None
                native._lib_failed = True
            agg = _mk()
            out = {}
            for s, (keys, bins, vals) in enumerate(streams):
                agg.update(keys, bins, [np.ones(len(keys), dtype=np.int64), vals])
                if s % 4 == 3:
                    k, b, accs = agg.extract(0, s // 4 + 1, s // 4 + 1)
                    out.update(_table(k, b, accs))
            k, b, accs = agg.extract(0, 1 << 30, 1 << 30)
            out.update(_table(k, b, accs))
            return out
        finally:
            native._lib, native._lib_failed = saved
            cfg.update({"native.enabled": saved_enabled})

    assert run(False) == run(True)
