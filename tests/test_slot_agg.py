"""Direct unit tests for the slot-directory aggregator (ops/slot_agg.py):
spill tier, region lifecycle, collision detection, and differential checks
against the dict-based numpy oracle under random interleaved streams.

A table that runs out of regions grows (tests/test_table_growth.py); the
host spill tier is what is left past the ceiling, so the spill tests here
hold the ceiling at the capacity the table starts with (``at_ceiling``)."""

import numpy as np
import pytest

from arroyo_tpu.hashing import splitmix64
from arroyo_tpu.ops import HostAggregator
from arroyo_tpu.ops.slot_agg import BinSlotDirectory, SlotAggregator
from interpreter_lock import lets_go_of_the_lock

KW = dict(cap=64, batch_cap=64, region_size=16)


def _mk(kinds=("count", "sum"), dtypes=(np.int64, np.int64), **kw):
    args = {**KW, **kw}
    return SlotAggregator(kinds, dtypes, **args)


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
    ora = HostAggregator(("count", "sum"), (np.int64, np.int64))
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
    ora = HostAggregator(kinds, dtypes)
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


class _without_native:
    """A host without the library (``native.enabled: false``, no compiler):
    every directory step goes through ``lookup_or_assign``."""

    def __enter__(self):
        from arroyo_tpu import config as cfg
        from arroyo_tpu import native

        self._saved = native._lib, native._lib_failed
        self._enabled = cfg.config().get("native.enabled", True)
        cfg.update({"native.enabled": False})
        native._lib, native._lib_failed = None, True

    def __exit__(self, *exc):
        from arroyo_tpu import config as cfg
        from arroyo_tpu import native

        native._lib, native._lib_failed = self._saved
        cfg.update({"native.enabled": self._enabled})
        return False


CELL_KW = dict(cap=65536, batch_cap=8192, region_size=2048)


def _check_directory(agg, groups):
    """The table as ``_alloc`` and a claim leave it, whichever path placed
    the groups: every region in one chain or free, a chain full but for its
    last region, a bin's slots holding that bin's groups, and every live
    (bin, key) that is found found at a slot that holds it. Until a close
    has raised the boundary a group holds one slot; after it an entry of a
    closed bin may hide a live one further along its probe path, which then
    takes a second slot (``_assemble`` combines the two)."""
    from arroyo_tpu import native
    from arroyo_tpu.ops.slot_agg import _DEAD_BIN

    d = agg.directory
    chained = [r for chain in d.bin_regions.values() for r in chain]
    assert sorted(chained + d.free_regions) == list(range(d.n_regions))
    live = {(b, k) for b, k in groups if b >= d.boundary}
    held = []
    for b, chain in d.bin_regions.items():
        assert chain and all(d.region_fill[r] == d.R for r in chain[:-1])
        assert 0 < d.region_fill[chain[-1]] <= d.R
        for r in chain:
            sl = slice(r * d.R, r * d.R + int(d.region_fill[r]))
            assert (d.slot_bins[sl] == b).all()
            held += [(b, k) for k in d.slot_keys[sl].tolist()]
    # past the ceiling a group lives in the host's spill store (and, once a
    # close has made room, in both)
    assert set(held) <= live <= set(held) | set(agg.spill)
    assert len(held) == len(set(held)) or d.boundary > _DEAD_BIN
    if held and native.available():
        bins, keys = (np.array(c, dtype=np.int64) for c in zip(*sorted(set(held))))
        slots = native.dir_resolve(keys, bins, d.hcode, d.hbin, d.hslot, d.boundary,
                                   d.slot_keys, d.slot_bins)[0]
        hit = slots >= 0
        assert hit.all() or d.boundary > _DEAD_BIN
        assert (d.slot_keys[slots[hit]] == keys[hit]).all()
        assert (d.slot_bins[slots[hit]] == bins[hit]).all()


def _steps_of(rows, new_keys, n_bins, seed, first_bin=0):
    """Steps of ``rows`` rows the way a cell's pacemaker meets them: each
    brings ``new_keys[i]`` first-seen keys and fills up with keys it has met,
    spread over ``n_bins`` bins a step."""
    rng = np.random.default_rng(seed)
    met, out = 0, []
    for fresh in new_keys:
        new = np.arange(met, met + fresh)
        old = rng.integers(0, max(met, 1), rows - fresh) if met else rng.choice(new, rows - fresh)
        met += fresh
        keys = np.concatenate([new, old])
        rng.shuffle(keys)
        bins = first_bin + rng.integers(0, n_bins, rows)
        out.append(("update", splitmix64(keys.astype(np.uint64)), bins.astype(np.int32)))
    return out


def _small_closing_stream():
    rng = np.random.default_rng(11)
    ops = []
    for s in range(24):
        ops.append(("update", rng.integers(0, 90, 120).astype(np.uint64),
                    rng.integers(s // 4, s // 4 + 2, 120).astype(np.int32)))
        if s % 4 == 3:
            ops.append(("close", s // 4 + 1))
    return ops


# (aggregator sizes, the table may not grow, ops): an op is ("update", keys,
# bins), ("close", below) or ("restore",), a snapshot restored into a new table
DIRECTORY_CASES = {
    "closes-raise-the-boundary": (KW, False, _small_closing_stream()),
    "q7-step-1000-misses-one-bin": (CELL_KW, False, _steps_of(7_400, [3_700, 1_000, 1_000], 1, 1)),
    "window-first-step-3700-misses": (CELL_KW, False, _steps_of(7_400, [3_700], 1, 2)
                                      + [("restore",)] + _steps_of(7_400, [3_700], 1, 3, 1)),
    "two-bins": (CELL_KW, False, _steps_of(7_400, [3_700, 1_000, 46], 2, 4)),
    "six-bins": (CELL_KW, False, _steps_of(7_400, [3_700, 1_000, 46], 6, 5) + [("restore",)]),
    "claim-after-a-close": (CELL_KW, False, _steps_of(7_400, [3_700, 1_000], 2, 6)
                            + [("close", 2)] + _steps_of(7_400, [3_700, 1_000], 2, 6, 2)
                            + [("close", 3)] + _steps_of(7_400, [1_000], 2, 7, 3)),
    "one-key-step": (CELL_KW, False, [("update", np.full(7_400, 42, dtype=np.uint64),
                                       np.zeros(7_400, dtype=np.int32))] * 2),
    "more-bins-than-a-claim-takes": (dict(KW, cap=256), False,
                                     [("update", np.arange(140, dtype=np.uint64) % 7,
                                       np.arange(140, dtype=np.int32) % 70)] * 2),
    "regions-run-out-mid-step-and-the-table-grows": (
        KW, False, _steps_of(120, [100, 20], 3, 8) + [("close", 1)]
        + _steps_of(120, [110], 2, 9, 1) + [("restore",)]),
    "regions-run-out-mid-step-at-the-ceiling": (
        KW, True, _steps_of(120, [100, 20], 3, 8) + [("close", 1)]
        + _steps_of(120, [110], 2, 9, 1) + [("restore",)]),
}


@pytest.mark.parametrize("case", list(DIRECTORY_CASES))
def test_native_dir_resolve_matches_numpy_fallback(case, monkeypatch):
    """Both paths of ``_resolve_slots`` (ah_dir_resolve + ah_dir_claim, and
    numpy's unique + ``lookup_or_assign``, the oracle) leave the same table
    (not the same slot numbers: the rounds of numpy against stream order)
    and so the same extracts, snapshots and restores, at the cells' shapes,
    across closes that raise the boundary, through growth and at the
    ceiling through the spill."""
    from arroyo_tpu import native

    if not native.available():
        pytest.skip("native library unavailable")
    kw, at_ceiling, ops = DIRECTORY_CASES[case]
    if at_ceiling:
        monkeypatch.setattr(SlotAggregator, "_ceiling", lambda self: self.cap)
    many_bins = case == "more-bins-than-a-claim-takes"

    def run(on_native):
        agg, out, groups, claims = _mk(**kw), [], set(), []
        if on_native and not many_bins:
            # no step of these cases may fall back to Python
            monkeypatch.setattr(BinSlotDirectory, "lookup_or_assign", None)
            claim = native.dir_claim

            def counted(*a):
                claims.append(claim(*a))  # the rows it had to leave at -1
                return claims[-1]

            monkeypatch.setattr(native, "dir_claim", counted)
        for op in ops:
            if op[0] == "update":
                _op, keys, bins = op
                ones = np.ones(len(keys), dtype=np.int64)
                agg.update(keys, bins, [ones, keys.view(np.int64) % 1000])
                groups |= set(zip(bins.tolist(), keys.view(np.int64).tolist()))
                _check_directory(agg, groups)
            elif op[0] == "close":
                out.append(_table(*agg.extract(0, op[1], op[1])))
                _check_directory(agg, groups)
            else:
                snap = agg.snapshot()
                out.append(_table(*snap))
                agg = _mk(**kw)
                agg.restore(*snap)
                assert _table(*agg.snapshot()) == out[-1]
        out.append(_table(*agg.extract(0, 1 << 30, 1 << 30)))
        monkeypatch.undo()
        if at_ceiling:
            monkeypatch.setattr(SlotAggregator, "_ceiling", lambda self: self.cap)
        return out, (agg.cap, claims)

    got, (cap, claims) = run(True)
    assert claims or many_bins
    with _without_native():
        want, (cap_numpy, _) = run(False)
    assert got == want and sum(map(len, got))
    assert cap == cap_numpy
    if case.startswith("regions-run-out"):
        assert (cap == kw["cap"]) == at_ceiling and max(claims) > 0


def test_native_directory_detects_a_code_collision():
    """A live entry whose code matches and whose identity does not is a
    64-bit collision on the native path as in ``lookup_or_assign``."""
    from arroyo_tpu import native

    if not native.available():
        pytest.skip("native library unavailable")
    agg = _mk()
    keys, bins = np.arange(8, dtype=np.uint64), np.zeros(8, dtype=np.int32)
    _ks, _b, slots, _left = agg._resolve_slots(keys, bins)
    agg.directory.slot_keys[slots[3]] = 99  # the slot of key 3 now says 99
    with pytest.raises(RuntimeError, match="collision"):
        agg._resolve_slots(keys, bins)


# (rows a step, first-seen keys of each step, bins a step): the cells' steps
LOCK_SHAPES = {
    "q7-steps-1000-misses": (7_400, [1_000] * 4, 1),
    "window-first-step-3700-misses": (7_400, [3_700, 1_000], 1),
    "two-bins": (7_400, [3_700, 1_000, 46], 2),
    "six-bins": (7_400, [3_700, 1_000, 46], 6),
    "one-key-steps": (7_400, [1, 0, 0], 1),
    "q8-steps-of-250-rows": (250, [250, 30, 30], 1),
}


@pytest.mark.parametrize("shape", list(LOCK_SHAPES))
def test_a_directory_step_hands_the_interpreter_lock_over_nowhere(shape, monkeypatch):
    """What ``agg.directory`` costs its thread is the lock: every call that
    lets go of it waits for a dozen threads to give it back. With the
    library bound through ``ctypes.PyDLL``, which keeps the lock, a
    ``_resolve_slots`` step at the cells' shapes makes no call that lets go:
    under ``ctypes.CDLL``, as it is shipped, the hand-overs that are left
    are the two native calls themselves, which run beside the other tasks'
    Python. numpy's path, the sorting calls inside ``lookup_or_assign``,
    lets go all along."""
    import ctypes

    from arroyo_tpu import native

    if not native.available():
        pytest.skip("native library unavailable")
    held = ctypes.PyDLL(native.lib_path())
    native._declare(held)
    monkeypatch.setattr(native, "_lib", held)
    rows, new_keys, n_bins = LOCK_SHAPES[shape]
    steps = [(keys, bins) for _op, keys, bins in _steps_of(rows, new_keys, n_bins, 12)]

    def resolve(agg):
        def run():
            for keys, bins in steps:
                assert agg._resolve_slots(keys, bins)[3] == 0
        return run

    agg = _mk(**CELL_KW)
    assert not lets_go_of_the_lock(resolve(agg))
    assert agg.directory.allocated >= sum(new_keys)
    if max(new_keys) >= 1_000:
        # a sort over a thousand codes lasts long enough for the waiting
        # thread to be seen taking the lock; a shorter one it may miss, and
        # so may a machine busy with five other test workers: asked thrice
        with _without_native():
            assert any(lets_go_of_the_lock(resolve(_mk(**CELL_KW))) for _ in range(3))
