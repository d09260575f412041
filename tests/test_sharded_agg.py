"""Multi-chip sharded aggregation on a virtual 8-device CPU mesh:
differential against the numpy oracle, plus key-ownership checks."""

import functools

import numpy as np
import pytest

from arroyo_tpu.hashing import hash_column, servers_for_hashes
from arroyo_tpu.ops import HostAggregator
from arroyo_tpu.parallel import ShardedAggregator, make_mesh


def _sources(n_dev, batch_cap, per_source):
    """[n_dev, batch_cap] arrays from one (keys_u64, bins, vals) triple a
    source shard: what each shard holds before the exchange, row for row."""
    k = np.zeros((n_dev, batch_cap), np.int64)
    b = np.zeros((n_dev, batch_cap), np.int32)
    valid = np.zeros((n_dev, batch_cap), bool)
    vs = [np.zeros((n_dev, batch_cap), v.dtype) for v in per_source[0][2]]
    for d, (keys, bins, vals) in enumerate(per_source):
        m = len(keys)
        assert m <= batch_cap
        k[d, :m], b[d, :m], valid[d, :m] = keys.view(np.int64), bins, True
        for lane, v in zip(vs, vals):
            lane[d, :m] = v
    return k, b, valid, vs


def _pad_sharded(n_dev, batch_cap, keys, bins, vals):
    """Scatter a flat stream round-robin across devices, pad to batch_cap."""
    return _sources(n_dev, batch_cap, [(keys[d::n_dev], bins[d::n_dev], [v[d::n_dev] for v in vals])
                                       for d in range(n_dev)])


def test_sharded_matches_oracle():
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("needs multi-device CPU mesh")
    mesh = make_mesh(4)
    rng = np.random.default_rng(7)
    agg = ShardedAggregator(mesh, ("sum", "count"), (np.int64, np.int64),
                            cap=1024, batch_cap=128, per_dest_cap=128,
                            max_probes=32, emit_cap=256)
    ora = HostAggregator(("sum", "count"), (np.int64, np.int64))
    for _ in range(4):
        n = 400
        keys = hash_column(rng.integers(0, 60, size=n).astype(np.int64))
        bins = rng.integers(0, 3, size=n).astype(np.int32)
        vals = rng.integers(1, 100, size=n).astype(np.int64)
        ones = np.ones(n, dtype=np.int64)
        ora.update(keys, bins, [vals, ones])
        k, b, valid, vs = _pad_sharded(4, 128, keys, bins, [vals, ones])
        agg.update_sharded(k, b, valid, vs)
    sk, sb, sa = agg.extract_all(0, 10, 10)
    ok, ob, oa = ora.extract(0, 10, 10)
    to_dict = lambda K, B, A: {
        (int(b_), int(k_)): (int(A[0][i]), int(A[1][i]))
        for i, (k_, b_) in enumerate(zip(K.view(np.int64), B))
    }
    assert to_dict(sk, sb, sa) == to_dict(ok, ob, oa)


def test_sharded_entries_live_on_owner_shard():
    """After the all_to_all, each (key) must reside on its range owner."""
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("needs multi-device CPU mesh")
    mesh = make_mesh(4)
    agg = ShardedAggregator(mesh, ("count",), (np.int64,), cap=256,
                            batch_cap=64, per_dest_cap=64, max_probes=16,
                            emit_cap=64)
    keys = hash_column(np.arange(100, dtype=np.int64))
    bins = np.zeros(100, dtype=np.int32)
    ones = np.ones(100, dtype=np.int64)
    k, b, valid, vs = _pad_sharded(4, 64, keys, bins, [ones])
    agg.update_sharded(k, b, valid, vs)
    keys_t, bins_t, occ_t = (np.asarray(agg.state[0]), np.asarray(agg.state[1]),
                             np.asarray(agg.state[2]))
    for d in range(4):
        present = keys_t[d][occ_t[d]].view(np.uint64)
        if len(present):
            assert (servers_for_hashes(present, 4) == d).all()


# ------------------- probe rounds: each shard leaves the loop on its own

def _keys_of_shard(rng, shard, n, n_dev=4):
    """``n`` distinct u64 keys in one shard's contiguous key range."""
    width = ((1 << 64) - 1) // n_dev + 1
    keys = np.uint64(shard * width) + rng.choice(1 << 40, size=n, replace=False).astype(np.uint64)
    assert (servers_for_hashes(keys, n_dev) == shard).all()
    return keys


def _rows(K, B, A):
    return {(int(b_), int(k_)): tuple(int(a[i]) for a in A)
            for i, (k_, b_) in enumerate(zip(K.view(np.int64), B))}


@pytest.mark.parametrize("max_probes", [8, 32])
@pytest.mark.parametrize("kinds", [("max",), ("max", "count")], ids=["one-lane", "two-lanes"])
def test_shards_with_unlike_loads_run_unlike_rounds_in_one_step(kinds, max_probes, monkeypatch):
    """Shard 0's table full, the other three empty: in one step shard 0
    probes to the bound and spills while the others leave after a round or
    two. The table equals the fixed-round loop's bit for bit, the rows the
    numpy oracle's, across a snapshot -> restore."""
    import jax
    from test_aggregate_device import fixed_rounds_merge

    from arroyo_tpu.parallel import sharded_agg

    if len(jax.devices()) < 4:
        pytest.skip("needs multi-device CPU mesh")
    dtypes = tuple(np.int64 for _ in kinds)
    kw = dict(cap=256, batch_cap=128, per_dest_cap=128, max_probes=max_probes,
              emit_cap=256, spill_cap=1024)
    rng = np.random.default_rng(max_probes + len(kinds))

    def batch(keys):
        return keys, np.zeros(len(keys), np.int32), [
            np.ones(len(keys), np.int64) if k == "count"
            else rng.integers(1, 1000, len(keys)) for k in kinds]

    # shard 0 fed past its 256 slots, then one step with new keys for every
    # shard and some that shard 0 already holds
    hot = _keys_of_shard(rng, 0, 400)
    step = np.concatenate([_keys_of_shard(rng, d, 60) for d in range(4)] + [hot[:40]])
    assert len(step) <= 4 * 128
    fill, step = batch(hot), batch(step[rng.permutation(len(step))])
    with monkeypatch.context() as m:  # the step is traced at its first call
        m.setattr(sharded_agg, "probe_merge",
                  lambda *a: fixed_rounds_merge(*a) + (np.int32(a[-1]),))
        fixed = ShardedAggregator(make_mesh(4), kinds, dtypes, **kw)
        fixed.update(*fill)
        fixed.update(*step)
    agg = ShardedAggregator(make_mesh(4), kinds, dtypes, **kw)
    ora = HostAggregator(kinds, dtypes)
    agg.update(*fill)
    ora.update(*fill)
    # as full as max_probes rounds make it, the rest in its spill buffer
    before = np.asarray(agg.state[-1]).copy()
    assert before[0] > 0 and (before[1:] == 0).all()  # no row, no round
    occ = np.asarray(agg.state[2])
    assert occ[0].mean() > 0.9 and not occ[1:].any()
    agg.update(*step)
    ora.update(*step)
    rounds = np.asarray(agg.state[-1]) - before
    assert rounds[0] == max_probes               # rows left over: to the bound
    assert (1 <= rounds[1:]).all() and (rounds[1:] <= 6).all()
    for got, want in zip(jax.tree_util.tree_leaves(agg.state[:-1]),
                         jax.tree_util.tree_leaves(fixed.state[:-1])):
        assert np.array_equal(np.asarray(got), np.asarray(want))
    assert (np.asarray(fixed.state[-1]) == fixed.host_steps * max_probes).all()
    # the snapshot reads the count, and what it holds restores to the same rows
    steps = agg.host_steps
    snap = agg.snapshot()
    assert agg.mesh_stats()["probe_rounds"] == int(np.asarray(agg.state[-1]).max())
    assert agg.mesh_stats()["probe_steps"] == steps
    assert agg.mesh_stats()["max_probes"] == max_probes
    want = _rows(*ora.extract(0, 1, 1))
    assert _rows(*snap) == want and len(want) == 400 + 240
    assert agg.overflow_rows >= 400 - 256
    again = ShardedAggregator(make_mesh(4), kinds, dtypes, **kw)
    again.restore(*snap)
    assert _rows(*again.extract_all(0, 1, 1)) == want
    assert _rows(*agg.extract_all(0, 1, 1)) == want
    # a restore starts the device's sums over; the host's count only grows
    agg.restore(*snap)
    assert agg.mesh_stats()["probe_rounds"] >= int(np.asarray(agg.state[-1]).max())
    assert _rows(*agg.snapshot()) == want


# ------------- the width behind the exchange: a narrow rung against the wide

_SIZES = dict(cap=512, batch_cap=128, per_dest_cap=128, max_probes=8, emit_cap=256,
              spill_cap=256)


def _flat(per_source):
    return (np.concatenate([p[0] for p in per_source]), np.concatenate([p[1] for p in per_source]),
            [np.concatenate([p[2][i] for p in per_source]) for i in range(len(per_source[0][2]))])


@functools.lru_cache(maxsize=None)
def _narrow_and_wide(kinds):
    """Two stores of one size, their steps traced here: one with the ladder
    as shipped, one whose ladder is empty, so that every step runs behind
    its exchange at the merged buffer's width, as every step did before."""
    from arroyo_tpu.parallel import sharded_agg

    dtypes = tuple(np.int64 for _ in kinds)
    assert sharded_agg._rungs(128, 4 * 128 + 128) == (8, 32, 128)
    agg = ShardedAggregator(make_mesh(4), kinds, dtypes, **_SIZES)
    ladder = sharded_agg._rungs
    sharded_agg._rungs = lambda blen, full: ()
    try:
        wide = ShardedAggregator(make_mesh(4), kinds, dtypes, **_SIZES)
        wide.update(np.zeros(1, np.uint64), np.zeros(1, np.int32),
                    [np.zeros(1, np.int64) for _ in kinds])  # the step is traced at its first call
    finally:
        sharded_agg._rungs = ladder
    return agg, wide


@pytest.mark.parametrize("n", [0, 1, 8, 9, 32, 33, 128, 129])
@pytest.mark.parametrize("kinds", [("sum",), ("max",), ("sum", "count"), ("min", "max")],
                         ids="-".join)
def test_a_narrow_rung_leaves_the_state_the_wide_path_leaves(kinds, n):
    """Shard 0 receives ``n`` rows of one step, from all four sources and
    some of them twice: none, one, a rung's width (8, 32, 128) and one over
    it (129 fits no narrow rung). Whatever rung a shard takes, its table, its
    spill buffer, its overflow count and its rounds are the wide path's, bit
    for bit; shard 0's table is four fifths full of other keys and of some of these
    before, so rows match, claim, lose a claim and spill."""
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("needs multi-device CPU mesh")
    agg, wide = _narrow_and_wide(kinds)
    rng = np.random.default_rng(n + len(kinds))

    def rows(keys):
        return keys, rng.integers(0, 2, len(keys)).astype(np.int32), [
            np.ones(len(keys), np.int64) if k == "count"
            else rng.integers(-1000, 1000, len(keys)) for k in kinds]

    mine = _keys_of_shard(rng, 0, max(n, 8))
    fill = rows(np.concatenate([_keys_of_shard(rng, 0, 400), mine[:n // 2],
                                _keys_of_shard(rng, 2, 60)]))
    # source s holds every fourth of the n rows; sources 1-3 hold two keys
    # of source 0's again, so the merge behind the exchange has rows to merge
    per_source = []
    for s in range(4):
        keys = mine[:n][s::4].copy()
        if s and len(keys) > 2:
            keys[-2:] = mine[:n][0::4][:2]
        per_source.append(rows(np.concatenate([keys, _keys_of_shard(rng, 1 + s % 3, 5)])))
    for a in (agg, wide):
        a.state = a._init_state()
        a.update(*fill)
        before = np.asarray(a.state[-2]).copy()
        a.update_sharded(*_sources(4, 128, per_source))
        a.took_wide = np.asarray(a.state[-2]) - before
    assert list(agg.took_wide) == [int(n > 128), 0, 0, 0]
    assert list(wide.took_wide) == [int(n > 0), 1, 1, 1]
    for got, want in zip(jax.tree_util.tree_leaves(agg.state[:-2] + agg.state[-1:]),
                         jax.tree_util.tree_leaves(wide.state[:-2] + wide.state[-1:])):
        assert np.array_equal(np.asarray(got), np.asarray(want))
    # shard 0's table is full: the append ran on rows, and lost none
    assert n < 128 or np.asarray(agg.state[7])[0] > n - 112
    assert not np.asarray(agg.state[4]).any()
    ora = HostAggregator(kinds, tuple(np.int64 for _ in kinds))
    ora.update(*fill)
    ora.update(*_flat(per_source))
    assert _rows(*agg.snapshot()) == _rows(*ora.extract(0, 2, 2))


@pytest.mark.parametrize("skew", ["rows-past-the-rungs", "rows-kept-local"])
def test_one_shard_on_the_wide_rung_beside_three_on_a_narrow_one(skew):
    """One step in which shard 0 alone takes the wide rung: because it
    receives more rows than a narrow rung holds, or because it keeps rows
    local that its lane to shard 1 (``per_dest_cap`` 32) had no room for.
    The rows are the numpy oracle's across a snapshot -> restore, and the
    device's count says which shard ran wide."""
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("needs multi-device CPU mesh")
    kinds, dtypes = ("sum", "max"), (np.int64, np.int64)
    kw = dict(_SIZES, per_dest_cap=128 if skew == "rows-past-the-rungs" else 32)
    rng = np.random.default_rng(len(skew))

    def rows(keys):
        return keys, np.zeros(len(keys), np.int32), [rng.integers(1, 1000, len(keys))
                                                     for _ in kinds]

    if skew == "rows-past-the-rungs":
        hot = _keys_of_shard(rng, 0, 132)  # 33 from each source: 132 > 128
        per_source = [rows(np.concatenate([hot[s::4]] + [_keys_of_shard(rng, d, 6)
                                                         for d in (1, 2, 3)]))
                      for s in range(4)]
    else:
        per_source = [rows(np.concatenate([_keys_of_shard(rng, d, 6) for d in (1, 2, 3)]))
                      for s in range(4)]
        per_source[0] = rows(_keys_of_shard(rng, 1, 40))  # 32 leave, 8 stay on shard 0
    agg = ShardedAggregator(make_mesh(4), kinds, dtypes, **kw)
    agg.update_sharded(*_sources(4, 128, per_source))
    assert list(agg.host_steps - np.asarray(agg.state[-2])) == [0, 1, 1, 1]  # narrow steps
    occ = np.asarray(agg.state[2])
    assert occ[0].sum() == (132 if skew == "rows-past-the-rungs" else 8)
    ora = HostAggregator(kinds, dtypes)
    ora.update(*_flat(per_source))
    want = _rows(*ora.extract(0, 1, 1))
    snap = agg.snapshot()
    # a step that one shard ran wide is no narrow step
    assert agg.mesh_stats()["narrow_steps"] == 0 and agg.mesh_stats()["probe_steps"] == 1
    assert _rows(*snap) == want
    again = ShardedAggregator(make_mesh(4), kinds, dtypes, **kw)
    again.restore(*snap)
    assert _rows(*again.extract_all(0, 1, 1)) == want
    assert _rows(*agg.extract_all(0, 1, 1)) == want
    # the restore's own step deals the rows round: no lane overflows, and
    # shard 0 still receives 132 rows where it held that many
    assert again.mesh_stats()["probe_steps"] == 1
    assert again.mesh_stats()["narrow_steps"] == int(skew == "rows-kept-local")


@pytest.mark.parametrize("shift", [0, 3, 100, 121])
def test_valid_rows_anywhere_in_the_batch_reach_the_table(shift):
    """The host deals a step's rows to the front of each shard's batch; a
    fused prefix leaves them where its filter did. Seven rows a shard, moved
    ``shift`` places back (121: the last is the batch's last row): the side
    before the exchange takes a rung that holds the last valid row, not the
    count of them."""
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("needs multi-device CPU mesh")
    kinds, dtypes = ("sum", "min"), (np.int64, np.int64)
    rng = np.random.default_rng(shift)
    per_source = [(_keys_of_shard(rng, (s + 1) % 4, 7), np.zeros(7, np.int32),
                   [rng.integers(1, 1000, 7) for _ in kinds]) for s in range(4)]
    k, b, valid, vs = _sources(4, 128, per_source)
    agg = ShardedAggregator(make_mesh(4), kinds, dtypes, **_SIZES)
    agg.update_sharded(*(np.roll(a, shift, axis=1) for a in (k, b, valid)),
                       [np.roll(v, shift, axis=1) for v in vs])
    ora = HostAggregator(kinds, dtypes)
    ora.update(*_flat(per_source))
    assert _rows(*agg.snapshot()) == _rows(*ora.extract(0, 1, 1))
    assert agg.mesh_stats()["narrow_steps"] == 1 and np.asarray(agg.state[2]).sum() == 28


def test_the_ladder_is_short_and_under_the_merged_buffer():
    from arroyo_tpu.parallel import sharded_agg

    assert sharded_agg._rungs(8192, 4 * 4096 + 8192) == (512, 2048, 8192)  # shipped sizes
    assert sharded_agg._rungs(8192, 8192) == (512, 2048)  # before the exchange
    assert sharded_agg._rungs(64, 64 + 64) == (4, 16, 64)
    assert sharded_agg._rungs(100, 4 * 100 + 100) == (8, 32, 128)  # a fused prefix's length
    for name in ("mesh.rung_select", "mesh.front_gather"):
        assert name in sharded_agg.STEP_PHASES


def test_warm_compiles_both_programs_and_leaves_the_state_as_it_was():
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("needs multi-device CPU mesh")
    agg = ShardedAggregator(make_mesh(4), ("max", "count"), (np.int64, np.int64), cap=256,
                            batch_cap=64, per_dest_cap=64, max_probes=8, emit_cap=64)
    keys = hash_column(np.arange(90, dtype=np.int64))
    agg.update(keys, np.zeros(90, np.int32), [np.arange(90), np.ones(90, np.int64)])
    before = [np.asarray(leaf).copy() for leaf in jax.tree_util.tree_leaves(agg.state)]
    stats = agg.mesh_stats()
    agg.warm()
    for was, leaf in zip(before, jax.tree_util.tree_leaves(agg.state)):
        assert np.array_equal(was, np.asarray(leaf))
    assert agg.mesh_stats() == stats
    fresh = ShardedAggregator(make_mesh(4), ("max",), (np.int64,), cap=256, batch_cap=64,
                              per_dest_cap=64, max_probes=8, emit_cap=64)
    fresh.warm()
    assert fresh._step._cache_size() == 1 and fresh._extract._cache_size() == 1
    # a step on no rows counts on no rung and runs no round
    assert not np.asarray(fresh.state[-1]).any() and not np.asarray(fresh.state[-2]).any()
    fresh.update(keys, np.zeros(90, np.int32), [np.arange(90)])
    assert len(fresh.extract_all(0, 1, 1)[0]) == 90
    assert fresh.mesh_stats()["narrow_steps"] == fresh.mesh_stats()["probe_steps"] == 1
    assert fresh._step._cache_size() == 1 and fresh._extract._cache_size() == 1
