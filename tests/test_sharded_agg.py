"""Multi-chip sharded aggregation on a virtual 8-device CPU mesh:
differential against the numpy oracle, plus key-ownership checks."""

import numpy as np
import pytest

from arroyo_tpu.hashing import hash_column, servers_for_hashes
from arroyo_tpu.ops import DeviceHashAggregator
from arroyo_tpu.parallel import ShardedAggregator, make_mesh


def _pad_sharded(n_dev, batch_cap, keys, bins, vals):
    """Scatter a flat stream round-robin across devices, pad to batch_cap."""
    k = np.zeros((n_dev, batch_cap), dtype=np.int64)
    b = np.zeros((n_dev, batch_cap), dtype=np.int32)
    valid = np.zeros((n_dev, batch_cap), dtype=bool)
    vs = [np.zeros((n_dev, batch_cap), dtype=v.dtype) for v in vals]
    for d in range(n_dev):
        rows = slice(d, len(keys), n_dev)
        m = len(keys[rows])
        assert m <= batch_cap
        k[d, :m] = keys[rows].view(np.int64)
        b[d, :m] = bins[rows]
        valid[d, :m] = True
        for i, v in enumerate(vals):
            vs[i][d, :m] = v[rows]
    return k, b, valid, vs


def test_sharded_matches_oracle():
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("needs multi-device CPU mesh")
    mesh = make_mesh(4)
    rng = np.random.default_rng(7)
    agg = ShardedAggregator(mesh, ("sum", "count"), (np.int64, np.int64),
                            cap=1024, batch_cap=128, per_dest_cap=128,
                            max_probes=32, emit_cap=256)
    ora = DeviceHashAggregator(("sum", "count"), (np.int64, np.int64), backend="numpy")
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
    ora = DeviceHashAggregator(kinds, dtypes, backend="numpy")
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
    fresh.update(keys, np.zeros(90, np.int32), [np.arange(90)])
    assert len(fresh.extract_all(0, 1, 1)[0]) == 90
    assert fresh._step._cache_size() == 1 and fresh._extract._cache_size() == 1
