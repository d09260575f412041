"""Differential tests: the device stores against the host store, and the
mesh shard's probe_merge against its fixed-round loop."""

import functools

import numpy as np
import pytest

from arroyo_tpu.ops import HostAggregator
from arroyo_tpu.ops.slot_agg import SlotAggregator, SlotExtractHandle


def _random_stream(rng, n, n_keys, n_bins):
    # spread over 64 bits, and not by the slot directory's own bin multiplier:
    # key ^ bin * that constant is the directory's code, and keys that are
    # small multiples of it collide there by construction (which it reports)
    keys = rng.integers(0, n_keys, size=n).astype(np.uint64) * np.uint64(0xD6E8FEB86659FD93)
    bins = rng.integers(0, n_bins, size=n).astype(np.int32)
    vals = rng.integers(1, 1000, size=n).astype(np.int64)
    return keys, bins, vals


def _as_dict(keys, bins, accs):
    return {
        (int(b), int(k)): tuple(int(a[i]) if np.issubdtype(a.dtype, np.integer) else float(a[i]) for a in accs)
        for i, (k, b) in enumerate(zip(keys.tolist(), bins.tolist()))
    }


@pytest.mark.parametrize("acc_kinds,acc_dtypes", [
    (("sum", "count"), (np.int64, np.int64)),
    (("min", "max"), (np.int64, np.int64)),
    (("sum",), (np.float64,)),
])
def test_jax_matches_numpy(acc_kinds, acc_dtypes):
    rng = np.random.default_rng(42)
    jx = SlotAggregator(acc_kinds, acc_dtypes, cap=1024, batch_cap=256, region_size=128)
    ora = HostAggregator(acc_kinds, acc_dtypes)
    for _ in range(5):
        keys, bins, vals = _random_stream(rng, 700, n_keys=50, n_bins=4)
        ins = []
        for k in acc_kinds:
            ins.append(np.ones(len(keys), dtype=np.int64) if k == "count" else vals)
        jx.update(keys, bins, ins)
        ora.update(keys, bins, ins)
    jk, jb, ja = jx.extract(0, 10, 10)
    ok, ob, oa = ora.extract(0, 10, 10)
    assert _as_dict(jk, jb, ja) == _as_dict(ok, ob, oa)


def test_extract_respects_ranges_and_freeing():
    agg = SlotAggregator(("count",), (np.int64,), cap=256, batch_cap=64, region_size=16)
    keys = np.arange(10, dtype=np.uint64)
    ones = np.ones(10, dtype=np.int64)
    for b in range(4):
        agg.update(keys, np.full(10, b, dtype=np.int32), [ones])
    # non-destructive range scan of bins [1,3), nothing freed
    k, b, a = agg.extract(1, 3, 0)
    assert len(k) == 20 and set(b.tolist()) == {1, 2}
    # still there
    k2, b2, _ = agg.extract(1, 3, 0)
    assert len(k2) == 20
    # destructive close of bins < 2
    k3, b3, _ = agg.extract(0, 2, 2)
    assert len(k3) == 20 and set(b3.tolist()) == {0, 1}
    k4, _, _ = agg.extract(0, 10, 0)
    assert len(k4) == 20  # only bins 2,3 remain


def test_null_string_keys_hash():
    from arroyo_tpu.hashing import hash_column

    col = np.array(["a", None, "b", None, "a"], dtype=object)
    h = hash_column(col)
    assert h[0] == h[4] and h[1] == h[3] and h[0] != h[1] != h[2]


def test_scan_range_over_more_than_one_region():
    """A bin whose groups fill more regions than one (the last one partly)
    is read whole, each slot once."""
    agg = SlotAggregator(("count",), (np.int64,), cap=64, batch_cap=64, region_size=16)
    keys = np.arange(40, dtype=np.uint64)
    agg.update(keys, np.zeros(40, dtype=np.int32), [np.ones(40, dtype=np.int64)])
    assert len(agg.directory.bin_regions[0]) == 3
    k, b, a = agg.scan_range(0, 1)
    assert len(k) == 40
    assert sorted(np.asarray(k).tolist()) == list(range(40))
    assert a[0].sum() == 40
    # non-destructive: second scan sees the same entries
    k2, _, _ = agg.scan_range(0, 1)
    assert len(k2) == 40
    agg.free_bins_below(1)
    k3, _, _ = agg.scan_range(0, 1)
    assert len(k3) == 0


def _one_shard(kinds, dtypes, **kw):
    from arroyo_tpu.parallel import ShardedAggregator, make_mesh

    return ShardedAggregator(make_mesh(1), kinds, dtypes, **kw)


@pytest.mark.parametrize("store", [
    lambda: _one_shard(("count",), (np.int64,), cap=256, batch_cap=128, max_probes=256,
                       emit_cap=64),
    lambda: SlotAggregator(("count",), (np.int64,), cap=256, batch_cap=128, region_size=16),
], ids=["mesh-shard", "slot-table"])
def test_probe_hole_no_duplicate_entries(store):
    """Freeing closed bins punches holes in the mesh shard's linear-probe
    chains, and gives a slot table's regions back to later bins; a later
    update of a live (key, bin) must not surface as two emitted rows.
    Differential test: interleaved updates + incremental closes, the device
    store against the host store."""
    rng = np.random.default_rng(7)
    jx = store()
    orc = HostAggregator(("count",), (np.int64,))
    got, want = {}, {}
    for step in range(30):
        n = 100
        keys = rng.integers(0, 40, n).astype(np.uint64)
        bins = rng.integers(step // 3, step // 3 + 3, n).astype(np.int32)
        ones = np.ones(n, dtype=np.int64)
        jx.update(keys, bins, [ones])
        orc.update(keys, bins, [ones])
        if step % 3 == 2:
            close = step // 3 + 1
            for agg, out in ((jx, got), (orc, want)):
                k, b, a = agg.extract(0, close, close)
                for kk, bb, aa in zip(k.tolist(), b.tolist(), a[0].tolist()):
                    assert (kk, bb) not in out, f"duplicate entry {(kk, bb)}"
                    out[(kk, bb)] = aa
    for agg, out in ((jx, got), (orc, want)):
        k, b, a = agg.extract(0, 1 << 30, 1 << 30)
        for kk, bb, aa in zip(k.tolist(), b.tolist(), a[0].tolist()):
            assert (kk, bb) not in out
            out[(kk, bb)] = aa
    assert got == want


def test_float_accumulators_through_a_close_and_a_scan():
    """Float lanes travel in a buffer of their own type (the TPU compiler
    refuses a 64-bit bitcast, so no int64 transport carries them) through a
    close and a non-destructive scan, and match the host store."""
    rng = np.random.default_rng(7)
    n = 5000
    keys = rng.integers(0, 50, n).astype(np.uint64)
    bins = rng.integers(0, 4, n).astype(np.int32)
    vals = rng.normal(size=n)

    dev = SlotAggregator(("sum", "min"), (np.float64, np.float64),
                         cap=4096, batch_cap=1024, region_size=512)
    ora = HostAggregator(("sum", "min"), (np.float64, np.float64))
    assert dev._n_flt_lanes == 2 and dev._n_int_lanes == 0
    for a in (dev, ora):
        a.update(keys, bins, [vals, vals])

    h = dev.extract_start(0, 2, 2)
    assert isinstance(h, SlotExtractHandle)
    assert all(ibuf is None and fbuf is not None for _regs, ibuf, fbuf in h._groups)
    dk, db, daccs = h.result()
    ok, ob, oaccs = ora.extract(0, 2, 2)

    def table(k, b, accs):
        return {(int(kk), int(bb)): (float(a0), float(a1))
                for kk, bb, a0, a1 in zip(k, b, accs[0], accs[1])}

    dt, ot = table(dk, db, daccs), table(ok, ob, oaccs)
    assert set(dt) == set(ot)
    for kk in dt:
        np.testing.assert_allclose(dt[kk], ot[kk], rtol=1e-12)
    # non-destructive scan of the remaining bins
    dk2, db2, daccs2 = dev.scan_range(2, 4)
    ok2, ob2, oaccs2 = ora.scan_range(2, 4)
    dt2, ot2 = table(dk2, db2, daccs2), table(ok2, ob2, oaccs2)
    assert set(dt2) == set(ot2)
    for kk in dt2:
        np.testing.assert_allclose(dt2[kk], ot2[kk], rtol=1e-12)


# ------------------------- probe_merge leaves its loop when no row is active

_C1, _C2 = np.uint64(0xFF51AFD7ED558CCD), np.uint64(0xC4CEB9FE1A85EC53)
PROBE_CAP, PROBE_ROWS = 1024, 256
LANES = {1: ("max",), 2: ("max", "sum")}


def fixed_rounds_merge(acc_kinds, table, u_key, u_bin, active0, u_accs, cap, max_probes):
    """The merge as it was while its loop could not leave early: every one
    of ``max_probes`` rounds runs, whatever it finds. What probe_merge has
    to equal bit for bit; a copy, so that the two cannot drift together."""
    import jax
    import jax.numpy as jnp

    keys_t, bins_t, occ_t, accs_t = table
    mask_cap = cap - 1
    z = u_key.astype(jnp.uint64) ^ (u_bin.astype(jnp.uint64) * jnp.uint64(_C1))
    z = (z ^ (z >> jnp.uint64(33))) * jnp.uint64(_C2)
    z = z ^ (z >> jnp.uint64(33))
    h0 = (z & jnp.uint64(mask_cap)).astype(jnp.int32)
    seg_pos = jnp.arange(u_key.shape[0], dtype=jnp.int32)

    def combine(kind, a, b):
        return a + b if kind in ("sum", "count") else (
            jnp.minimum(a, b) if kind == "min" else jnp.maximum(a, b))

    def probe(i, carry):
        keys_c, bins_c, occ_c, accs_c, active = carry
        cand = (h0 + i) & mask_cap
        match = active & occ_c[cand] & (keys_c[cand] == u_key) & (bins_c[cand] == u_bin)
        empty_here = active & ~occ_c[cand]
        claims = jnp.full(cap, -1, dtype=jnp.int32).at[
            jnp.where(empty_here, cand, cap)].max(seg_pos, mode="drop")
        write = match | (empty_here & (claims[cand] == seg_pos))
        safe = jnp.where(write, cand, cap)
        accs_c = tuple(
            accs_c[j].at[safe].set(
                jnp.where(match, combine(acc_kinds[j], accs_c[j][cand], u_accs[j]), u_accs[j]),
                mode="drop")
            for j in range(len(acc_kinds)))
        return (keys_c.at[safe].set(u_key, mode="drop"), bins_c.at[safe].set(u_bin, mode="drop"),
                occ_c.at[safe].set(True, mode="drop"), accs_c, active & ~write)

    *table, still_active = jax.lax.fori_loop(
        0, max_probes, probe, (keys_t, bins_t, occ_t, tuple(accs_t), active0))
    return tuple(table), still_active


def _slot_of(keys_i64, bins, cap):
    """numpy's copy of the slot a (key, bin) probes first."""
    z = keys_i64.view(np.uint64) ^ (bins.astype(np.uint64) * _C1)
    z = (z ^ (z >> np.uint64(33))) * _C2
    z = z ^ (z >> np.uint64(33))
    return (z & np.uint64(cap - 1)).astype(np.int64)


@functools.lru_cache(maxsize=None)
def _merges(kinds, max_probes, cap=PROBE_CAP):
    """(probe_merge, the fixed-round loop), jitted, for one accumulator set."""
    import jax

    from arroyo_tpu.ops.aggregate import probe_merge

    return tuple(
        jax.jit(lambda table, k, b, act, accs, f=f: f(kinds, table, k, b, act, accs,
                                                      cap, max_probes))
        for f in (probe_merge, fixed_rounds_merge))


def _empty_table(kinds, cap=PROBE_CAP):
    from arroyo_tpu.ops.aggregate import _identity

    return (np.zeros(cap, np.int64), np.zeros(cap, np.int32), np.zeros(cap, bool),
            tuple(np.full(cap, _identity(k, np.dtype(np.int64)), np.int64) for k in kinds))


def _table_at(load, kinds, rng):
    """A table with ``load`` of its slots taken, each key where the loop
    would have put it (full: whatever 64 rounds could not place is put into
    the slots left over, so that no slot is empty), and the keys in it."""
    n = int(load * PROBE_CAP)
    keys = rng.choice(1 << 40, size=PROBE_CAP, replace=False).astype(np.int64)
    table = _empty_table(kinds)
    if n:
        vals = tuple(rng.integers(1, 1000, PROBE_CAP) for _ in kinds)
        table, left = _merges(kinds, 64)[1](
            table, keys, np.zeros(PROBE_CAP, np.int32), np.arange(PROBE_CAP) < n, vals)
        table = tuple(np.array(t) for t in table[:3]) + (tuple(np.array(a) for a in table[3]),)
        left = np.flatnonzero(np.asarray(left))
        holes = np.flatnonzero(~table[2])[:len(left)]
        table[0][holes], table[2][holes] = keys[left], True
    assert int(table[2].sum()) == n
    return table, keys[:n]


def _equal(got, want) -> None:
    import jax

    got, want = jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)
    assert len(got) == len(want)
    for g, w in zip(got, want):
        g, w = np.asarray(g), np.asarray(w)
        assert g.dtype == w.dtype and np.array_equal(g, w)


@pytest.mark.parametrize("lanes", [1, 2])
@pytest.mark.parametrize("max_probes", [1, 2, 64])
@pytest.mark.parametrize("load", [0.0, 0.1, 0.5, 1.0], ids=["empty", "tenth", "half", "full"])
def test_probe_merge_equals_the_fixed_rounds_bit_for_bit(load, max_probes, lanes):
    kinds = LANES[lanes]
    rng = np.random.default_rng(int(load * 10) * 100 + max_probes * 2 + lanes)
    table, resident = _table_at(load, kinds, rng)
    # 200 active rows: up to 80 of them keys the table holds (they merge),
    # the rest new (they claim a slot, or find none); 56 rows of padding
    known = resident[rng.permutation(len(resident))[:80]]
    keys = np.concatenate([known, (1 << 41) + rng.choice(1 << 40, PROBE_ROWS - len(known),
                                                         replace=False)]).astype(np.int64)
    keys = keys[rng.permutation(PROBE_ROWS)]
    bins = np.zeros(PROBE_ROWS, np.int32)
    active = np.arange(PROBE_ROWS) < 200
    vals = tuple(rng.integers(1, 1000, PROBE_ROWS) for _ in kinds)
    early, fixed = _merges(kinds, max_probes)
    got_table, got_active, rounds = early(table, keys, bins, active, vals)
    want_table, want_active = fixed(table, keys, bins, active, vals)
    _equal((got_table, got_active), (want_table, want_active))
    rounds, left = int(rounds), int(np.asarray(got_active).sum())
    assert 1 <= rounds <= max_probes
    assert left == 0 or rounds == max_probes  # rows left: it ran to the bound
    if load <= 0.1:
        assert rounds <= 6 and (left == 0 or max_probes < 6)
    if load == 1.0:
        # no empty slot: the rows whose key the table holds merge where they
        # meet it, every new key stays active for the spill buffer
        assert rounds == max_probes and left >= 200 - 80
        assert np.array_equal(np.asarray(got_table[2]), table[2])


@pytest.mark.parametrize("lanes", [1, 2])
@pytest.mark.parametrize("rows", [50, 200])
def test_probe_merge_claim_race_places_one_row_a_round(rows, lanes):
    """Every row probes the same empty slot in the same round: one wins it
    (the highest position), the rest move on together."""
    kinds = LANES[lanes]
    rng = np.random.default_rng(rows + lanes)
    many = rng.choice(1 << 50, size=400_000, replace=False).astype(np.int64)
    same = many[_slot_of(many, np.zeros(len(many), np.int32), PROBE_CAP) == 17]
    assert len(same) >= rows
    keys = np.concatenate([same[:rows], np.zeros(PROBE_ROWS - rows, np.int64)])
    bins = np.zeros(PROBE_ROWS, np.int32)
    active = np.arange(PROBE_ROWS) < rows
    vals = tuple(rng.integers(1, 1000, PROBE_ROWS) for _ in kinds)
    early, fixed = _merges(kinds, 64)
    table = _empty_table(kinds)
    got_table, got_active, rounds = early(table, keys, bins, active, vals)
    _equal((got_table, got_active), fixed(table, keys, bins, active, vals))
    assert int(rounds) == min(rows, 64)
    assert int(np.asarray(got_active).sum()) == max(rows - 64, 0)
    placed = np.flatnonzero(np.asarray(got_table[2]))
    assert placed.tolist() == list(range(17, 17 + min(rows, 64)))
    # the highest position wins each round
    assert np.asarray(got_table[0])[17] == keys[rows - 1]


@pytest.mark.parametrize("lanes", [1, 2])
def test_probe_merge_runs_no_round_without_an_active_row(lanes):
    kinds = LANES[lanes]
    rng = np.random.default_rng(lanes)
    table, _resident = _table_at(0.5, kinds, rng)
    keys = rng.choice(1 << 40, PROBE_ROWS, replace=False).astype(np.int64)
    vals = tuple(rng.integers(1, 1000, PROBE_ROWS) for _ in kinds)
    none = np.zeros(PROBE_ROWS, bool)
    early, fixed = _merges(kinds, 64)
    got_table, got_active, rounds = early(table, keys, np.zeros(PROBE_ROWS, np.int32), none, vals)
    assert int(rounds) == 0 and not np.asarray(got_active).any()
    _equal(got_table, table)
    _equal((got_table, got_active),
           fixed(table, keys, np.zeros(PROBE_ROWS, np.int32), none, vals))
