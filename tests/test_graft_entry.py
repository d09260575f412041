"""The driver's entry points: ``entry()`` hands over the production
one-chip step, jittable, and ``dryrun_multichip`` stays importable."""

import numpy as np


def test_entry_is_the_slot_tables_step_and_runs_under_jit():
    import jax

    import __graft_entry__ as graft
    from arroyo_tpu.ops import slot_agg

    fn, args = graft.entry()
    state, slots, vals = args
    cap = len(state[0])
    assert fn is slot_agg._build_slot_jax(
        ("sum", "count", "max"), (np.dtype(np.int64),) * 3, cap, 256)[0].__wrapped__
    out = jax.jit(fn)(*args)
    again = jax.jit(fn)(*args)  # nothing was donated: the arguments are still there

    slots, (v_sum, v_max) = np.asarray(slots), [np.asarray(v) for v in vals]
    real = slots < cap
    assert 0 < (~real).sum() < len(slots)  # the step's padding is among the rows
    want_sum, want_cnt = np.zeros(cap, np.int64), np.zeros(cap, np.int64)
    want_max = np.full(cap, np.iinfo(np.int64).min)
    np.add.at(want_sum, slots[real], v_sum[real])
    np.add.at(want_cnt, slots[real], 1)
    np.maximum.at(want_max, slots[real], v_max[real])
    for got in (out, again):
        for g, w in zip(got, (want_sum, want_cnt, want_max)):
            assert np.array_equal(np.asarray(g), w)
    assert callable(graft.dryrun_multichip)
