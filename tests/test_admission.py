"""A row enters a window aggregate three ways: staged (``_run_staged``), from
a compiled segment (``insert_arrays``) and as the host half of the fused mesh
step (``mesh_insert_begin``). Whichever way the same rows come, the operator's
bin space, late boundary and bin bookkeeping end the same, and the rows the
mesh mask selects are the rows the other two hand to the store: a checkpoint
and a replay may not depend on the route."""

from __future__ import annotations

import numpy as np
import pytest

from arroyo_tpu.batch import KEY_FIELD, TIMESTAMP_FIELD, Batch
from arroyo_tpu.hashing import hash_columns

W = 1_000_000  # micros: tumbling width, sliding slide (its width is 3 slides)
BASE = 100  # the absolute bin the preset bin space is anchored at


class Recorder:
    """A store that keeps what ``update`` is handed."""

    def __init__(self):
        self.calls = []
        self.staged_batches = 1

    def update(self, hashes, rel, vals, partials=False):
        self.calls.append((np.asarray(hashes), np.asarray(rel), [np.asarray(v) for v in vals]))


class Tumbling:
    name, op_name = "tumbling", "tumbling_aggregate"
    state = ("base_bin", "late_rows", "open_bins", "emitted_before_rel")

    def cfg(self):
        return {"width_micros": W, "key_fields": ["k"], "backend": "jax",
                "aggregates": [("cnt", "count", None), ("mx", "max", "v")],
                "input_dtype_of": lambda e: np.dtype(np.int64)}

    def preset(self, op):
        op.base_bin, op.emitted_before_rel, op.open_bins = BASE, 3, {3, 4}

    def late(self, rel):
        return rel < 3


class Sliding(Tumbling):
    """The boundary is the next window to fire."""

    name, op_name = "sliding-window-fired", "sliding_aggregate"
    state = ("base_bin", "late_rows", "open_bins", "min_bin", "max_bin", "next_window",
             "_late_before")

    def cfg(self):
        return dict(super().cfg(), width_micros=3 * W, slide_micros=W)

    def preset(self, op):
        op.base_bin, op.next_window, op._late_before = BASE, 3, 2
        op.min_bin, op.max_bin, op.open_bins = 3, 4, {3, 4}


class SlidingExtracted(Sliding):
    """The boundary is the bins already read off the device."""

    name = "sliding-bin-extracted"

    def preset(self, op):
        op.base_bin, op.next_window, op._late_before = BASE, 1, 3
        op.min_bin, op.max_bin, op.open_bins = 3, 4, {3, 4}


OPERATORS = [Tumbling(), Sliding(), SlidingExtracted()]

# name -> (the bin space is preset, each row's bin relative to BASE)
SCENARIOS = {
    "first-batch": (False, [7, 5, 6, 5, 9]),
    "no-late-rows": (True, [3, 4, 4, 6, 3]),
    "some-late": (True, [1, 3, 2, 5, 3, 0, -4]),
    "all-late": (True, [0, 1, 2, 2, -1]),
    "empty-input": (True, []),
    # 2**32 bins behind: an int32 cast alone would bring it back on time
    "a-row-wraps-int32": (True, [4, 5 - 2 ** 32, 3]),
}


def make(kind):
    from arroyo_tpu.engine.engine import construct_operator
    from arroyo_tpu.expr import Col
    from arroyo_tpu.graph import OpName

    cfg = kind.cfg()
    cfg["aggregates"] = [(n, k, Col(e) if e else None) for n, k, e in cfg["aggregates"]]
    return construct_operator(OpName(kind.op_name), cfg)


def rows_of(rel_bins):
    rel = np.asarray(rel_bins, dtype=np.int64)
    n = len(rel)
    ts = (rel + BASE) * W + np.arange(n, dtype=np.int64) * 17
    k = np.arange(n, dtype=np.int64) % 3
    return Batch({TIMESTAMP_FIELD: ts, "k": k, "v": k * 7 + ts % 13,
                  KEY_FIELD: hash_columns([k])})


def entered(kind, preset, batch, entry):
    """A fresh operator, the batch through one entry: (its state after, the
    rows its store was handed, the mask the mesh entry returned)."""
    op = make(kind)
    op._setup_key_transport(batch)
    op._agg = rec = Recorder()
    if preset:
        kind.preset(op)
    ts = batch.timestamps
    bins_abs = ts // W
    hashes = batch.keys.astype(np.uint64)
    vals = [np.ones(len(ts), dtype=np.int64), np.asarray(batch["v"]), np.asarray(batch["k"])]
    mask = "not the mesh entry"
    if entry == "_run_staged":
        op._stage.add(batch)
        op.flush_staged(None, None)  # runs _run_staged while rows are staged
    elif entry == "insert_arrays":
        op.insert_arrays(hashes, bins_abs, vals, None)
    else:
        mask = op.mesh_insert_begin(bins_abs, None)
    state = {name: (set(v) if isinstance(v := getattr(op, name), set) else v)
             for name in kind.state}
    return state, rec.calls, mask


@pytest.mark.parametrize("scenario", SCENARIOS)
@pytest.mark.parametrize("kind", OPERATORS, ids=lambda k: k.name)
def test_the_three_entries_admit_the_same_rows_and_leave_the_same_state(kind, scenario):
    preset, rel_bins = SCENARIOS[scenario]
    batch = rows_of(rel_bins)
    staged, staged_rows, _ = entered(kind, preset, batch, "_run_staged")
    compiled, compiled_rows, _ = entered(kind, preset, batch, "insert_arrays")
    fused, fused_rows, mask = entered(kind, preset, batch, "mesh_insert_begin")

    assert staged == compiled == fused
    assert fused_rows == []  # the mesh program does the update

    # the rows the store was handed, by either entry, are the rows the mask selects
    n = batch.num_rows
    keep = np.ones(n, dtype=bool) if mask is None else mask
    assert keep.dtype == bool and len(keep) == n
    assert len(staged_rows) == len(compiled_rows) == (1 if keep.any() else 0)
    for calls in (staged_rows, compiled_rows):
        for hashes, rel, vals in calls:
            assert rel.dtype == np.int32
            assert np.array_equal(hashes, batch.keys.astype(np.uint64)[keep])
            assert np.array_equal(
                rel, (batch.timestamps // W - staged["base_bin"])[keep].astype(np.int32))
            want = [np.ones(int(keep.sum())), np.asarray(batch["v"])[keep],
                    np.asarray(batch["k"])[keep]]
            assert len(vals) == 3 and all(np.array_equal(g, w) for g, w in zip(vals, want))

    # and the scenario is the one its name says
    rel = np.asarray(rel_bins, dtype=np.int64)
    if not preset:
        assert staged["base_bin"] == BASE + min(rel_bins) and staged["late_rows"] == 0
        assert mask is None and staged["open_bins"] == {r - min(rel_bins) for r in rel_bins}
    elif scenario != "a-row-wraps-int32":
        late = kind.late(rel)
        assert staged["late_rows"] == int(late.sum())
        assert np.array_equal(keep, ~late)
        assert staged["open_bins"] == {3, 4} | set(rel[~late].tolist())
    if scenario == "a-row-wraps-int32":
        # compared before the cast, in both operators since they share _admit (PR 53)
        assert staged["late_rows"] == 1 and not keep[1]


def test_the_late_boundary_is_compared_once_an_operator():
    """What the comments "must be mirrored" used to ask of a reader: the
    comparison with the late boundary occurs once in numpy, in the ``_admit``
    the two operators share and all three entries reach (``_run_staged``
    through ``_hook``), each operator saying only what its boundary is
    (``_late_boundary``); the native pass (PR 53) is handed that same number
    and its count of late rows is the one other place ``late_rows`` grows."""
    import inspect

    from arroyo_tpu.windows.sliding import SlidingAggregate
    from arroyo_tpu.windows.tumbling import StagedAggregate, TumblingAggregate

    entries = ("_run_staged", "insert_arrays", "mesh_insert_begin")
    shared = inspect.getsource(StagedAggregate)
    assert shared.count("rel < late_before") == 1
    assert shared.count("self.late_rows +=") == 2 and "self.late_rows += made.late" in shared
    assert shared.count("self._late_boundary()") == 2  # _admit's, and the pass's argument
    for cls in (TumblingAggregate, SlidingAggregate):
        own = vars(cls)
        assert "_late_boundary" in own and "_admit" not in own and not set(entries) & set(own)
        assert "late_rows +=" not in inspect.getsource(cls).replace(shared, "")
    for entry in ("_hook", "insert_arrays", "mesh_insert_begin"):
        body = inspect.getsource(getattr(StagedAggregate, entry))
        assert body.count("self._admit(") == 1 and "late_rows" not in body
    for entry in ("_update", "_run_made", "insert_arrays", "mesh_insert_begin"):
        body = inspect.getsource(getattr(StagedAggregate, entry))
        assert body.count("self._note_rel(") + body.count("self._note_bins(") == 1
