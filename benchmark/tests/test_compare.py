"""The comparator can fail: a window missing, a row doubled, a price off by
one, a checkpoint not completed — and each other guarantee broken."""

import copy

import pytest

from harness import compare

DUE = [0, 10, 20]
WANT = {0: [(1001, 500)], 10: [(1002, 700), (1003, 700)], 20: [(1004, 900)]}
SOUND = {
    "checkpoints_triggered": [3, 4], "checkpoints_not_completed": [],
    "ingest": [{"aggregate": "a", "rows_received": 920, "rows_expected": 920}],
    "late_rows": 0, "spilled_rows": 0, "off_platform": [], "short_of_chips": [],
    "compiles_in_window": [],
    "partials_compared": 6, "partials_wrong": [],
}


def judge(got=None, **broken):
    g = dict(copy.deepcopy(SOUND), **broken)
    return compare.judge(DUE, copy.deepcopy(WANT) if got is None else got, WANT, g)


def test_sound_run_is_correct():
    v = judge()
    assert v["correct"] and v["attempted"] == 3 and v["failed"] == 0
    assert all("value" in c and ("limit" in c or "at_least" in c) for c in v["compared"])


def test_row_order_does_not_matter():
    got = copy.deepcopy(WANT)
    got[10].reverse()
    assert judge(got)["correct"]


@pytest.mark.parametrize("name,mutate,kind", [
    ("window missing", lambda g: g.pop(10), "missing"),
    ("row doubled", lambda g: g[10].append((1002, 700)), "doubled"),
    ("price off by one", lambda g: g.__setitem__(20, [(1004, 901)]), "wrong"),
    ("stray row", lambda g: g[0].append((1999, 1)), "wrong"),
    ("row lost", lambda g: g.__setitem__(10, [(1002, 700)]), "wrong"),
])
def test_bad_rows_fail(name, mutate, kind):
    got = copy.deepcopy(WANT)
    mutate(got)
    v = judge(got)
    assert not v["correct"], name
    assert v["failed"] == 1 and len(v["windows"][kind]) == 1


@pytest.mark.parametrize("broken", [
    {"checkpoints_not_completed": [4]},
    {"checkpoints_triggered": []},
    {"ingest": [{"aggregate": "a", "rows_received": 919, "rows_expected": 920}]},
    {"ingest": [{"aggregate": "a", "rows_received": 921, "rows_expected": 920}]},
    {"ingest": []},
    {"late_rows": 1},
    {"spilled_rows": 512},
    {"off_platform": [["cpu"]]},
    {"short_of_chips": [{"devices": 1}]},
    {"compiles_in_window": ["jit(go)"]},
    {"partials_wrong": [["agg_4", 10]]},
    {"partials_compared": 0},
])
def test_broken_guarantee_fails(broken):
    v = judge(**broken)
    assert not v["correct"]
    assert v["failed"] == 0  # the rows were right; the guarantee was not


def test_no_due_window_is_not_correct():
    assert not compare.judge([], {}, {}, copy.deepcopy(SOUND))["correct"]


@pytest.mark.parametrize("chips,devices,short", [(1, 1, 0), (4, 4, 0), (4, 1, 3), (4, 2, 3)])
def test_a_cell_whose_state_lies_on_fewer_devices_than_its_chips(chips, devices, short):
    aggregates = [{"devices": devices, "id": i} for i in range(3)]
    v = judge(short_of_chips=compare.short_of_chips(aggregates, chips))
    entry = next(c for c in v["compared"] if c["name"] == "aggregates_short_of_chips")
    assert entry["value"] == short and entry["limit"] == 0
    assert v["correct"] is (short == 0) and v["failed"] == 0
