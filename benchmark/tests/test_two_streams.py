"""A two-stream query in the shape of NEXmark Query 8 through run.py
--rehearse, from a copy of the benchmark under the fixture's own manifest
(``data/two-streams``; no cell of the repo's): two first-level aggregates
of one width fed by different events. Each is held to the partial of its
own key and to its own count of rows; swapped partials, a count off by one
and a seller column shifted by one event each come out not ``correct`` for
that reason alone."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from bench_paths import BENCH, ROOT

FIXTURE = os.path.join("tests", "data", "two-streams")
CELL = "two-streams-sat"
PERSONS, SELLERS = "agg_4_tumbling_aggregate", "agg_10_tumbling_aggregate"

SWAPPED = '''
_sound_partials = partials
def partials(window):
    p = _sound_partials(window)
    return {PERSONS: p[SELLERS], SELLERS: p[PERSONS]}
'''
OFF_BY_ONE = '''
_sound_ingested = ingested
def ingested(events_sent):
    n = _sound_ingested(events_sent)
    return {**n, SELLERS: n[SELLERS] + 1}
'''
UNNAMED = '''
_sound_partials, _sound_ingested = partials, ingested
def partials(window):
    return {PERSONS: _sound_partials(window)[PERSONS]}
def ingested(events_sent):
    return {PERSONS: _sound_ingested(events_sent)[PERSONS]}
'''


def run(tmp_path, script="run.py", *args, doctored=""):
    """-> (first line, last line) of a rehearsal of the fixture's cell, with
    ``doctored`` appended to the copy's reference."""
    root = tmp_path / "copy"
    shutil.copytree(BENCH, root / "benchmark", ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(BENCH, FIXTURE, "BENCHMARK.json"), root / "BENCHMARK.json")
    with open(root / "benchmark" / FIXTURE / "two-streams.py", "a") as f:
        f.write(doctored)
    p = subprocess.run(
        [sys.executable, str(root / "benchmark" / script), "--workload", CELL,
         "--seed", "2147483701", "--seconds", "2", "--rehearse", *args],
        cwd=root, env=dict(os.environ, PYTHONPATH=ROOT, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = [json.loads(l) for l in p.stdout.splitlines() if l.startswith("{")]
    return lines[0], lines[-1]


def failing(line) -> set:
    return {name for name, c in line["compared"].items()
            if (c["value"] > c["limit"] if "limit" in c else c["value"] < c["at_least"])}


def every_aggregate_got_its_own_rows(first):
    assert [a["keyed_on"] for a in first["ingest"]] == [["person.id"], ["auction.seller"]]
    for a in first["ingest"]:
        assert a["rows_received"] == a["rows_expected"] > 0, first["ingest"]
    persons, auctions = (a["rows_received"] / a["source_events"] for a in first["ingest"])
    assert persons == pytest.approx(1 / 50, rel=0.01) and auctions == pytest.approx(3 / 50, rel=0.01)


@pytest.mark.parametrize("trace", ["0", "1"])
def test_each_aggregate_is_held_to_its_own_partial_and_count(tmp_path, trace):
    first, line = run(tmp_path, "run.py", "--trace", trace)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0, line
    assert line["compared"]["partials_compared"]["value"] == 2 * line["attempted"]
    assert line["compared"]["aggregates_checked"]["value"] == 2
    every_aggregate_got_its_own_rows(first)
    assert line["metrics"] == {} and line["rehearsal_metrics"]


@pytest.mark.parametrize("doctored,reason", [
    pytest.param(SWAPPED, "partials_wrong", id="partials_swapped"),
    pytest.param(OFF_BY_ONE, "rows_lost_or_doubled", id="ingested_off_by_one")])
def test_a_doctored_reference_fails_for_its_reason_alone(tmp_path, doctored, reason):
    first, line = run(tmp_path, "run.py", "--trace", "0", doctored=doctored)
    assert line["correct"] is False and failing(line) == {reason}, line["compared"]
    assert line["failed"] == 0  # every window's rows at the sink are the reference's
    if reason == "partials_wrong":
        # both aggregates, in every due window
        assert line["compared"][reason]["value"] == 2 * line["attempted"]
        every_aggregate_got_its_own_rows(first)
    else:
        assert line["compared"][reason]["value"] == 1


def test_an_aggregate_the_reference_does_not_name_is_wrong(tmp_path):
    _first, line = run(tmp_path, "run.py", "--trace", "0", doctored=UNNAMED)
    assert line["correct"] is False, line
    assert failing(line) == {"partials_wrong", "rows_lost_or_doubled"}, line["compared"]
    assert line["compared"]["partials_wrong"]["value"] == line["attempted"]


def test_a_seller_column_shifted_by_one_event_is_not_correct(tmp_path):
    first, line = run(tmp_path, os.path.join("tests", "control.py"), "--break", "shifted_seller")
    assert line["correct"] is False, line
    # the rows per aggregate are as many as before; what they say is not
    # (a window of which the join finds no row any more counts as missing)
    assert {"partials_wrong"} <= failing(line) <= {
        "partials_wrong", "windows_wrong", "windows_missing"}, line["compared"]
    every_aggregate_got_its_own_rows(first)
    # the seller's aggregate alone, in every due window
    assert line["compared"]["partials_wrong"]["value"] == line["attempted"]
    with open(next((tmp_path / "copy" / "chiprun_out").rglob("report.json"))) as f:
        wrong = json.load(f)["guarantees"]["partials_wrong"]
    assert {who for who, _ws in wrong} == {SELLERS}, wrong
