"""q8-paced and q8-sat at rehearsal size on the CPU: both come out
``correct`` with two partials a due window and each aggregate held to its
own count (the persons, 1 of every 50 events; the auctions, 3 of 50); the
traced line carries every per-layer metric this configuration brought that
a rehearsal can read; the probe's least bytes against hand-counted cases."""

import json
import os
import subprocess
import sys

import pytest

from bench_paths import BENCH, ROOT
from harness import cells, roofline_probe

# a rehearsal window holds 40 persons and ~100 sellers: under the shipped
# device.join-min-rows (2,048) its join probes with numpy, and on a CPU the
# device path has to be forced besides (operators/joins.py _jax_on_host_cpu)
DEVICE_JOIN = {"ARROYO_TPU__DEVICE__JOIN_MIN_ROWS": "0",
               "ARROYO_TPU__DEVICE__FORCE_DEVICE_JOIN": "true"}
NEEDS_THE_CHIP = {"probe_device_us", "probe_roofline"}  # both read the device trace


def lines_of(cell: str, trace: str, env=None) -> tuple[dict, dict]:
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell,
         "--seed", "2147483711", "--seconds", "2", "--trace", trace, "--rehearse"],
        cwd=ROOT, capture_output=True, text=True, timeout=300,
        env=dict(os.environ, **(env or {})))
    assert p.returncode == 0, p.stderr[-2000:]
    lines = [json.loads(l) for l in p.stdout.splitlines() if l.startswith("{")]
    return lines[0], lines[-1]


@pytest.mark.parametrize("cell", ["q8-paced", "q8-sat"])
def test_each_aggregate_is_held_to_its_own_partial_and_count(cell):
    first, line = lines_of(cell, "0")
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0, line
    assert line["compared"]["partials_compared"]["value"] == 2 * line["attempted"]
    assert line["compared"]["aggregates_checked"]["value"] == 2
    assert [a["keyed_on"] for a in first["ingest"]] == [["person.id"], ["auction.seller"]]
    for a in first["ingest"]:
        assert a["rows_received"] == a["rows_expected"] > 0, first["ingest"]
    persons, auctions = (a["rows_received"] / a["source_events"] for a in first["ingest"])
    assert persons == pytest.approx(1 / 50, rel=0.01) and auctions == pytest.approx(3 / 50, rel=0.01)
    assert line["metrics"] == {} and line["rehearsal_metrics"]


@pytest.mark.parametrize("env,share", [
    pytest.param(None, 0.0, id="numpy-probe-under-join-min-rows"),
    pytest.param(DEVICE_JOIN, 100.0, id="device-probe-forced")])
def test_the_traced_paced_rehearsal_reports_the_joins_metrics(env, share):
    _first, line = lines_of("q8-paced", "1", env)
    assert line["correct"] is True and line["attempted"] > 0, line
    got = line["rehearsal_metrics"]
    brought = {m["name"] for m in cells.Cell("q8-paced").metrics("per_layer")
               if m["layer"] == "windowed join"}
    assert brought == {"join_probe_ms", "join_device_share"} | NEEDS_THE_CHIP
    assert brought - NEEDS_THE_CHIP <= set(got), sorted(got)
    assert got["join_device_share"]["value"] == share
    assert got["join_probe_ms"]["value"] > 0
    # the trail reaches the join: its hold is read where q7's one-row join's is
    assert got["wm_hold_join_ms"]["value"] > 0 and got["closes_on_wake_share"]["value"] > 0
    assert line["metrics"] == {}


def test_the_traced_saturated_rehearsal_reports_what_q7_sat_does():
    _first, line = lines_of("q8-sat", "1")
    assert line["correct"] is True and line["attempted"] > 0, line
    want = {m["name"] for m in cells.Cell("q7-sat").metrics("per_layer")}
    assert want == {m["name"] for m in cells.Cell("q8-sat").metrics("per_layer")}
    device = {m["name"] for m in cells.manifest()["per_layer"] if m["source"] == "device_trace"}
    assert want - device <= set(line["rehearsal_metrics"]), sorted(line["rehearsal_metrics"])


@pytest.mark.parametrize("l_cap,r_cap,bytes_", [
    # keys in at 8 bytes a row; order out at 4 a build row, lo and hi at 4 a probe row each
    (64, 64, 64 * 8 + 64 * 8 + 64 * 4 + 2 * 64 * 4),
    (2048, 4096, 2048 * 8 + 4096 * 8 + 4096 * 4 + 2 * 2048 * 4),
    (2048, 8192, 131072),
    (131072, 64, 131072 * 16 + 64 * 12)])
def test_probe_bytes_against_hand_counted_cases(l_cap, r_cap, bytes_):
    assert roofline_probe.probe_bytes(l_cap, r_cap) == bytes_
