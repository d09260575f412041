"""run.py --rehearse at a tiny size on the CPU: both configurations, both
mixes; what the line must carry; what no accelerator must do."""

import json
import os
import subprocess
import sys

import pytest

from bench_paths import BENCH, ROOT
from harness import cells


def run(*args, env=None):
    return subprocess.run([sys.executable, os.path.join(BENCH, "run.py"), *args],
                          cwd=ROOT, capture_output=True, text=True, timeout=300,
                          env=dict(os.environ, **(env or {})))


@pytest.mark.parametrize("workload", [w["name"] for w in cells.manifest()["workloads"]])
@pytest.mark.parametrize("trace", ["0", "1"])
def test_rehearsal(workload, trace):
    p = run("--workload", workload, "--seed", "2147483659", "--seconds", "2",
            "--trace", trace, "--rehearse")
    assert p.returncode == 0, p.stderr[-2000:]
    lines = [json.loads(l) for l in p.stdout.splitlines() if l.startswith("{")]
    line = lines[-1]
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0, \
        p.stdout[-3000:]
    assert line["device"]["platform"] == "cpu"
    # a CPU number never stands under a device metric's name
    assert line["metrics"] == {}
    cell = cells.Cell(workload)
    group = "per_layer" if trace == "1" else "end_to_end"
    wanted = {m["name"] for m in cell.metrics(group)}
    got = set(line["rehearsal_metrics"])
    assert got <= wanted
    if trace == "0":
        assert got == wanted
    # every number compared is printed beside its limit
    compared = [l for l in lines if "what" in l]
    assert len(compared) >= 10 and all("limit" in c or "at_least" in c for c in compared)
    assert "setup_parts_s" in lines[0] and "effective_settings" in lines[0]
    # the same numbers, short, last in the line and last on standard error
    assert list(line)[-1] == "compared" and len(line["compared"]) == len(compared)
    assert p.stderr.strip().splitlines()[-1].startswith("compared compiles_in_window: ")
    if cell.traffic["event_rate"]:
        checkpoints_and_the_closes_they_met(lines[0])


def checkpoints_and_the_closes_they_met(first):
    """A paced cell's first line: every checkpoint of the window complete,
    with where in the slide it fell; no two triggers of the run closer than
    the interval; the closes a barrier met counted."""
    with open(os.path.join(BENCH, "harness", "rehearsal.json")) as f:
        interval = json.load(f)["config"]["checkpoint.interval-ms"] / 1e3
    assert first["checkpoints"], first
    for e in first["checkpoints"]:
        assert e["completed"] and 0.0 <= e["at_s"] <= 2.0 and 0.0 <= e["phase"] < 1.0, first
    assert first["trigger_gaps_s"] and all(g >= interval for g in first["trigger_gaps_s"]), first
    # at this size a close can arrive before its last event was due, a batch
    # ahead, and so before the trigger: any count up to all of them
    assert first["closes"] > 0
    assert all(0 <= i < first["closes"] for i in first["closes_struck"]), first


def test_no_accelerator_is_a_nonzero_exit_and_no_result():
    p = run("--workload", "q7-sat", "--seed", "1", "--seconds", "1", "--trace", "0",
            env={"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert not [l for l in p.stdout.splitlines() if l.startswith("{")]


def test_unknown_workload_is_an_error():
    p = run("--workload", "no-such-cell", "--seed", "1", "--seconds", "1", "--rehearse")
    assert p.returncode != 0 and "no-such-cell" in p.stderr
