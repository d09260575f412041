"""The rest of a run with the timed path broken underneath comes out
``correct: false``; unbroken, true. Skips the look for a chip (--rehearse)."""

import json
import os
import subprocess
import sys

import pytest

from bench_paths import BENCH, ROOT


def control(broken: str, workload: str, seed: int = 3) -> dict:
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "tests", "control.py"), "--break", broken,
         "--workload", workload, "--seed", str(seed), "--seconds", "2", "--rehearse"],
        cwd=ROOT, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("workload", ["q7-sat", "q5-paced"])
def test_unbroken_is_correct(workload):
    line = control("none", workload)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0, line


@pytest.mark.parametrize("broken,workload", [
    ("lossy_ingest", "q7-sat"),
    ("lossy_ingest", "q5-paced"),
    ("doubled_ingest", "q5-sat"),
    ("off_by_one", "q7-paced"),
    ("off_by_one", "q5-sat"),
    ("unchanged_state", "q7-sat"),
    ("checkpoint_never_durable", "q7-sat"),
])
def test_broken_is_not_correct(broken, workload):
    line = control(broken, workload)
    assert line["correct"] is False, (broken, workload, line)
