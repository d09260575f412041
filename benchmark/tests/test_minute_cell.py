"""q7-minute-sat at rehearsal size on the CPU: the line carries every
per-layer metric this cell brought; a table that starts small grows under
the harness with nothing spilled; with the growth ceiling held at the
initial capacity the same run spills and is not ``correct``."""

import json
import os
import subprocess
import sys

import pytest

from bench_paths import BENCH, ROOT

CELL = "q7-minute-sat"
NEW_METRICS = {"table_fill_share", "table_grows_in_window", "close_read_ms.sat",
               "snapshot_read_ms"}
# a rehearsal window holds 700-1,500 auctions: a table that starts at 256
# slots has to grow (the shipped 65,536 never would at this size)
SMALL_TABLE = {"ARROYO_TPU__DEVICE__TABLE_CAPACITY": "256",
               "ARROYO_TPU__DEVICE__REGION_SIZE": "32"}


def last_line(script: str, *args, env=None) -> dict:
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, script), "--workload", CELL, "--seed", "2147483693",
         "--seconds", "2", "--rehearse", *args],
        cwd=ROOT, capture_output=True, text=True, timeout=300,
        env=dict(os.environ, **(env or {})))
    assert p.returncode == 0, p.stderr[-2000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_the_traced_rehearsal_reports_every_new_metric():
    line = last_line("run.py", "--trace", "1")
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0, line
    got = line["rehearsal_metrics"]
    assert NEW_METRICS <= set(got), sorted(got)
    assert got["table_grows_in_window"]["value"] == 0.0
    assert 0.0 < got["table_fill_share"]["value"] < 100.0
    assert got["close_read_ms.sat"]["value"] > 0 and got["snapshot_read_ms"]["value"] > 0
    assert line["metrics"] == {}


@pytest.mark.parametrize("broken", ["none", "held_ceiling"])
def test_a_held_ceiling_spills_and_is_not_correct(broken):
    line = last_line(os.path.join("tests", "control_ceiling.py"), "--break", broken,
                     env=SMALL_TABLE)
    compared = line["compared"]
    assert compared["windows_wrong"]["value"] == 0 and compared["partials_wrong"]["value"] == 0
    if broken == "none":
        # the table grew instead (inside the window at this size, so the
        # programs it warmed count as compiled there)
        assert compared["rows_spilled"]["value"] == 0, line
    else:
        assert compared["rows_spilled"]["value"] > 0 and line["correct"] is False, line
        assert compared["compiles_in_window"]["value"] == 0, line
