"""A deployment whose window state is sharded over four devices, added by
files alone (``data/mesh4``; no cell of the repo's): a configuration whose
``settings`` state ``device.mesh-devices: 4`` and a cell with ``chips: 4``,
through run.py --rehearse on four CPU devices from a copy of the benchmark
under the fixture's own manifest. The probes see the sharded aggregate's
steps, closes and snapshots, its state lies on four devices, and the same
cell with the setting taken away, its state on one device, is not
``correct`` for that reason alone. A settings key the program does not
declare is refused by name."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from bench_paths import BENCH, ROOT
from test_two_streams import failing

FIXTURE = os.path.join("tests", "data", "mesh4")
CELL = "mesh4-sat"


def run(tmp_path, *args, settings=None):
    """-> (process, lines, report) of a rehearsal of the fixture's cell,
    with the copy's ``settings`` replaced where given."""
    root = tmp_path / "copy"
    shutil.copytree(BENCH, root / "benchmark", ignore=shutil.ignore_patterns("__pycache__"))
    fixture = root / "benchmark" / FIXTURE
    shutil.copy(fixture / "BENCHMARK.json", root / "BENCHMARK.json")
    shutil.copy(fixture / "sat-mesh4.json", root / "benchmark" / "traffic")
    if settings is not None:
        with open(fixture / "mesh4.json") as f:
            config = json.load(f)
        config["settings"] = settings
        with open(fixture / "mesh4.json", "w") as f:
            json.dump(config, f)
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    p = subprocess.run(
        [sys.executable, str(root / "benchmark" / "run.py"), "--workload", CELL,
         "--seed", "2147483801", "--seconds", "2", "--rehearse", *args],
        cwd=root, env=dict(env, PYTHONPATH=ROOT, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=600)
    lines = [json.loads(l) for l in p.stdout.splitlines() if l.startswith("{")]
    reports = list((root / "chiprun_out").rglob("report.json"))
    report = None
    if reports:
        with open(reports[0]) as f:
            report = json.load(f)
    return p, lines, report


@pytest.mark.parametrize("trace", ["0", "1"])
def test_a_sharded_aggregate_is_seen_and_lies_on_four_devices(tmp_path, trace):
    p, lines, report = run(tmp_path, "--trace", trace)
    assert p.returncode == 0, p.stderr[-2000:]
    first, line = lines[0], lines[-1]
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0, line
    # the deployment's setting was scoped and is printed beside the five
    assert first["effective_settings"]["device.mesh-devices"] == 4
    assert first["effective_settings"]["checkpoint.interval-ms"] == 1000  # the rehearsal's, on top
    assert len(first["effective_settings"]) == 6
    assert line["device"]["count"] == 4
    assert len(line["device"]["memory_peak_bytes_per_chip"]) == 4
    assert line["device"]["memory_peak_bytes"] == max(line["device"]["memory_peak_bytes_per_chip"])
    assert line["compared"]["aggregates_short_of_chips"] == {"value": 0, "limit": 0}
    assert line["compared"]["rows_spilled"] == {"value": 0, "limit": 0}
    assert line["compared"]["compiles_in_window"]["value"] == 0
    assert len(report["aggregates"]) == 2
    for agg in report["aggregates"]:
        assert agg["class"] == "ShardedAggregator" and agg["devices"] == 4, agg
        # a step per device program, a close from its dispatch to its rows
        # on the host, a snapshot per checkpoint
        calls = agg["calls"]
        assert calls["ingest"] > 0 and calls["snapshot"] > 0, agg
        assert calls["close"] == calls["fetch"] >= line["attempted"], agg
    assert all(s["steps"] > 0 for s in report["records"]["steps"])
    # the slower chain's closes at least (the other scan may be windows ahead)
    assert len(report["records"]["close_fetch_ms"]) >= line["attempted"]
    # no warm-up of the harness's own for a sharded aggregate
    assert "warm_close_reads_s" not in first["setup_parts_s"]
    assert line["metrics"] == {} and line["rehearsal_metrics"]


def test_four_chips_asked_for_and_state_on_one_is_not_correct(tmp_path):
    p, lines, report = run(tmp_path, "--trace", "0", settings={})
    assert p.returncode == 0, p.stderr[-2000:]
    line = lines[-1]
    assert line["correct"] is False and failing(line) == {"aggregates_short_of_chips"}, \
        line["compared"]
    assert line["compared"]["aggregates_short_of_chips"]["value"] == 2
    assert line["failed"] == 0  # every window's rows are the reference's
    assert {a["class"] for a in report["aggregates"]} == {"SlotAggregator"}
    assert len(lines[0]["effective_settings"]) == 5  # no settings: nothing scoped


@pytest.mark.parametrize("settings,named", [
    ({"device.mesh-devcies": 4}, "device.mesh-devcies"),
    ({"device.mesh-devices": 4, "device": {"mesh-devices": 4}}, "'device'"),
    ({"nosuch.section.key": 1}, "nosuch.section.key")])
def test_an_undeclared_settings_key_is_refused_by_name(tmp_path, settings, named):
    p, lines, _report = run(tmp_path, "--trace", "0", settings=settings)
    assert p.returncode not in (0, 4) and not lines, p.stdout[-2000:]
    assert named in p.stderr and "mesh4" in p.stderr, p.stderr[-2000:]
    assert "Traceback" not in p.stderr
