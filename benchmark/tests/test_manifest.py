"""BENCHMARK.json against the contract's letter rules, every file it names,
and the proof that a cell, a mix and a metric are added by adding files."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench_paths import BENCH, ROOT
from harness import cells

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    return cells.manifest()


def test_keys_and_limits(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs", "workloads",
                             "end_to_end", "per_layer"}
    assert 1 <= manifest["run_seconds"] <= 51 and isinstance(manifest["run_seconds"], int)
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert manifest["paths"] == ["benchmark"]
    assert 1 <= len(manifest["workloads"]) <= 24 and 1 <= len(manifest["configs"]) <= 24
    four = sum(1 for w in manifest["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(manifest["workloads"]) // 2)


def test_names_units_and_texts(manifest):
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in manifest[group]:
            assert NAME.match(e["name"]), e["name"]
            names.append((group in ("end_to_end", "per_layer"), e["name"]))
    assert len(names) == len(set(names)), "a name twice"
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["config"]) and NAME.match(w["traffic"]) and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"] and "\t" not in w["why"]
    pairs = [(w["config"], w["traffic"]) for w in manifest["workloads"]]
    assert len(pairs) == len(set(pairs))
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert 1 <= len(c["source"]) <= 200 and 1 <= len(c["why"]) <= 200
        assert any(w["config"] == c["name"] for w in manifest["workloads"])
    for m in manifest["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in manifest["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert m["source"] in SOURCES and 1 <= len(m["layer"]) <= 200
        assert m["moves"] in {e["name"] for e in manifest["end_to_end"]}
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    assert any(m["name"] == "setup_s" and "workloads" not in m for m in manifest["end_to_end"])
    for m in manifest["per_layer"]:
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"


def test_every_file_named_exists_and_every_cell_reports(manifest):
    for w in manifest["workloads"]:
        cell = cells.Cell(w["name"])
        assert callable(cell.reference.rows) and callable(cell.reference.ingested)
        assert cell.config["reduced"] == [] and cell.config["assumed"]
        assert len(cell.config["source"]) <= 200
        e2e = [m["name"] for m in cell.metrics("end_to_end")]
        assert "setup_s" in e2e and len(e2e) >= 2
        layer = cell.metrics("per_layer")
        assert layer
        for m in cell.metrics("end_to_end") + layer:
            assert callable(cell.reader(m["name"]))
        # a per-layer metric moves an end-to-end metric its cell reports
        for m in layer:
            assert m["moves"] in e2e, (w["name"], m["name"])
    for c in manifest["configs"]:
        assert c["file"].startswith("benchmark/") and os.path.exists(os.path.join(ROOT, c["file"]))
    for dirpath, _dirs, files in os.walk(BENCH):
        if "__pycache__" in dirpath:
            continue
        for f in files:
            assert re.match(r"^[A-Za-z0-9_.\-]+$", f), os.path.join(dirpath, f)


def test_no_reader_without_an_entry(manifest):
    named = {m["name"] for m in manifest["end_to_end"] + manifest["per_layer"]}
    readers = {f[:-3] for f in os.listdir(os.path.join(BENCH, "metrics")) if f.endswith(".py")}
    assert readers == named, readers ^ named


def test_a_cell_a_mix_and_a_metric_are_added_by_adding_files(tmp_path):
    """A copy of the benchmark, plus files and appended entries, nothing
    that was there edited: the new cell runs and reports the new metric."""
    root = tmp_path / "copy"
    shutil.copytree(BENCH, root / "benchmark", ignore=shutil.ignore_patterns("__pycache__"))
    m = cells.manifest()
    before = json.dumps(m, sort_keys=True)
    mix = json.loads((root / "benchmark" / "traffic" / "paced-32k.json").read_text())
    mix["what"] = "a later PR's mix"
    (root / "benchmark" / "traffic" / "paced-later.json").write_text(json.dumps(mix))
    (root / "benchmark" / "metrics" / "closes_seen.py").write_text(
        "def read(run):\n    return float(len(run['closes']))\n")
    m["workloads"].append({"name": "q5-later", "config": "nexmark-q5-hot-items",
                           "traffic": "paced-later", "chips": 1, "why": "added by files alone"})
    for e in m["end_to_end"]:
        if e["name"] == "latency_p50_ms":
            e["workloads"] = e["workloads"] + ["q5-later"]
    m["per_layer"].append({"name": "closes_seen", "unit": "count", "better": "higher",
                           "source": "host_clock", "layer": "engine end to end",
                           "moves": "latency_p50_ms", "workloads": ["q5-later"]})
    (root / "BENCHMARK.json").write_text(json.dumps(m))
    assert json.dumps(cells.manifest(), sort_keys=True) == before
    env = dict(os.environ, PYTHONPATH=ROOT, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(root / "benchmark" / "run.py"), "--workload", "q5-later",
         "--seed", "5", "--seconds", "2", "--trace", "1", "--rehearse"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, p.stdout[-3000:]
    assert line["rehearsal_metrics"]["closes_seen"]["value"] == line["attempted"] > 0, line
    assert line["metrics"] == {}
