"""Finding a cell's files by the names ``BENCHMARK.json`` gives. The
harness knows no cell, configuration, traffic mix or metric by name: a
later PR adds one by adding files and appending entries.

A configuration's ``.json`` may carry ``"settings"``: a flat object of
dotted keys of ``arroyo_tpu/config.py`` to values, which the runner scopes
for the whole run and prints in ``effective_settings``. It is for what the
*deployment* fixes and a user of it would set too: the number of chips its
window state is sharded over (``device.mesh-devices``, with ``chips: 4`` on
its cells). It is not for tuning. Every other setting stays at its shipped
default, which is what the seven cells measure and what users get: a cell
that sets ``device.batch-capacity``, ``pipeline.source-batch-size``,
chaining or any other tuning key measures a system nobody runs by default,
and a reviewer should refuse it. A key the program does not declare is
refused before the engine is built."""

from __future__ import annotations

import importlib.util
import json
import os
import string

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


class UnknownCell(KeyError):
    pass


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def manifest(root: str = ROOT) -> dict:
    return _json(os.path.join(root, "BENCHMARK.json"))


class Cell:
    """One entry of ``workloads`` with everything its names lead to."""

    def __init__(self, name: str, root: str = ROOT):
        self.manifest = m = manifest(root)
        bench = os.path.join(root, "benchmark")
        try:
            self.entry = next(w for w in m["workloads"] if w["name"] == name)
        except StopIteration:
            raise UnknownCell(f"no workload {name!r} in BENCHMARK.json "
                              f"(have {[w['name'] for w in m['workloads']]})") from None
        self.name = name
        self.chips = int(self.entry["chips"])
        cfg = next(c for c in m["configs"] if c["name"] == self.entry["config"])
        stem = os.path.splitext(os.path.join(root, cfg["file"]))[0]
        self.config = _json(stem + ".json")
        with open(stem + ".sql") as f:
            self.sql_template = f.read()
        self.reference = _module(stem + ".py", "bench_reference")
        self.traffic = _json(os.path.join(bench, "traffic", self.entry["traffic"] + ".json"))
        self._metrics_dir = os.path.join(bench, "metrics")

    def _reports(self, metric: dict) -> bool:
        return "workloads" not in metric or self.name in metric["workloads"]

    def metrics(self, group: str) -> list[dict]:
        """The entries of ``end_to_end`` or ``per_layer`` this cell reports."""
        return [x for x in self.manifest[group] if self._reports(x)]

    def reader(self, metric_name: str):
        """``read(run) -> number or None`` of ``metrics/<name>.py``."""
        return _module(os.path.join(self._metrics_dir, metric_name + ".py"),
                       "bench_metric_" + metric_name.replace(".", "_").replace("-", "_")).read

    def sql(self, seed: int, sink: str, event_rate: float,
            generator_overrides: dict | None = None) -> str:
        gen = dict(self.config["generator"], **(generator_overrides or {}))
        return string.Template(self.sql_template).substitute(
            seed=seed, sink=sink, event_rate=f"{event_rate:g}",
            inter_event_micros=gen["inter_event_micros"],
            first_event_micros=gen["first_event_micros"])
