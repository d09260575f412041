"""The seven readers of the device's waits (metrics/device_stalls.*,
device_wait_max_ms.*, agg_device_wait_share, watch_late_max_ms.*; shared
code in harness/readers_stall.py) over a hand-made ring: a clean window
reads 0.0 and not None, a window with two stalled waits reads 2 and the
longer wait, a stall outside the window is not counted, a program that
lacks the watch gives nothing; and a traced rehearsal of a paced cell, a
saturated cell and the four-device cell reports every one of them that its
``workloads`` lists name."""

import json
import os
import subprocess
import sys
import threading

import pytest

from bench_paths import BENCH, ROOT
from harness import cells

from arroyo_tpu.obs import trace

PACED = ["q5-paced", "q7-paced", "q8-paced"]
ONE_CHIP_SAT = ["q7-sat", "q5-sat", "q7-minute-sat", "q8-sat"]
NEW = {
    "device_stalls.paced": ("count", "program_span", "device", "latency_p50_ms", PACED),
    "device_stalls.sat": ("count", "program_span", "device", "events_per_s", ONE_CHIP_SAT),
    "device_wait_max_ms.paced": ("ms", "program_span", "device", "latency_p50_ms", PACED),
    "device_wait_max_ms.sat": ("ms", "program_span", "device", "events_per_s",
                               ONE_CHIP_SAT + ["q7-mesh4"]),
    "agg_device_wait_share": ("%", "program_counter", "device", "events_per_s",
                              ONE_CHIP_SAT + ["q7-mesh4"]),
    "watch_late_max_ms.paced": ("ms", "program_span", "interpreter lock", "latency_p50_ms",
                                PACED),
    "watch_late_max_ms.sat": ("ms", "program_span", "interpreter lock", "events_per_s",
                              ONE_CHIP_SAT + ["q7-mesh4"]),
}
S = 1_000_000_000
# windows no record of a real run overlaps: a century of monotonic time on
BASE = 3_000_000_000 * S


def reader(name):
    return cells.Cell("q7-sat").reader(name)


def ring(records):
    """Append hand-made records to a ring of their own thread's."""
    def write():
        r = trace._ring()
        for name, node, t0, t1, args in records:
            r.append((name, ("hand-made", node, 0), None, t0, t1, args))
    t = threading.Thread(target=write)
    t.start()
    t.join()


def window(i, tasks=()):
    """The i-th hand-made window, ten seconds long, a minute from the last."""
    lo = BASE + i * 60 * S
    return {"window": {"opened": lo / 1e9, "closed": (lo + 10 * S) / 1e9},
            "tasks": list(tasks)}, lo


def test_the_manifest_has_the_seven_as_the_issue_names_them():
    by_name = {m["name"]: m for m in cells.manifest()["per_layer"]}
    assert list(by_name)[-7:] == list(NEW)  # appended, in the table's order
    for name, (unit, source, layer, moves, workloads) in NEW.items():
        m = by_name[name]
        assert (m["unit"], m["better"], m["source"], m["layer"], m["moves"], m["workloads"]) \
            == (unit, "lower", source, layer, moves, workloads), name
        assert os.path.exists(os.path.join(BENCH, "metrics", name + ".py"))
    # not q7-mesh4: until A5 its closes queue behind the steps by design
    assert "q7-mesh4" not in by_name["device_stalls.sat"]["workloads"]


def test_a_clean_window_reads_zero_and_not_none():
    run, lo = window(0)
    ring([("agg.close", "agg_4", lo + S, lo + S + 5_000_000, {"rows": 10})])
    for name in NEW:
        if name != "agg_device_wait_share":
            assert reader(name)(run) == 0.0, name


def test_two_stalled_waits_read_two_and_the_longer_wait():
    run, lo = window(1)
    ring([
        # a stall before the window opened, and its wait, over before it too
        ("device.stall", "agg_4", lo - 5 * S, lo - 5 * S, {"waited": "agg.fetch"}),
        ("agg.fetch", "agg_4", lo - 6 * S, lo - 3 * S, {"stalled": True}),
        # two inside it, both first-level aggregates at once
        ("device.stall", "agg_4", lo + 3 * S, lo + 3 * S, {"waited": "agg.fetch"}),
        ("device.stall", "agg_9", lo + 3 * S, lo + 3 * S + 1000, {"waited": "agg.fetch"}),
        ("agg.fetch", "agg_4", lo + 2 * S, lo + 2 * S + 2_160_000_000, {"stalled": True}),
        ("agg.fetch", "agg_9", lo + 2 * S, lo + 2 * S + 2_157_000_000, {"stalled": True}),
        ("agg.drain", "agg_4", lo + 5 * S, lo + 5 * S + 30_000_000, None),
        ("join.fetch", "join_16", lo + 6 * S, lo + 6 * S + 4_000_000, None),
        # and one after it closed
        ("device.stall", "agg_9", lo + 12 * S, lo + 12 * S, {"waited": "agg.fetch"}),
        ("watch.tick", "watch", lo + 1 * S, lo + 1 * S, {"late_max_ms": 7.5, "ticks": 10}),
        ("watch.tick", "watch", lo + 4 * S, lo + 4 * S, {"late_max_ms": 2003.0, "ticks": 1}),
        ("watch.tick", "watch", lo + 11 * S, lo + 11 * S, {"late_max_ms": 9000.0, "ticks": 1}),
    ])
    for kind in ("paced", "sat"):
        assert reader(f"device_stalls.{kind}")(run) == 2.0
        assert reader(f"device_wait_max_ms.{kind}")(run) == 2160.0
        assert reader(f"watch_late_max_ms.{kind}")(run) == 2003.0


def test_a_wait_that_straddles_an_edge_of_the_window_counts_whole():
    run, lo = window(2)
    ring([("agg.fetch", "agg_4", lo - S, lo + S, None)])
    assert reader("device_wait_max_ms.paced")(run) == 2000.0


def test_the_busiest_aggregates_share_of_its_wall_waiting_for_the_device():
    tasks = [{"node": "agg_4", "stage": "aggregate", "self_time_s": 9.0},
             {"node": "agg_9", "stage": "aggregate", "self_time_s": 1.0},
             {"node": "value_2", "stage": "prefix", "self_time_s": 20.0}]
    run, lo = window(3, tasks)
    keys = dict.fromkeys(("cpu", "inbox_wait", "put_wait", "put_wait_in_hook",
                          "device_wait_in_hook", "self_time", "self_cpu"), 0.0)
    ring([("task.account", "agg_4", lo + S, lo + S, dict(keys, device_wait=1.0)),
          ("task.account", "agg_4", lo + 9 * S, lo + 9 * S, dict(keys, device_wait=7.0)),
          ("task.account", "agg_9", lo + S, lo + S, dict(keys, device_wait=0.0)),
          ("task.account", "agg_9", lo + 9 * S, lo + 9 * S, dict(keys, device_wait=0.0))])
    assert reader("agg_device_wait_share")(run) == pytest.approx(75.0)
    run["tasks"] = tasks[2:]
    assert reader("agg_device_wait_share")(run) is None  # no aggregate task: nothing to read


def test_a_program_without_the_watch_gives_nothing(monkeypatch):
    """The parent's program records no device.stall, watch.tick or
    join.fetch: the readers return nothing and do not raise, and the line
    leaves the metrics out."""
    run, lo = window(4)
    ring([("agg.fetch", "agg_4", lo + S, lo + 2 * S, None)])
    monkeypatch.setattr(trace, "SPAN_NAMES", tuple(
        n for n in trace.SPAN_NAMES if n not in ("device.stall", "watch.tick", "join.fetch")))
    for name in NEW:
        if name != "agg_device_wait_share":
            assert reader(name)(run) is None, name


@pytest.mark.parametrize("cell", ["q7-paced", "q8-sat", "q7-mesh4"])
def test_a_traced_rehearsal_reports_every_one_its_lists_name(cell):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell,
         "--seed", "2147483941", "--seconds", "2", "--trace", "1", "--rehearse"],
        cwd=ROOT, capture_output=True, text=True, timeout=600, env=env)
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads([l for l in p.stdout.splitlines() if l.startswith("{")][-1])
    assert line["correct"] is True
    got = line["rehearsal_metrics"]
    named = {name for name, spec in NEW.items() if cell in spec[4]}
    assert named and named <= set(got), named - set(got)
    for name in named:
        assert isinstance(got[name]["value"], float) and got[name]["value"] >= 0.0
        assert got[name]["unit"] == NEW[name][0]
    for name in named:
        if name.startswith("device_stalls"):
            assert got[name]["value"] == 0.0  # no wait of a rehearsal lasts a second
    if cell == "q7-mesh4":
        # the sharded aggregate's task waits for the device inside its closes
        assert got["agg_device_wait_share"]["value"] > 0.0
        assert got["device_wait_max_ms.sat"]["value"] > 0.0
