"""q5-hour-sat at rehearsal size on the CPU: the cell comes out ``correct``
with two partials a due window, its traced line carries the four per-layer
metrics this configuration brought, and a program without the pane
combine's span and counters (the parent's) leaves them out without raising;
and the data files: the source's length, the cut named where it is made, a
query text that differs from ``nexmark-q5-hot-items.sql`` in the two
intervals and its comment only."""

import json
import os
import subprocess
import sys

import pytest

from bench_paths import BENCH, ROOT
from harness import cells, readers_combine

CELL, CONFIG = "q5-hour-sat", "nexmark-q5-hour"
NEW_METRICS = {"pane_combine_ms", "pane_combine_share", "close_rows_per_event",
               "second_level_us_per_row"}
CUT = ["window.width_micros", "window.slide_micros"]


# A rehearsal slide is 200 events, so a backlog's 8,192-row step closes forty
# windows at once, and the second-level aggregate, one key a bin and one
# 2,048-slot region a bin, runs out of its table's 32 regions holding 32 keys:
# the table grows at a moment the machine's load picks, inside the window in
# every other run (13-15 compiles there, ``correct`` false). The cell's slide
# is 10,000 events and no chip run grew a table (PERF.md section 7, "From PR
# 43" (6)). This file tests the harness's plumbing, so it gives the rehearsal
# regions that many windows at once cannot use up, and says so here.
SMALL_REGIONS = {"ARROYO_TPU__DEVICE__REGION_SIZE": "64"}


def lines_of(trace: str) -> tuple[dict, dict]:
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", "4300000001", "--seconds", "3", "--trace", trace, "--rehearse"],
        cwd=ROOT, capture_output=True, text=True, timeout=300,
        env=dict(os.environ, **SMALL_REGIONS))
    assert p.returncode == 0, p.stderr[-2000:]
    lines = [json.loads(l) for l in p.stdout.splitlines() if l.startswith("{")]
    return lines[0], lines[-1]


@pytest.fixture(scope="module")
def traced():
    return lines_of("1")


def test_the_rehearsal_is_correct_with_two_partials_a_window(traced):
    first, line = traced
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 60, line
    assert line["compared"]["partials_compared"]["value"] == 2 * line["attempted"]
    assert line["compared"]["aggregates_checked"]["value"] == 2
    assert line["compared"]["checkpoints_triggered"]["value"] >= 1
    assert [a["keyed_on"] for a in first["ingest"]] == [["bid.auction"]] * 2
    assert line["metrics"] == {}


def test_the_traced_rehearsal_reports_every_new_metric(traced):
    _first, line = traced
    got = line["rehearsal_metrics"]
    assert NEW_METRICS <= set(got), sorted(got)
    assert got["pane_combine_ms"]["value"] > 0
    assert 0.0 < got["pane_combine_share"]["value"] < 100.0
    # a rehearsal slide holds 184 bids on a few dozen auctions, a window
    # about three hundred: a close puts out more rows than a slide brought in
    assert 0.5 < got["close_rows_per_event"]["value"] < 20.0
    assert got["second_level_us_per_row"]["value"] > 0
    brought = {m["name"]: m for m in cells.Cell(CELL).metrics("per_layer")
               if m["name"] in NEW_METRICS}
    assert set(brought) == NEW_METRICS
    assert all(m["workloads"] == [CELL] and m["moves"] == "events_per_s"
               for m in brought.values())


def test_a_program_without_the_span_gives_none_and_does_not_raise(monkeypatch):
    """What the parent's tree gives the readers: no ``agg.combine`` among its
    span names, no ``window_rows_emitted`` in its account marks."""
    from arroyo_tpu.obs import trace

    cell = cells.Cell(CELL)
    run = {"window": {"opened": 0.0, "closed": 1.0, "events": 10_000},
           "tasks": [{"node": "agg", "stage": "aggregate", "first_level": True,
                      "self_time_s": 0.5, "rows_in": 9_200}]}
    monkeypatch.setattr(trace, "SPAN_NAMES",
                        tuple(n for n in trace.SPAN_NAMES if n != "agg.combine"))
    monkeypatch.setattr(trace, "account_over", lambda *a, **k: {"wall": 1.0, "cpu": 0.5})
    assert readers_combine.combines(run) is None
    for name in sorted(NEW_METRICS):
        assert cell.reader(name)(run) is None, name


def test_the_data_files_say_what_was_cut_and_nothing_else_differs():
    m = cells.manifest()
    entry = next(c for c in m["configs"] if c["name"] == CONFIG)
    cell = cells.Cell(CELL)
    assert len(entry["source"]) <= 200 and entry["source"] == cell.config["source"]
    assert entry["reduced"] == cell.config["reduced"] == CUT
    window = cell.config["window"]
    assert window == {"width_micros": 60_000_000, "slide_micros": 1_000_000}
    assert window["width_micros"] // window["slide_micros"] == 3_600_000_000 // 60_000_000 == 60
    assert "settings" not in cell.config and cell.config["assumed"]
    older = cells.Cell("q5-sat").config
    for key in ("result", "guarantees", "engine"):
        assert cell.config[key] == older[key], key
    assert {k: v for k, v in cell.config["generator"].items()
            if k != "bid_to_auction_rule"} == older["generator"]
    mix, minute = cell.traffic, cells.Cell("q7-minute-sat").traffic
    assert {k: v for k, v in mix.items() if k != "what"} == \
        {k: v for k, v in minute.items() if k != "what"}

    def statements(stem: str) -> str:
        with open(os.path.join(BENCH, "configs", stem + ".sql")) as f:
            return "\n".join(l for l in f.read().splitlines() if not l.startswith("--"))

    theirs, ours = statements("nexmark-q5-hot-items"), statements(CONFIG)
    assert theirs.count("hop(interval '2 seconds', interval '10 seconds')") == 2
    assert ours == theirs.replace("hop(interval '2 seconds', interval '10 seconds')",
                                  "hop(interval '1 second', interval '60 seconds')")


def test_the_reference_is_the_windows_own_count_whatever_its_width():
    import numpy as np

    ref = cells.Cell(CELL).reference
    window = {"bid": np.array([True, True, False, True, True, True]),
              "auction": np.array([7, 9, 0, 7, 8, 9])}
    assert ref.rows(window) == [(7, 2), (9, 2)]
    assert ref.partials(window)[2].tolist() == [[7, 2], [8, 1], [9, 2]]
    assert ref.rows({"bid": np.zeros(3, bool), "auction": np.zeros(3, np.int64)}) == []
