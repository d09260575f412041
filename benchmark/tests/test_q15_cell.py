"""q15-sat at rehearsal size on the CPU: the cell comes out ``correct`` with
two partials a due window (the two first levels of the distinct split, by
what the plan keys them on), its traced line carries the per-layer metric
this configuration brought, and a program without the counter (the
parent's) leaves it out without raising; and the data files: the source's
length, the cut named where the manifest names it, the rest of the
deployment q7's. Also what ``test_manifest_appended.py`` holds of the
manifest, in the form that this configuration's named cut leaves true (its
last line pins the cuts to PR 43's one, and no PR but a ``benchmark`` PR may
edit it)."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from bench_paths import BENCH, ROOT
from harness import cells

CELL, CONFIG = "q15-sat", "nexmark-q15-bid-stats"
NEW_METRIC = "distinct_pairs_per_event"
CUT = ["window.width_micros", "window.slide_micros"]
BIDDERS, AUCTIONS = ["bid.bidder"], ["bid.auction"]


def lines_of(trace: str) -> tuple[dict, dict]:
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", "4700000001", "--seconds", "3", "--trace", trace, "--rehearse"],
        cwd=ROOT, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = [json.loads(l) for l in p.stdout.splitlines() if l.startswith("{")]
    return lines[0], lines[-1]


@pytest.fixture(scope="module")
def traced():
    return lines_of("1")


def test_the_rehearsal_is_correct_with_two_partials_a_window(traced):
    first, line = traced
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 20, line
    assert line["compared"]["partials_compared"]["value"] == 2 * line["attempted"]
    assert line["compared"]["aggregates_checked"]["value"] == 2
    assert line["compared"]["aggregates_off_platform"]["value"] == 0
    assert line["compared"]["checkpoints_triggered"]["value"] >= 1
    # one scan, keyed twice: both first levels are fed its bids
    assert sorted(a["keyed_on"] for a in first["ingest"]) == [AUCTIONS, BIDDERS]
    assert len({a["source_events"] for a in first["ingest"]}) == 1
    assert line["metrics"] == {}


def test_the_traced_rehearsal_reports_the_new_metric_and_the_lists_it_joined(traced):
    _first, line = traced
    got = line["rehearsal_metrics"]
    # a rehearsal window's 1,840 bids meet a few hundred bidders and auctions
    assert 0.05 < got[NEW_METRIC]["value"] < 0.92
    for name in ("second_level_us_per_row", "table_fill_share", "table_grows_in_window",
                 "close_read_ms.sat", "snapshot_read_ms", "agg_us_per_event"):
        assert name in got, name
    assert got["table_grows_in_window"]["value"] == 0
    cell = cells.Cell(CELL)
    brought = next(m for m in cell.metrics("per_layer") if m["name"] == NEW_METRIC)
    assert brought["workloads"] == [CELL] and brought["moves"] == "events_per_s"
    assert brought["layer"] == "slot aggregate" and brought["source"] == "program_counter"
    # every list q7-sat is on took the cell behind it
    ours = {m["name"] for g in ("end_to_end", "per_layer") for m in cell.metrics(g)}
    q7 = {m["name"] for g in ("end_to_end", "per_layer") for m in cells.Cell("q7-sat").metrics(g)}
    assert q7 <= ours and "close_rows_per_event" not in ours
    # and the lists it joined keep the cells they had, in front
    second = next(m for m in cell.metrics("per_layer") if m["name"] == "second_level_us_per_row")
    assert second["workloads"] == ["q5-hour-sat", CELL]


def test_a_program_without_the_counter_gives_none_and_does_not_raise(monkeypatch):
    """What the parent's tree gives the reader: no ``distinct_pairs`` in its
    account marks, or no marks to difference at all."""
    from arroyo_tpu.obs import trace

    read = cells.Cell(CELL).reader(NEW_METRIC)
    run = {"window": {"opened": 0.0, "closed": 1.0, "events": 100_000},
           "tasks": [{"node": "agg", "stage": "aggregate", "first_level": True}]}
    monkeypatch.setattr(trace, "account_over", lambda *a, **k: {"wall": 1.0, "cpu": 0.5})
    assert read(run) is None
    monkeypatch.setattr(trace, "account_over", lambda *a, **k: None)
    assert read(run) is None
    monkeypatch.delattr(trace, "account_over")
    assert read(run) is None
    # and with the counter: the tables' pairs together, over the window's events
    two = dict(run, tasks=run["tasks"] * 2)
    monkeypatch.setattr(trace, "account_over", lambda *a, **k: {"distinct_pairs": 12_000},
                        raising=False)
    assert read(two) == pytest.approx(0.24)


def test_the_data_files_say_what_was_cut_and_the_rest_is_q7s():
    m = cells.manifest()
    entry = next(c for c in m["configs"] if c["name"] == CONFIG)
    cell = cells.Cell(CELL)
    assert len(entry["source"]) <= 200 and entry["source"] == cell.config["source"]
    assert entry["reduced"] == cell.config["reduced"] == CUT
    assert cell.config["window"] == {"width_micros": 10_000_000, "slide_micros": 10_000_000}
    assert "86,400,000,000" in cell.config["reduced_why"]
    assert "settings" not in cell.config and len(cell.config["assumed"]) >= 7
    q7 = cells.Cell("q7-sat")
    for key in ("guarantees", "engine", "window"):
        assert cell.config[key] == q7.config[key], key
    assert {k: v for k, v in cell.config["generator"].items()
            if k not in ("price_rule", "bid_to_auction_rule")} == q7.config["generator"]
    assert cell.traffic == q7.traffic and cell.entry["traffic"] == "sat" and cell.chips == 1
    assert len(cell.config["result"]["columns"]) == 12
    with open(os.path.join(BENCH, "configs", CONFIG + ".sql")) as f:
        text = f.read()
    assert text.count("FILTER (WHERE") == 9 and text.count("count(DISTINCT") == 8
    assert "CASE" not in text and "tumble(interval '10 seconds')" in text


def test_every_cell_names_its_cut_where_the_manifest_does_and_ten_cells_stand():
    m = cells.manifest()
    entries = {c["name"]: c for c in m["configs"]}
    for w in m["workloads"]:
        cell = cells.Cell(w["name"])
        assert cell.config["reduced"] == entries[w["config"]]["reduced"], w["name"]
        assert cell.config["assumed"] and len(cell.config["source"]) <= 200
    assert {n: c["reduced"] for n, c in entries.items() if c["reduced"]} == {
        "nexmark-q5-hour": CUT, CONFIG: CUT}
    assert len(m["workloads"]) == 10 and len(m["configs"]) == 7
    assert [w["name"] for w in m["workloads"] if w["chips"] == 4] == ["q7-mesh4"]
    assert m["workloads"][-1]["name"] == CELL and m["per_layer"][-1]["name"] == NEW_METRIC


def test_the_reference_counts_with_sets_and_answers_by_key():
    ref = cells.Cell(CELL).reference
    window = {"bid": np.array([True, True, False, True, True, True]),
              "auction": np.array([7, 9, 0, 7, 8, 9]),
              "bid.bidder": np.array([1, 1, 0, 2, 2, 2]),
              "price": np.array([5_000, 20_000, 0, 2_000_000, 9_999, 999_999])}
    assert ref.rows(window) == [(5, 2, 2, 1, 2, 2, 2, 1, 3, 2, 1, 1)]
    parts = ref.partials(window)
    assert parts[("bid.bidder",)].tolist() == [[1, 2, 1, 1, 0], [2, 3, 1, 1, 1]]
    assert parts[("bid.auction",)].tolist() == [[7, 2, 1, 0, 1], [8, 1, 1, 0, 0], [9, 2, 0, 2, 0]]
    assert ref.rows({k: np.zeros(3, v.dtype) for k, v in window.items()}) == []
    assert ref.ingested(100) == {("bid.bidder",): 92, ("bid.auction",): 92}
    with open(os.path.join(BENCH, "configs", CONFIG + ".py")) as f:
        assert "arroyo_tpu" not in f.read()
