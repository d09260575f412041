"""top5-sat at rehearsal size on the CPU: ``cells.py`` finds the new
configuration, mix, cell and three readers by their names, the cell comes out
``correct`` with one partial a due window and five rows a window at the sink,
its traced line carries the three per-layer metrics of the layer ``window
top-n`` beside those of the lists it joined, and every one of the three
readers answers None, without raising, on a program that has neither the
``wf.rank`` span nor the two counters (the parent's: the traced runs of every
cell are made with this benchmark over the parent's program too). Also what
the manifest has to keep true in the appended form: every per-layer metric a
list of cells, the new ones ``top5-sat`` alone, eleven cells and eight
configurations, one of them on four chips."""

import json
import os
import subprocess
import sys
from collections import namedtuple

import numpy as np
import pytest

from bench_paths import BENCH, ROOT
from harness import cells

CELL, CONFIG, MIX = "top5-sat", "nexmark-top5-minute", "sat-top5"
NEW = ["topn_rank_ms", "topn_rank_share", "topn_rows_in_per_event"]
Span = namedtuple("Span", "node t0_ns t1_ns args")


def lines_of(trace: str) -> tuple[dict, dict]:
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", "5100000001", "--seconds", "3", "--trace", trace, "--rehearse"],
        cwd=ROOT, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = [json.loads(l) for l in p.stdout.splitlines() if l.startswith("{")]
    return lines[0], lines[-1]


@pytest.fixture(scope="module")
def traced():
    return lines_of("1")


def test_the_rehearsal_is_correct_with_one_partial_a_window(traced):
    first, line = traced
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 20, line
    assert line["compared"]["partials_compared"]["value"] == line["attempted"]
    assert line["compared"]["aggregates_checked"]["value"] == 1
    assert line["compared"]["rows_late"]["value"] == 0
    assert line["compared"]["checkpoints_triggered"]["value"] >= 1
    assert line["compared"]["checkpoints_not_completed"]["value"] == 0
    # the bid stream is read once, by the one per-auction count
    assert [a["keyed_on"] for a in first["ingest"]] == [["bid.auction"]]
    assert line["metrics"] == {}


def test_the_traced_rehearsal_reports_the_three_new_metrics_and_the_lists_it_joined(traced):
    _first, line = traced
    got = line["rehearsal_metrics"]
    for name in NEW:
        assert name in got, name
    assert got["topn_rank_ms"]["value"] > 0 and 0 < got["topn_rank_share"]["value"] < 100
    # a rehearsal window's 11,040 bids meet 1,400-1,900 auctions; a close every 400 events
    assert 2.0 < got["topn_rows_in_per_event"]["value"] < 8.0
    for name in ("pane_combine_ms", "pane_combine_share", "close_rows_per_event",
                 "table_fill_share", "table_grows_in_window", "close_read_ms.sat",
                 "snapshot_read_ms", "agg_us_per_event", "agg_busy_share", "gil_wait_share"):
        assert name in got, name
    cell = cells.Cell(CELL)
    ours = {m["name"] for g in ("end_to_end", "per_layer") for m in cell.metrics(g)}
    hour = {m["name"] for g in ("end_to_end", "per_layer")
            for m in cells.Cell("q5-hour-sat").metrics(g)}
    # every list q5-hour-sat is on took the cell behind it, but the one that
    # reads a second aggregate, which this plan has none of
    assert hour - ours == {"second_level_us_per_row"} and ours - hour == set(NEW)
    assert {"events_per_s", "setup_s"} <= {m["name"] for m in cell.metrics("end_to_end")}
    for m in cell.manifest["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["moves"] == "events_per_s"
            assert m["layer"] == "window top-n" and m["better"] == "lower"
    assert {m["name"]: m["source"] for m in cell.manifest["per_layer"] if m["name"] in NEW} == {
        "topn_rank_ms": "program_span", "topn_rank_share": "program_span",
        "topn_rows_in_per_event": "program_counter"}


RUN = {"window": {"opened": 10.0, "closed": 12.0, "events": 400_000},
       "tasks": [{"node": "agg", "op": "sliding_aggregate", "stage": "aggregate",
                  "first_level": True},
                 {"node": "wf", "op": "window_function", "stage": "post", "first_level": False}]}


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_span_or_the_counters_gives_none_and_does_not_raise(
        name, monkeypatch):
    """What the parent's tree gives each reader: no ``wf.rank`` in its
    ``SPAN_NAMES``, no ``window_fn_rows_in`` in its account marks, or no
    marks to difference at all; and records with no window at all."""
    from arroyo_tpu.obs import trace

    read = cells.Cell(CELL).reader(name)
    monkeypatch.setattr(trace, "SPAN_NAMES",
                        tuple(n for n in trace.SPAN_NAMES if n != "wf.rank"))
    monkeypatch.setattr(trace, "account_over", lambda *a, **k: {"wall": 1.0, "cpu": 0.5})
    assert read(RUN) is None
    monkeypatch.setattr(trace, "account_over", lambda *a, **k: None)
    assert read(RUN) is None
    monkeypatch.delattr(trace, "account_over")
    monkeypatch.delattr(trace, "SPAN_NAMES")
    assert read(RUN) is None
    assert read({"window": {}, "tasks": []}) is None and read({}) is None


def test_the_readers_read_the_span_and_the_counter_where_the_program_has_them(monkeypatch):
    from arroyo_tpu.obs import trace

    s = 10**9
    spans = [Span("wf", 9 * s, 10 * s + s // 10, {"rows_in": 70_000}),      # cut by the opening
             Span("wf", 11 * s, 11 * s + s // 5, {"rows_in": 80_000}),
             Span("other", 11 * s, 11 * s + s // 2, {"rows_in": 10})]       # a lesser task
    asked = []

    def fake_spans(name, t0=None, t1=None, *a, **k):
        asked.append((name, t0, t1))
        return spans

    monkeypatch.setattr(trace, "spans", fake_spans)
    cell = cells.Cell(CELL)
    assert cell.reader("topn_rank_ms")(RUN) == pytest.approx(500.0)         # 1,100 / 200 / 500 ms
    assert cell.reader("topn_rank_share")(RUN) == pytest.approx(100.0 * 0.3 / 2.0)
    assert asked == [("wf.rank", 10 * s, 12 * s)] * 2
    monkeypatch.setattr(trace, "spans", lambda *a, **k: [])
    assert cell.reader("topn_rank_ms")(RUN) is None
    assert cell.reader("topn_rank_share")(RUN) is None
    over = []

    def fake_account(node, t0, t1):
        over.append((node, t0, t1))
        return {"window_fn_rows_in": 1_560_000, "window_fn_rows_out": 100}

    monkeypatch.setattr(trace, "account_over", fake_account)
    assert cell.reader("topn_rows_in_per_event")(RUN) == pytest.approx(3.9)
    assert over == [("wf", 10 * s, 12 * s)]                                 # the ranking task alone
    assert cell.reader("topn_rows_in_per_event")(dict(RUN, tasks=RUN["tasks"][:1])) is None


# One process a cell, as a run is: the rehearsal in it, then the three readers
# over its records and its span ring, as they are and as the parent's would be.
DRIVE = """
import json, os, sys, time
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path[:0] = [{root!r}, {bench!r}]
from harness import cells, runner
from arroyo_tpu.obs import trace

run = runner.Run(cells.Cell({cell!r}), 5100000002, 3.0, True, True, time.monotonic())
result = run.execute()
records = result["records"]
new = cells.Cell({new_cell!r})
as_it_is = {{name: new.reader(name)(records) for name in {names!r}}}
trace.SPAN_NAMES = tuple(n for n in trace.SPAN_NAMES if n != "wf.rank")
for m in trace.spans("task.account"):
    for field in ("window_fn_rows_in", "window_fn_rows_out"):
        (m.args or {{}}).pop(field, None)
stripped = {{name: new.reader(name)(records) for name in {names!r}}}
print(json.dumps({{"correct": result["verdict"]["correct"], "as_it_is": as_it_is,
                   "stripped": stripped}}))
"""


@pytest.mark.parametrize("cell", ["q5-paced", "q7-sat"])
def test_the_new_readers_give_none_on_the_records_of_a_cell_the_benchmark_had(cell):
    script = DRIVE.format(root=ROOT, bench=BENCH, cell=cell, new_cell=CELL, names=NEW)
    p = subprocess.run([sys.executable, "-c", script], cwd=ROOT, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    said = json.loads([l for l in p.stdout.splitlines() if l.startswith("{")][-1])
    assert said["correct"] is True, said
    # no window function in its plan: nothing to read on this tree's program
    # either, and nothing on the parent's
    assert said["as_it_is"] == dict.fromkeys(NEW) == said["stripped"], said


def test_every_per_layer_metric_lists_its_cells_and_the_new_ones_list_top5_sat_alone():
    m = cells.manifest()
    assert [x["name"] for x in m["per_layer"] if not isinstance(x.get("workloads"), list)] == []
    names = {w["name"] for w in m["workloads"]}
    assert all(x["workloads"] and set(x["workloads"]) <= names for x in m["per_layer"])
    assert [x["name"] for x in m["per_layer"][-3:]] == NEW and len(m["per_layer"]) == 58
    for w in m["workloads"]:
        mine = {x["name"] for x in cells.Cell(w["name"]).metrics("per_layer")}
        assert (set(NEW) <= mine) == (w["name"] == CELL) and (w["name"] == CELL or not mine & set(NEW))
    # each reader stands alone under metrics/: nothing new under harness/
    for name in NEW:
        assert os.path.exists(os.path.join(BENCH, "metrics", name + ".py"))
    assert not os.path.exists(os.path.join(BENCH, "harness", "readers_topn.py"))


def test_eleven_cells_stand_and_every_cell_names_its_cut_where_the_manifest_does():
    m = cells.manifest()
    entries = {c["name"]: c for c in m["configs"]}
    for w in m["workloads"]:
        cell = cells.Cell(w["name"])
        assert cell.config["reduced"] == entries[w["config"]]["reduced"], w["name"]
        assert cell.config["assumed"] and len(cell.config["source"]) <= 200
        assert len(w["why"]) <= 200 and "\n" not in w["why"]
    assert len(m["workloads"]) == 11 and len(m["configs"]) == 8
    assert [w["name"] for w in m["workloads"] if w["chips"] == 4] == ["q7-mesh4"]
    assert m["workloads"][-1] == dict(m["workloads"][-1], name=CELL, config=CONFIG,
                                      traffic=MIX, chips=1)
    assert m["configs"][-1]["name"] == CONFIG and m["configs"][-1]["reduced"] == []
    assert len({(w["config"], w["traffic"]) for w in m["workloads"]}) == 11
    assert m["run_seconds"] == 50
    assert {x["name"]: x["bound"] for x in m["end_to_end"]} == {
        "events_per_s": 0.15, "latency_p50_ms": 0.25, "setup_s": 0.25}


def test_the_data_files_cut_nothing_and_say_what_they_assume():
    m = cells.manifest()
    entry = next(c for c in m["configs"] if c["name"] == CONFIG)
    cell = cells.Cell(CELL)
    assert entry["source"] == cell.config["source"] and entry["file"].endswith(CONFIG + ".json")
    assert entry["reduced"] == cell.config["reduced"] == [] and "settings" not in cell.config
    assert cell.config["window"] == {"width_micros": 60_000_000, "slide_micros": 2_000_000}
    assert cell.config["result"] == {"columns": ["auction", "num", "row_num"],
                                     "window_start": "ws"}
    hour = cells.Cell("q5-hour-sat")
    for key in ("guarantees", "engine", "generator"):
        assert cell.config[key] == hour.config[key], key
    assumed = " ".join(cell.config["assumed"])
    for said in ("from memory", "ORDER BY num DESC, auction ASC", "ties",
                 "bid-to-auction rule", "B19", "local disk", "faster than wall time"):
        assert said in assumed, said
    assert cell.chips == 1 and cell.entry["traffic"] == MIX
    minute = cells.Cell("q7-minute-sat").traffic
    assert {k: v for k, v in cell.traffic.items() if k != "what"} == {
        k: v for k, v in minute.items() if k != "what"}
    assert cell.traffic["event_rate"] == 0 and cell.traffic["warmup_events"] == 2_100_000
    with open(os.path.join(BENCH, "configs", CONFIG + ".sql")) as f:
        text = f.read()
    assert "ROW_NUMBER() OVER (" in text and "row_num <= 5" in text
    assert "hop(interval '2 seconds', interval '60 seconds')" in text
    assert "ORDER BY num DESC, auction ASC" in text and text.count("FROM nexmark") == 1


def test_the_reference_ranks_by_count_then_by_the_lower_id():
    ref = cells.Cell(CELL).reference
    window = {"bid": np.array([True] * 12 + [False]),
              "auction": np.array([5, 5, 5, 9, 9, 7, 7, 3, 8, 2, 6, 4, 0])}
    # 5 thrice, 7 and 9 twice, then six auctions once: the two lowest ids get in
    assert ref.rows(window) == [(5, 3, 1), (7, 2, 2), (9, 2, 3), (2, 1, 4), (3, 1, 5)]
    few = {"bid": np.array([True, True, True, False]), "auction": np.array([4, 4, 1, 0])}
    assert ref.rows(few) == [(4, 2, 1), (1, 1, 2)]
    assert ref.rows({k: v[:0] for k, v in window.items()}) == []
    parts = ref.partials(few)
    assert list(parts) == [2] and parts[2].tolist() == [[1, 1], [4, 2]]
    assert ref.ingested(100) == 92 and ref.ingested(1_000_003) == 920_000
    with open(os.path.join(BENCH, "configs", CONFIG + ".py")) as f:
        assert "arroyo_tpu" not in f.read()
