"""What PR 47 adds to the benchmark, held against a program that lacks what
it adds to the program. The traced runs of every cell are made with this
benchmark over the parent's program too, so: the one new reader,
``distinct_pairs_per_event``, is fed the records of a traced rehearsal of two
cells the benchmark had (a paced one and a saturated one) with the counter's
field struck from every ``task.account`` mark, as the parent's marks are, and
has to give None without raising; and the manifest keeps every per-layer
metric to a list of cells, the new one to ``q15-sat`` alone, so that no line
of an old cell asks for it."""

import json
import os
import subprocess
import sys

import pytest

from bench_paths import BENCH, ROOT
from harness import cells

NEW_METRIC, CELL = "distinct_pairs_per_event", "q15-sat"

# One process a cell, as a run is: the rehearsal in it, then the reader over
# its records and its span ring, before and after the field is struck.
DRIVE = """
import json, os, sys, time
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path[:0] = [{root!r}, {bench!r}]
from harness import cells, runner
from arroyo_tpu.obs import trace

run = runner.Run(cells.Cell({cell!r}), 4700000002, 3.0, True, True, time.monotonic())
result = run.execute()
records = result["records"]
read = cells.Cell({new_cell!r}).reader({metric!r})
marks = trace.spans("task.account")
with_field = sum(1 for m in marks if "distinct_pairs" in (m.args or {{}}))
as_it_is = read(records)
for m in marks:
    (m.args or {{}}).pop("distinct_pairs", None)
left = sum(1 for m in trace.spans("task.account") if "distinct_pairs" in (m.args or {{}}))
print(json.dumps({{"correct": result["verdict"]["correct"], "marks": len(marks),
                   "with_field": with_field, "left": left, "as_it_is": as_it_is,
                   "stripped": read(records),
                   "first_level": sum(1 for t in records["tasks"] if t["first_level"])}}))
"""


@pytest.mark.parametrize("cell", ["q5-paced", "q7-sat"])
def test_the_new_reader_gives_none_on_the_records_of_a_program_without_the_counter(cell):
    script = DRIVE.format(root=ROOT, bench=BENCH, cell=cell, new_cell=CELL, metric=NEW_METRIC)
    p = subprocess.run([sys.executable, "-c", script], cwd=ROOT, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    said = json.loads([l for l in p.stdout.splitlines() if l.startswith("{")][-1])
    assert said["correct"] is True and said["marks"] > 4 and said["first_level"] >= 1, said
    # this tree's marks carry the field (0 in a plan with no split); struck, none does
    assert said["with_field"] == said["marks"] and said["left"] == 0, said
    assert said["as_it_is"] == 0.0 and said["stripped"] is None, said


def test_every_per_layer_metric_lists_its_cells_and_the_new_one_lists_q15_sat_alone():
    m = cells.manifest()
    assert [x["name"] for x in m["per_layer"] if not isinstance(x.get("workloads"), list)] == []
    names = {w["name"] for w in m["workloads"]}
    assert all(x["workloads"] and set(x["workloads"]) <= names for x in m["per_layer"])
    new = [x for x in m["per_layer"] if x["name"] == NEW_METRIC]
    assert len(new) == 1 and new[0]["workloads"] == [CELL]
    assert m["per_layer"][-1] is new[0]


def test_no_old_cell_reports_the_new_metric_and_no_module_joined_the_harness():
    for w in cells.manifest()["workloads"]:
        mine = {x["name"] for x in cells.Cell(w["name"]).metrics("per_layer")}
        assert (NEW_METRIC in mine) == (w["name"] == CELL), w["name"]
    # the reader stands alone under metrics/: nothing new under harness/
    with open(os.path.join(BENCH, "metrics", NEW_METRIC + ".py")) as f:
        assert "harness" not in f.read()
    assert not os.path.exists(os.path.join(BENCH, "harness", "readers_distinct.py"))
