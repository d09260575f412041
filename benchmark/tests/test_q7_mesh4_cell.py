"""q7-mesh4 at rehearsal size on the CPU with four host devices: the manifest
names the cell's files and they load; the rehearsal comes out ``correct``
with every aggregate's state on four devices; the three readers of the mesh
step give a number where the program's spans and the device's program are
there and nothing where they are not; the step's least bytes follow the rows
it carried and the accumulator lanes, not any padded shape."""

import json
import os
import subprocess
import sys

import pytest

from bench_paths import BENCH, ROOT
from harness import cells, roofline_mesh

CELL = "q7-mesh4"
MESH_STEP = {"mesh_step_device_us", "mesh_step_roofline", "mesh_step_fill_share"}
# read by spans the mesh path records since PR 39, and by no reader before
NOW_READ = {"agg_dispatch_us_per_event", "close_read_ms.sat", "snapshot_read_ms"}
# look for jit_step, a host directory, or count a blocked dispatch as lock wait
NOT_ON_A_MESH = {"step_device_us", "step_roofline", "agg_directory_us_per_event",
                 "gil_wait_share"}


def test_the_manifest_names_the_cell_and_its_files_load():
    cell = cells.Cell(CELL)
    assert cell.chips == 4 and cell.entry["traffic"] == "sat-mesh4"
    assert cell.entry["config"] == cell.config["name"] == "nexmark-q7-mesh4"
    # what the deployment fixes, and nothing that tunes
    assert cell.config["settings"] == {"device.mesh-devices": 4}
    assert cell.config["reduced"] == [] and len(cell.config["source"]) <= 200
    q7 = cells.Cell("q7-sat")
    for key in ("generator", "window", "result"):
        assert cell.config[key] == q7.config[key], key
    assert cell.sql_template == q7.sql_template  # the query does not change with the chips
    assert cell.traffic == dict(
        cell.traffic, kind="saturated", event_rate=0, warmup_events=300000,
        warmup_checkpoints=1, warmup_deadline_seconds=600, drain_seconds=60,
        trace_after_seconds=2, trace_seconds=3)
    assert [m["name"] for m in cell.metrics("end_to_end")] == ["events_per_s", "setup_s"]
    layer = {m["name"]: m for m in cell.metrics("per_layer")}
    assert MESH_STEP | NOW_READ <= set(layer) and not NOT_ON_A_MESH & set(layer)
    for name in MESH_STEP:
        assert layer[name]["layer"] == "mesh step" and layer[name]["workloads"] == [CELL]
        assert callable(cell.reader(name))
    # the one cell on four chips
    assert [w["name"] for w in cell.manifest["workloads"] if w["chips"] == 4] == [CELL]


@pytest.mark.parametrize("trace", ["0", "1"])
def test_the_rehearsal_is_correct_with_its_state_on_four_devices(trace):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", "2147483939", "--seconds", "2", "--trace", trace, "--rehearse"],
        cwd=ROOT, capture_output=True, text=True, timeout=600, env=env)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = [json.loads(l) for l in p.stdout.splitlines() if l.startswith("{")]
    first, line = lines[0], lines[-1]
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0, line
    assert first["effective_settings"]["device.mesh-devices"] == 4
    assert line["device"]["count"] == 4
    for name in ("aggregates_short_of_chips", "rows_spilled", "compiles_in_window",
                 "rows_late", "partials_wrong", "checkpoints_not_completed"):
        assert line["compared"][name]["value"] == 0, (name, line["compared"][name])
    assert line["compared"]["partials_compared"]["value"] == 2 * line["attempted"]
    assert line["metrics"] == {} and line["rehearsal_metrics"]
    if trace == "1":
        got = line["rehearsal_metrics"]
        device = {m["name"] for m in cells.manifest()["per_layer"]
                  if m["source"] == "device_trace"}
        want = {m["name"] for m in cells.Cell(CELL).metrics("per_layer")}
        assert want - device <= set(got), sorted(want - device - set(got))
        # a stage hands over at most device.batch-capacity rows of a room of four
        assert 0 < got["mesh_step_fill_share"]["value"] <= 25.0
        assert got["agg_dispatch_us_per_event"]["value"] > 0
        assert got["close_read_ms.sat"]["value"] > 0 and got["snapshot_read_ms"]["value"] > 0


@pytest.mark.parametrize("rows,lane_bytes,bytes_", [
    # a record (key 8, bin 4, valid 1, the lanes) three times; a slot's key, bin and
    # occupancy compared, each lane read and written
    (1, 0, 3 * 13 + 13),
    (1, 8, 3 * 21 + 13 + 16),
    (7212, 16, 7212 * (52 + 5 * 16)),
    (0, 16, 0)])
def test_step_bytes_against_hand_counted_cases(rows, lane_bytes, bytes_):
    assert roofline_mesh.step_bytes(rows, lane_bytes) == bytes_


RECORDED = r'''
import json, threading, time
import numpy as np
import jax
from harness import cells, roofline_mesh
from arroyo_tpu.metrics import TaskMetrics
from arroyo_tpu.obs import trace
from arroyo_tpu.ops.slot_agg import SlotAggregator
from arroyo_tpu.parallel import ShardedAggregator, make_mesh

assert len(jax.devices()) == 4, jax.devices()
rng = np.random.default_rng(39)
keys = rng.integers(1, 2**63, 600, dtype=np.uint64)
bins = np.zeros(600, dtype=np.int32)
vals = [np.arange(600, dtype=np.int64), np.ones(600, dtype=np.int64)]
kinds, dtypes = ("max", "count"), (np.dtype(np.int64),) * 2

def drive(node, make):
    def work():
        trace.bind("recorded", node, 0, TaskMetrics("recorded", node, 0))
        agg = make()
        agg.staged_batches = 3
        agg.update(keys, bins, vals)
        agg.extract_start(0, 1, 1).result()
        trace.unbind()
    t = threading.Thread(target=work); t.start(); t.join()

t0 = time.monotonic()
shapes = {"narrow": (64, 64), "wide": (256, 32), "lanes": (256, None)}
for node, (batch_cap, per_dest_cap) in shapes.items():
    drive(node, lambda: ShardedAggregator(make_mesh(4), kinds, dtypes, cap=1024, batch_cap=batch_cap,
                                          per_dest_cap=per_dest_cap, max_probes=16, emit_cap=256,
                                          spill_cap=64))
drive("one-chip", lambda: SlotAggregator(kinds, dtypes, cap=4096, batch_cap=256, region_size=2048))
t1 = time.monotonic()

def run_of(programs, t_lo=t0, t_hi=t1):
    return {"window": {"opened": t_lo, "closed": t_hi, "seconds": t_hi - t_lo, "events": 600},
            "devtrace": {"window_s": 3.0, "busy_s": 2.9, "programs": programs} if programs else None,
            "peaks": {"hbm_bytes_per_s": 819e9}}

step = {"jit_local_step": {"seconds": 8.0, "runs": 16, "median_us": 500000.0}}
read = {n: cells.Cell("q7-mesh4").reader(n)
        for n in ("mesh_step_device_us", "mesh_step_roofline", "mesh_step_fill_share")}
spans = roofline_mesh.mesh_steps(run_of(step))
by_node = {}
for s in spans:
    by_node.setdefault(s.node, []).append(s.args)
out = {"nodes": sorted(by_node),
       "steps": {n: len(a) for n, a in by_node.items()},
       "rows": {n: sum(x["rows"] for x in a) for n, a in by_node.items()},
       "room": {n: sorted({x["room"] for x in a}) for n, a in by_node.items()},
       "batches": {n: [x["batches"] for x in a] for n, a in by_node.items()},
       "bytes": {n: sum(roofline_mesh.step_bytes(x["rows"], x["lane_bytes"]) for x in a)
                 for n, a in by_node.items()},
       "on_a_trace": {n: r(run_of(step)) for n, r in read.items()},
       "without_a_trace": {n: r(run_of(None)) for n, r in read.items()},
       "one_chip_trace": {n: r(run_of({"jit_step": step["jit_local_step"]})) for n, r in read.items()},
       # a window in which no sharded aggregate ran a step: the one-chip spans alone
       "no_mesh_spans": {n: r(run_of(step, t1, time.monotonic() + 1)) for n, r in read.items()},
       "one_chip_dispatches": len([s for s in trace.spans("agg.dispatch", job="recorded")
                                   if s.node == "one-chip" and "room" not in s.args])}
print(json.dumps(out))
'''


def test_the_three_readers_on_a_recorded_run_and_bytes_that_ignore_padding():
    p = subprocess.run(
        [sys.executable, "-c", RECORDED], capture_output=True, text=True, timeout=600,
        env=dict(os.environ, PYTHONPATH=os.pathsep.join([ROOT, BENCH]), JAX_PLATFORMS="cpu",
                 XLA_FLAGS="--xla_force_host_platform_device_count=4"))
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.splitlines()[-1])
    # the one-chip aggregate's steps carry no room and are no mesh step
    assert out["nodes"] == ["lanes", "narrow", "wide"] and out["one_chip_dispatches"] > 0
    # 600 rows: three steps of 4 x 64, one of 4 x 256; the first says what was staged
    assert out["steps"] == {"narrow": 3, "wide": 1, "lanes": 1}
    assert out["rows"] == {"narrow": 600, "wide": 600, "lanes": 600}
    assert out["room"] == {"narrow": [256], "wide": [1024], "lanes": [1024]}
    assert out["batches"]["narrow"] == [3, 1, 1] and out["batches"]["wide"] == [3]
    # the same rows and lanes are the same bytes, whatever batch_cap and per_dest_cap pad to
    assert out["bytes"]["narrow"] == out["bytes"]["wide"] == out["bytes"]["lanes"] \
        == 600 * (52 + 5 * 16)
    got = out["on_a_trace"]
    assert got["mesh_step_device_us"] == 500000.0
    # the fullest aggregate (all three carried 600 rows; the first of them): rows over room
    assert got["mesh_step_fill_share"] in (pytest.approx(100 * 600 / 768),
                                           pytest.approx(100 * 600 / 1024))
    # all the window's bytes a second, times the traced 3 s, over the peak, over 8 chip-seconds
    assert 0 < got["mesh_step_roofline"] < 1e-3
    # nothing to read: no trace, a one-chip trace, spans without room
    assert out["without_a_trace"] == {"mesh_step_device_us": None, "mesh_step_roofline": None,
                                      "mesh_step_fill_share": got["mesh_step_fill_share"]}
    assert out["one_chip_trace"]["mesh_step_device_us"] is None
    assert out["one_chip_trace"]["mesh_step_roofline"] is None
    assert out["no_mesh_spans"] == {"mesh_step_device_us": 500000.0, "mesh_step_roofline": None,
                                    "mesh_step_fill_share": None}
