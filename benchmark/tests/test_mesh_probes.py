"""What the harness reads off a deployment over several chips, on plain
data and on a ``ShardedAggregator`` over four CPU devices: settings held to
the program's own table of defaults, each chip's memory, and
``slot_watch`` recording a step, a close, a snapshot and a row in a shard's
spill buffer. The names it wraps exist with the parameters it passes on."""

import importlib
import inspect
import json
import os
import subprocess
import sys

import pytest

from bench_paths import BENCH, ROOT
from harness import compare, runner

from arroyo_tpu import config as cfg

DEFAULTS = cfg.Config({"device": {"mesh-devices": 0, "table-capacity": 65536},
                       "pipeline": {"chaining": {"enabled": False}}})


def test_declared_settings_pass_as_they_are():
    assert runner._declared({}, DEFAULTS) == {}
    s = {"device.mesh-devices": 4, "pipeline.chaining.enabled": True}
    assert runner._declared(s, DEFAULTS) == s and runner._declared(s, DEFAULTS) is not s


@pytest.mark.parametrize("key", ["device.mesh-devcies", "device", "pipeline.chaining",
                                 "device.mesh-devices.more", "mesh-devices", ""])
def test_an_undeclared_key_or_a_section_is_refused_by_name(key):
    with pytest.raises(runner.UndeclaredSetting, match=repr(key)):
        runner._declared({"device.table-capacity": 1, key: 4}, DEFAULTS)


def test_every_declared_leaf_of_the_programs_own_table_passes():
    def leaves(d, at=()):
        for k, v in d.items():
            yield from leaves(v, at + (k,)) if isinstance(v, dict) else [".".join(at + (k,))]

    keys = list(leaves(cfg._DEFAULTS))
    assert "device.mesh-devices" in keys and len(keys) > 50
    assert runner._declared({k: 0 for k in keys}, cfg.Config(cfg._DEFAULTS))


def test_memory_is_read_from_every_chip_and_the_fullest_is_the_peak():
    stats = [{"peak_bytes_in_use": 5, "bytes_in_use": 1}, {"peak_bytes_in_use": 9}, None, {}]
    assert runner._memory_peaks(stats) == [5, 9, 0, 0]
    assert runner._memory_peaks(stats[:1]) == [5]  # chips: 1 reads device 0, as before


def test_aggregates_short_of_chips_on_plain_data():
    on_one = [{"devices": 1, "class": "SlotAggregator"}] * 3
    on_four = [{"devices": 4, "class": "ShardedAggregator"}] * 3
    assert compare.short_of_chips(on_one, 1) == [] == compare.short_of_chips(on_four, 4)
    assert len(compare.short_of_chips(on_one, 4)) == 3
    assert len(compare.short_of_chips(on_four[:1] + on_one[:2], 4)) == 2
    assert compare.short_of_chips(on_four, 1) == []  # more devices than asked for is not short


# as tests/test_span_account.py pins the slot aggregate's: a rename breaks a
# harness that no other kind of PR may edit
WRAPPED = [
    ("ShardedAggregator.update_sharded", ["self", "key_i64", "bins", "valid", "vals"]),
    ("ShardedAggregator._drain_spill", ["self", "emit_lo", "emit_hi", "free_below"]),
    ("ShardedAggregator.extract_start", ["self", "emit_lo", "emit_hi", "free_below"]),
    ("ShardedAggregator.snapshot", ["self"]),
    ("ShardedAggregator.mesh_stats", ["self"]),
    ("_ReadyHandle.result", ["self"]),
]


@pytest.mark.parametrize("name,params", WRAPPED, ids=[w[0] for w in WRAPPED])
def test_the_sharded_names_the_harness_wraps_still_exist(name, params):
    obj = importlib.import_module("arroyo_tpu.parallel.sharded_agg")
    for part in name.split("."):
        obj = getattr(obj, part)
    assert list(inspect.signature(obj).parameters) == params


WATCHED = '''
import json, sys
import numpy as np
import jax
from harness import probes
from arroyo_tpu.parallel import ShardedAggregator, make_mesh
from arroyo_tpu.ops.slot_agg import SlotAggregator

assert len(jax.devices()) == 4, jax.devices()
spans = []

class annotate:
    def __init__(self, name): self.name = name
    def __enter__(self): spans.append(self.name)
    def __exit__(self, *exc): return False

def sharded(cap, max_probes, spill_cap=64):
    return ShardedAggregator(make_mesh(4), ("max", "count"), (np.dtype(np.int64),) * 2, cap=cap,
                             batch_cap=64, max_probes=max_probes, emit_cap=64, spill_cap=spill_cap)

rng = np.random.default_rng(int(sys.argv[1]))
keys = rng.integers(1, 2**63, 200, dtype=np.uint64) * np.uint64(2)
vals = [np.arange(200, dtype=np.int64), np.ones(200, dtype=np.int64)]
out = {}
with probes.slot_watch(annotate) as seen:
    roomy, tight = sharded(256, 8), sharded(16, 1)  # 200 keys do not fit 4 x 16 slots
    for agg in (roomy, tight):
        agg.update(keys, np.zeros(200, dtype=np.int32), vals)
    out["steps"] = [sum(1 for _t, i in seen.step_times if i == id(a)) for a in (roomy, tight)]
    out["before_any_close"] = seen.spilled_rows()
    tight.snapshot()
    out["after_snapshot"] = seen.spilled_rows()
    rows = {}
    for name, agg in (("roomy", roomy), ("tight", tight)):
        handle = agg.extract_start(0, 1, 1)
        out["landed_before_result_" + name] = id(agg) in seen.landed
        k, b, accs = handle.result()
        rows[name] = sorted(zip(k.tolist(), accs[0].tolist(), accs[1].tolist()))
    out["same_rows"] = rows["roomy"] == rows["tight"] == sorted(
        zip(keys.tolist(), vals[0].tolist(), vals[1].tolist()))
    out["landed"] = [id(a) in seen.landed for a in (roomy, tight)]
    out["closes"] = len(seen.closes)
    out["close_order"] = all(t0 <= t1 for t0, t1 in seen.closes)
    out["overflow"] = [seen.mesh_overflow.get(id(a), 0) for a in (roomy, tight)]
    out["spilled_rows"] = seen.spilled_rows()
    # emitted and gone from the buffers: a second close finds none, the mark stays
    tight.extract_start(1, 2, 2).result()
    out["residency_after"] = tight.mesh_stats()["overflow_rows"]
    out["spilled_rows_after"] = seen.spilled_rows()
    out["calls"] = {n: seen.calls[id(tight), n] for n in ("ingest", "close", "fetch", "snapshot")}
    out["aggregators"] = sorted(type(a).__name__ for a in seen.aggregators.values())
    out["warms_by_closing"] = [probes.warms_by_closing(tight),
                               probes.warms_by_closing(object())]
    out["devices"] = len({d for arr in jax.tree_util.tree_leaves(tight.state)
                          for d in arr.devices()})
out["spans"] = sorted(set(spans))
out["restored"] = [ShardedAggregator.update_sharded.__name__, ShardedAggregator._drain_spill.__name__,
                   ShardedAggregator.extract_start.__qualname__, SlotAggregator.snapshot.__qualname__]
print(json.dumps(out))
'''


@pytest.mark.parametrize("seed", [1, 2147483801])
def test_slot_watch_sees_a_sharded_aggregate_on_four_devices(seed):
    p = subprocess.run(
        [sys.executable, "-c", WATCHED, str(seed)], capture_output=True, text=True, timeout=600,
        env=dict(os.environ, PYTHONPATH=os.pathsep.join([ROOT, BENCH]), JAX_PLATFORMS="cpu",
                 XLA_FLAGS="--xla_force_host_platform_device_count=4"))
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.splitlines()[-1])
    assert out["steps"] == [1, 1]  # 200 rows are one step of 4 x 64
    assert out["before_any_close"] == 0  # nothing has read the buffers' fill yet
    assert out["after_snapshot"] > 0  # the snapshot has
    assert out["landed_before_result_roomy"] is False and out["landed"] == [True, True]
    assert out["same_rows"]  # a spilled row is slower, not lost
    assert out["closes"] == 2 and out["close_order"]
    assert out["overflow"][0] == 0 and out["overflow"][1] > 0
    assert out["spilled_rows"] == out["overflow"][1] == out["after_snapshot"]
    assert out["residency_after"] == 0 and out["spilled_rows_after"] == out["spilled_rows"]
    assert out["calls"] == {"ingest": 1, "close": 2, "fetch": 2, "snapshot": 1}
    assert out["aggregators"] == ["ShardedAggregator", "ShardedAggregator"]
    assert out["warms_by_closing"] == [True, False] and out["devices"] == 4
    assert out["spans"] == ["close", "fetch", "ingest", "snapshot"]
    assert out["restored"] == ["update_sharded", "_drain_spill",
                               "ShardedAggregator.extract_start", "SlotAggregator.snapshot"]
