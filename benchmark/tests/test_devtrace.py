"""The trace reduction on a small recorded trace (data/trace_cut.json: the
first 250 ms of a traced q7-sat window on a TPU v5 lite, PR 24, as
``devtrace.load`` gave it) against sums made another way, and on hand-made
traces whose answers are known by inspection."""

import json
import os

import pytest

from harness import devtrace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "trace_cut.json")


def test_hand_made_trace():
    ms = 1e6
    trace = {
        "devices": {"/device:TPU:0": {
            "XLA Modules": [["jit_step", 1 * ms, 2 * ms], ["jit_step", 5 * ms, 1 * ms],
                            ["jit_go", 8 * ms, 0.5 * ms]],
            "XLA Ops": [["op", 1 * ms, 1 * ms], ["op", 1.5 * ms, 1.5 * ms],  # overlap: 1..3
                        ["op", 5 * ms, 1 * ms], ["op", 8 * ms, 0.5 * ms]]}},
        "host": [[devtrace.WINDOW_SPAN, 0.0, 10 * ms],
                 ["ingest", 0.5 * ms, 0.4 * ms], ["generate", 3 * ms, 2 * ms],
                 ["generate", 3.2 * ms, 1.5 * ms], ["fetch", 6.2 * ms, 1.0 * ms],
                 ["ingest", 6.0 * ms, 0.3 * ms]],
    }
    r = devtrace.reduce(trace)
    assert r["window_s"] == pytest.approx(0.010)
    assert r["busy_s"] == pytest.approx(0.0035)          # 2 + 1 + 0.5 ms
    assert r["programs"]["jit_step"] == {"seconds": pytest.approx(0.003), "runs": 2,
                                         "median_us": pytest.approx(1500.0)}
    assert r["device_ops"][0] == ["jit_step", pytest.approx(0.003)]
    assert r["device_ops"][1] == ["jit_go", pytest.approx(0.0005)]
    # idle gaps, longest first: 3..5 (generate), 6..8 (fetch), 8.5..10, 0..1 (ingest)
    assert [g[0] for g in r["idle_gaps"]] == ["generate", "fetch", "no-span", "ingest"]
    assert [g[1] for g in r["idle_gaps"]] == pytest.approx([0.002, 0.002, 0.0015, 0.001])
    assert 1 - r["busy_s"] / r["window_s"] == pytest.approx(0.65)


def test_nothing_on_the_device_reads_as_nothing():
    assert devtrace.reduce({"devices": {}, "host": []}) is None
    assert devtrace.reduce({"devices": {"/device:TPU:0": {"XLA Ops": [], "XLA Modules": []}},
                            "host": [[devtrace.WINDOW_SPAN, 0.0, 1e6]]}) is None


def test_two_chips_average():
    dev = {"XLA Modules": [["jit_step", 0.0, 4e6]], "XLA Ops": [["op", 0.0, 4e6]]}
    idle = {"XLA Modules": [], "XLA Ops": [["op", 0.0, 2e6]]}
    r = devtrace.reduce({"devices": {"/device:TPU:0": dev, "/device:TPU:1": idle},
                         "host": [[devtrace.WINDOW_SPAN, 0.0, 8e6]]})
    assert r["busy_s"] == pytest.approx(0.003) and r["window_s"] == pytest.approx(0.008)


def test_recorded_trace():
    with open(DATA) as f:
        trace = json.load(f)
    r = devtrace.reduce(trace)
    window = next(e for e in trace["host"] if e[0] == devtrace.WINDOW_SPAN)
    lo, hi = window[1], window[1] + window[2]
    (plane, lines), = trace["devices"].items()
    # busy time another way: paint a 100 ns raster
    step = 100.0
    cells = bytearray(int((hi - lo) / step) + 1)
    for _n, start, dur in lines["XLA Ops"]:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            for i in range(int((a - lo) / step), int((b - lo) / step)):
                cells[i] = 1
    assert r["busy_s"] == pytest.approx(sum(cells) * step / 1e9, rel=0.02)
    assert r["window_s"] == pytest.approx((hi - lo) / 1e9)
    # per program: plain sums of the module events inside the window
    sums, runs = {}, {}
    for name, start, dur in lines["XLA Modules"]:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            sums[name] = sums.get(name, 0.0) + (b - a) / 1e9
            runs[name] = runs.get(name, 0) + 1
    assert set(r["programs"]) == set(sums) and "jit_step" in sums
    for name in sums:
        assert r["programs"][name]["seconds"] == pytest.approx(sums[name])
        assert r["programs"][name]["runs"] == runs[name]
    # busy + idle gaps make the window (every gap is listed: top is large)
    full = devtrace.reduce(trace, top=10_000)
    assert full["busy_s"] + sum(g[1] for g in full["idle_gaps"]) == pytest.approx(full["window_s"])
    assert 0 < r["busy_s"] < r["window_s"]
