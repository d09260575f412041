"""From the profiler's trace to device busy time, idle gaps and time per
program. Two steps, so that the arithmetic can be checked on a small
recorded trace without the profiler: ``load`` turns an ``.xplane.pb`` into
plain lists, ``reduce`` does the sums.

On a TPU the trace has one plane per chip, ``/device:TPU:<n>``, with a line
``XLA Modules`` (one event per program run, named ``jit_step(<id>)``) and a
line ``XLA Ops`` (one event per operation inside it). Busy time is the union
of the operations' intervals; a program's time is the sum of its module
events. Host spans (``jax.profiler.TraceAnnotation``) sit on the threads of
``/host:CPU`` on the same clock.
"""

from __future__ import annotations

import re
import statistics
from typing import Iterable, Optional

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = "/host:CPU"
MODULES, OPS = "XLA Modules", "XLA Ops"
WINDOW_SPAN = "bench_window"
_PROGRAM_ID = re.compile(r"\(\d+\)$")


def load(path: str, host_spans: Iterable[str]) -> dict:
    """The trace as plain data: per device plane its module and op events,
    and the host spans whose names are asked for (plus the window's own).
    Times in nanoseconds on the trace's clock."""
    from jax.profiler import ProfileData

    wanted = set(host_spans) | {WINDOW_SPAN}
    devices, host = {}, []
    for plane in ProfileData.from_file(path).planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {}
            for line in plane.lines:
                if line.name in (MODULES, OPS):
                    lines[line.name] = [
                        [_PROGRAM_ID.sub("", e.name) if line.name == MODULES else "op",
                         float(e.start_ns), float(e.duration_ns)]
                        for e in line.events]
            devices[plane.name] = lines
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name in wanted:
                        host.append([e.name, float(e.start_ns), float(e.duration_ns)])
    return {"devices": devices, "host": host}


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for lo, hi in sorted(intervals):
        if merged and lo <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], hi)
        else:
            merged.append([lo, hi])
    return [(lo, hi) for lo, hi in merged]


def _clip(events, lo: float, hi: float) -> list[tuple[str, float, float]]:
    out = []
    for name, start, dur in events:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            out.append((name, a, b))
    return out


def reduce(trace: dict, top: int = 10) -> Optional[dict]:
    """-> busy_s and window_s (busy averaged over the chips; busy_s_per_chip
    in the order of the planes' names), per program
    its seconds, runs and median run in microseconds, the programs that
    took most time, and the longest idle gaps of the busiest-idle chip
    labelled by the host span that covers most of each. None when no
    operation ran on a device inside the window."""
    window = [e for e in trace["host"] if e[0] == WINDOW_SPAN]
    device_events = [e for lines in trace["devices"].values()
                     for evs in lines.values() for e in evs]
    if not device_events:
        return None
    if window:
        lo, hi = window[0][1], window[0][1] + window[0][2]
    else:
        lo = min(e[1] for e in device_events)
        hi = max(e[1] + e[2] for e in device_events)
    busy_per_chip, programs, gaps = [], {}, []
    for _plane, lines in sorted(trace["devices"].items()):
        ops = _clip(lines.get(OPS) or lines.get(MODULES) or [], lo, hi)
        busy = _union([(a, b) for _n, a, b in ops])
        busy_per_chip.append(sum(b - a for a, b in busy))
        for name, a, b in _clip(lines.get(MODULES, []), lo, hi):
            programs.setdefault(name, []).append(b - a)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    if not any(busy_per_chip):
        return None
    spans = _clip([e for e in trace["host"] if e[0] != WINDOW_SPAN], lo, hi)

    def label(a: float, b: float) -> str:
        # the span that covers most of the gap; threads that run the same
        # span side by side count once
        by_name: dict[str, list[tuple[float, float]]] = {}
        for name, s, e in spans:
            if min(e, b) > max(s, a):
                by_name.setdefault(name, []).append((max(s, a), min(e, b)))
        cover = {n: sum(hi - lo for lo, hi in _union(iv)) for n, iv in by_name.items()}
        return max(cover, key=cover.get) if cover else "no-span"

    per_program = {}
    for name, durs in programs.items():
        per_program[name] = {"seconds": sum(durs) / 1e9, "runs": len(durs),
                             "median_us": statistics.median(durs) / 1e3}
    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "busy_s": sum(busy_per_chip) / len(busy_per_chip) / 1e9,
        "busy_s_per_chip": [b / 1e9 for b in busy_per_chip],
        "window_s": (hi - lo) / 1e9,
        "programs": per_program,
        "device_ops": [[n, p["seconds"]] for n, p in sorted(
            per_program.items(), key=lambda kv: -kv[1]["seconds"])[:top]],
        "idle_gaps": [[label(a, b), (b - a) / 1e9] for a, b in gaps[:top]],
    }
