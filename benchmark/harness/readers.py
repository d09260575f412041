"""Arithmetic that several metric readers share. A reader takes the run's
``records`` (see ``runner.py``) and returns one number, or None when it
finds nothing to read; the harness then leaves the metric out."""

from __future__ import annotations

from typing import Optional

from . import stats


def tasks(run: dict, stage: str) -> list[dict]:
    return [t for t in run["tasks"] if t["stage"] == stage]


def us_per_event(run: dict, stage: str) -> Optional[float]:
    """Self-time of the stage's tasks, summed, per source event of the
    span. Self-time is the program's own (obs/profile.py): wall time of the
    operator's hooks, for a source its thread's CPU time."""
    ts, events = tasks(run, stage), run["span"]["events"]
    if not ts or events <= 0:
        return None
    return sum(t["self_time_s"] for t in ts) / events * 1e6


def latencies(run: dict) -> list[float]:
    return [c["latency_ms"] for c in run["closes"]]


def latency_percentile(run: dict, q: float) -> Optional[float]:
    return stats.percentile(latencies(run), q)


def device_idle_share(run: dict) -> Optional[float]:
    d = run["devtrace"]
    if not d or d["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - d["busy_s"] / d["window_s"])


def compiles_in_window(run: dict) -> float:
    return float(len(run["compiles_in_window"]))


def program(run: dict, name: str) -> Optional[dict]:
    d = run["devtrace"]
    return d["programs"].get(name) if d else None
