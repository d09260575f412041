"""Median over the window's checkpoints of trigger -> metadata durable
(epoch spans of obs/trace.py)."""
from harness import stats


def read(run):
    return stats.median([e["trigger_to_durable_ms"] for e in run["epochs"]
                         if e["trigger_to_durable_ms"] is not None])
