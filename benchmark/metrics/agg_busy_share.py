"""Busy share of the busiest aggregate task: its self-time over the span."""
from harness import readers


def read(run):
    ts, span = readers.tasks(run, "aggregate"), run["span"]["seconds"]
    if not ts or span <= 0:
        return None
    return 100.0 * max(t["self_time_s"] for t in ts) / span
