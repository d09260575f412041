"""Median over the closes of the whole window periods a result left after
its last event was due."""
from harness import readers, stats


def read(run):
    if not run["period_ms"]:
        return None
    return stats.median(stats.periods_late(readers.latencies(run), run["period_ms"]))
