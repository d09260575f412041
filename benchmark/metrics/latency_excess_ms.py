"""Median over the closes of latency less its whole periods: the part that
answers to the engine's speed."""
from harness import readers, stats


def read(run):
    if not run["period_ms"]:
        return None
    return stats.median(stats.latency_excess(readers.latencies(run), run["period_ms"]))
