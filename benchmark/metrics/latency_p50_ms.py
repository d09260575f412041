"""Median over the window's closes of last event due -> result at the sink."""
from harness import readers


def read(run):
    return readers.latency_percentile(run, 50.0)
