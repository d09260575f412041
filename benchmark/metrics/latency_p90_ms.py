"""90th percentile over the window's closes of the same latency."""
from harness import readers


def read(run):
    return readers.latency_percentile(run, 90.0)
