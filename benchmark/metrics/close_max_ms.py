"""The worst close of the window: the largest last event due -> result at
the sink. Where a run's pipeline stalled, this is how long."""
from harness import readers


def read(run):
    return readers.latency_percentile(run, 100.0)
