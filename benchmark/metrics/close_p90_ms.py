"""The tail of the window's closes: 90th percentile of last event due ->
result at the sink. A per-layer reading: one stall of the pipeline, seen in
about one run in six, makes a fifth of a run's closes late and this number
five to thirteen times itself, so no bound under 25% holds it (PERF.md)."""
from harness import readers


def read(run):
    return readers.latency_percentile(run, 90.0)
