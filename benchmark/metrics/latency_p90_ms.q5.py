"""The tail of q5's closes: 90th percentile of last event due -> result at
the sink. A per-layer number, because a race in the emission order makes it
bimodal from run to run and no bound can hold it."""
from harness import readers


def read(run):
    return readers.latency_percentile(run, 90.0)
