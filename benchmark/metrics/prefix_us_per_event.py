"""Self-time of the stateless tasks between source and first aggregate
(watermark, filter, key) per event of the stream."""
from harness import readers


def read(run):
    return readers.us_per_event(run, "prefix")
