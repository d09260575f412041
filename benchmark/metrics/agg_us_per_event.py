"""Self-time of the aggregate tasks (slot directory, dispatch of the device
step, closes) per event of the stream."""
from harness import readers


def read(run):
    return readers.us_per_event(run, "aggregate")
