"""CPU time of the source tasks per event of the stream (the query scans
the stream more than once, and every scan generates it)."""
from harness import readers


def read(run):
    return readers.us_per_event(run, "source")
