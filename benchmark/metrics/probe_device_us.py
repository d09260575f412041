"""Median device time of one probe of the windowed join (program jit_probe:
the build side's sort and both searches), from the trace."""
from harness import readers


def read(run):
    p = readers.program(run, "jit_probe")
    return p["median_us"] if p else None
