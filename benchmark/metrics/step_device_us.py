"""Median device time of one scatter step (program jit_step), from the trace."""
from harness import readers


def read(run):
    p = readers.program(run, "jit_step")
    return p["median_us"] if p else None
