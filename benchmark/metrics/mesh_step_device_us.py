"""Median device time of one step of the sharded aggregate on one chip
(program jit_local_step: local sort-reduce, owner bucketing, all_to_all,
merge sort-reduce, probe-merge), from the trace; every chip of the mesh runs
each step, and the median is over all their runs."""
from harness import readers


def read(run):
    p = readers.program(run, "jit_local_step")
    return p["median_us"] if p else None
