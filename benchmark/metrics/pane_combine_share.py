"""Share of the window that the fullest first-level aggregate task (the one
whose closes combined the most rows) spent inside agg.combine: the part of
one thread's time that goes into combining bins it has combined before."""
from harness import readers_combine


def read(run):
    return readers_combine.fullest_share(run)
