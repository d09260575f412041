"""The pane combine of a sliding window's close, by the program's own span:
median over the window's closes of agg.combine (the window's bins
concatenated, combined by key and made into the window's columns, on the
aggregate task's own thread), every sliding aggregate's closes together."""
from harness import readers_combine, stats


def read(run):
    return stats.median([(s.t1_ns - s.t0_ns) / 1e6
                         for s in readers_combine.combines(run) or ()])
