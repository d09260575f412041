"""The windowed join's probe by the program's own span: median over the
window's closes of join.probe (dispatch of the sort and search -> the
pairs, expanded, on the host: on a fetch worker for a device probe, on the
join's own thread for a numpy one)."""
from harness import readers_join, stats


def read(run):
    return stats.median([(s.t1_ns - s.t0_ns) / 1e6 for s in readers_join.probes(run)])
