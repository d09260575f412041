"""Median over the closes of: the join's last rows.out of the window less
the window at the join (first wm.in past the window's start)."""
from harness import stats


def read(run):
    from arroyo_tpu.obs import trace
    join = [t["node"] for t in run["tasks"] if t["stage"] == "join"]
    if not hasattr(trace, "crossings") or not join:
        return None
    starts, opened = [c["ws"] for c in run["closes"]], int(run["window"]["opened"] * 1e9)
    left = {tid: at for tid, at, _ in trace.stamps("rows.out", join[0], t0=opened)}
    at_join = trace.crossings("wm.in", join[0], [ws + 1 for ws in starts], t0=opened)
    return stats.median([(left[ws] - a) / 1e6 for ws, a in zip(starts, at_join)
                         if a is not None and ws in left])
