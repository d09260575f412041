"""Median over the closes of: the window at the join (first wm.in past the
window's start) less its closing watermark at the last first-level
aggregate: the aggregates' holds and the hops between them."""
from harness import stats


def read(run):
    from arroyo_tpu.obs import trace
    first = [t["node"] for t in run["tasks"] if t["first_level"]]
    join = [t["node"] for t in run["tasks"] if t["stage"] == "join"]
    if not hasattr(trace, "crossings") or not first or not join:
        return None
    width, opened = run["config"]["window"]["width_micros"], int(run["window"]["opened"] * 1e9)
    starts = [c["ws"] for c in run["closes"]]
    reach = trace.crossings("wm.in", first, [ws + width for ws in starts], t0=opened)
    at_join = trace.crossings("wm.in", join, [ws + 1 for ws in starts], t0=opened)
    return stats.median([(b - a) / 1e6 for a, b in zip(reach, at_join) if None not in (a, b)])
