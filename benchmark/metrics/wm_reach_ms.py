"""Median over the closes of: the window's closing watermark at the last of
the first-level aggregates (first wm.in at or past the window's end) less
the due time of the window's last event. Source, watermark generator,
prefix and the queues between them."""
from harness import stats


def read(run):
    from arroyo_tpu.obs import trace
    first = [t["node"] for t in run["tasks"] if t["first_level"]]
    if not hasattr(trace, "crossings") or not first:
        return None
    width, opened = run["config"]["window"]["width_micros"], int(run["window"]["opened"] * 1e9)
    reach = trace.crossings("wm.in", first, [c["ws"] + width for c in run["closes"]], t0=opened)
    return stats.median([t / 1e6 - c["due"] * 1e3
                         for t, c in zip(reach, run["closes"]) if t is not None])
