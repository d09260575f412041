"""How late the generator ran, by the program's own spans: median over the
window's batches of source.emit's start less due_ns, the schedule's due
time of the batch's first event. gen_late_ms's twin from inside."""
from harness import stats


def read(run):
    from arroyo_tpu.obs import trace
    w = run["window"]
    if not hasattr(trace, "spans"):
        return None
    got = trace.spans("source.emit", int(w["opened"] * 1e9), int(w["closed"] * 1e9))
    return stats.median([(s.t0_ns - s.args["due_ns"]) / 1e6
                         for s in got if "due_ns" in (s.args or {})])
