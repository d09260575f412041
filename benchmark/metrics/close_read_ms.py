"""Window close by the program's own span: median agg.close (extract_start
entered -> rows on the host). close_fetch_ms's twin from inside."""
from harness import stats


def read(run):
    from arroyo_tpu.obs import trace
    w = run["window"]
    if not hasattr(trace, "spans"):
        return None
    got = trace.spans("agg.close", int(w["opened"] * 1e9), int(w["closed"] * 1e9))
    return stats.median([(s.t1_ns - s.t0_ns) / 1e6 for s in got])
