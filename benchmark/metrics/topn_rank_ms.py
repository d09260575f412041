"""The ranking of one window by the SQL window function, by the program's
own span: median over the window's closes of ``wf.rank`` (a bucket's rows
cut to each partition's first N in the ORDER BY order, or all of them
ordered where the plan hands down no limit, and made into the output
columns, on the window-function task's own thread), every window-function
task's buckets together. A program without the span (the name is not in its
``SPAN_NAMES``, as the parent's is not) gives None, and the line leaves the
metric out."""
from harness import stats


def read(run):
    from arroyo_tpu.obs import trace

    window = run.get("window") or {}
    if "wf.rank" not in getattr(trace, "SPAN_NAMES", ()) or "opened" not in window:
        return None
    spans = trace.spans("wf.rank", int(window["opened"] * 1e9), int(window["closed"] * 1e9))
    return stats.median([(s.t1_ns - s.t0_ns) / 1e6 for s in spans])
