"""Share of the window that the window-function task spent inside
``wf.rank`` (the one that ranked the most rows, where a plan has several),
each span cut by the window's edges, as ``pane_combine_share`` is built: the
part of one thread's time that goes into ranking what a close hands it. A
program without the span (the name is not in its ``SPAN_NAMES``, as the
parent's is not) gives None, and the line leaves the metric out."""


def read(run):
    from arroyo_tpu.obs import trace

    window = run.get("window") or {}
    if "wf.rank" not in getattr(trace, "SPAN_NAMES", ()) or "opened" not in window:
        return None
    t0, t1 = int(window["opened"] * 1e9), int(window["closed"] * 1e9)
    by_node: dict = {}
    for s in trace.spans("wf.rank", t0, t1):
        by_node.setdefault(s.node, []).append(s)
    if not by_node or t1 <= t0:
        return None
    busiest = max(by_node.values(),
                  key=lambda ss: sum((s.args or {}).get("rows_in", 0) for s in ss))
    inside = sum(min(s.t1_ns, t1) - max(s.t0_ns, t0) for s in busiest)
    return 100.0 * inside / (t1 - t0)
