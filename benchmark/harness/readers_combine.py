"""What the readers of the pane combine share: a sliding aggregate closes a
window by combining the ``width / slide`` bins it holds on the host, and the
program records each such close as an ``agg.combine`` span on the
aggregate's own thread (args ``bins``: bins that fed it, ``rows_in``: rows
concatenated, ``rows``: rows the window emits) and adds the rows up in the
counters ``arroyo_worker_window_rows_combined`` / ``_emitted``, which every
``task.account`` mark carries. Beside readers.py, which no PR edits. A
program without the span (the name is not in its ``SPAN_NAMES``) gives None
and the line leaves the metric out."""

from __future__ import annotations

from typing import Optional


def _edges(run: dict) -> tuple:
    w = run["window"]
    return int(w["opened"] * 1e9), int(w["closed"] * 1e9)


def combines(run: dict) -> Optional[list]:
    """The ``agg.combine`` spans that overlap the measured window."""
    from arroyo_tpu.obs import trace

    if "agg.combine" not in getattr(trace, "SPAN_NAMES", ()):
        return None
    return trace.spans("agg.combine", *_edges(run))


def fullest_share(run: dict) -> Optional[float]:
    """Share of the window that the first-level aggregate task which
    combined the most rows spent inside ``agg.combine``, each span cut by
    the window's edges."""
    spans = combines(run)
    t0, t1 = _edges(run)
    if not spans or t1 <= t0:
        return None
    by_node: dict = {}
    for s in spans:
        by_node.setdefault(s.node, []).append(s)
    fullest = max(by_node.values(),
                  key=lambda ss: sum((s.args or {}).get("rows_in", 0) for s in ss))
    inside = sum(min(s.t1_ns, t1) - max(s.t0_ns, t0) for s in fullest)
    return 100.0 * inside / (t1 - t0)


def rows_emitted_per_event(run: dict) -> Optional[float]:
    """Rows the first-level aggregates' closes emitted in the window (the
    counter ``arroyo_worker_window_rows_emitted``, differenced by their
    ``task.account`` marks) over the events their scans read meanwhile: each
    such aggregate is fed by one scan of the window's events."""
    from arroyo_tpu.obs import trace

    aggs = [t["node"] for t in run["tasks"] if t.get("first_level")]
    events = run["window"]["events"]
    if not hasattr(trace, "account_over") or not aggs or events <= 0:
        return None
    accounts = [trace.account_over(node, *_edges(run)) for node in aggs]
    emitted = [a["window_rows_emitted"] for a in accounts
               if a and "window_rows_emitted" in a]
    return sum(emitted) / (len(emitted) * events) if emitted else None
