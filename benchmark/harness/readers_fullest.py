"""A span of the fullest aggregate alone. Beside readers.py, which no PR
edits: the program's agg.close and agg.snapshot spans carry the rows they
read (arg ``rows``), so a reader can tell q7's ~80k-key per-auction
aggregate from the one-key aggregate beside it."""

from __future__ import annotations

from typing import Optional


def ms_per_10k_rows(run: dict, name: str) -> Optional[float]:
    """Milliseconds the ``name`` spans of the window took for each 10,000
    rows they read (all their time over all their rows), of the task whose
    spans read the most rows; None where the program records no ``rows``.

    By the row and not a median of lengths: a window holds three or four
    closes and five snapshots, and a snapshot reads anything from no rows
    to a whole window's, by where the checkpoint falls in the window."""
    from arroyo_tpu.obs import trace

    if not hasattr(trace, "spans"):
        return None
    w = run["window"]
    by_node: dict = {}
    for s in trace.spans(name, int(w["opened"] * 1e9), int(w["closed"] * 1e9)):
        if s.args and "rows" in s.args:
            by_node.setdefault(s.node, []).append(s)
    if not by_node:
        return None
    fullest = max(by_node.values(), key=lambda spans: max(s.args["rows"] for s in spans))
    rows = sum(s.args["rows"] for s in fullest)
    if not rows:
        return None
    return sum(s.t1_ns - s.t0_ns for s in fullest) / 1e6 / rows * 1e4
