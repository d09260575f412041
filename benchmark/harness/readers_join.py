"""What the windowed join's readers share: the program's ``join.probe``
spans of the measured window (one per window with both sides present, from
the probe's dispatch to the pairs on the host; args ``left``, ``right``,
``l_cap``, ``r_cap``, ``pairs``, ``on``). Beside readers.py, which no PR
edits. A program without the span gives none, and the readers nothing."""

from __future__ import annotations


def probes(run: dict) -> list:
    from arroyo_tpu.obs import trace

    w = run["window"]
    return trace.spans("join.probe", int(w["opened"] * 1e9), int(w["closed"] * 1e9))
