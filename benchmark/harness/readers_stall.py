"""What the readers of the device's waits share: the program's watch thread
(arroyo_tpu/obs/trace.py) writes a ``device.stall`` mark when a wait for the
device has been open for a second and a ``watch.tick`` mark once a second
with how late it woke; every wait for the device that lasted a millisecond
is an ``agg.fetch`` / ``agg.drain`` / ``join.fetch`` record. Beside
readers.py, which no PR edits. A program without the watch (the names are
not in its ``SPAN_NAMES``) gives None and the line leaves the metric out; a
program with it gives a number, 0.0 where nothing was long."""

from __future__ import annotations

from typing import Optional

DEVICE_WAITS = ("agg.fetch", "agg.drain", "join.fetch")


def _window_spans(run: dict, name: str) -> Optional[list]:
    """The ``name`` records that overlap the measured window; None where
    the program does not record under that name."""
    from arroyo_tpu.obs import trace

    if name not in getattr(trace, "SPAN_NAMES", ()):
        return None
    w = run["window"]
    return trace.spans(name, int(w["opened"] * 1e9), int(w["closed"] * 1e9))


def stalls(run: dict) -> Optional[float]:
    """Waits for the device the watch flagged inside the window."""
    marks = _window_spans(run, "device.stall")
    return None if marks is None else float(len(marks))


def wait_max_ms(run: dict) -> Optional[float]:
    """The longest wait for the device that overlaps the window, any task."""
    if _window_spans(run, "join.fetch") is None:
        return None
    waits = [s for name in DEVICE_WAITS for s in _window_spans(run, name)]
    return max(((s.t1_ns - s.t0_ns) / 1e6 for s in waits), default=0.0)


def watch_late_max_ms(run: dict) -> Optional[float]:
    """The latest the watch thread woke inside the window: the process's
    pulse, a thread that needs only the interpreter lock and a CPU."""
    ticks = _window_spans(run, "watch.tick")
    if ticks is None:
        return None
    return max((s.args["late_max_ms"] for s in ticks), default=0.0)
