"""Self-time of the aggregate tasks that are not first-level (the window's
max over the per-auction counts: one key a bin, fed every row a first-level
close emits) per row they received in the span."""
from harness import readers


def read(run):
    ts = [t for t in readers.tasks(run, "aggregate") if not t["first_level"]]
    rows = sum(t["rows_in"] for t in ts)
    if not ts or rows <= 0:
        return None
    return sum(t["self_time_s"] for t in ts) / rows * 1e6
