"""Thread CPU of the aggregate tasks (directory, dispatch, close and their
share of the task loop's hooks) per event of the stream."""
from harness import readers


def read(run):
    ts, events = readers.tasks(run, "aggregate"), run["span"]["events"]
    if not ts or events <= 0:
        return None
    return sum(t["self_cpu_s"] for t in ts) / events * 1e6
