"""Thread CPU of the stateless tasks between source and first aggregate
(watermark, filter, key) per event of the stream: prefix_us_per_event less
its waits for the interpreter lock and for room downstream."""
from harness import readers


def read(run):
    ts, events = readers.tasks(run, "prefix"), run["span"]["events"]
    if not ts or events <= 0:
        return None
    return sum(t["self_cpu_s"] for t in ts) / events * 1e6
