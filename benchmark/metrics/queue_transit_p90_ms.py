"""90th percentile of the time a batch spent in a task's inbox, over all
tasks (arroyo_worker_queue_transit_seconds; bucket upper bound)."""
from harness import stats


def read(run):
    ts = [t for t in run["tasks"] if sum(t["transit_counts"])]
    if not ts:
        return None
    counts = [sum(c) for c in zip(*(t["transit_counts"] for t in ts))]
    q = stats.histogram_quantile(ts[0]["transit_bounds"], counts, 0.9)
    return None if q is None else q * 1e3
