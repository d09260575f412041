"""Window close: median of close dispatched (jit go) -> rows on the host."""
from harness import stats


def read(run):
    return stats.median(run["close_fetch_ms"])
