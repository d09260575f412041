"""How late the generator ran: median over the window's batches of handed
over - due on the connector's schedule. Negative: ahead of the schedule."""
from harness import stats


def read(run):
    return stats.median(run["gen_late_ms"])
