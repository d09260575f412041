"""Share of the window the busiest aggregate task spent off its CPU waiting
on an empty inbox (arroyo_worker_inbox_wait_seconds, from its task.account
marks): with agg_busy_share, what the thread that feeds the chip does."""


def read(run):
    from arroyo_tpu.obs import trace
    aggs = [t for t in run["tasks"] if t["stage"] == "aggregate"]
    if not hasattr(trace, "account_over") or not aggs:
        return None
    w, node = run["window"], max(aggs, key=lambda t: t["self_time_s"])["node"]
    a = trace.account_over(node, int(w["opened"] * 1e9), int(w["closed"] * 1e9))
    return 100.0 * a["inbox_wait"] / a["wall"] if a and a["wall"] > 0 else None
