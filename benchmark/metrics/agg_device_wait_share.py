"""Share of the window the busiest aggregate task spent off its CPU waiting
for the device (arroyo_worker_device_wait_seconds, from its task.account
marks): under 2% where nothing waits for the device; on a mesh whose queue
is never empty, most of the task's wall. The task is chosen as
agg_starved_share chooses it."""


def read(run):
    from arroyo_tpu.obs import trace
    aggs = [t for t in run["tasks"] if t["stage"] == "aggregate"]
    if not hasattr(trace, "account_over") or not aggs:
        return None
    w, node = run["window"], max(aggs, key=lambda t: t["self_time_s"])["node"]
    a = trace.account_over(node, int(w["opened"] * 1e9), int(w["closed"] * 1e9))
    return 100.0 * a["device_wait"] / a["wall"] if a and a["wall"] > 0 else None
