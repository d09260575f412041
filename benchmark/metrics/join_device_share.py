"""Share of the window's joins (windows with both sides present) whose
probe ran on the device: the program's counters
arroyo_worker_join_probes_device / _host of the join tasks, as their
task.account marks difference them over the window. Expect 100: a side
under device.join-min-rows sends a window's probe to numpy on the join's
own thread, which nothing else shows."""


def read(run):
    from arroyo_tpu.obs import trace
    joins = [t["node"] for t in run["tasks"] if t["stage"] == "join"]
    if not joins:
        return None
    w = run["window"]
    accounts = [trace.account_over(node, int(w["opened"] * 1e9), int(w["closed"] * 1e9))
                for node in joins]
    device = sum(a.get("join_probes_device", 0) for a in accounts if a)
    host = sum(a.get("join_probes_host", 0) for a in accounts if a)
    if device + host <= 0:
        return None
    return 100.0 * device / (device + host)
