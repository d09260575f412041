"""Mean over the source tasks of the share of the window spent waiting in
TaskInbox.put for room downstream (arroyo_worker_put_wait_seconds): near 0
the generator sets the pace, high and back-pressure does."""


def read(run):
    from arroyo_tpu.obs import trace
    w = run["window"]
    if not hasattr(trace, "account_over"):
        return None
    accts = [trace.account_over(t["node"], int(w["opened"] * 1e9), int(w["closed"] * 1e9))
             for t in run["tasks"] if t["stage"] == "source"]
    shares = [100.0 * a["put_wait"] / a["wall"] for a in accts if a and a["wall"] > 0]
    return sum(shares) / len(shares) if shares else None
