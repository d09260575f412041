"""Wall the aggregate tasks' threads spent feeding their key-skew sketches
(``TaskMetrics.observe_keys``: in the run loop, outside every hook, so in no
self-time) per event of the span: the ``sketch`` field of the time account,
as the tasks' task.account marks difference it over the window, summed as
agg_cpu_us_per_event sums CPU. Nothing where the account has no such field."""


def read(run):
    from arroyo_tpu.obs import trace
    aggs = [t["node"] for t in run["tasks"] if t["stage"] == "aggregate"]
    events = run["span"]["events"]
    if not hasattr(trace, "account_over") or not aggs or events <= 0:
        return None
    w = run["window"]
    accounts = [trace.account_over(node, int(w["opened"] * 1e9), int(w["closed"] * 1e9))
                for node in aggs]
    spent = [a["sketch"] for a in accounts if a and "sketch" in a]
    return sum(spent) / events * 1e6 if spent else None
