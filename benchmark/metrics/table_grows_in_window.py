"""Times a slot table ran out of regions and doubled inside the window
(expect 0: a growth holds its aggregate task for seconds and compiles a
dozen programs): the program's counter arroyo_worker_table_grows, summed
over the aggregate tasks, as their task.account marks difference it over
the window."""


def read(run):
    from arroyo_tpu.obs import trace
    aggs = [t["node"] for t in run["tasks"] if t["stage"] == "aggregate"]
    if not hasattr(trace, "account_over") or not aggs:
        return None
    w = run["window"]
    accounts = [trace.account_over(node, int(w["opened"] * 1e9), int(w["closed"] * 1e9))
                for node in aggs]
    grows = [a["table_grows"] for a in accounts if a and "table_grows" in a]
    return float(sum(grows)) if grows else None
