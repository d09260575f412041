"""(window, value) rows the distinct split's first-level aggregates closed
for every event of the window: the counter ``arroyo_worker_distinct_pairs``,
which every ``task.account`` mark of a program that has it carries as
``distinct_pairs``, differenced over the window and summed over the
first-level aggregates (the distinct tables together), over the window's
events. The state count(DISTINCT) keeps where a count keeps one row a key.
A reading of the deployment's shape like ``close_rows_per_event``, ~0.25
where a 100,000-event window holds 9-11k bidders and 14-15k auctions: its
direction says nothing of speed; a change that drops pairs moves it, and
``correct`` with it. A program without the counter (its marks carry no such
field) gives None, and the line leaves the metric out."""


def read(run):
    from arroyo_tpu.obs import trace

    account_over = getattr(trace, "account_over", None)
    window = run.get("window") or {}
    events = window.get("events") or 0
    aggs = [t["node"] for t in run.get("tasks") or () if t.get("first_level")]
    if account_over is None or not aggs or events <= 0:
        return None
    edges = int(window["opened"] * 1e9), int(window["closed"] * 1e9)
    accounts = [account_over(node, *edges) for node in aggs]
    pairs = [a["distinct_pairs"] for a in accounts if a and "distinct_pairs" in a]
    return sum(pairs) / events if pairs else None
