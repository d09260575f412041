"""Rows the window-function tasks took in for every event of the window:
the counter ``arroyo_worker_window_fn_rows_in``, which every
``task.account`` mark of a program that has it carries as
``window_fn_rows_in``, differenced over the window and summed over the
window-function tasks, over the window's events. What a close hands the
ranking: ~3.9 where a 600,000-event window of 74-89k auctions closes every
20,000 events and five rows leave. A reading of the deployment's shape like
``close_rows_per_event``: its direction says nothing of speed; a change
that drops rows moves it, and ``correct`` with it. A program without the
counter (its marks carry no such field, as the parent's do not) gives None,
and the line leaves the metric out."""


def read(run):
    from arroyo_tpu.obs import trace

    account_over = getattr(trace, "account_over", None)
    window = run.get("window") or {}
    events = window.get("events") or 0
    ranked = [t["node"] for t in run.get("tasks") or () if t.get("op") == "window_function"]
    if account_over is None or not ranked or events <= 0:
        return None
    edges = int(window["opened"] * 1e9), int(window["closed"] * 1e9)
    accounts = [account_over(node, *edges) for node in ranked]
    rows = [a["window_fn_rows_in"] for a in accounts if a and "window_fn_rows_in" in a]
    return sum(rows) / events if rows else None
