"""Share of the window's closes that left their operator on the completion
wake and not at its next input or a forced drain: the program's counters
arroyo_worker_closes_on_wake / _on_input, summed over the window tasks. The
guard that the one-slide hold does not come back unseen."""


def read(run):
    woke = sum(t.get("closes_on_wake", 0) for t in run["tasks"])
    waited = sum(t.get("closes_on_input", 0) for t in run["tasks"])
    if woke + waited <= 0:
        return None
    return 100.0 * woke / (woke + waited)
