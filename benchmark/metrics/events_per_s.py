"""Source events per second of the measured window, boundary to boundary."""


def read(run):
    w = run["window"]
    if run["traffic"]["event_rate"] or w["seconds"] <= 0:
        return None
    return w["events"] / w["seconds"]
