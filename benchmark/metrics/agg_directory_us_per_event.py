"""Wall time inside the program's own agg.directory spans (ops/slot_agg.py),
summed over the aggregates, per event of the measured window."""


def read(run):
    from arroyo_tpu.obs import trace
    w = run["window"]
    if not hasattr(trace, "spans") or w["events"] <= 0:
        return None
    got = trace.spans("agg.directory", int(w["opened"] * 1e9), int(w["closed"] * 1e9))
    return sum(s.t1_ns - s.t0_ns for s in got) / 1e3 / w["events"] if got else None
