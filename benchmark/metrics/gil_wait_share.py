"""Over the prefix and aggregate tasks: the share of their hooks' wall time
in which the thread neither ran nor waited for anything named (hook wall -
hook CPU - waits in hooks for room downstream and for the device): the
interpreter lock and the scheduler."""


def read(run):
    from arroyo_tpu.obs import trace
    w = run["window"]
    if not hasattr(trace, "account_over"):
        return None
    a = [trace.account_over(t["node"], int(w["opened"] * 1e9), int(w["closed"] * 1e9))
         for t in run["tasks"] if t["stage"] in ("prefix", "aggregate")]
    wall = sum(x["self_time"] for x in a if x)
    held = sum(x["self_time"] - x["self_cpu"] - x["put_wait_in_hook"]
               - x["device_wait_in_hook"] for x in a if x)
    return 100.0 * held / wall if wall > 0 else None
