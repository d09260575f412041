"""How full the fullest slot table got in the window: over the window's
closes, the most slots live at a close's dispatch (the moment a table is
fullest, before the closing bins give their regions back) over the table's
capacity then. The two numbers are the program's table gauges
(arroyo_worker_table_live_slots / arroyo_worker_table_capacity) as each
agg.close span carries them (args live, cap). Near 100% the next window's
keys make the table grow again, inside the window."""


def read(run):
    from arroyo_tpu.obs import trace
    w = run["window"]
    if not hasattr(trace, "spans"):
        return None
    shares = [100.0 * s.args["live"] / s.args["cap"]
              for s in trace.spans("agg.close", int(w["opened"] * 1e9), int(w["closed"] * 1e9))
              if s.args and s.args.get("cap")]
    return max(shares) if shares else None
