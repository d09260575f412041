"""The sharded aggregate's step against its roofline: the least time the
cell's chips could take for the bytes the traced window's steps had to move
(harness/roofline_mesh.py: from the rows and the accumulator lanes of the
window's agg.dispatch spans, not from any padded shape) over the device time
jit_local_step took on them. The steps on the device in the traced seconds
were dispatched before them (the host runs ahead of a mesh that sets the
pace), so the trace is given its share of the whole window's bytes: bytes a
second over the window, times the traced seconds. Bytes-bound: the peak is
HBM bandwidth, of every chip; the program's seconds are chip-seconds."""
from harness import readers, roofline_mesh


def read(run):
    p = readers.program(run, "jit_local_step")
    steps = roofline_mesh.mesh_steps(run)
    w = run["window"]
    if not p or not run["peaks"] or p["seconds"] <= 0 or not steps or w["seconds"] <= 0:
        return None
    moved = sum(roofline_mesh.step_bytes(s.args["rows"], s.args.get("lane_bytes", 0))
                for s in steps)
    in_trace = moved / w["seconds"] * run["devtrace"]["window_s"]
    return 100.0 * (in_trace / run["peaks"]["hbm_bytes_per_s"]) / p["seconds"]
