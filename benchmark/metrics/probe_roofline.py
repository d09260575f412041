"""The windowed join's probe against its roofline: the least time the chip
could take for the bytes the traced probes had to move
(harness/roofline_probe.py, from the bucket pairs the window's join.probe
spans carry: one pair in every window once the build side has passed 4,096
rows, so their mean, times jit_probe's runs in the trace) over the device
time they took. Bytes-bound: the peak is HBM bandwidth; a sort and two
searches do more than their least bytes, so the share is small."""
from harness import readers, readers_join, roofline_probe


def read(run):
    p = readers.program(run, "jit_probe")
    on_device = [s for s in readers_join.probes(run) if s.args["on"] == "device"]
    if not p or not run["peaks"] or p["seconds"] <= 0 or not on_device:
        return None
    per_probe = sum(roofline_probe.probe_bytes(s.args["l_cap"], s.args["r_cap"])
                    for s in on_device) / len(on_device)
    return 100.0 * (p["runs"] * per_probe / run["peaks"]["hbm_bytes_per_s"]) / p["seconds"]
