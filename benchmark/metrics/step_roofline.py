"""The scatter step against its roofline: the least time the chip could
take for the bytes the traced window's steps had to move (harness/roofline.py,
from their shapes) over the device time they took. Bytes-bound: the peak is
HBM bandwidth."""
from harness import readers, roofline


def read(run):
    p = readers.program(run, "jit_step")
    if not p or not run["peaks"] or p["seconds"] <= 0:
        return None
    moved = sum(s["steps"] * roofline.step_bytes(s["batch_rows"], s["acc_kinds"], s["acc_dtypes"])
                for s in run["steps"])
    return 100.0 * (moved / run["peaks"]["hbm_bytes_per_s"]) / p["seconds"]
