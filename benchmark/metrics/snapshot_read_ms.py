"""The checkpoint's read of the fullest aggregate, by the row: milliseconds
of agg.snapshot span (every live slot read to the host, on the task's
thread, while the barrier waits) for each 10,000 rows read, over the
window's checkpoints. By the row because a snapshot's size follows the
checkpoint's phase in the window, from no rows to a whole window's. The
fullest aggregate is the task whose snapshots read the most rows (the
span's rows arg)."""
from harness import readers_fullest


def read(run):
    return readers_fullest.ms_per_10k_rows(run, "agg.snapshot")
