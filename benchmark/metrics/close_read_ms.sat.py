"""The close read of the fullest aggregate in a saturated cell, by the row:
milliseconds of agg.close span (extract_start entered -> the window's rows
on the host) for each 10,000 rows read, over the window's closes. The
fullest aggregate is the task whose closes read the most rows (the span's
rows arg): q7's per-auction aggregate, not a mean over it and the one-key
global maximum beside it."""
from harness import readers_fullest


def read(run):
    return readers_fullest.ms_per_10k_rows(run, "agg.close")
