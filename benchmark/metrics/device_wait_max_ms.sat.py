"""The longest wait for the device that overlaps the window, of any task: the
longest agg.fetch / agg.drain / join.fetch record (a close's rows, a
snapshot's read, a forced drain, a join's probe)."""
from harness import readers_stall


def read(run):
    return readers_stall.wait_max_ms(run)
