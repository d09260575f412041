"""The longest wait for the device that overlaps the window, of any task: the
longest agg.fetch / agg.drain / join.fetch record (a close's rows, a forced
drain, a join's probe). Beside close_max_ms, the harness's stamp of the worst
close from outside: this is the part of it the device kept the task waiting."""
from harness import readers_stall


def read(run):
    return readers_stall.wait_max_ms(run)
