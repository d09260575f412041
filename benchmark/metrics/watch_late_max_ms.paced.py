"""The latest the program's watch thread woke from its 100 ms sleep inside
the window (the worst late_max_ms of its watch.tick marks): one hand-over of
the interpreter lock is up to 5 ms; a process held off its CPUs, or a thread
that keeps the lock, shows here as long as it lasted."""
from harness import readers_stall


def read(run):
    return readers_stall.watch_late_max_ms(run)
