"""Waits for the device that outlasted a second inside the window (expect 0):
the program's device.stall marks, one a flagged wait, written by its watch
thread while the wait still lasted, with what everything else was doing."""
from harness import readers_stall


def read(run):
    return readers_stall.stalls(run)
