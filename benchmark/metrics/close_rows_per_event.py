"""Result rows a first-level aggregate's closes put out for every event its
scan read in the window (arroyo_worker_window_rows_emitted over the window's
events, the mean over the first-level aggregates). A reading of the
deployment's shape, 8-9 at sixty slides to a window where five slides read
~0.5: a change that drops rows moves it, and ``correct`` with it."""
from harness import readers_combine


def read(run):
    return readers_combine.rows_emitted_per_event(run)
