"""The yardstick: everything a run is measured and judged by lives here,
and imports nothing of the program except in ``runner.py`` (which drives
it) and ``probes.py`` (which wraps its calls from outside)."""
