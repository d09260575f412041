"""Where the benchmark and the repo are, for the harness's own tests."""

import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
