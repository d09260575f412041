"""The harness's own tests run on the CPU: ``python -m pytest benchmark/tests``
from the root of the repo. They are not part of the repo's tier-1 suite."""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
for p in (os.path.dirname(BENCH), BENCH, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
