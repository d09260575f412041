#!/usr/bin/env python3
"""The control of "how correct is decided": one cell at its own size with
one guarantee broken underneath (``breaks.py``), run like the benchmark.

    python3 benchmark/tests/control.py --break lossy_ingest \
        --workload q7-sat --seed 5 --seconds 10

The last line is the benchmark's own; it has to say ``"correct": false``
(true for ``--break none``). The benchmark's own runs never run this."""

import argparse
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
for p in (os.path.dirname(BENCH), BENCH, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)


def main() -> int:
    t_start = time.monotonic()
    ap = argparse.ArgumentParser()
    ap.add_argument("--break", dest="broken", required=True)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import breaks
    from harness import runner

    with breaks.BREAKS[args.broken]():
        return runner.main(args.workload, args.seed, args.seconds, False,
                           args.rehearse, t_start)


if __name__ == "__main__":
    sys.exit(main())
