#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process that holds the chip itself: it plans the cell's SQL, runs it
through the engine at shipped defaults, warms up, measures for --seconds,
compares what the sink received with the plain reference, and prints one
JSON object as the last line of its standard output. Without a TPU (or
with fewer chips than the cell asks for) it exits non-zero and prints no
result. ``--rehearse`` drives the same plumbing on the CPU at a tiny size
for the harness's own tests; its line carries no metric.
"""

import os
import sys
import time


def _process_started() -> float:
    """``time.monotonic()`` of the start of this process, interpreter
    start-up included, from /proc; now, where /proc cannot say."""
    now = time.monotonic()
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
        return now - max(0.0, up - ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return now


def main() -> int:
    t_start = _process_started()
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from harness import runner

    return runner.main(args.workload, args.seed, args.seconds, bool(args.trace),
                       args.rehearse, t_start)


if __name__ == "__main__":
    sys.exit(main())
