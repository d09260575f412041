#!/usr/bin/env python3
"""One paced cell at another rate than its mix states: the tool for the
sweep that finds the rate a configuration sustains, once, on the chip.

    python3 benchmark/tests/sweep.py --workload q7-paced --rate 40000 --seed 5 --seconds 15

Prints one line: the rate, the closes' latencies in order (a backlog shows
as latencies that grow through the run), the mean of their first and last
quarter, their median and 90th percentile, which closes a barrier met, and
how late the generator ran (the harness's stamps and the program's own
``source.emit`` spans). A cell's committed rate is about four fifths of
the highest rate at which the generator stays within a slide of its
schedule and the latencies do not grow."""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
for p in (os.path.dirname(BENCH), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)


def main() -> int:
    t_start = time.monotonic()
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rate", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    from harness import runner, stats
    from harness.cells import Cell

    cell = Cell(args.workload)
    if not cell.traffic["event_rate"]:
        print(f"{args.workload} is not a paced cell", file=sys.stderr)
        return 2
    cell.traffic["event_rate"] = args.rate
    run = runner.Run(cell, args.seed, args.seconds, False, args.rehearse, t_start)
    if args.rehearse:
        run.rate = args.rate
    result = run.execute()
    rec = result["records"]
    lat = [c["latency_ms"] for c in rec["closes"]]
    quarter = max(1, len(lat) // 4)
    print(json.dumps({
        "workload": args.workload, "rate": args.rate, "correct": result["verdict"]["correct"],
        "attempted": result["verdict"]["attempted"], "failed": result["verdict"]["failed"],
        "latencies_ms": [round(x, 1) for x in lat],
        "first_quarter_ms": sum(lat[:quarter]) / quarter if lat else None,
        "last_quarter_ms": sum(lat[-quarter:]) / quarter if lat else None,
        "latency_p50_ms": stats.percentile(lat, 50), "latency_p90_ms": stats.percentile(lat, 90),
        "struck": [i for i, c in enumerate(rec["closes"]) if c["struck"]],
        "latency_struck_ms": stats.median(
            [c["latency_ms"] for c in rec["closes"] if c["struck"]]),
        "checkpoints": rec["epochs"], "trigger_gaps_s": rec["trigger_gaps_s"],
        "gen_late_p50_ms": stats.median(rec["gen_late_ms"]),
        "gen_late_p99_ms": stats.percentile(rec["gen_late_ms"], 99),
        "src_late_ms": cell.reader("src_late_ms")(rec),
        "setup_s": rec["setup_s"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
