#!/usr/bin/env python
"""Per-operator cost profile of the q7 bench pipeline.

Runs bench.run_config with the runtime profiler (arroyo_tpu/obs/profile.py
— the same attribution `arroyo_tpu explain` renders for live jobs) and
prints the per-operator self-time / busy% / state / hot-key table, so a
perf win can be attributed to the operator that earned it.

`--stages` additionally monkey-patches timing wrappers around the
fine-grained hot-path stages (source generation, slot-aggregate update,
window close dispatch/fetch, emission) for intra-operator drill-down —
the methodology that found round 2's fetch-latency stall. Nested keys
overlap: agg_process_total includes agg_update_chunk, which includes
dir_lookup.

Usage:
    python tools/profile_stages.py [events] [batch_size] [--stages]
    JAX_PLATFORMS=cpu python tools/profile_stages.py 200000

Runs on jax's default platform (the chip where there is one); the host
clock it reads says where host time goes, not what the device costs.
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import arroyo_tpu
from arroyo_tpu import config as cfg


def print_profile(job_id: str) -> None:
    from arroyo_tpu.metrics import registry
    from arroyo_tpu.obs.profile import job_profile

    prof = job_profile(registry.job_metrics(job_id))
    print("\nper-operator cost profile (obs/profile.py):")
    for op, p in sorted(prof.items(),
                        key=lambda kv: -sum((kv[1]["self_time"] or {}).values())):
        st = p.get("self_time") or {}
        cats = "  ".join(f"{c} {v * 1000:9.1f}ms" for c, v in
                         sorted(st.items(), key=lambda kv: -kv[1]) if v)
        line = f"  {op:34s} busy {p.get('busy_pct') or 0:5.1f}%  {cats}"
        if p.get("self_us_per_row") is not None:
            line += f"  {p['self_us_per_row']:.2f}us/row"
        print(line)
        rows = p.get("state_rows") or {}
        if any(rows.values()):
            print("  " + " " * 34 + "state: " + "  ".join(
                f"{t}={rows[t]:,}r/{(p.get('state_bytes') or {}).get(t, 0):,}B"
                for t in sorted(rows)))
        hot = p.get("hot_keys") or []
        if hot:
            print("  " + " " * 34 + "hot:   " + "  ".join(
                f"{e['key'][:8]} {100 * e.get('share', 0):.1f}%"
                for e in hot[:5]))


def main() -> None:
    import bench

    args = [a for a in sys.argv[1:] if a != "--stages"]
    stages = "--stages" in sys.argv[1:]
    events = int(args[0]) if len(args) > 0 else 1_000_000
    batch = int(args[1]) if len(args) > 1 else 32_768

    arroyo_tpu._load_operators()
    cfg.update({
        "pipeline.source-batch-size": batch,
        "pipeline.chaining.enabled": True,
        "device.batch-capacity": batch,
        "device.table-capacity": 65536,
        "device.emit-capacity": 8192,
        "profile.enabled": True,
        "checkpoint.storage-url": "/tmp/arroyo-tpu-bench/checkpoints",
    })

    T: dict[str, float] = {}
    C: dict[str, int] = {}

    def wrap(obj, name, key):
        orig = getattr(obj, name)

        def timed(*a, **k):
            t0 = time.perf_counter()
            r = orig(*a, **k)
            T[key] = T.get(key, 0.0) + (time.perf_counter() - t0)
            C[key] = C.get(key, 0) + 1
            return r

        setattr(obj, name, timed)

    if stages:
        from arroyo_tpu.connectors import nexmark as nx
        from arroyo_tpu.operators import builtin as bi
        from arroyo_tpu.ops import slot_agg as sa
        from arroyo_tpu.windows import tumbling as tw

        wrap(nx.NexmarkSource, "_generate", "source_generate")
        wrap(bi.ValueOperator, "process_batch", "value_op_total")
        wrap(bi.KeyOperator, "process_batch", "key_op_total")
        wrap(tw.TumblingAggregate, "process_batch", "agg_process_total")
        wrap(sa.SlotAggregator, "_update_chunk", "agg_update_chunk")
        wrap(sa.BinSlotDirectory, "lookup_or_assign", "dir_lookup")
        wrap(sa.SlotAggregator, "extract_start", "close_dispatch")
        wrap(sa.SlotExtractHandle, "result", "close_fetch_materialize")
        wrap(tw.TumblingAggregate, "_emit_entries", "emit_entries")

    bench.run_config("q7", bench.build_q7, "jax", 50_000, batch)  # warmup
    T.clear()
    C.clear()
    wall, _rows, _lat, _walls = bench.run_config(
        "q7", bench.build_q7, "jax", events, batch)
    print(f"\n{events} events in {wall:.2f}s = {events / wall:,.0f} ev/s")
    print_profile("bench-q7-jax")
    if stages:
        print("\nfine-grained stage wraps (--stages; nested keys overlap):")
        for k, v in sorted(T.items(), key=lambda kv: -kv[1]):
            print(f"  {k:26s} {v * 1000:8.1f} ms   x{C[k]}")


if __name__ == "__main__":
    main()
