"""Layered configuration.

Mirrors the reference's figment-style loader
(crates/arroyo-rpc/src/config.rs:29-92: compiled default.toml -> config files
-> env overrides) with Python's tomllib and ``ARROYO_TPU__SECTION__KEY``
environment variables. Defaults mirror crates/arroyo-rpc/default.toml.
"""

from __future__ import annotations

import contextlib
import copy
import os
import threading
import tomllib
from typing import Any

_DEFAULTS: dict[str, Any] = {
    "pipeline": {
        "source-batch-size": 512,  # default.toml: rows per source flush
        "source-batch-linger-ms": 100,
        "update-aggregate-flush-interval-ms": 1000,
        "allowed-restarts": 20,
        "healthy-duration-ms": 120_000,
        "worker-heartbeat-timeout-ms": 30_000,
        "default-checkpoint-interval-ms": 10_000,
        "chaining": {"enabled": False},
        "compaction": {"enabled": False, "checkpoints-to-compact": 4},
    },
    "worker": {
        "queue-size": 8192,  # rows of in-flight budget per input edge
        "task-slots": 16,
    },
    "engine": {
        # adaptive micro-batch coalescing on the emission path: sub-threshold
        # output batches accumulate in the collector (and, cross-worker, as
        # framed bytes in the data plane's send buffer) until a row/byte/time
        # limit trips or a signal (watermark/barrier/stop/EOF) flushes them.
        # Signals ALWAYS flush first, so ordering, barrier alignment, and
        # byte-exact checkpoint recovery are untouched by coalescing.
        "coalesce": {
            "enabled": True,
            "max-rows": 4096,      # flush once this many rows are pending
            "max-bytes": 1_048_576,  # ... or this many (approximate) bytes
            "max-delay-ms": 5,     # ... or the oldest pending row is this old
        },
    },
    "segment": {
        # whole-segment XLA compilation (engine/segment.py): chained runs
        # marked compilable at plan time trace into ONE jitted call per
        # micro-batch. A segment that fails to trace — or whose first-batch
        # verification is not bit-identical to the interpreted path — falls
        # back per segment with a SEGMENT_FALLBACK event, never a failure.
        "compile": {
            "enabled": True,
            # process-wide LRU of compiled (segment, schema) entries;
            # schema/parallelism changes key new entries rather than
            # mis-executing stale traces
            "cache-max": 32,
            # batches below this many rows (input, or survivors of the
            # hoisted leading filter) run interpreted: measured on the
            # 2-core CPU box, the jit dispatch + XLA call overhead beats
            # per-op numpy only from ~8k rows up (a 4096-row A/B lost 7%).
            # Both paths are verified interchangeable per batch, so mixing
            # by size is free; TPU deployments that stage full device
            # batches can lower this.
            "min-rows": 8192,
        },
    },
    "device": {
        # TPU runtime knobs (no reference equivalent; this is the jax backend)
        "enabled": True,  # lower window aggregates to jax when possible
        "batch-capacity": 8192,  # padded device batch size (rows)
        # slots the keyed HBM state table STARTS with: a slot table that
        # runs out of regions doubles itself (ops/slot_agg.py _grow), up to
        # a share of the device's memory
        "table-capacity": 65536,
        # the most linear-probing rounds a step of the device hash table
        # runs (ops/aggregate.py probe_merge leaves its loop when no row is
        # left unplaced: a bound, not the rounds run)
        "max-probes": 64,
        "emit-capacity": 8192,  # padded rows per window-close extraction
        "region-size": 2048,  # slots a (bin) region of the slot table holds
        "spill-capacity": 2048,  # mesh aggregator: overflow rows per step
        # > 1: window aggregates shard their key space over this many
        # devices (parallel/sharded_agg.py); 0 = one chip
        "mesh-devices": 0,
        # windowed joins smaller than join-min-rows (either side) probe on
        # the host; force-device-join sends them to the device even where
        # jax runs on a CPU (tests, chip_smoke.py)
        "join-min-rows": 2048,
        "force-device-join": False,
    },
    "checkpoint": {
        "storage-url": "/tmp/arroyo-tpu/checkpoints",
        "interval-ms": 10_000,
        "file-format": "parquet",  # or "npz" (also the fallback without pyarrow)
        # stuck-checkpoint watchdog: a triggered epoch not globally durable
        # within this window is declared failed, its torn shards subsumed,
        # and the checkpoint retried; after max-consecutive-failures the
        # worker set is restored from the last complete checkpoint. 0 = off.
        "timeout-ms": 600_000,
        "max-consecutive-failures": 3,
        # controller-driven GC: compact + drop old checkpoints every N
        # completed epochs (never past the newest complete one). 0 = off.
        "compaction": {"epochs": 0},
    },
    "state": {
        # tiered state backend (state/spill.py): keep the hot working set
        # in memory and spill cold hash-range partitions as parquet runs
        # (bloom filter + min/max zone maps per run) to checkpoint storage
        # once a subtask's resident state passes the budget. Off by
        # default: operators fall back to fully-resident state.
        "spill": {
            "enabled": False,
            # per-subtask resident-state budget, measured with the same
            # estimator that feeds the arroyo_state_bytes gauges
            "budget-bytes": 64 * 1024 * 1024,
            # hash-range partitions per subtask (rounded up to a power of
            # two); the spill/eviction granularity
            "partition-count": 16,
            # split spilled runs into files of roughly this size; also the
            # compaction output granularity
            "target-file-bytes": 4 * 1024 * 1024,
            # generations per partition before an online compaction merges
            # them (bounds probe read amplification)
            "max-runs": 4,
            # after a spill, keep shrinking until resident state is at or
            # below budget * headroom (a low-water mark, so every breach
            # does not trigger a new spill immediately)
            "headroom": 0.75,
        },
        # checkpoint-artifact checksum verification (state/tables.py,
        # state/integrity.py): "restore" verifies envelopes only on the
        # restore path (the read that matters for correctness), "always"
        # also verifies hot reads (spill probes, compaction inputs),
        # "off" trusts storage end to end
        "integrity": {"verify": "restore"},
    },
    "storage": {
        # shared resilience layer (utils/retry.py) for object-store ops
        "retry": {
            "max-attempts": 4,
            "base-delay-ms": 50,
            "max-delay-ms": 2000,
            "multiplier": 2.0,
            "jitter": 0.5,
        },
        # unset: 8 MiB, and parts of max(threshold, S3's 5 MiB minimum)
        # (state/storage.py)
        "multipart-threshold-bytes": None,
        "multipart-part-size-bytes": None,
    },
    "native": {"enabled": True},  # false: numpy fallbacks, no cpp/ build
    "compiler": {
        "endpoint": None,  # unset: UDFs build in-process
        "artifacts-url": None,  # unset: <checkpoint.storage-url>/udf-artifacts
    },
    "node": {"id": None},  # unset: a fresh node_<uuid> per NodeServer
    "kubernetes-scheduler": {
        "namespace": "arroyo-tpu",
        "image": "arroyo-tpu:latest",
        "controller-url": "http://arroyo-api:5115",
        "worker-env": {},
        "pod-startup-timeout-s": 120,
    },
    "testing": {
        # smoke harness only: per-line source delay, and the mid-stream
        # gate that holds a single_file source until N epochs completed
        "source-read-delay-micros": 0,
        "source-gate-epochs": 0,
    },
    "faults": {
        # deterministic fault injection (arroyo_tpu.faults); empty = off.
        # e.g. "storage.put:fail_once@epoch=2,worker:crash@barrier=3"
        "plan": "",
        "seed": 0,
    },
    "controller": {
        "scheduler": "embedded",
        # size of each job's worker set (start_workers); >1 enables the
        # controller-owned cross-worker checkpoint coordination
        "workers-per-job": 1,
    },
    "fleet": {
        # multi-tenant shared worker pool (controller/fleet.py). A job's
        # slot demand is max(n_workers, parallelism) — one slot per
        # parallel pipeline lane, at least one per worker process. 0 =
        # UNLIMITED synthetic pool: admission always grants and the whole
        # fleet layer is pass-through (the single-tenant default). The
        # node scheduler derives capacity from registered node daemons'
        # live /status slots instead when this is 0.
        "slots": 0,
        # deficit-round-robin admission: slot credit added per tenant per
        # dequeue round (larger jobs accumulate credit across rounds, so
        # a many-small-jobs tenant cannot starve a few-big-jobs tenant)
        "drr-quantum": 1,
        # deterministic (no jitter) exponential backoff after a placement
        # rejection (node 409 / injected admission fault): the job re-
        # queues at the head of its tenant's queue but is ineligible for
        # base * 2^(k-1) seconds after its k-th consecutive rejection
        "requeue-backoff-base-s": 0.5,
        "requeue-backoff-max-s": 30.0,
        # per-job supervision-step budget (ControllerServer.tick): a job
        # whose step overruns it emits JOB_TICK_OVERRUN and is
        # deprioritized (skipped for up to `tick-penalty-max` ticks, then
        # always runs again — never starved). 0 disables the budget.
        "tick-budget-ms": 250,
        "tick-penalty-max": 4,
        "quota": {
            # per-tenant ceilings, applied to EVERY tenant individually
            # (0 = unlimited); override one tenant via
            # fleet.quota.tenants.<name>.max-slots / .max-jobs. A job
            # whose own demand exceeds max-slots is REJECTED (it could
            # never run); a job that merely pushes current usage past the
            # quota QUEUES until a peer finishes.
            "max-slots": 0,
            "max-jobs": 0,
        },
        "autoscale": {
            # fleet-level elasticity: sustained capacity-blocked queue
            # demand (or per-job scale-ups the pool could not place)
            # grows the pool toward demand through the scheduler's
            # provision hook; synthetic pools (embedded/process) apply
            # the new size directly, cluster pools surface it as the
            # arroyo_fleet_target_workers gauge for the node-pool
            # autoscaler to actuate. Same rails as the per-job loop:
            # hysteresis, cooldown, clamped bounds.
            "enabled": False,
            "max-slots": 64,
            "up-ticks": 3,
            "down-ticks": 20,
            "cooldown-s": 15.0,
            # free slots to keep above demand after a resize
            "headroom-slots": 0,
        },
    },
    "profile": {
        # runtime cost attribution (obs/profile.py): per-operator self-time
        # accounting in the task run loop, state-size gauges, key-skew
        # sketches, and the span ring the benchmark's per-layer metrics read.
        # On by default. What it costs on the chip (PERF.md section 6):
        # q7-sat read 9.6% slower with it on at 47k events/s (two pairs,
        # builder PR 25) and x0.66-0.72 of its rate at 190k (off 257-287k,
        # four pairs, builder PR 41). A third of that was the per-batch key
        # sketch, 1.19 us an event on the pace-setting thread, nearly all
        # of it waiting for the interpreter lock its numpy calls let go of;
        # since PR 45 it makes no such call (obs/sketch.py; the account's
        # ``sketch`` says what it takes: 0.24 us an event) and q7-sat reads
        # x0.79 of the rate with profiling off (226k of 282-290k, builder
        # PR 45). What is left is begin()/end(), the spans and the watch
        "enabled": True,
        "sketch": {
            "capacity": 64,      # space-saving summary entries per subtask
            # count 1/N batches; 1 (default) is row-deterministic under
            # replay regardless of coalescing batch boundaries — sampling
            # >1 is cheaper but boundary-sensitive (see obs/sketch.py)
            "sample-every": 1,
            "topk": 5,           # hot keys exported per operator
        },
    },
    "health": {
        # controller-side health monitors (obs/health.py): rules evaluated
        # every supervision tick over the merged job metrics, with
        # hysteresis — fire after fire-ticks consecutive breaching ticks,
        # clear after clear-ticks healthy ones (no flapping on a metric
        # oscillating around its threshold)
        "enabled": True,
        "fire-ticks": 3,
        "clear-ticks": 5,
        "watermark-lag-max-s": 900.0,
        "backpressure-max": 0.9,
        "queue-transit-p99-max-ms": 1000.0,
        "sink-latency-p99-max-s": 600.0,
        "checkpoint-failure-streak": 2,
        # memory pressure: worst subtask's resident state bytes as a
        # fraction of state.spill.budget-bytes (spill keeps it below 1.0;
        # sustained breach means spill is off, failing, or falling behind)
        "memory-pressure-max": 0.9,
    },
    "autoscaler": {
        # elastic autoscaler (controller/autoscaler.py): closes the loop
        # from the health sensors to worker count through the coordinated
        # checkpoint/drain/restore rescale path. Off by default — turning
        # it on hands the parallelism knob to the control loop.
        "enabled": False,
        "min-parallelism": 1,
        "max-parallelism": 8,
        # hysteresis: consecutive pressured ticks before a scale-up /
        # consecutive proven-headroom ticks before a scale-down
        "up-ticks": 3,
        "down-ticks": 10,
        # step sizing: up multiplies (ceil), down halves (floor), always
        # at least one step and always clamped to the bounds above
        "up-factor": 2.0,
        "down-factor": 0.5,
        # scale-up pressure thresholds over the merged metrics snapshot
        "up-backpressure": 0.8,
        "up-queue-transit-p99-ms": 750.0,
        "up-watermark-lag-s": 30.0,
        "up-sink-latency-p99-s": 30.0,
        # scale-down headroom ceilings (worst-subtask busy%, backpressure)
        "down-busy-max-pct": 25.0,
        "down-backpressure-max": 0.1,
        # cooldown after any worker-set (re)start; exponential backoff
        # after a disrupted scale transition
        "cooldown-s": 30.0,
        "backoff-base-s": 10.0,
        "backoff-multiplier": 2.0,
        "backoff-max-s": 300.0,
    },
    "obs": {
        # structured job event log (obs/events.py): bounded per-job ring
        "events": {"max-per-job": 512},
    },
    "logging": {
        # reference [logging] section: console | json | logfmt
        "format": "console",
        "level": "INFO",
        # install the JobEvent bridge handler: stdlib log records carrying
        # job context (extra={"job_id": ...}) land in the job event feed
        "capture-events": False,
    },
    # auth-token unset: the API gates nothing
    "api": {"http-port": 5115, "auth-token": None},
    "admin": {"http-port": 5114},
}


class Config:
    def __init__(self, data: dict[str, Any]):
        self._data = data

    def get(self, path: str, default=None):
        """Dotted-path lookup: config().get("worker.queue-size")."""
        cur: Any = self._data
        for part in path.split("."):
            if not isinstance(cur, dict) or part not in cur:
                return default
            cur = cur[part]
        return cur

    def section(self, name: str) -> dict:
        return self._data.get(name, {})

    def with_overrides(self, overrides: dict[str, Any]) -> "Config":
        data = copy.deepcopy(self._data)
        for path, value in overrides.items():
            _set_path(data, path, value)
        return Config(data)


def _set_path(data: dict, path: str, value):
    parts = path.split(".")
    cur = data
    for p in parts[:-1]:
        cur = cur.setdefault(p, {})
    cur[parts[-1]] = value


def _merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = _merge(out[k], v)
        else:
            out[k] = v
    return out


def _load() -> Config:
    data = copy.deepcopy(_DEFAULTS)
    paths = ["/etc/arroyo-tpu/config.toml",
             os.path.expanduser("~/.config/arroyo-tpu/config.toml"),
             "arroyo-tpu.toml"]
    env_file = os.environ.get("ARROYO_TPU_CONFIG")
    if env_file:
        paths.append(env_file)
    for path in paths:
        if not os.path.exists(path):
            continue
        with open(path, "rb") as f:
            data = _merge(data, tomllib.load(f))
    # ARROYO_TPU__WORKER__QUEUE_SIZE=1024 -> worker.queue-size
    for key, val in os.environ.items():
        if not key.startswith("ARROYO_TPU__"):
            continue
        parts = [p.lower().replace("_", "-") for p in key[len("ARROYO_TPU__"):].split("__")]
        parsed: Any = val
        for conv in (int, float):
            try:
                parsed = conv(val)
                break
            except ValueError:
                continue
        if val.lower() in ("true", "false"):
            parsed = val.lower() == "true"
        _set_path(data, ".".join(parts), parsed)
    return Config(data)


_lock = threading.Lock()
_config: Config | None = None


def config() -> Config:
    global _config
    with _lock:
        if _config is None:
            _config = _load()
        return _config


def update(overrides: dict[str, Any]) -> None:
    """Live-update config (used by tests; reference smoke_tests.rs:46)."""
    global _config
    with _lock:
        base = _config if _config is not None else _load()
        _config = base.with_overrides(overrides)


@contextlib.contextmanager
def scoped(overrides: dict[str, Any]):
    """``update`` for the length of a block, then exactly the config that
    was live before it (a key the block introduced is gone again, not left
    behind as None)."""
    global _config
    before = config()
    update(overrides)
    try:
        yield
    finally:
        with _lock:
            _config = before


def reset() -> None:
    global _config
    with _lock:
        _config = None
