"""What the benchmark sees of the running program, taken from outside:
wrappers laid around the program's own calls for the length of one run,
and a sink of the benchmark's own. Nothing here changes what the program
computes; every wrapper calls the original and stamps ``time.monotonic()``.

  SourceLog     each batch a scan of the stream hands to the engine: when,
                and how far the scan has got; the schedule's origin as the
                running source holds it
  SinkProbe     the sink the SQL names: result batches with their arrival
  SlotWatch     the window aggregators' calls (the slot aggregate and the
                sharded one): steps dispatched, rows spilled to the host
                store or left in a shard's spill buffer, close dispatched
                -> rows on the host
  HookWatch     the hook each task's thread is inside, and since when
  CompileLog    every backend compile jax makes, with when it ended, and
                whether one is running
"""

from __future__ import annotations

import collections
import contextlib
import sys
import threading
import time
from typing import Optional

import numpy as np

SINK_CONNECTOR = "bench_probe"


# ------------------------------------------------------------------ source


class SourceLog:
    """One scan of the stream. ``origin`` is the source's own ``started``:
    the connector holds event i of a paced stream to ``origin + i / rate``."""

    def __init__(self, node_id: str):
        self.node_id = node_id
        self.origin: Optional[float] = None
        self.origin_exact = False
        self.sent = 0                       # events handed over so far
        self.t: list[float] = []            # when each batch was handed over
        self.first: list[int] = []          # event number the batch starts at

    def crossing(self, event_number: int) -> Optional[float]:
        """When the scan handed over the batch that took it to or past
        ``event_number`` events."""
        i = int(np.searchsorted(np.asarray(self.first), event_number, side="left"))
        # batch i-1 starts before the boundary and ends at first[i] (or at
        # `sent` for the newest batch): it is the one that reaches it
        if i == 0:
            return None if event_number > 0 else (self.t[0] if self.t else None)
        end = self.first[i] if i < len(self.first) else self.sent
        return self.t[i - 1] if end >= event_number else None


class _CollectorProbe:
    """Stands in for the collector inside ``NexmarkSource.run``."""

    def __init__(self, inner, log: SourceLog, entered: float, annotate):
        self._inner = inner
        self._log = log
        self._entered = entered
        self._annotate = annotate

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def collect(self, batch):
        log = self._log
        if log.origin is None:
            # the schedule's origin is a local of the running source; read
            # it there, and fall back to when run() was entered
            started = sys._getframe(1).f_locals.get("started")
            log.origin_exact = isinstance(started, float)
            log.origin = started if log.origin_exact else self._entered
        log.t.append(time.monotonic())
        log.first.append(log.sent)
        log.sent += batch.num_rows
        with self._annotate("emit"):
            self._inner.collect(batch)


@contextlib.contextmanager
def source_probe(annotate):
    """-> {node_id: SourceLog}, filled while nexmark sources run."""
    from arroyo_tpu.connectors.nexmark import NexmarkSource

    logs: dict[str, SourceLog] = {}
    run, generate = NexmarkSource.run, NexmarkSource._generate

    def probed_run(self, sctx, collector):
        node = sctx.ctx.task_info.node_id
        log = logs.setdefault(node, SourceLog(node))
        return run(self, sctx, _CollectorProbe(collector, log, time.monotonic(), annotate))

    def probed_generate(self, numbers):
        with annotate("generate"):
            return generate(self, numbers)

    NexmarkSource.run = probed_run
    NexmarkSource._generate = probed_generate
    try:
        yield logs
    finally:
        NexmarkSource.run = run
        NexmarkSource._generate = generate


# -------------------------------------------------------------------- sink


class SinkProbe:
    """Result batches as they reach the sink, stamped on arrival. One per
    run; the engine builds the operator, which finds this by ``install``."""

    def __init__(self):
        self.arrivals: list[tuple[float, object]] = []
        self._lock = threading.Lock()

    def install(self) -> None:
        from arroyo_tpu.connectors import register_sink
        from arroyo_tpu.operators.base import Operator

        probe = self

        class ProbeSink(Operator):
            def __init__(self, cfg: dict):
                pass

            def process_batch(self, batch, ctx, collector, input_index=0):
                with probe._lock:
                    probe.arrivals.append((time.monotonic(), batch))

        register_sink(SINK_CONNECTOR)(ProbeSink)

    def snapshot(self) -> list[tuple[float, object]]:
        with self._lock:
            return list(self.arrivals)

    def windows_seen(self, column: str) -> dict[int, float]:
        """window start (micros) -> arrival of its newest row so far."""
        out: dict[int, float] = {}
        for t, batch in self.snapshot():
            for ws in np.unique(np.asarray(batch[column]).astype(np.int64)).tolist():
                out[ws] = max(t, out.get(ws, t))
        return out


# ------------------------------------------------------- first-level taps


def tap_collector(task, into: list) -> None:
    """Keep a reference to every batch the task emits (its collector's
    ``collect``), in ``into``. Laid on one task object before it starts."""
    collect = task.collector.collect

    def tapped(batch, *args, **kw):
        into.append(batch)
        return collect(batch, *args, **kw)

    task.collector.collect = tapped


# ------------------------------------------------------- window aggregates


class SlotWatch:
    def __init__(self):
        self.step_times: list[tuple[float, int]] = []  # (when, id(aggregator))
        self.spills: list[int] = []             # rows per spilled batch
        self.closes: list[tuple[float, float]] = []  # dispatched, rows on host
        self.aggregators: dict[int, object] = {}
        self.landed: set[int] = set()  # id(aggregator) of every close whose rows are in
        # (id(aggregator), "ingest" | "close" | "fetch" | "snapshot") -> calls
        self.calls: collections.Counter = collections.Counter()
        # id(sharded aggregator) -> the most rows seen in its spill buffers
        self.mesh_overflow: dict[int, int] = {}

    def spilled_rows(self) -> int:
        """Rows that left the tables they belong in: a slot aggregate's
        batches to the host store, and the most a sharded aggregate held in
        its per-shard spill buffers (residency, so a high-water mark: a sum
        over the closes would count a row that stays once a close)."""
        return int(sum(self.spills)) + sum(self.mesh_overflow.values())


@contextlib.contextmanager
def slot_watch(annotate):
    """The calls of both window aggregators, ``SlotAggregator`` (one chip)
    and ``ShardedAggregator`` (``device.mesh-devices`` > 1), into one
    ``SlotWatch``: an aggregate of either class is a step per device
    program, a close from its dispatch to its rows on the host, a snapshot."""
    from arroyo_tpu.ops.slot_agg import SlotAggregator, SlotExtractHandle
    from arroyo_tpu.parallel.sharded_agg import ShardedAggregator, _ReadyHandle

    seen = SlotWatch()
    laid = []

    def lay(owner, name, wrapper_of):
        original = getattr(owner, name)
        laid.append((owner, name, original))
        setattr(owner, name, wrapper_of(original))

    def span(agg: int, name: str):
        seen.calls[agg, name] += 1
        return annotate(name)

    def step(update):
        def stepped(self, *args):
            seen.aggregators.setdefault(id(self), self)
            seen.step_times.append((time.monotonic(), id(self)))
            with span(id(self), "ingest"):
                return update(self, *args)
        return stepped

    def spill(spill_update):
        def spilled(self, keys_i64, bins_i64, vals):
            seen.spills.append(len(keys_i64))
            return spill_update(self, keys_i64, bins_i64, vals)
        return spilled

    def close(extract):
        def extract_start(self, emit_lo, emit_hi, free_below):
            t0 = time.monotonic()
            with span(id(self), "close"):
                handle = extract(self, emit_lo, emit_hi, free_below)
            handle._bench_dispatched = (t0, id(self))
            return handle
        return extract_start

    def fetch(result):
        def handle_result(self):
            t0, agg = getattr(self, "_bench_dispatched", (None, None))
            with span(agg, "fetch"):
                out = result(self)
            if t0 is not None:
                seen.closes.append((t0, time.monotonic()))
                seen.landed.add(agg)
            return out
        return handle_result

    def snap(snapshot):
        def agg_snapshot(self):
            with span(id(self), "snapshot"):
                return snapshot(self)
        return agg_snapshot

    def mesh_snap(snapshot):
        annotated = snap(snapshot)

        def agg_snapshot(self):
            out = annotated(self)
            overflowed(self, self.overflow_rows)  # the buffers' fill, just read
            return out
        return agg_snapshot

    def overflowed(agg, rows: int) -> None:
        seen.mesh_overflow[id(agg)] = max(seen.mesh_overflow.get(id(agg), 0), int(rows))

    def drain(drain_spill):
        def drained(self, emit_lo, emit_hi, free_below):
            out = drain_spill(self, emit_lo, emit_hi, free_below)
            # what the buffers held as the close found them: the rows it
            # emitted from them and the rows it left
            overflowed(self, len(out[0]) + self.overflow_rows)
            return out
        return drained

    lay(SlotAggregator, "_update_chunk", step)
    lay(SlotAggregator, "_spill_update", spill)
    lay(SlotAggregator, "extract_start", close)
    lay(SlotAggregator, "snapshot", snap)
    lay(SlotExtractHandle, "result", fetch)
    # the sharded close gathers inside extract_start and hands back a handle
    # that is ready at once (ROADMAP A5): its dispatch -> result says so
    lay(ShardedAggregator, "update_sharded", step)
    lay(ShardedAggregator, "_drain_spill", drain)
    lay(ShardedAggregator, "extract_start", close)
    lay(ShardedAggregator, "snapshot", mesh_snap)
    lay(_ReadyHandle, "result", fetch)
    try:
        yield seen
    finally:
        for owner, name, original in reversed(laid):
            setattr(owner, name, original)


# ------------------------------------------------------------------ hooks


class HookWatch:
    """The program sums a hook's wall time into its task's self-time when
    the hook returns (``obs/profile.py`` ``begin``/``end``), so a sample
    taken from outside counts a hook that straddles it whole, on the side
    it ends on: a saturated aggregate then reads busy more than all of a
    short window. This keeps the start of the hook each task is inside, so
    that a sample can add the part of it that lies behind."""

    _ENDING = object()

    def __init__(self):
        self.inside: dict[int, object] = {}  # id(task metrics) -> perf_counter at begin

    def self_time(self, metrics) -> float:
        """The task's self-time up to now, the running hook's part so far
        with it. Read without a lock: again, if the hook ended meanwhile."""
        key = id(metrics)
        while True:
            began = self.inside.get(key)
            if began is self._ENDING:
                time.sleep(0)  # let the task's thread finish the sum
                continue
            total = sum(metrics.self_time.values())
            if self.inside.get(key) is began:
                return total + (0.0 if began is None else time.perf_counter() - began)


@contextlib.contextmanager
def hook_watch():
    from arroyo_tpu.obs.profile import TaskProfiler

    seen = HookWatch()
    inside, ending = seen.inside, HookWatch._ENDING
    begin, end = TaskProfiler.begin, TaskProfiler.end

    def probed_begin(self):
        t0 = begin(self)
        inside.setdefault(id(self.metrics), t0[0])
        return t0

    def probed_end(self, category, t0):
        key = id(self.metrics)
        outermost = inside.get(key) is t0[0]
        if outermost:
            inside[key] = ending  # the sum is about to change: no sample now
        end(self, category, t0)
        if outermost:
            del inside[key]

    TaskProfiler.begin, TaskProfiler.end = probed_begin, probed_end
    try:
        yield seen
    finally:
        TaskProfiler.begin, TaskProfiler.end = begin, end


CLOSE_READ_BUCKETS = (1, 2, 4, 8, 16)


def warms_by_closing(agg) -> bool:
    """A sharded aggregate: the harness has no warm-up of its own for it
    (``warm_close_reads`` names the slot aggregate's programs, and the
    close-read warm-up already exists twice: ROADMAP C10). Its one extract
    program has run once its first close has landed; whatever else it
    compiles later shows in ``compiles_in_window``."""
    from arroyo_tpu.parallel.sharded_agg import ShardedAggregator

    return isinstance(agg, ShardedAggregator)


def warm_close_reads(agg) -> int:
    """Compile, on arrays of the state's own shapes, every close-read
    program the slot aggregate can meet later: ``_read_regions`` buckets the
    regions of one read to a power of two up to 16, reads with and without
    clearing, and ``clear`` alone. Which bucket a close needs depends on
    how many keys and bins it covers, so the stream's first windows do not
    meet them all; met inside the measured window, one costs a compile
    there. Returns the programs run; the aggregate's own state is not
    touched."""
    import jax
    import jax.numpy as jnp

    def fresh():
        return tuple(jnp.full(agg.cap, 0, dtype=d) for d in agg.acc_dtypes)

    n = 0
    for k in CLOSE_READ_BUCKETS:
        if k * agg.region_size > agg.cap:
            break
        bases = np.zeros(k, dtype=np.int64)
        for do_clear in (True, False):
            jax.block_until_ready(agg._read_multi(k, do_clear)(fresh(), bases))
            n += 1
    jax.block_until_ready(agg._clear(fresh(), np.int64(0)))
    return n + 1


# ------------------------------------------------------------- compile log

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CompileLog:
    """Backend compiles (a load from the persistent cache counts: it is a
    program the process met for the first time) and cache hits."""

    def __init__(self):
        self.compiles: list[tuple[float, str, float]] = []  # ended, fun, seconds
        self.begun: list[float] = []  # when each began (appends are atomic, += is not)
        self.cache_hits: list[float] = []

    def install(self) -> None:
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_scalar_listener(self._scalar)

    def _scalar(self, event: str, _value, **_kw) -> None:
        # jax records the event's start as a scalar when the compile begins
        # and its duration when it ends, failed or not
        if event == _BACKEND_COMPILE:
            self.begun.append(time.monotonic())

    def running(self) -> bool:
        """A backend compile has begun, on whatever thread, and not ended."""
        return len(self.begun) > len(self.compiles)

    def activity(self) -> int:
        """Goes up when a compile begins and when one ends."""
        return len(self.begun) + len(self.compiles)

    def _duration(self, event: str, seconds: float, **kw) -> None:
        if event == _BACKEND_COMPILE:
            self.compiles.append((time.monotonic(), str(kw.get("fun_name")), seconds))

    def _event(self, event: str, **_kw) -> None:
        if event == _CACHE_HIT:
            self.cache_hits.append(time.monotonic())

    def between(self, t0: float, t1: float) -> list[tuple[float, str, float]]:
        return [c for c in self.compiles if t0 <= c[0] <= t1]


# -------------------------------------------------------------- annotation


def annotator(trace: bool):
    """Host spans in the profiler's own trace, in a traced run; nothing at
    all otherwise."""
    if not trace:
        return lambda _name: contextlib.nullcontext()
    import jax.profiler

    return jax.profiler.TraceAnnotation
