#!/usr/bin/env python3
"""The standing proof that SQL -> planner -> engine -> device state runs on
the chip: one process, the entry points `python -m arroyo_tpu run` uses
(``arroyo_tpu.sql.plan_query`` -> ``arroyo_tpu.engine.Engine``), shipped
``config.py`` defaults, data from the ``nexmark`` connector under ``--seed``,
every result compared row for row with a plain numpy computation over an
engine-free replay of the same generator.

  preflight  platform must be ``tpu``; versions, compile-cache directory and
             the native host library (built from the tracked source) printed
  phase A    the main path at a real size: q7-shaped tumbling max/count,
             4,000,000 events in 40 windows, one mid-stream checkpoint ->
             stop -> restore in a new Engine; exactly-once output
  phase B    every other program the single-chip engine dispatches, each a
             short SQL job: sliding reads, the device join probe, float
             accumulator lanes, the updating aggregate's slot gather, the
             compiled segment
  phase C    with four or more devices: the key-sharded mesh aggregate, at
             shipped defaults (host-bucketed exchange) and fused into the
             compiled segment, each through checkpoint -> restore

Usage:
    python chip_smoke.py [--seed N]       on the chip (one process per chip)
    python chip_smoke.py --rehearse       the same plumbing on the CPU at a
                                          few thousand events (tests use it)

Without ``--rehearse`` a platform other than ``tpu`` is a non-zero exit and
no summary. One JSON line per phase, then the summary as the last line of
stdout; a report is kept under ``chiprun_out/chip_smoke/``.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import functools
import importlib.metadata
import json
import os
import sys
import tempfile
import time
import traceback
from typing import Callable, Optional

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(ROOT, "chiprun_out", "chip_smoke")

WIDTH = 10_000_000  # tumbling / sliding window width, micros
SLIDE = 2_000_000


@dataclasses.dataclass(frozen=True)
class Size:
    """What a run is sized by. ``config`` is laid over the shipped defaults
    for the whole run — empty on the chip."""

    main_events: int         # phase A
    side_events: int         # each phase B job
    mesh_events: int         # each phase C job
    inter_event_micros: int  # event-time step of the generator
    event_rate: int          # events/s of wall time the source holds to; 0 = none
    updating_groups: int     # keys of the non-windowed aggregate
    config: dict


# 10,000 events per second of event time: 100,000 events and ~14k live
# auction keys per 10 s window, inside the shipped 65,536-slot table.
REAL = Size(main_events=4_000_000, side_events=400_000, mesh_events=400_000,
            inter_event_micros=100, event_rate=0, updating_groups=1000, config={})

MESH_CUT = ("4 windows, not 40: the mesh path takes about half a second a step "
            "on four v5e chips (PERF.md, PR 21), and four windows still cross "
            "a checkpoint")

# the same plumbing at a size the CPU backend compiles and runs in seconds:
# 6 windows of 2,000 events; small tables in many small regions so sliding's
# live bins still fit without host spill; the join's device branch forced,
# because on a CPU backend it routes to numpy by itself; the source held to
# a rate so that a stream this short is still running when the checkpoint
# barrier goes in
REHEARSAL = Size(main_events=12_000, side_events=12_000, mesh_events=8_000,
                 inter_event_micros=5_000, event_rate=8_000,
                 updating_groups=50, config={
                     "device.table-capacity": 8192,
                     "device.batch-capacity": 1024,
                     "device.emit-capacity": 1024,
                     "device.spill-capacity": 1024,
                     "device.region-size": 256,
                     "device.max-probes": 32,
                     "device.join-min-rows": 16,
                     "device.force-device-join": True,
                 })


class SmokeFailure(AssertionError):
    """A phase saw something other than what it must see."""


def check(cond: bool, message: str) -> None:
    if not cond:
        raise SmokeFailure(message)


# ------------------------------------------------------------ compile log

_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)
_BACKEND_COMPILE = _COMPILE_EVENTS[2]
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CompileLog:
    """Every trace / lower / compile-or-load jax does, with when it ended,
    so a phase can tell first-call compilation from running. Compile
    seconds are wall seconds during which some thread was compiling: two
    tasks compiling at once are not counted twice."""

    def __init__(self):
        self.durations: list[tuple[float, str, float, str]] = []
        self.cache_hits: list[float] = []

    def install(self) -> None:
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, seconds: float, **kw) -> None:
        if event in _COMPILE_EVENTS:
            self.durations.append(
                (time.monotonic(), event, seconds, str(kw.get("fun_name"))))

    def _event(self, event: str, **_kw) -> None:
        if event == _CACHE_HIT:
            self.cache_hits.append(time.monotonic())

    def between(self, t0: float, t1: float) -> dict:
        ds = [d for d in self.durations if t0 <= d[0] <= t1]
        busy, covered = 0.0, t0
        for end, _event, seconds, _fn in sorted(ds):
            start = max(end - seconds, covered)
            if end > start:
                busy += end - start
                covered = end
        return {
            "programs": sum(1 for d in ds if d[1] == _BACKEND_COMPILE),
            "compile_s": round(busy, 3),
            "cache_hits": sum(1 for t in self.cache_hits if t0 <= t <= t1),
        }

    def programs_after(self, t0: float, t1: float) -> list[str]:
        return [d[3] for d in self.durations
                if d[1] == _BACKEND_COMPILE and t0 < d[0] <= t1]


COMPILES = CompileLog()


# ----------------------------------------------------------------- oracle


def replay(events: int, size: Size, seed: int, columns: list[str]) -> dict:
    """The stream the engine will see, from the generator alone."""
    from arroyo_tpu.batch import TIMESTAMP_FIELD
    from arroyo_tpu.connectors.nexmark import NexmarkSource

    src = NexmarkSource({
        "event_count": events, "inter_event_micros": size.inter_event_micros,
        "first_event_micros": 0, "seed": seed, "include_strings": False,
        "columns": columns})
    b = src._generate(np.arange(events, dtype=np.uint64))
    out = {c: np.asarray(b[c]) for c in columns + ["bid", "auction"]}
    out["ts"] = np.asarray(b[TIMESTAMP_FIELD])
    return out


def group_by(window_start: np.ndarray, key: np.ndarray):
    """-> (window_start, key, inverse) of the distinct (window, key) pairs in
    (window, key) order; window starts may be negative (sliding)."""
    check(bool((key >= 0).all() and (key < 1 << 40).all()), "oracle key range")
    w = window_start // SLIDE
    w0 = int(w.min()) if len(w) else 0
    code, inv = np.unique((w - w0) * (1 << 40) + key, return_inverse=True)
    return ((code >> 40) + w0) * SLIDE, code & ((1 << 40) - 1), inv


def oracle_tumbling(ev: dict, bids_only: bool = True) -> dict:
    bid = ev["bid"] if bids_only else np.ones(len(ev["ts"]), dtype=bool)
    ws, auc, inv = group_by((ev["ts"][bid] // WIDTH) * WIDTH, ev["bid.auction"][bid])
    mx = np.full(len(ws), np.iinfo(np.int64).min, dtype=np.int64)
    np.maximum.at(mx, inv, ev["bid.price"][bid])
    return {"ws": ws, "auction": auc, "mx": mx,
            "cnt": np.bincount(inv, minlength=len(ws)).astype(np.int64)}


def oracle_sliding(ev: dict) -> dict:
    bid = ev["bid"]
    sbin = (ev["ts"][bid] // SLIDE) * SLIDE
    auc = ev["bid.auction"][bid]
    # a bid in slide bin s counts in the windows starting s-(W-S) .. s
    n = WIDTH // SLIDE
    starts = np.concatenate([sbin - k * SLIDE for k in range(n)])
    ws, a, inv = group_by(starts, np.tile(auc, n))
    return {"ws": ws, "auction": a,
            "num": np.bincount(inv, minlength=len(ws)).astype(np.int64)}


def oracle_join(ev: dict) -> dict:
    w = (ev["ts"] // WIDTH) * WIDTH
    bid, auc = ev["bid"], ev["auction"]
    bws, bkey, binv = group_by(w[bid], ev["bid.auction"][bid])
    bids = np.bincount(binv, minlength=len(bws)).astype(np.int64)
    aws, akey, ainv = group_by(w[auc], ev["auction.id"][auc])
    reserve = np.full(len(aws), np.iinfo(np.int64).min, dtype=np.int64)
    np.maximum.at(reserve, ainv, ev["auction.reserve"][auc])
    left = {(int(s), int(k)): int(v) for s, k, v in zip(bws, bkey, bids)}
    rows = sorted((s, k, left[(s, k)], int(r))
                  for s, k, r in zip(aws.tolist(), akey.tolist(), reserve)
                  if (s, k) in left)
    cols = np.array(rows, dtype=np.int64).reshape(-1, 4)
    return {"ws": cols[:, 0], "auction": cols[:, 1], "bids": cols[:, 2],
            "reserve": cols[:, 3]}


def oracle_float(ev: dict) -> dict:
    bid = ev["bid"]
    ws, auc, inv = group_by((ev["ts"][bid] // WIDTH) * WIDTH, ev["bid.auction"][bid])
    price = ev["bid.price"][bid].astype(np.float64)
    # halves of integers: every partial sum is exact in fewer than 40 bits,
    # so neither the order the device adds them in nor its float64 (a pair
    # of float32 on a TPU: ~48 bits, float32's range) can show. `seventh`
    # is the inexact one, held to FLOAT_REL_TOL
    total = np.bincount(inv, weights=price * 0.5, minlength=len(ws))
    cnt = np.bincount(inv, minlength=len(ws))
    return {"ws": ws, "auction": auc, "total": total,
            "mean": np.bincount(inv, weights=price, minlength=len(ws)) / cnt,
            "seventh": np.bincount(inv, weights=price / 7.0, minlength=len(ws))}


def oracle_updating(ev: dict, groups: int) -> dict:
    bid = ev["bid"]
    g = ev["bid.auction"][bid] % groups
    c = np.bincount(g, minlength=groups).astype(np.int64)
    total = np.zeros(groups, dtype=np.int64)
    np.add.at(total, g, ev["bid.price"][bid])
    return {int(k): (int(c[k]), int(total[k])) for k in np.flatnonzero(c)}


# ------------------------------------------------------------------- jobs


def source_ddl(events: int, size: Size, seed: int) -> str:
    # quoted dotted identifiers are the connector's flattened columns;
    # event_count is quoted to keep the connector's option coercion honest
    return f'''CREATE TABLE nexmark (
  "bid" BOOLEAN, "auction" BOOLEAN, "auction.id" BIGINT,
  "auction.reserve" BIGINT, "bid.auction" BIGINT, "bid.price" BIGINT
) WITH (connector = 'nexmark', event_count = '{events}',
  inter_event_micros = {size.inter_event_micros}, first_event_micros = 0,
  event_rate = {size.event_rate}, seed = {seed});
'''


def sink_ddl(columns: str, path: str, fmt: str = "json") -> str:
    # single_file keeps its lines in state and rewrites the file at every
    # checkpoint and at close: the file read back is exactly-once output
    return (f"CREATE TABLE out ({columns}) WITH (connector = 'single_file', "
            f"path = '{path}', format = '{fmt}', type = 'sink');\n")


Q_TUMBLING = '''INSERT INTO out SELECT auction, mx, cnt, window.start FROM (
  SELECT "bid.auction" AS auction, max("bid.price") AS mx, count(*) AS cnt,
    tumble(interval '10 seconds') AS window
  FROM nexmark WHERE "bid" GROUP BY "bid.auction", window);'''
C_TUMBLING = "auction BIGINT, mx BIGINT, cnt BIGINT, ws TIMESTAMP"
# the same aggregate over every event (the other kinds carry auction 0,
# price 0): the fused mesh program refuses a chain with a filter behind its
# first member, and the planner puts WHERE behind the watermark
Q_TUMBLING_UNFILTERED = Q_TUMBLING.replace('WHERE "bid" ', "")

Q_SLIDING = '''INSERT INTO out SELECT auction, num, window.start FROM (
  SELECT "bid.auction" AS auction, count(*) AS num,
    hop(interval '2 seconds', interval '10 seconds') AS window
  FROM nexmark WHERE "bid" GROUP BY "bid.auction", window);'''
C_SLIDING = "auction BIGINT, num BIGINT, ws TIMESTAMP"

Q_JOIN = '''INSERT INTO out SELECT b.auction, b.bids, a.reserve, b.window.start FROM
  (SELECT "bid.auction" AS auction, count(*) AS bids,
     tumble(interval '10 seconds') AS window
   FROM nexmark WHERE "bid" GROUP BY "bid.auction", window) b
JOIN
  (SELECT "auction.id" AS auction, max("auction.reserve") AS reserve,
     tumble(interval '10 seconds') AS window
   FROM nexmark WHERE "auction" GROUP BY "auction.id", window) a
ON b.auction = a.auction AND b.window = a.window;'''
C_JOIN = "auction BIGINT, bids BIGINT, reserve BIGINT, ws TIMESTAMP"

Q_FLOAT = '''INSERT INTO out SELECT auction, total, mean, seventh, window.start FROM (
  SELECT "bid.auction" AS auction,
    sum(CAST("bid.price" AS DOUBLE) * 0.5) AS total,
    avg(CAST("bid.price" AS DOUBLE)) AS mean,
    sum(CAST("bid.price" AS DOUBLE) / 7.0) AS seventh,
    tumble(interval '10 seconds') AS window
  FROM nexmark WHERE "bid" GROUP BY "bid.auction", window);'''
C_FLOAT = "auction BIGINT, total DOUBLE, mean DOUBLE, seventh DOUBLE, ws TIMESTAMP"
# what an inexact float64 sum may be off by, relative: summation order on any
# backend, and on a TPU the float32-pair emulation (measured there: 0.1 + 0.2
# comes back as 0.2999999999999998)
FLOAT_REL_TOL = 1e-12

Q_UPDATING = '''INSERT INTO out SELECT "bid.auction" % {g} AS g, count(*) AS c,
  sum("bid.price") AS total
FROM nexmark WHERE "bid" GROUP BY "bid.auction" % {g};'''
C_UPDATING = "g BIGINT, c BIGINT, total BIGINT"


@dataclasses.dataclass
class Leg:
    """One Engine incarnation, kept for what its operators can tell."""

    engine: object
    t0: float
    t1: float = 0.0
    source_rows: int = 0

    def operators(self, cls) -> list:
        found = []
        for task in self.engine.tasks.values():
            op = task.operator
            for member in getattr(op, "members", None) or [op]:
                if isinstance(member, cls):
                    found.append(member)
        return found


def job_seconds(legs: list["Leg"]) -> float:
    """Wall seconds the engines ran, planning and restore included; the
    oracle and the comparison are the rest of a phase's wall."""
    return round(sum(leg.t1 - leg.t0 for leg in legs), 3)


def _source_rows(engine) -> int:
    return sum(t.metrics.counters["arroyo_worker_messages_sent"]
               for t in engine.source_tasks())


def run_job(sql: str, job_id: str, *, checkpoint_at_rows: Optional[int] = None,
            timeout: float = 900.0) -> list[Leg]:
    """Plan ``sql`` and run it to the end of its stream. With
    ``checkpoint_at_rows``: leg 1 runs until its sources have sent that
    many rows, takes checkpoint 1 and stops; leg 2 is a new Engine restored
    from it (a new plan too, as a restarted worker would make)."""
    from arroyo_tpu.engine import Engine
    from arroyo_tpu.metrics import registry
    from arroyo_tpu.sql import plan_query

    def begin(restore_epoch: Optional[int]) -> Leg:
        # the registry keeps a job's counters across engines: each leg
        # counts its own rows
        registry.clear_job(job_id)
        leg = Leg(Engine(plan_query(sql).graph, job_id=job_id,
                         restore_epoch=restore_epoch), time.monotonic())
        leg.engine.start()
        return leg

    def alive(leg: Leg) -> bool:
        return any(t.thread is not None and t.thread.is_alive()
                   for t in leg.engine.tasks.values())

    def finish(leg: Leg) -> Leg:
        leg.engine.join(timeout=timeout)  # raises what a task raised
        leg.t1 = time.monotonic()
        leg.source_rows = _source_rows(leg.engine)
        return leg

    legs = []
    restore = None
    if checkpoint_at_rows is not None:
        leg = begin(None)
        deadline = time.monotonic() + timeout
        while alive(leg) and _source_rows(leg.engine) < checkpoint_at_rows:
            check(time.monotonic() < deadline, f"{job_id}: timed out")
            time.sleep(0.002)
        done = leg.engine.checkpoint_and_wait(1, timeout=timeout)
        check(done.outcome == "completed",
              f"{job_id}: checkpoint 1 {done.outcome} — the stream ended "
              f"before the barrier, or the barrier stuck: {done!r}")
        leg.engine.stop()
        legs.append(finish(leg))
        restore = 1
    legs.append(finish(begin(restore)))
    return legs


def read_sink(path: str) -> list[dict]:
    with open(path) as f:
        return json.loads("[" + ",".join(line for line in f if line.strip()) + "]")


def columns_of(rows: list[dict], names: list[str]) -> dict:
    """Sink rows as columns in (ws, first key) order; ``ws`` in micros."""
    out = {}
    for n in names:
        vals = [r[n] for r in rows]
        if n == "ws":
            out[n] = np.array(vals, dtype="datetime64[us]").astype(np.int64)
        else:
            out[n] = np.array(vals)
    order = np.lexsort((out[names[0]], out["ws"]))
    return {n: v[order] for n, v in out.items()}


def compare(got: dict, want: dict) -> int:
    """Row for row, duplicates and all; returns rows compared."""
    n_got, n_want = len(got["ws"]), len(want["ws"])
    check(n_got == n_want, f"{n_got} rows out, oracle has {n_want}")
    for name, w in want.items():
        g = got[name]
        if not np.array_equal(g, w):
            bad = np.flatnonzero(g != w)
            i = int(bad[0])
            raise SmokeFailure(
                f"column {name!r}: {len(bad)} of {n_want} rows differ; first at "
                f"row {i}: got {g[i]!r}, oracle row "
                f"{ {k: v[i].item() for k, v in want.items()} }")
    return n_want


# ------------------------------------------------------- operator checks


def check_slot_state(legs: list[Leg], cls, platform: str, seen: SlotWatch) -> dict:
    """From the operators that ran: the one-chip device store, accumulators resident
    on the expected platform, scatter steps compiled, no host spill."""
    from arroyo_tpu.ops.slot_agg import SlotAggregator

    programs = 0
    for leg in legs:
        ops = leg.operators(cls)
        check(bool(ops), f"no {cls.__name__} in the running graph")
        for op in ops:
            agg = getattr(op, "_agg", None) or getattr(op, "_dev", None)
            check(isinstance(agg, SlotAggregator),
                  f"{cls.__name__} holds {type(agg).__name__}, not a SlotAggregator")
            for arr in agg.state:
                plats = {d.platform for d in arr.devices()}
                check(plats == {platform},
                      f"state array lives on {plats}, expected {platform}")
            check(not agg.spill, f"{len(agg.spill)} groups left in the host spill store")
            programs += agg._step._cache_size() + agg._step_merge._cache_size()
    check(programs > 0, "no scatter step was ever compiled")
    check(not seen.spills, f"{len(seen.spills)} batches ({sum(seen.spills)} rows) "
          f"overflowed to the host spill store")
    return {"step_programs": programs, "host_spill_batches": 0}


@dataclasses.dataclass
class SlotWatch:
    spills: list = dataclasses.field(default_factory=list)  # rows per overflowing batch
    closes: list = dataclasses.field(default_factory=list)  # when each close was dispatched


@contextlib.contextmanager
def slot_watch():
    """What the slot aggregate did while a job ran, seen at its own
    methods: batches that overflowed to the host spill store (the store
    empties at window close, so its size at the end says nothing), and the
    moment each window close had been dispatched, its read program
    compiled."""
    from arroyo_tpu.ops.slot_agg import SlotAggregator

    seen = SlotWatch()
    spill, extract = SlotAggregator._spill_update, SlotAggregator.extract_start

    def spill_update(self, keys_i64, bins_i64, vals):
        seen.spills.append(len(keys_i64))
        return spill(self, keys_i64, bins_i64, vals)

    def extract_start(self, emit_lo, emit_hi, free_below):
        handle = extract(self, emit_lo, emit_hi, free_below)
        seen.closes.append(time.monotonic())
        return handle

    SlotAggregator._spill_update = spill_update
    SlotAggregator.extract_start = extract_start
    try:
        yield seen
    finally:
        SlotAggregator._spill_update = spill
        SlotAggregator.extract_start = extract


# ----------------------------------------------------------------- phases


@dataclasses.dataclass
class Ctx:
    size: Size
    seed: int
    platform: str
    workdir: str
    results: list = dataclasses.field(default_factory=list)

    def sink_path(self, name: str) -> str:
        return os.path.join(self.workdir, f"{name}.json")


def phase(ctx: Ctx, label: str, name: str, body: Callable[[Ctx], dict]) -> None:
    t0 = time.monotonic()
    rec = {"phase": label, "name": name, "ok": False}
    try:
        rec.update(body(ctx))
        rec["ok"] = True
    except Exception as e:  # noqa: BLE001 - reported, and fails the run
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()
    t1 = time.monotonic()
    rec.update(COMPILES.between(t0, t1))
    rec["wall_s"] = round(t1 - t0, 3)
    # compilation happens inside the job, on the threads that first call
    rec["run_s"] = round(rec.get("job_s", t1 - t0) - rec["compile_s"], 3)
    ctx.results.append(rec)
    print(json.dumps({k: v for k, v in rec.items() if k != "traceback"}), flush=True)
    if not rec["ok"]:
        print(rec["traceback"], file=sys.stderr, flush=True)


def phase_a(ctx: Ctx) -> dict:
    from arroyo_tpu.windows.tumbling import TumblingAggregate

    events = ctx.size.main_events
    sql = (source_ddl(events, ctx.size, ctx.seed)
           + sink_ddl(C_TUMBLING, ctx.sink_path("a")) + Q_TUMBLING)
    with slot_watch() as seen:
        legs = run_job(sql, "smoke-a", checkpoint_at_rows=events // 2)
    got = columns_of(read_sink(ctx.sink_path("a")), ["auction", "mx", "cnt", "ws"])
    want = oracle_tumbling(replay(events, ctx.size, ctx.seed, ["bid.auction", "bid.price"]))
    rows = compare(got, want)
    windows = len(np.unique(want["ws"]))
    expected = -(-events * ctx.size.inter_event_micros // WIDTH)
    check(windows == expected, f"{windows} windows, expected {expected}")
    leg1, leg2 = legs
    check(0 < leg1.source_rows and 0 < leg2.source_rows < events,
          f"checkpoint was not mid-stream: leg 1 sent {leg1.source_rows} rows, "
          f"leg 2 {leg2.source_rows} of {events}")
    # a recompile per batch would cost seconds on the chip. After the first
    # window closed, leg 1 still meets the close read for a larger
    # region-count bucket (a power of two up to 16; the key set grows from
    # ~6k in the first window to ~15k) and the checkpoint's non-clearing
    # snapshot read — a handful, whatever the stream's length. A restored
    # leg meets nothing new once its merge step has run.
    late = []
    for leg in legs:
        closes = [t for t in seen.closes if leg.t0 <= t <= leg.t1]
        check(bool(closes), "a leg closed no window")
        late.append(COMPILES.programs_after(closes[0], leg.t1))
    check(len(late[0]) <= 4 and not late[1],
          f"programs compiled after the first window closed: {late}")
    out = check_slot_state(legs, TumblingAggregate, ctx.platform, seen)
    out.update(job_s=job_seconds(legs), events=events, windows=windows,
               rows_compared=rows, restored_from_epoch=1, leg1_source_rows=leg1.source_rows,
               leg2_source_rows=leg2.source_rows,
               compiled_after_first_window={"leg1": late[0], "leg2": late[1]})
    return out


def side_job(ctx: Ctx, name: str, query: str, columns: str,
             overrides: Optional[dict] = None,
             fmt: str = "json") -> tuple[list[Leg], list[dict], SlotWatch]:
    from arroyo_tpu import config as cfg

    sql = (source_ddl(ctx.size.side_events, ctx.size, ctx.seed)
           + sink_ddl(columns, ctx.sink_path(name), fmt) + query)
    with cfg.scoped(overrides or {}), slot_watch() as seen:
        legs = run_job(sql, f"smoke-{name}")
    return legs, read_sink(ctx.sink_path(name)), seen


def phase_b_sliding(ctx: Ctx) -> dict:
    from arroyo_tpu.windows.sliding import SlidingAggregate

    legs, rows, seen = side_job(ctx, "b-sliding", Q_SLIDING, C_SLIDING)
    want = oracle_sliding(replay(ctx.size.side_events, ctx.size, ctx.seed, ["bid.auction"]))
    n = compare(columns_of(rows, ["auction", "num", "ws"]), want)
    out = check_slot_state(legs, SlidingAggregate, ctx.platform, seen)
    out.update(job_s=job_seconds(legs), events=ctx.size.side_events,
               windows=len(np.unique(want["ws"])), rows_compared=n)
    return out


def phase_b_join(ctx: Ctx) -> dict:
    from arroyo_tpu.config import config
    from arroyo_tpu.operators.joins import InstantJoin
    from arroyo_tpu.ops import join_probe

    legs, rows, _ = side_job(ctx, "b-join", Q_JOIN, C_JOIN)
    want = oracle_join(replay(ctx.size.side_events, ctx.size, ctx.seed,
                              ["auction.id", "auction.reserve", "bid.auction"]))
    n = compare(columns_of(rows, ["auction", "bids", "reserve", "ws"]), want)
    joins = legs[0].operators(InstantJoin)
    check(bool(joins) and all(j.backend == "jax" for j in joins),
          "the windowed join did not run on the jax backend")
    # the probe program is compiled by the device branch and nothing else
    probes = join_probe._probe_jit()._cache_size()
    check(probes > 0, "device_join_start never ran: every window took the numpy probe")
    return {"job_s": job_seconds(legs), "events": ctx.size.side_events,
            "windows": len(np.unique(want["ws"])),
            "rows_compared": n, "probe_programs": probes,
            "join_min_rows": int(config().get("device.join-min-rows", 2048))}


def phase_b_float(ctx: Ctx) -> dict:
    from arroyo_tpu.windows.tumbling import TumblingAggregate

    legs, rows, seen = side_job(ctx, "b-float", Q_FLOAT, C_FLOAT)
    want = oracle_float(replay(ctx.size.side_events, ctx.size, ctx.seed,
                               ["bid.auction", "bid.price"]))
    got = columns_of(rows, ["auction", "total", "mean", "seventh", "ws"])
    inexact, inexact_want = got.pop("seventh"), want.pop("seventh")
    n = compare(got, want)
    err = float(np.max(np.abs(inexact - inexact_want) / np.abs(inexact_want)))
    check(err <= FLOAT_REL_TOL, f"inexact float sum is off by {err:.3g} relative, "
          f"more than {FLOAT_REL_TOL:g}")
    out = check_slot_state(legs, TumblingAggregate, ctx.platform, seen)
    aggs = [op._agg for op in legs[0].operators(TumblingAggregate)]
    check(any(a._n_flt_lanes for a in aggs), "no float accumulator lane on the device")
    out.update(job_s=job_seconds(legs), events=ctx.size.side_events,
               windows=len(np.unique(want["ws"])), rows_compared=n,
               inexact_sum_max_rel_err=err)
    return out


def phase_b_updating(ctx: Ctx) -> dict:
    from arroyo_tpu.operators.updating_aggregate import UpdatingAggregate

    g = ctx.size.updating_groups
    legs, rows, seen = side_job(ctx, "b-updating", Q_UPDATING.format(g=g),
                                  C_UPDATING, fmt="debezium_json")
    # replay the changelog: a delete must name the row it removes
    table: dict = {}
    for r in rows:
        before, after = r.get("before"), r.get("after")
        if before is not None:
            check(table.pop(before["g"], None) == (before["c"], before["total"]),
                  f"retraction of a row that was not there: {before}")
        if after is not None:
            check(after["g"] not in table, f"append over a live row: {after}")
            table[after["g"]] = (after["c"], after["total"])
    want = oracle_updating(replay(ctx.size.side_events, ctx.size, ctx.seed,
                                  ["bid.auction", "bid.price"]), g)
    check(table == want, f"final table differs from the oracle in "
          f"{len(set(table.items()) ^ set(want.items()))} entries")
    ops = legs[0].operators(UpdatingAggregate)
    check(bool(ops) and all(op.device_mode for op in ops),
          "the updating aggregate did not run in device mode")
    out = check_slot_state(legs, UpdatingAggregate, ctx.platform, seen)
    gathers = sum(op._dev._read_slots.cache_info().currsize for op in ops)
    check(gathers > 0, "the flush's slot gather was never built")
    out.update(job_s=job_seconds(legs), events=ctx.size.side_events,
               changelog_rows=len(rows), rows_compared=len(want),
               gather_programs=gathers)
    return out


def phase_b_segment(ctx: Ctx) -> dict:
    from arroyo_tpu.obs.events import recorder
    from arroyo_tpu.windows.tumbling import TumblingAggregate

    legs, rows, seen = side_job(ctx, "b-segment", Q_TUMBLING, C_TUMBLING, {
        "pipeline.chaining.enabled": True, "segment.compile.min-rows": 1})
    want = oracle_tumbling(replay(ctx.size.side_events, ctx.size, ctx.seed,
                                  ["bid.auction", "bid.price"]))
    n = compare(columns_of(rows, ["auction", "mx", "cnt", "ws"]), want)
    events = recorder.events("smoke-b-segment")
    compiled = [e for e in events if e["code"] == "SEGMENT_COMPILED"]
    fallback = [e for e in events if e["code"] == "SEGMENT_FALLBACK"]
    check(bool(compiled), "no SEGMENT_COMPILED event: the segment never engaged")
    check(not fallback, "SEGMENT_FALLBACK: " + "; ".join(e["message"] for e in fallback))
    out = check_slot_state(legs, TumblingAggregate, ctx.platform, seen)
    out.update(job_s=job_seconds(legs), events=ctx.size.side_events,
               windows=len(np.unique(want["ws"])), rows_compared=n,
               segment_compiled=len(compiled), segment_fallback=0)
    return out


def phase_c(ctx: Ctx, fused: bool) -> dict:
    import jax

    from arroyo_tpu import config as cfg
    from arroyo_tpu.parallel.sharded_agg import (ShardedAggregator, dispatch_counts,
                                                 reset_dispatch_counts)
    from arroyo_tpu.windows.tumbling import TumblingAggregate

    events = ctx.size.mesh_events
    name = "c-fused" if fused else "c-host-exchange"
    overrides = {"device.mesh-devices": 4}
    if fused:
        overrides.update({"pipeline.chaining.enabled": True,
                          "segment.compile.min-rows": 1})
    sql = (source_ddl(events, ctx.size, ctx.seed)
           + sink_ddl(C_TUMBLING, ctx.sink_path(name))
           + (Q_TUMBLING_UNFILTERED if fused else Q_TUMBLING))
    reset_dispatch_counts()
    with cfg.scoped(overrides):
        legs = run_job(sql, f"smoke-{name}", checkpoint_at_rows=events // 2)
    want = oracle_tumbling(replay(events, ctx.size, ctx.seed, ["bid.auction", "bid.price"]),
                           bids_only=not fused)
    n = compare(columns_of(read_sink(ctx.sink_path(name)),
                           ["auction", "mx", "cnt", "ws"]), want)
    homes = set()
    for leg in legs:
        for op in leg.operators(TumblingAggregate):
            check(isinstance(op._agg, ShardedAggregator),
                  f"mesh mode built a {type(op._agg).__name__}")
            for arr in jax.tree.leaves(op._agg.state):
                devs = {s.device for s in arr.addressable_shards}
                check(len(devs) == 4 and {d.platform for d in devs} == {ctx.platform},
                      f"state shards sit on {sorted(map(str, devs))}")
                homes |= devs
    counts = dispatch_counts()
    if fused:
        check(counts["fused_steps"] > 0, f"no fused step ran: {counts}")
    else:
        check(counts["host_steps"] > 0 and counts["fused_steps"] == 0,
              f"host-exchange run dispatched {counts}")
    return {"job_s": job_seconds(legs), "events": events,
            "windows": len(np.unique(want["ws"])),
            "rows_compared": n, "restored_from_epoch": 1, "cut": MESH_CUT,
            "shard_devices": sorted(str(d) for d in homes), "dispatch": counts}


# -------------------------------------------------------------- preflight


def preflight(rehearse: bool) -> dict:
    """Everything the phases stand on, printed before any of them runs.
    Exits here, naming what it found, when the platform is not a TPU."""
    import jax
    import jaxlib

    import arroyo_tpu.ops  # x64 pin + the compile-cache rule  # noqa: F401
    from arroyo_tpu import native

    dev = jax.devices()[0]
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed"
    info = {
        "platform": dev.platform, "device_kind": dev.device_kind,
        "device_count": len(jax.devices()),
        "jax": jax.__version__, "jaxlib": jaxlib.__version__, "libtpu": libtpu,
        "compile_cache_dir": jax.config.jax_compilation_cache_dir,
        "compile_cache_from_env": bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")),
        "rehearsal": rehearse,
    }
    if dev.platform != "tpu" and not rehearse:
        print(f"chip_smoke: no TPU — jax found platform={dev.platform!r} "
              f"({dev.device_kind} x{len(jax.devices())}). This script proves the "
              f"chip path and does not continue on another platform "
              f"(--rehearse drives the plumbing on the CPU).", file=sys.stderr)
        sys.exit(4)
    # a library that cannot be built from the tracked source and loaded is
    # an error here, not a slower host path nobody hears about
    info["native_library"] = os.path.relpath(native.require()._name, ROOT)
    print(json.dumps({"phase": "preflight", **info}), flush=True)
    return info


# ------------------------------------------------------------------- main


def run(rehearse: bool = False, seed: int = 0) -> dict:
    """Preflight and every phase; returns the report (``ok`` says whether
    all of them passed)."""
    import arroyo_tpu
    from arroyo_tpu import config as cfg

    info = preflight(rehearse)
    if rehearse:
        # the CPU backend's cached programs buy a rehearsal nothing, and
        # each load of one logs machine-feature complaints
        import jax

        jax.config.update("jax_enable_compilation_cache", False)
    COMPILES.install()
    arroyo_tpu._load_operators()
    size = REHEARSAL if rehearse else REAL
    t0 = time.monotonic()
    with tempfile.TemporaryDirectory(prefix="chip-smoke-") as workdir, \
            cfg.scoped({**size.config,
                        "checkpoint.storage-url": os.path.join(workdir, "checkpoints")}):
        ctx = Ctx(size, seed, info["platform"], workdir)
        phase(ctx, "A", "tumbling max/count, checkpoint -> restore", phase_a)
        phase(ctx, "B", "sliding count", phase_b_sliding)
        phase(ctx, "B", "windowed join, device probe", phase_b_join)
        phase(ctx, "B", "float sum/avg lanes", phase_b_float)
        phase(ctx, "B", "updating aggregate, slot gather", phase_b_updating)
        phase(ctx, "B", "compiled segment", phase_b_segment)
        if info["device_count"] >= 4:
            phase(ctx, "C", "mesh x4, host-bucketed exchange",
                  functools.partial(phase_c, fused=False))
            phase(ctx, "C", "mesh x4, fused segment",
                  functools.partial(phase_c, fused=True))
    report = {
        "ok": all(r["ok"] for r in ctx.results),
        "preflight": info, "seed": seed, "phases": ctx.results,
        "wall_s": round(time.monotonic() - t0, 3),
        "compile_s": round(sum(r["compile_s"] for r in ctx.results), 3),
        "programs": sum(r["programs"] for r in ctx.results),
        "cache_hits": sum(r["cache_hits"] for r in ctx.results),
        # [function, seconds] of every compile-or-load, in order
        "backend_compiles": [[d[3], round(d[2], 4)] for d in COMPILES.durations
                             if d[1] == _BACKEND_COMPILE],
    }
    os.makedirs(OUT_DIR, exist_ok=True)
    with open(os.path.join(OUT_DIR, "report.json"), "w") as f:
        json.dump(report, f, indent=1)
        f.write("\n")
    return report


def main(argv: Optional[list[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the nexmark generator's random draws")
    ap.add_argument("--rehearse", action="store_true",
                    help="drive the same plumbing on the CPU at a tiny size")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "arroyo_tpu")):
        print("chip_smoke: not in a checkout — arroyo_tpu/ is not beside this "
              "script", file=sys.stderr)
        return 5
    report = run(rehearse=args.rehearse, seed=args.seed)
    print(json.dumps({k: report[k] for k in
                      ("wall_s", "compile_s", "programs", "cache_hits")}), flush=True)
    if not report["ok"]:
        failed = [f"{r['phase']}: {r['name']}" for r in report["phases"] if not r["ok"]]
        print(f"chip_smoke: FAILED — {'; '.join(failed)}", file=sys.stderr)
        return 1
    pf = report["preflight"]
    summary = {"ok": True, "device": {"platform": pf["platform"],
                                      "kind": pf["device_kind"],
                                      "count": pf["device_count"]}}
    if args.rehearse:
        # a rehearsal proves plumbing, never the chip: no "ok" to misread
        summary = {"rehearsal": True, "passed": True, "device": summary["device"]}
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
