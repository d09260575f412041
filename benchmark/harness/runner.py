"""One run of one cell: set-up, warm-up, the measured window, the drain,
the comparison with the reference, the result line.

One process, which holds the chip itself. One loop on the main thread does
everything the benchmark does while the engine runs (it triggers the
checkpoints the controller would, watches the scans' progress, opens and
closes the window, starts and stops the profiler): no helper thread
competes with the engine's for the interpreter.

The measured window opens when the slower scan of the stream passes a
window boundary (a multiple of the slide, in events) after warm-up, and
closes on the last such boundary inside ``--seconds``. In a paced cell the
boundaries are points on the connector's own schedule. Warm-up is over
when the mix's events and checkpoints are behind and the compiler is quiet:
the harness aims at the next boundary on a tick with no backend compile
running, and opens there only if none began or ended on the way; else it
aims again. A tree that runs faster than the mix's event count foresaw
opens a slide later instead of compiling inside its window.

A configuration's ``.py`` is its plain reference. It defines
``rows(window)`` (the result rows of one whole window of
``harness.stream.generate``), ``partials(window)`` (what each first-level
aggregate emits for it, sorted rows of key columns then aggregates) and
``ingested(events_sent)`` (the rows a first-level aggregate has received
once its scan has handed over that many events). ``partials`` and
``ingested`` may answer per aggregate, in a dict by what the plan shows the
aggregate is keyed on: the tuple of its ``key_fields``, ``()`` for a global
one. The first references answer ``partials`` by the number of columns a
row has and ``ingested`` with one number for all; both forms are read.

``records`` (what the metric readers in ``benchmark/metrics/`` are given)
is a plain dict:

  cell, config, traffic  the entry of BENCHMARK.json and the two data files
  trace                  bool: a traced run
  seconds                the window asked for
  setup_s                process start -> window opens
  window                 {opened, closed, seconds, events}: boundary to
                         boundary, on time.monotonic()
  span                   {seconds, events}: first loop tick after opening
                         -> the tick that ended the window, each on the
                         clock as the counters were read; what the task
                         counters below are differences over
  period_ms              wall time of one slide at the cell's rate (paced)
  closes                 per due window that reached the sink: {ws, due,
                         arrived, latency_ms, struck} (paced); ``struck``:
                         a barrier (trigger -> metadata durable) overlapped
                         due -> arrived
  gen_late_ms            per batch handed over in the window: sent - due
  tasks                  per task over ``span``: {node, op, stage,
                         first_level, self_time_s (a hook that straddles an
                         edge of the span counts for its part inside),
                         self_cpu_s, rows_in,
                         rows_out, transit_bounds, transit_counts,
                         closes_on_wake, closes_on_input}
  steps                  per window aggregate: {batch_rows, acc_kinds,
                         acc_dtypes, steps (in the traced window, or in the
                         window)}
  close_fetch_ms         close dispatched -> rows on the host, per close
  epochs                 per checkpoint triggered in the window: {epoch,
                         completed, trigger_to_durable_ms, at_s (after the
                         window opened), phase (of the slide, paced)}
  trigger_gaps_s         between all the run's triggers, in order
  compiles_in_window     names of programs compiled inside the window
  opening                the boundary the window opened on, the one first
                         aimed at, and the last compile before it
  device                 {platform, kind, count, memory_peak_bytes (of the
                         fullest of the cell's chips),
                         memory_peak_bytes_per_chip}
  peaks                  the chip's published peaks (harness/peaks.json)
  devtrace               harness.devtrace.reduce() of the traced window
"""

from __future__ import annotations

import gc
import glob
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Optional

import numpy as np

from . import compare, devtrace, probes, roofline, stats, stream
from .cells import BENCH_DIR, ROOT, Cell

TICK_S = 0.1
HOST_SPANS = ("generate", "emit", "ingest", "close", "fetch", "snapshot")
EFFECTIVE_KEYS = ("pipeline.source-batch-size", "worker.queue-size",
                  "pipeline.chaining.enabled", "device.table-capacity",
                  "checkpoint.interval-ms")
STATELESS = {"value", "key", "watermark"}
HOST_DEVICES = "xla_force_host_platform_device_count"


class NoAccelerator(RuntimeError):
    pass


class RunFailed(RuntimeError):
    pass


class UndeclaredSetting(ValueError):
    pass


def _say(obj) -> None:
    print(json.dumps(obj), flush=True)


def _stages(graph) -> dict[str, dict]:
    """node id -> {op, stage, first_level}: ``source``; ``prefix`` for a
    stateless operator with only sources and stateless operators upstream;
    ``aggregate`` (``first_level`` when no aggregate or join is upstream);
    ``join``; ``sink``; ``post`` for the stateless rest."""
    out: dict[str, dict] = {}

    def stateful_upstream(nid: str) -> bool:
        for e in graph.in_edges(nid):
            up = graph.nodes[e.src].op.value
            if up.endswith("_aggregate") or "join" in up or stateful_upstream(e.src):
                return True
        return False

    for nid, node in graph.nodes.items():
        op = node.op.value
        behind = stateful_upstream(nid)
        if op == "source" or op == "sink":
            stage = op
        elif op.endswith("_aggregate"):
            stage = "aggregate"
        elif "join" in op:
            stage = "join"
        elif op in STATELESS and not behind:
            stage = "prefix"
        else:
            stage = "post"
        out[nid] = {"op": op, "stage": stage,
                    "first_level": stage == "aggregate" and not behind}
    return out


def _scan_of(graph, nid: str) -> Optional[str]:
    """The source a first-level aggregate is fed by."""
    while True:
        edges = graph.in_edges(nid)
        if not edges:
            return nid
        if len(edges) > 1:
            return None
        nid = edges[0].src


def _keyed_on(graph, nid: str) -> tuple:
    """What a reference names a first-level aggregate by: the columns the
    plan keys it on, ``()`` for a global one."""
    return tuple(graph.nodes[nid].config.get("key_fields") or ())


def _wrong_partials(ref: dict, tapped: dict[str, Optional[np.ndarray]],
                    keyed_on: dict[str, tuple]) -> list:
    """The first-level aggregates whose rows of one window differ from the
    reference's partial, and the partials no aggregate answered for.
    ``ref`` by key columns (tuples): an aggregate is held to the partial of
    its own key, one the reference does not name is wrong, and so is a
    partial left over. ``ref`` by the number of columns a row has (the first
    references' form): an aggregate is held to the partial of its width."""
    by_key = all(isinstance(k, tuple) for k in ref)
    wrong, answered = [], set()
    for nid, rows in tapped.items():
        if by_key:
            name = keyed_on[nid]
        else:
            name = None if rows is None else rows.shape[1]
        answered.add(name)
        if rows is None or name not in ref or not np.array_equal(rows, ref[name]):
            wrong.append(nid)
    if by_key:
        wrong += [list(k) for k in ref if k not in answered]
    return wrong


NOT_VALUES = {"window_start", "window_end", "_timestamp", "_key"}


def _partial_rows(batches: list, due: set) -> dict[int, np.ndarray]:
    """A first-level aggregate's emitted rows of the due windows: window
    start -> its rows (key columns, then aggregates, as emitted), sorted."""
    parts: dict[int, list[np.ndarray]] = {}
    for b in batches:
        ws = np.asarray(b["window_start"]).astype(np.int64)
        rows = np.column_stack([np.asarray(b[c]).astype(np.int64)
                                for c in b.columns if c not in NOT_VALUES])
        for w in np.unique(ws).tolist():
            if w in due:
                parts.setdefault(w, []).append(rows[ws == w])
    out = {}
    for w, chunks in parts.items():
        rows = np.concatenate(chunks)
        out[w] = rows[np.lexsort(rows.T[::-1])]
    return out


def _declared(settings: dict, defaults) -> dict:
    """A configuration's ``settings`` as they will be scoped: every key a
    dotted path to a value ``defaults`` (the program's ``Config`` of its own
    table of shipped settings) declares. A key it does not, or one that
    names a section, is refused by name: a misspelt key would otherwise run
    the shipped default under the deployment's name."""
    missing = object()
    for key in settings:
        at = defaults.get(key, missing)
        if at is missing or isinstance(at, dict):
            raise UndeclaredSetting(
                f"settings key {key!r} is not a setting arroyo_tpu/config.py declares")
    return dict(settings)


def _memory_peaks(stats: list) -> list[int]:
    """``peak_bytes_in_use`` of each chip's ``memory_stats()``, 0 where the
    backend reports none (the CPU's)."""
    return [int((s or {}).get("peak_bytes_in_use", 0)) for s in stats]


def _task_sample(engine, hooks) -> dict:
    out = {}
    for (nid, _sub), task in engine.tasks.items():
        m = task.metrics
        out[nid] = (hooks.self_time(m), sum(m.self_cpu.values()),
                    m.counters["arroyo_worker_messages_recv"],
                    m.counters["arroyo_worker_messages_sent"],
                    list(m.queue_transit.counts),
                    m.counters.get("arroyo_worker_closes_on_wake", 0),
                    m.counters.get("arroyo_worker_closes_on_input", 0))
    return out


def _operators(engine):
    for task in engine.tasks.values():
        op = task.operator
        yield from (getattr(op, "members", None) or [op])


class Run:
    def __init__(self, cell: Cell, seed: int, seconds: float, trace: bool,
                 rehearse: bool, t_start: float):
        self.cell, self.seed, self.seconds = cell, seed, float(seconds)
        self.trace, self.rehearse, self.t_start = trace, rehearse, t_start
        self.parts: dict[str, float] = {}
        self.rehearsal = {}
        if rehearse:
            with open(os.path.join(BENCH_DIR, "harness", "rehearsal.json")) as f:
                self.rehearsal = json.load(f)
        self.gen_overrides = dict(self.rehearsal.get("generator", {}))
        gen = dict(cell.config["generator"], **self.gen_overrides)
        self.inter = int(gen["inter_event_micros"])
        self.width_events = cell.config["window"]["width_micros"] // self.inter
        self.hop_events = cell.config["window"]["slide_micros"] // self.inter
        self.rate = float(cell.traffic["event_rate"])
        if self.rate and rehearse:
            self.rate = float(self.rehearsal["paced_event_rate"])
        # filled as the run goes
        self.effective: dict = {}
        self.taps: dict[str, list] = {}
        self.opening: Optional[dict] = None
        self.traced_steps: Optional[list] = None
        self.loaded_trace: Optional[dict] = None

    def _setting(self, name: str):
        """A number of the traffic mix, or the rehearsal's in its place."""
        return self.rehearsal.get(name, self.cell.traffic[name])

    # ------------------------------------------------------------ set-up

    def _mark(self, name: str, t0: float) -> float:
        now = time.monotonic()
        self.parts[name] = now - t0
        return now

    def device(self) -> dict:
        import jax

        devs = jax.devices()
        d = devs[0]
        if not self.rehearse and (d.platform != "tpu" or len(devs) < self.cell.chips):
            raise NoAccelerator(
                f"cell {self.cell.name} needs {self.cell.chips} tpu chip(s); jax found "
                f"{len(devs)} device(s) of platform {d.platform!r}")
        return {"platform": d.platform, "kind": d.device_kind,
                "count": self.cell.chips if not self.rehearse else len(devs)}

    def execute(self) -> dict:
        t = self._mark("interpreter_s", self.t_start)
        flags = os.environ.get("XLA_FLAGS", "")
        if self.rehearse and self.cell.chips > 1 and HOST_DEVICES not in flags:
            # a rehearsal of a cell over several chips: as many CPU devices
            os.environ["XLA_FLAGS"] = f"{flags} --{HOST_DEVICES}={self.cell.chips}".strip()
        import arroyo_tpu  # imports jax, places the compile cache
        from arroyo_tpu import config as cfg
        from arroyo_tpu import native

        arroyo_tpu._load_operators()
        t = self._mark("import_s", t)
        settings = _declared(self.cell.config.get("settings", {}), cfg.Config(cfg._DEFAULTS))
        device = self.device()
        t = self._mark("backend_start_s", t)
        native.require()
        t = self._mark("native_library_s", t)
        self.compiles = probes.CompileLog()
        self.compiles.install()
        self.sink = probes.SinkProbe()
        self.sink.install()
        workdir = tempfile.mkdtemp(prefix="arroyo-bench-")
        annotate = probes.annotator(self.trace)
        try:
            # what the deployment fixes, and the rehearsal's size on top of it
            with cfg.scoped(dict(settings, **self.rehearsal.get("config", {}))), \
                    probes.source_probe(annotate) as scans, \
                    probes.slot_watch(annotate) as slots, \
                    probes.hook_watch() as hooks:
                self.scans, self.slots, self.hooks = scans, slots, hooks
                self.effective = {k: cfg.config().get(k) for k in (*EFFECTIVE_KEYS, *settings)}
                return self._drive(device, workdir, t)
        finally:
            shutil.rmtree(workdir, ignore_errors=True)

    # ---------------------------------------------------------- the run

    def _drive(self, device: dict, workdir: str, t: float) -> dict:
        from arroyo_tpu import config as cfg
        from arroyo_tpu.engine import Engine
        from arroyo_tpu.obs.trace import recorder as epochs
        from arroyo_tpu.sql import plan_query

        cell = self.cell
        sql = cell.sql(self.seed, probes.SINK_CONNECTOR, self.rate, self.gen_overrides)
        graph = plan_query(sql).graph
        job = f"bench-{cell.name}"
        engine = Engine(graph, job_id=job, storage_url=os.path.join(workdir, "checkpoints"))
        stages = _stages(engine.graph)
        t = self._mark("plan_and_build_s", t)
        interval = float(cfg.config().get("checkpoint.interval-ms")) / 1e3
        if not engine.tasks:
            engine.build()
        for nid, s in stages.items():
            if s["first_level"]:
                probes.tap_collector(engine.tasks[(nid, 0)], self.taps.setdefault(nid, []))
        engine.start()
        started = time.monotonic()
        try:
            out = self._loop(engine, job, epochs, interval, started, stages)
        finally:
            engine.stop()
            engine.join(timeout=60)  # raises what a task raised: the run fails
        return self._finish(engine, stages, device, out)

    def _durable(self, epochs, job: str, epoch: int) -> Optional[int]:
        for e in epochs.events(job, epoch):
            if e["event"] == "metadata_durable":
                return e["t_us"]
        return None

    def _boundary_time(self, b: int) -> Optional[float]:
        """When the slower scan reached boundary ``b`` (in slides): on the
        schedule in a paced cell, as handed over in a saturated one."""
        n = b * self.hop_events
        if self.rate:
            return max(s.origin for s in self.scans.values()) + n / self.rate
        ts = [s.crossing(n) for s in self.scans.values()]
        return None if any(x is None for x in ts) else max(ts)

    def _loop(self, engine, job, epochs, interval, started, stages) -> dict:
        import jax.profiler

        traffic = self.cell.traffic
        n_scans = sum(1 for s in stages.values() if s["stage"] == "source")
        n_aggs = sum(1 for s in stages.values() if s["stage"] == "aggregate")
        warmed: dict[int, int] = {}
        next_ckpt, epoch = started + interval, 1
        triggers: list[tuple[int, float]] = []
        state, b0, t_open, t_end = "warmup", None, None, None
        aimed_first, aimed_at, aims = None, None, 0
        open_sample = end_sample = None
        open_sent = end_sent = None
        trace_at = trace_until = None
        trace_dir, window_span = None, None
        steps_mark = None
        series = []
        deadline = None
        warm_events = int(self._setting("warmup_events"))
        warm_deadline = float(self._setting("warmup_deadline_seconds"))
        warm_need = int(self._setting("warmup_checkpoints"))
        while True:
            time.sleep(TICK_S)
            now = time.monotonic()
            if any(t.thread is not None and not t.thread.is_alive()
                   for t in engine.tasks.values()):
                raise RunFailed("a task of the engine ended before the run did")
            if now >= next_ckpt and state != "drain":
                engine.trigger_checkpoint(epoch)
                triggers.append((epoch, now))
                epoch += 1
                # as the controller does: the interval runs from the tick that
                # fired, so no two triggers are closer than it
                next_ckpt = now + interval
            ready = len(self.scans) == n_scans and all(
                s.origin is not None for s in self.scans.values())
            sent = min(s.sent for s in self.scans.values()) if ready else 0
            if len(series) < 4096 and (not series or now - series[-1][0] >= 1.0):
                series.append((now, sent))
            if state in ("warmup", "armed") and now - started > warm_deadline:
                raise RunFailed(
                    f"warm-up not over after {warm_deadline:g} s: {sent} events sent, "
                    f"{len(warmed)} of {n_aggs} aggregates seen, checkpoints "
                    f"{[(e, bool(self._durable(epochs, job, e))) for e, _ in triggers]}, "
                    f"aimed at {aims} boundaries, compiles {self.compiles.compiles[-3:]}")
            if state == "warmup":
                cold = [key for key in list(self.slots.aggregators) if key not in warmed]
                ran = False
                for key in cold:
                    agg = self.slots.aggregators[key]
                    if probes.warms_by_closing(agg):
                        if key in self.slots.landed:
                            warmed[key] = 0
                        continue
                    t0, ran = time.monotonic(), True
                    warmed[key] = probes.warm_close_reads(agg)
                    self.parts["warm_close_reads_s"] = (
                        self.parts.get("warm_close_reads_s", 0.0) + time.monotonic() - t0)
                if ran:
                    # this tick's `now` and `sent` predate the programs just
                    # compiled: warm-up ends on a tick that compiled nothing
                    continue
                # read before the look at running(): a compile that begins
                # after either shows as activity at the boundary
                quiet_at = self.compiles.activity()
                if (sent >= warm_events and len(warmed) >= n_aggs
                        and len(triggers) >= warm_need and all(
                            self._durable(epochs, job, e) for e, _ in triggers[:warm_need])
                        and not self.compiles.running()):
                    if self.rate:
                        origin = max(s.origin for s in self.scans.values())
                        b0 = int((now + 0.3 - origin) * self.rate // self.hop_events) + 1
                    else:
                        b0 = sent // self.hop_events + 1
                    gc.collect()
                    gc.freeze()
                    self.parts["warmup_stream_s"] = now - started
                    aimed_at, aims, state = quiet_at, aims + 1, "armed"
                    aimed_first = b0 if aimed_first is None else aimed_first
            elif state == "armed":
                at = self._boundary_time(b0)
                if at is not None and at <= now:
                    if self.compiles.activity() != aimed_at:
                        # a compile began or ended on the way to the boundary:
                        # the window would open on its heels, or on it. Aim again
                        state = "warmup"
                        continue
                    t_open, state = at, "open"
                    self.opening = self._opening(b0, aimed_first, aims, t_open)
                    # the compiler's own seconds inside set-up, loads from the
                    # cache included; side by side on several threads: a sum
                    self.parts["backend_compiles_s"] = sum(
                        c[2] for c in self.compiles.compiles)
                    open_sample, open_sent = self._sample(engine)
                    if self.trace:
                        trace_at = t_open + float(traffic["trace_after_seconds"])
                        trace_until = trace_at + float(self._setting("trace_seconds"))
            elif state == "open":
                if trace_at is not None and now >= trace_at and trace_dir is None:
                    trace_dir = tempfile.mkdtemp(prefix="arroyo-bench-trace-")
                    opts = jax.profiler.ProfileOptions()
                    opts.python_tracer_level = 0
                    opts.host_tracer_level = 1
                    jax.profiler.start_trace(trace_dir, profiler_options=opts)
                    window_span = jax.profiler.TraceAnnotation(devtrace.WINDOW_SPAN)
                    window_span.__enter__()
                    steps_mark = len(self.slots.step_times)
                elif window_span is not None and now >= trace_until:
                    window_span.__exit__(None, None, None)
                    window_span = None
                    self.traced_steps = self.slots.step_times[steps_mark:]
                    jax.profiler.stop_trace()
                if now >= t_open + self.seconds and window_span is None:
                    t_end = now
                    end_sample, end_sent = self._sample(engine)
                    deadline = now + float(traffic["drain_seconds"])
                    state = "drain"
                    b1 = self._last_boundary(b0, t_open)
                    due_ws = [(b * self.hop_events - self.width_events) * self.inter
                              for b in range(b0 + 1, b1 + 1)]
                    in_window = [(e, at) for e, at in triggers
                                 if t_open <= at <= t_open + self.seconds]
            elif state == "drain":
                seen = self.sink.windows_seen(self.cell.config["result"]["window_start"])
                pending = [e for e, _ in in_window if not self._durable(epochs, job, e)]
                # every due window is in; or a later one is, and a due window
                # still out has no rows to come: results leave in window order
                landed = all(ws in seen for ws in due_ws) or (
                    bool(seen) and max(seen) > due_ws[-1])
                if (landed and not pending) or now >= deadline:
                    break
        return {"b0": b0, "b1": b1, "t_open": t_open, "t_end": t_end, "due_ws": due_ws,
                "open_sample": open_sample, "end_sample": end_sample,
                "open_sent": open_sent, "end_sent": end_sent,
                "triggers": in_window, "fired": [at for _, at in triggers],
                "trace_dir": trace_dir, "series": series,
                "drain_s": time.monotonic() - t_end,
                "epochs": {e: (at, self._durable(epochs, job, e)) for e, at in in_window},
                "trigger_wall_us": {e: next((x["t_us"] for x in epochs.events(job, e)
                                             if x["event"] == "trigger"), None)
                                    for e, _ in in_window}}

    def _sample(self, engine) -> tuple:
        """((when, the tasks' counters), events the slower scan has sent),
        all read now: the tick's own clock is older by what the tick has
        done since, which is the profiler's stop in a short traced run."""
        return ((time.monotonic(), _task_sample(engine, self.hooks)),
                min(s.sent for s in self.scans.values()))

    def _opening(self, b0: int, aimed_first: int, aims: int, t_open: float) -> dict:
        last = max(self.compiles.compiles, default=None)
        return {"boundary": b0, "event": b0 * self.hop_events,
                "first_aimed_boundary": aimed_first, "aims": aims,
                "last_compile": None if last is None else {
                    "program": last[1], "seconds": last[2],
                    "ended_s_before_opening": t_open - last[0]}}

    def _last_boundary(self, b0: int, t_open: float) -> int:
        b = b0
        while True:
            at = self._boundary_time(b + 1)
            if at is None or at > t_open + self.seconds + 1e-6:
                return b
            b += 1

    # ------------------------------------------------------- after the run

    def _finish(self, engine, stages, device, out) -> dict:
        import jax

        cell = self.cell
        t_open = out["t_open"]
        t_close = self._boundary_time(out["b1"])
        events = (out["b1"] - out["b0"]) * self.hop_events
        # the fullest of the cell's chips, and each of them
        per_chip = _memory_peaks([d.memory_stats() for d in jax.devices()[:cell.chips]])
        device["memory_peak_bytes"] = max(per_chip)
        device["memory_peak_bytes_per_chip"] = per_chip

        # what the run saw of each guarantee
        keyed_on = {n: _keyed_on(engine.graph, n) for n, s in stages.items() if s["first_level"]}
        ingest = []
        for nid, name in keyed_on.items():
            scan = self.scans.get(_scan_of(engine.graph, nid))
            m = engine.tasks[(nid, 0)].metrics
            if scan is not None:
                expected = cell.reference.ingested(scan.sent)
                if isinstance(expected, dict):
                    # per aggregate; one the reference does not name has none
                    expected = expected.get(name)
                ingest.append({
                    "aggregate": nid, "keyed_on": list(name), "source_events": scan.sent,
                    "rows_received": m.counters["arroyo_worker_messages_recv"],
                    "rows_expected": expected})
        platform = device["platform"]
        off_platform, aggregates = [], []
        for agg in self.slots.aggregators.values():
            # a sharded aggregate's state is a tree of arrays, each over the mesh
            on = {d for arr in jax.tree_util.tree_leaves(agg.state) for d in arr.devices()}
            plats = sorted({d.platform for d in on})
            aggregates.append({"batch_rows": agg.batch_cap, "capacity": agg.cap,
                               "acc_kinds": list(agg.acc_kinds),
                               "acc_dtypes": [str(d) for d in agg.acc_dtypes],
                               "platforms": plats, "devices": len(on), "id": id(agg),
                               "class": type(agg).__name__,
                               # over the whole run, warm-up included
                               "calls": {name: n for (who, name), n in
                                         self.slots.calls.items() if who == id(agg)}})
            if plats != [platform]:
                off_platform.append(plats)
        compiled = [c[1] for c in self.compiles.between(t_open, out["t_end"])]
        guarantees = {
            "checkpoints_triggered": [e for e, _ in out["triggers"]],
            "checkpoints_not_completed": [e for e, (_at, d) in out["epochs"].items() if d is None],
            "ingest": ingest,
            "late_rows": sum(int(getattr(op, "late_rows", 0) or 0) for op in _operators(engine)),
            "spilled_rows": self.slots.spilled_rows(),
            "off_platform": off_platform,
            "short_of_chips": compare.short_of_chips(aggregates, cell.chips),
            "compiles_in_window": compiled,
        }

        # the sink's rows of the due windows, and the reference's
        res = cell.config["result"]
        due = set(out["due_ws"])
        got: dict[int, list[tuple]] = {ws: [] for ws in due}
        arrived: dict[int, float] = {}
        for at, batch in self.sink.snapshot():
            ws_col = np.asarray(batch[res["window_start"]]).astype(np.int64)
            cols = [np.asarray(batch[c]).astype(np.int64).tolist() for c in res["columns"]]
            for i, ws in enumerate(ws_col.tolist()):
                if ws in due:
                    got[ws].append(tuple(c[i] for c in cols))
                    arrived[ws] = max(at, arrived.get(ws, at))
        del engine
        gc.unfreeze()
        t_ref = time.monotonic()
        partial_rows = {nid: _partial_rows(batches, due) for nid, batches in self.taps.items()}
        want, wrong, compared = {}, [], 0
        for ws in out["due_ws"]:
            lo = ws // self.inter
            window = stream.generate(lo, lo + self.width_events, self.seed)
            want[ws] = cell.reference.rows(window)
            tapped = {nid: by_ws.get(ws) for nid, by_ws in partial_rows.items()}
            compared += len(tapped)
            wrong += [[who, ws] for who in _wrong_partials(
                cell.reference.partials(window), tapped, keyed_on)]
        guarantees["partials_compared"], guarantees["partials_wrong"] = compared, wrong
        verdict = compare.judge(out["due_ws"], got, want, guarantees)
        reference_s = time.monotonic() - t_ref

        records = self._records(out, stages, device, aggregates, arrived, compiled,
                                t_close, events)
        return {"verdict": verdict, "records": records, "guarantees": guarantees,
                "aggregates": [{k: v for k, v in a.items() if k != "id"} for a in aggregates],
                "reference_s": reference_s, "series": out["series"], "drain_s": out["drain_s"]}

    def _records(self, out, stages, device, aggregates, arrived, compiled,
                 t_close, events) -> dict:
        cell, t_open = self.cell, out["t_open"]
        (t0, a), (t1, b) = out["open_sample"], out["end_sample"]
        tasks = []
        for nid, s in stages.items():
            if nid not in a or nid not in b:
                continue
            from arroyo_tpu.metrics import TRANSIT_BUCKETS

            tasks.append(dict(
                s, node=nid, self_time_s=b[nid][0] - a[nid][0],
                self_cpu_s=b[nid][1] - a[nid][1], rows_in=b[nid][2] - a[nid][2],
                rows_out=b[nid][3] - a[nid][3], transit_bounds=list(TRANSIT_BUCKETS),
                transit_counts=[y - x for x, y in zip(a[nid][4], b[nid][4])],
                closes_on_wake=b[nid][5] - a[nid][5],
                closes_on_input=b[nid][6] - a[nid][6]))
        origin = max(s.origin for s in self.scans.values()) if self.rate else None
        epochs, barriers = [], []
        for e, (at, durable_us) in out["epochs"].items():
            trig = out["trigger_wall_us"].get(e)
            stall = None if durable_us is None or trig is None else (durable_us - trig) / 1e6
            barriers.append((at, None if stall is None else at + stall))
            epochs.append({"epoch": e, "completed": durable_us is not None,
                           "trigger_to_durable_ms": None if stall is None else stall * 1e3,
                           "at_s": at - t_open,
                           "phase": stats.phase_of(at, origin, self.hop_events / self.rate)
                           if self.rate else None})
        fired = out["fired"]
        closes, gen_late = [], []
        period_ms = None
        if self.rate:
            period_ms = self.hop_events / self.rate * 1e3
            for ws in out["due_ws"]:
                if ws in arrived:
                    last = ws // self.inter + self.width_events - 1
                    due = origin + last / self.rate
                    closes.append({"ws": ws, "due": due, "arrived": arrived[ws],
                                   "latency_ms": (arrived[ws] - due) * 1e3})
            met = stats.struck([(c["due"], c["arrived"]) for c in closes], barriers)
            for c, hit in zip(closes, met):
                c["struck"] = hit
            for s in self.scans.values():
                for at, first in zip(s.t, s.first):
                    if t_open <= at <= t_open + self.seconds:
                        gen_late.append((at - (s.origin + first / self.rate)) * 1e3)
        reduced = None
        if out["trace_dir"]:
            try:
                paths = glob.glob(os.path.join(
                    out["trace_dir"], "plugins", "profile", "*", "*.xplane.pb"))
                if paths:
                    self.loaded_trace = devtrace.load(paths[0], HOST_SPANS)
                    reduced = devtrace.reduce(self.loaded_trace)
            finally:
                shutil.rmtree(out["trace_dir"], ignore_errors=True)
        in_span = self.traced_steps
        if in_span is None:
            in_span = [s for s in self.slots.step_times if t_open <= s[0] <= out["t_end"]]
        steps = []
        for agg in aggregates:
            steps.append({"batch_rows": agg["batch_rows"], "acc_kinds": agg["acc_kinds"],
                          "acc_dtypes": agg["acc_dtypes"],
                          "steps": sum(1 for _t, i in in_span if i == agg["id"])})
        return {
            "cell": cell.entry, "config": cell.config, "traffic": cell.traffic,
            "trace": self.trace, "seconds": self.seconds,
            "setup_s": t_open - self.t_start,
            "window": {"opened": t_open, "closed": t_close,
                       "seconds": t_close - t_open, "events": events},
            "span": {"seconds": t1 - t0, "events": out["end_sent"] - out["open_sent"]},
            "period_ms": period_ms, "closes": closes, "gen_late_ms": gen_late,
            "tasks": tasks, "steps": steps,
            "close_fetch_ms": [(b_ - a_) * 1e3 for a_, b_ in self.slots.closes
                               if t_open <= a_ <= out["t_end"]],
            "epochs": epochs, "trigger_gaps_s": [b_ - a_ for a_, b_ in zip(fired, fired[1:])],
            "compiles_in_window": compiled, "opening": self.opening, "device": device,
            "peaks": None if self.rehearse else roofline.peaks(device["kind"]),
            "devtrace": reduced,
        }


def _report_dir(cell: str, seed: int, trace: bool) -> str:
    base = os.path.join(ROOT, "chiprun_out", "benchmark", cell)
    n = 0
    while os.path.exists(os.path.join(base, f"seed{seed}-trace{int(trace)}-{n}")):
        n += 1
    path = os.path.join(base, f"seed{seed}-trace{int(trace)}-{n}")
    os.makedirs(path)
    return path


def main(workload: str, seed: int, seconds: float, trace: bool, rehearse: bool,
         t_start: float) -> int:
    cell = Cell(workload)
    run = Run(cell, seed, seconds, trace, rehearse, t_start)
    try:
        result = run.execute()
    except NoAccelerator as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 4
    except UndeclaredSetting as e:
        print(f"benchmark: {cell.entry['config']}: {e}", file=sys.stderr)
        return 5
    records, verdict = result["records"], result["verdict"]
    _say({"setup_parts_s": run.parts, "setup_s": records["setup_s"],
          "effective_settings": run.effective, "reference_s": result["reference_s"],
          "drain_s": result["drain_s"], "window": records["window"],
          "opening": records["opening"], "ingest": result["guarantees"]["ingest"],
          "checkpoints": records["epochs"], "trigger_gaps_s": records["trigger_gaps_s"],
          "closes_struck": [i for i, c in enumerate(records["closes"]) if c.get("struck")],
          "closes": len(records["closes"])})
    for c in verdict["compared"]:
        _say(c)
    group = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in cell.metrics(group):
        value = cell.reader(m["name"])(records)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device = dict(records["device"])
    line = {"correct": verdict["correct"], "attempted": verdict["attempted"],
            "failed": verdict["failed"], "metrics": metrics, "device": device}
    reduced = records["devtrace"]
    if trace and reduced is not None:
        device["busy_s"], device["window_s"] = reduced["busy_s"], reduced["window_s"]
        line["breakdown"] = {"device_ops": reduced["device_ops"],
                             "idle_gaps": reduced["idle_gaps"]}
    if rehearse:
        # a CPU number never stands under a device metric's name
        line["rehearsal_metrics"], line["metrics"] = metrics, {}
    report = {
        "cell": cell.name, "seed": seed, "seconds": seconds, "trace": trace,
        "line": line, "compared": verdict["compared"], "windows": verdict["windows"],
        "setup_parts_s": run.parts, "effective_settings": run.effective,
        "guarantees": result["guarantees"], "aggregates": result["aggregates"],
        "reference_s": result["reference_s"], "drain_s": result["drain_s"],
        "series": result["series"],
        "records": {k: v for k, v in records.items() if k not in ("config", "traffic")},
    }
    try:
        out_dir = _report_dir(cell.name, seed, trace)
        with open(os.path.join(out_dir, "report.json"), "w") as f:
            json.dump(report, f, indent=1, default=float)
        if run.loaded_trace:
            with open(os.path.join(out_dir, "trace.json"), "w") as f:
                json.dump(run.loaded_trace, f)
    except OSError as e:
        print(f"benchmark: report not written: {e}", file=sys.stderr)
    # every number compared beside its limit: last in the line, and the last
    # lines of standard error
    line["compared"] = {c["name"]: {k: v for k, v in c.items() if k not in ("name", "what")}
                        for c in verdict["compared"]}
    for name, c in line["compared"].items():
        print(f"compared {name}: {json.dumps(c)}", file=sys.stderr)
    sys.stderr.flush()
    _say(line)
    return 0
