"""Epoch-lifecycle tracing: correlated span trees for checkpoint epochs.

The reference engine surfaces per-operator rates and backpressure, but when
an epoch takes 90 seconds — or never completes — counters cannot say WHERE
the time went. This module records the checkpoint lifecycle as a timeline of
correlated events per epoch:

    trigger                controller (or single-worker engine) injects the
                           barrier into the sources
    align_start            a subtask saw its FIRST barrier input and began
                           holding traffic behind the alignment
    snapshot_start         alignment complete (every live input delivered the
                           barrier); the subtask starts writing its snapshot
    ack                    the subtask's snapshot is durable and its
                           checkpoint-completed response was posted
    metadata_durable       the job-level metadata marker is durable (global
                           coverage across every worker — 2PC phase 1)
    commit_sent            phase-2 commit left the controller for a worker
    commit_delivered       a worker's engine delivered the commit to its
                           committing operators

Events land in a process-global, bounded, in-memory ring (per job, newest
``obs.trace.max-epochs`` epochs) so the recorder is safe to leave on in
production. Multi-process workers relay their events to the controller over
the existing JSON-lines protocol (``{"event": "span", ...}``); the
controller's recorder therefore always holds the whole job's timeline and
persists it to the DB for ``GET /api/v1/jobs/<id>/traces``.

Exports:

    chrome_trace(...)       Chrome trace-event JSON (trace-viewer /
                            Perfetto's "Open with legacy UI" loads it as-is)
    timeline_report(...)    human-readable per-epoch timeline naming the
                            exact subtask whose barrier never arrived or
                            whose snapshot never acked — attached to the
                            wedged-epoch watchdog report and to
                            CheckpointWait timeouts
    phase_durations(...)    align/snapshot/ack/commit wall seconds per epoch

Beside the epoch recorder sits the **span ring**: what each task thread was
doing, per batch, per window close, per wait — never per row. A task thread
``bind``s itself (engine/task.py, under ``profile.enabled``); from then on

    span(name, trace_id=None, **args)   context manager: one record per use
    mark(name, trace_id=None, **args)   an instant (t0 == t1)
    wait(kind, name, **args)            a span that is also charged, less the
                                        CPU burnt inside it, to the task's
                                        time account (TaskMetrics.account);
                                        recorded only when it lasted >= 1 ms
    open_span(name, ...)                a span another thread may end

A ``wait`` for the device (``DEVICE_WAIT``) is also a *registered* wait: it
stands in a process-wide table from its first moment to its last, with the
jitted ``program`` whose output it waits for, and every lane publishes its
innermost open span. One **watch thread** (started at the first ``bind`` of
a process, gone when the last lane unbinds; never under ``profile.enabled:
false``, which binds nothing) wakes every ``WATCH_TICK_NS``, measures how
late it woke (the process's pulse: a thread that needs only the interpreter
lock and a CPU), and writes down a wait that has been open for ``STALL_NS``
while it still lasts: one ``device.stall`` mark (what every other task and
thread was inside of, the scheduler's counters, an allocator call as a probe
of the runtime), the counter ``arroyo_worker_device_stalls`` and a
``DEVICE_STALLED`` job event. Once a second it writes a ``watch.tick`` mark.

They append ``(name, ident, trace_id, t0_ns, t1_ns, args)`` to the calling
thread's own ring: one writer per ring, no lock, ``RING_CAPACITY`` records
(the oldest fall out). Stamps are ``time.monotonic_ns()``, the clock the
inbox stamps transit with. A ``span`` also holds a
``jax.profiler.TraceAnnotation("arroyo." + name)``, so a profiler session
shows it on its own thread's line in the host plane, on the device trace's
clock; no duration is ever taken across the two clocks. On a thread that
is not bound (profiling off, or a caller outside the engine) every one of
them is a no-op.

``trace_id`` ties the stamps of one window together. Window operators give
the window's END in event micros; watermark marks (``wm.in``/``wm.out``)
give the watermark's value; the windowed join, which knows no width, gives
the rows' event timestamp (the window's START as the aggregates stamp it).
``window(trace_id)`` sets it for the spans opened below it (the slot
aggregate knows bins, not event time).

    spans(name, t0, t1, node, job)      the records, oldest first
    account_over(node, t0, t1, job)     one operator's time account over an
                                        interval, from its task.account marks
    stamps(name, node, job, t0)         (trace_id, t0_ns, t1_ns) of one name
                                        on one operator
    crossings(name, node, values, ...)  per value, the first stamp whose
                                        trace_id reached it
    to_wall_us(t_ns)                    ring stamp -> wall micros (one pair
                                        of clock readings taken at import)

``spans()`` and ``SPAN_NAMES`` are the interface benchmark readers use.
"""

from __future__ import annotations

import logging
import os
import sys
import threading
import time
from collections import deque
from typing import Iterable, NamedTuple, Optional

try:
    import resource  # Unix: the watch reads the process's involuntary switches
except ImportError:
    resource = None

_log = logging.getLogger("arroyo_tpu.obs.trace")

# the epoch lifecycle, in causal order (used for stable sorting of events
# that share a timestamp, and by the timeline report)
EVENT_ORDER = ("trigger", "align_start", "snapshot_start", "ack",
               "metadata_durable", "commit_sent", "commit_delivered")

_EVENT_RANK = {name: i for i, name in enumerate(EVENT_ORDER)}


def now_us() -> int:
    """Wall-clock micros — the same clock CheckpointBarrier timestamps use,
    so spans correlate with barrier metadata across processes."""
    return int(time.time() * 1e6)


class EpochTraceRecorder:
    """Bounded per-job ring of epoch timelines. Single global instance
    (``recorder``); every record is an at-most-once fact keyed by
    (event, node, subtask, worker), so duplicate reports (an embedded
    engine and its controller sharing the process) collapse to the first
    observation instead of double-counting."""

    def __init__(self, max_epochs: int = 32, max_events_per_epoch: int = 4096):
        self.max_epochs = max_epochs
        self.max_events = max_events_per_epoch
        self._lock = threading.Lock()
        # job -> {epoch -> {(event, node, subtask, worker) -> t_us}}
        self._jobs: dict[str, dict[int, dict[tuple, int]]] = {}

    def record(self, job_id: str, epoch: int, event: str,
               node: Optional[str] = None, subtask: Optional[int] = None,
               worker: Optional[int] = None, t_us: Optional[int] = None) -> None:
        t = now_us() if t_us is None else int(t_us)
        key = (event, node, subtask, worker)
        with self._lock:
            epochs = self._jobs.setdefault(job_id, {})
            ev = epochs.get(epoch)
            if ev is None:
                ev = epochs[epoch] = {}
                while len(epochs) > self.max_epochs:
                    epochs.pop(min(epochs))
            if key not in ev and len(ev) < self.max_events:
                ev[key] = t

    def epochs(self, job_id: str) -> list[int]:
        with self._lock:
            return sorted(self._jobs.get(job_id, ()))

    def events(self, job_id: str, epoch: int) -> list[dict]:
        """One epoch's timeline, oldest first (ties broken causally)."""
        with self._lock:
            ev = dict(self._jobs.get(job_id, {}).get(epoch, {}))
        out = [
            {"epoch": epoch, "event": k[0], "node": k[1], "subtask": k[2],
             "worker": k[3], "t_us": t}
            for k, t in ev.items()
        ]
        out.sort(key=lambda e: (e["t_us"], _EVENT_RANK.get(e["event"], 99)))
        return out

    def ingest(self, job_id: str, events: Iterable[dict]) -> None:
        """Replay relayed/persisted event dicts (the controller feeds worker
        ``span`` events through here; the API feeds DB rows)."""
        for e in events:
            self.record(job_id, int(e["epoch"]), e["event"], e.get("node"),
                        e.get("subtask"), e.get("worker"), e.get("t_us"))

    def clear_job(self, job_id: str) -> None:
        with self._lock:
            self._jobs.pop(job_id, None)


recorder = EpochTraceRecorder()


# ------------------------------------------------------------ derived views


def _by_subtask(events: list[dict]) -> dict[tuple, dict[str, int]]:
    """(node, subtask) -> {event -> t_us} for per-subtask events."""
    out: dict[tuple, dict[str, int]] = {}
    for e in events:
        if e["node"] is None:
            continue
        out.setdefault((e["node"], e["subtask"]), {})[e["event"]] = e["t_us"]
    return out


def _job_event(events: list[dict], name: str, last: bool = False) -> Optional[int]:
    ts = [e["t_us"] for e in events if e["event"] == name]
    if not ts:
        return None
    return max(ts) if last else min(ts)


def phase_durations(events: list[dict]) -> dict[str, float]:
    """Job-level sequential phase decomposition of one epoch, in seconds:

        align     trigger            -> last subtask's snapshot_start
                  (waiting for barriers to traverse the graph and align)
        snapshot  last snapshot_start -> last ack (state writes)
        ack       last ack           -> metadata_durable (marker publish)
        commit    metadata_durable   -> last commit event (2PC phase 2)

    Phases whose boundary events are missing are omitted; the sum of the
    returned values is the trigger->commit wall time actually observed.
    """
    trigger = _job_event(events, "trigger")
    snap = _job_event(events, "snapshot_start", last=True)
    ack = _job_event(events, "ack", last=True)
    durable = _job_event(events, "metadata_durable")
    commit = max(filter(None, (
        _job_event(events, "commit_sent", last=True),
        _job_event(events, "commit_delivered", last=True))), default=None)
    out: dict[str, float] = {}
    for name, lo, hi in (("align", trigger, snap), ("snapshot", snap, ack),
                         ("ack", ack, durable), ("commit", durable, commit)):
        if lo is not None and hi is not None:
            out[name] = max(0.0, (hi - lo) / 1e6)
    return out


def dominant_phase(phases: dict[str, float]) -> Optional[str]:
    if not phases:
        return None
    return max(phases, key=lambda k: phases[k])


def chrome_trace(job_id: str, events_by_epoch: dict[int, list[dict]],
                 job_events: Optional[list[dict]] = None,
                 ring_spans: Optional[Iterable] = None) -> dict:
    """Chrome trace-event JSON for one job's recorded epochs.

    Spans render one track per subtask (tid = "node/subtask") inside one
    process (pid = job): per subtask an "align" span (align_start ->
    snapshot_start) and a "snapshot" span (snapshot_start -> ack); at the
    job level an "epoch N" span (trigger -> metadata_durable) and a
    "commit" span (metadata_durable -> last commit event). A phase still
    open when the trace was taken (a wedged subtask) is emitted as a "B"
    begin-event with no matching end — trace viewers render it running to
    the end of the timeline, which is exactly the visual for "stuck".

    ``job_events`` (structured obs.events dicts): entries scoped to a
    rendered epoch are added as instant markers — an OPERATOR_PANIC or
    EPOCH_WEDGED lands on its subtask's (or the job's "events") track at
    the exact wall time, so one Perfetto view correlates the span tree
    with the event feed.

    ``ring_spans`` (``spans(job=...)``, or their ``_asdict()`` forms as the
    API ships them): the span ring's records of the job, on the same per-subtask
    tracks — what each task was doing between the epochs' phases."""
    out: list[dict] = []

    def span(name: str, tid: str, t0: Optional[int], t1: Optional[int],
             epoch: int, **args) -> None:
        if t0 is None:
            return
        base = {"name": name, "cat": "checkpoint", "pid": job_id, "tid": tid,
                "ts": t0, "args": {"epoch": epoch, **args}}
        if t1 is None:
            out.append({**base, "ph": "B"})
        else:
            out.append({**base, "ph": "X", "dur": max(0, t1 - t0)})

    for epoch, events in sorted(events_by_epoch.items()):
        trigger = _job_event(events, "trigger")
        durable = _job_event(events, "metadata_durable")
        commit = max(filter(None, (
            _job_event(events, "commit_sent", last=True),
            _job_event(events, "commit_delivered", last=True))), default=None)
        span(f"epoch {epoch}", "epoch", trigger, durable, epoch)
        span("commit", "epoch", durable, commit, epoch)
        for (node, sub), ev in sorted(_by_subtask(events).items()):
            tid = f"{node}/{sub}"
            align0 = ev.get("align_start")
            snap0 = ev.get("snapshot_start")
            ack = ev.get("ack")
            span("align", tid, align0, snap0, epoch)
            span("snapshot", tid, snap0, ack, epoch)
            if align0 is None and snap0 is None and ack is not None:
                # source subtasks snapshot without alignment; give the ack a
                # point on the track so every participant is visible
                out.append({"name": "ack", "cat": "checkpoint", "ph": "i",
                            "pid": job_id, "tid": tid, "ts": ack, "s": "t",
                            "args": {"epoch": epoch}})
    rendered = set(events_by_epoch)
    for ev in job_events or ():
        if ev.get("epoch") is None or int(ev["epoch"]) not in rendered:
            continue
        tid = (f"{ev['node']}/{ev['subtask']}"
               if ev.get("node") is not None and ev.get("subtask") is not None
               else "events")
        out.append({
            "name": ev.get("code", "EVENT"), "cat": "events", "ph": "i",
            "pid": job_id, "tid": tid, "ts": int(ev["ts_us"]), "s": "p",
            "args": {"epoch": int(ev["epoch"]),
                     "level": ev.get("level"),
                     "message": ev.get("message", "")},
        })
    for sp in ring_spans or ():
        sp = sp if isinstance(sp, dict) else sp._asdict()
        args = dict(sp.get("args") or {})
        if sp.get("trace_id") is not None:
            args["trace_id"] = sp["trace_id"]
        ev = {"name": sp["name"], "cat": "span", "pid": job_id,
              "tid": f"{sp['node']}/{sp['subtask']}",
              "ts": to_wall_us(sp["t0_ns"]), "args": args}
        if sp["t1_ns"] > sp["t0_ns"]:
            out.append({**ev, "ph": "X", "dur": (sp["t1_ns"] - sp["t0_ns"]) / 1e3})
        else:
            out.append({**ev, "ph": "i", "s": "t"})
    return {"traceEvents": out, "displayTimeUnit": "ms"}


def timeline_report(job_id: str, epoch: int, events: list[dict],
                    expected: Optional[Iterable[tuple]] = None) -> str:
    """Human-readable epoch timeline plus a diagnosis naming the exact
    subtask that is holding the epoch: barriers that never arrived
    (``expected`` subtasks with no events at all) and snapshots that never
    acked. This is what the wedged-epoch watchdog and chaos-test failures
    attach, so a stuck checkpoint is self-diagnosing instead of a
    log-archaeology session."""
    if not events:
        return (f"epoch {epoch} of job {job_id}: no trace events recorded "
                "(trigger never reached the engine?)")
    t0 = events[0]["t_us"]
    lines = [f"epoch {epoch} trace ({job_id}):"]
    for e in events:
        who = ""
        if e["node"] is not None:
            who = f"  {e['node']}/{e['subtask']}"
        elif e["worker"] is not None:
            who = f"  worker {e['worker']}"
        lines.append(f"  +{(e['t_us'] - t0) / 1e3:9.1f}ms  {e['event']}{who}")
    by_sub = _by_subtask(events)
    # root causes first: a subtask that STARTED its snapshot (or alignment)
    # and never acked is holding the epoch; subtasks whose barrier never
    # arrived are usually its downstream victims
    stuck: list[str] = []
    for (node, sub), ev in sorted(by_sub.items()):
        if "ack" in ev:
            continue
        if "snapshot_start" in ev:
            stuck.append(f"{node}/{sub}: snapshot started, never acked")
        else:
            stuck.append(f"{node}/{sub}: aligning, barrier(s) still missing "
                         "on some input")
    victims = [f"{key[0]}/{key[1]}: barrier never arrived"
               for key in sorted(set(expected or ())) if key not in by_sub]
    if len(victims) > 6:
        victims = victims[:6] + [f"... and {len(victims) - 6} more"]
    stuck += victims
    if stuck:
        lines.append("  stuck: " + "; ".join(stuck))
    else:
        phases = phase_durations(events)
        if phases:
            dom = dominant_phase(phases)
            lines.append("  phases: " + "  ".join(
                f"{k}={v * 1e3:.1f}ms" + ("  <- dominant" if k == dom else "")
                for k, v in phases.items()))
    return "\n".join(lines)


# ------------------------------------------------------------ the span ring

# every name the program records under; the benchmark's readers and
# tests/test_span_account.py hold the engine to this tuple
SPAN_NAMES = (
    # the task run loop, the inbox and the source's schedule (engine/)
    "task.inbox_wait", "task.put_wait", "task.account",
    # the slot aggregate (ops/slot_agg.py, ops/prefetch.py)
    "agg.directory", "agg.dispatch", "agg.spill", "agg.close", "agg.fetch",
    "agg.drain", "agg.snapshot", "agg.grow",
    # a sliding window's close on the host: its bins concatenated and
    # combined by key, or the last window's rows slid by one bin, and made
    # into the window's columns (windows/sliding.py; pane_combine below)
    "agg.combine",
    # the SQL window function ranks one bucket's rows, or cuts them to each
    # partition's first N (operators/window_fn.py; window_rank below)
    "wf.rank",
    # the nexmark source (connectors/nexmark.py)
    "source.generate", "source.emit", "source.pace",
    # the watermark trail (engine/task.py, operators/collector.py, windows/,
    # operators/joins.py)
    "wm.in", "wm.out", "rows.out",
    # a close whose rows left on a completion wake (windows/, operators/joins.py)
    "close.wake",
    # the join's next probe size compiled ahead, on a fetch worker
    # (operators/joins.py _prewarm)
    "join.prewarm",
    # a windowed join's probe of one window, from its dispatch to the pairs
    # on the host (operators/joins.py; join_probe below)
    "join.probe",
    # the join's wait for its probe's three columns (ops/join_probe.py
    # JoinHandle.result), inside join.probe
    "join.fetch",
    # the watch thread (below): a device wait open for STALL_NS, written
    # while it lasts under the waiting task's name; the process's pulse,
    # once a second
    "device.stall", "watch.tick",
)
# the three kinds of wait a task's time account knows (TaskMetrics.account)
INBOX_WAIT, PUT_WAIT, DEVICE_WAIT = "inbox_wait", "put_wait", "device_wait"

RING_CAPACITY = 1 << 16          # records per thread; the oldest fall out
WAIT_SPAN_MIN_NS = 1_000_000     # a wait shorter than this is counted, not recorded
ACCOUNT_MARK_NS = 200_000_000    # a task.account mark at least this often
_MAX_DEAD_RINGS = 64             # rings of ended threads kept for readers
WATCH_TICK_NS = 100_000_000      # the watch thread's sleep: a tenth of STALL_NS
# a wait for the device open this long is a stall: one-chip waits are ~4 ms
# and the stalls met 1.4-6 s; a mesh's closes wait 0.2-0.65 s and its
# barriers 0.55-0.92 s (PERF.md section 5)
STALL_NS = 1_000_000_000
STALL_EVENT_NS = 60_000_000_000  # a job's DEVICE_STALLED events are this far apart
_MAX_THREADS_NAMED = 32          # threads a device.stall mark names

# ring stamps are monotonic; the Chrome export wants wall time
_CLOCK_PAIR = (time.monotonic_ns(), time.time_ns())

_ANNOTATION_NAMES = {n: "arroyo." + n for n in SPAN_NAMES}
_annotation = None  # jax.profiler.TraceAnnotation, imported at first use


def to_wall_us(t_ns: int) -> int:
    return (t_ns - _CLOCK_PAIR[0] + _CLOCK_PAIR[1]) // 1000


class Span(NamedTuple):
    name: str
    job: Optional[str]
    node: Optional[str]
    subtask: Optional[int]
    trace_id: Optional[int]
    t0_ns: int
    t1_ns: int
    args: Optional[dict]


class _Local(threading.local):
    lane = None   # the Lane this thread is bound to
    ring = None   # this thread's deque of records


_tls = _Local()
_rings: list = []  # (thread, deque), oldest first
_rings_lock = threading.Lock()


def _ring() -> deque:
    ring = _tls.ring
    if ring is None:
        ring = _tls.ring = deque(maxlen=RING_CAPACITY)
        with _rings_lock:
            dead = [r for r in _rings if not r[0].is_alive()]
            for r in dead[:max(0, len(dead) - _MAX_DEAD_RINGS)]:
                _rings.remove(r)
            _rings.append((threading.current_thread(), ring))
    return ring


class Lane:
    """One task's identity on its thread: whose records these are, and the
    TaskMetrics its waits are charged to."""

    __slots__ = ("ident", "metrics", "trace_id", "next_account_ns", "open")

    def __init__(self, job: str, node: str, subtask: int, metrics):
        self.ident = (job, node, subtask)
        self.metrics = metrics
        self.trace_id: Optional[int] = None
        self.next_account_ns = 0
        # the innermost span the task's own thread is inside of, as
        # (name, t0_ns), for a reader on another thread (the watch)
        self.open: Optional[tuple] = None

    def account(self, now_ns: Optional[int] = None, force: bool = False) -> None:
        """Drop the task's cumulative time account into the ring (at most
        every ACCOUNT_MARK_NS unless forced): a reader differences two of
        them over any interval. Called by the owning thread only."""
        now = time.monotonic_ns() if now_ns is None else now_ns
        if now < self.next_account_ns and not force:
            return
        self.next_account_ns = now + ACCOUNT_MARK_NS
        m = self.metrics
        args = dict(m.account, cpu=time.thread_time(),
                    self_time=sum(m.self_time.values()),
                    self_cpu=sum(m.self_cpu.values()),
                    table_grows=m.counters["arroyo_worker_table_grows"],
                    device_stalls=m.counters["arroyo_worker_device_stalls"],
                    join_probes_device=m.counters["arroyo_worker_join_probes_device"],
                    join_probes_host=m.counters["arroyo_worker_join_probes_host"],
                    steps_dispatched=m.counters["arroyo_worker_steps_dispatched"],
                    batches_staged=m.counters["arroyo_worker_batches_staged"],
                    rows_precombined=m.counters["arroyo_worker_rows_precombined"],
                    steps_made_native=m.counters["arroyo_worker_steps_made_native"],
                    window_rows_combined=m.counters["arroyo_worker_window_rows_combined"],
                    window_rows_emitted=m.counters["arroyo_worker_window_rows_emitted"],
                    pane_closes_running=m.counters["arroyo_worker_pane_closes_running"],
                    pane_closes_full=m.counters["arroyo_worker_pane_closes_full"],
                    distinct_pairs=m.counters["arroyo_worker_distinct_pairs"],
                    window_fn_rows_in=m.counters["arroyo_worker_window_fn_rows_in"],
                    window_fn_rows_out=m.counters["arroyo_worker_window_fn_rows_out"],
                    directory_fallback_steps=m.counters[
                        "arroyo_worker_directory_fallback_steps"])
        _ring().append(("task.account", self.ident, None, now, now, args))

    def account_due_s(self) -> float:
        """Seconds until the next account mark is due (a wait's bound)."""
        return max(0.0, (self.next_account_ns - time.monotonic_ns()) / 1e9)


def bind(job: str, node: str, subtask: int, metrics) -> Lane:
    """Make the calling thread the task's: its spans carry the task's
    identity and its waits are charged to ``metrics``."""
    global _watch
    before = _tls.lane
    lane = _tls.lane = Lane(job, node, subtask, metrics)
    _ring()
    with _watch_lock:
        _bound.pop(before, None)
        _bound[lane] = threading.current_thread()
        if _watch is None or _watch.stop.is_set():
            _watch = _Watch()
            _watch.start()
    return lane


def unbind() -> None:
    lane = _tls.lane
    if lane is not None:
        lane.account(force=True)
        with _watch_lock:
            _bound.pop(lane, None)
            if not _bound and _watch is not None:
                _watch.stop.set()  # it leaves at its next wake, which is now
    _tls.lane = None


def current() -> Optional[Lane]:
    return _tls.lane


def current_window() -> tuple:
    """(the calling thread's lane, the window it is working on): what a
    handle keeps at dispatch for the wait a fetch worker does on its behalf
    (``wait(..., lane=, trace_id=)``)."""
    lane = _tls.lane
    return lane, (None if lane is None else lane.trace_id)


class _Null:
    """What span()/wait() return on a thread that records nothing."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def end(self) -> None:
        pass

    def note(self, **args) -> None:
        pass


NO_SPAN = _Null()


def _annotate(name: str):
    global _annotation
    if _annotation is None:
        import jax.profiler

        _annotation = jax.profiler.TraceAnnotation
    ann = _annotation(_ANNOTATION_NAMES.get(name) or "arroyo." + name)
    ann.__enter__()
    return ann


_NOT_PUBLISHED = object()  # _Span.outer of a span another thread runs for the lane


class _Span:
    __slots__ = ("lane", "name", "trace_id", "args", "t0", "ann", "deferred", "outer")

    def __init__(self, lane, name, trace_id, args, deferred=False):
        self.lane, self.name, self.args = lane, name, args or None
        self.trace_id = lane.trace_id if trace_id is None else trace_id
        self.deferred = deferred

    def _begin(self) -> None:
        self.ann = _annotate(self.name)
        self.t0 = time.monotonic_ns()
        lane = self.lane
        if lane is _tls.lane:  # the task's own thread: say what it is inside of
            self.outer, lane.open = lane.open, (self.name, self.t0)
        else:
            self.outer = _NOT_PUBLISHED

    def _leave(self) -> None:
        if self.outer is not _NOT_PUBLISHED:
            self.lane.open = self.outer
        self.ann.__exit__(None, None, None)

    def __enter__(self):
        self._begin()
        return self

    def __exit__(self, *exc):
        if not self.deferred:
            self.end()
        self._leave()
        return False

    def note(self, **args) -> None:
        """More args, known only once the span has begun."""
        self.args = dict(self.args or (), **args)

    def end(self) -> None:
        """Close the span now, on whichever thread calls (a deferred span
        is ended by the thread that finishes the work it began)."""
        _ring().append((self.name, self.lane.ident, self.trace_id, self.t0,
                        time.monotonic_ns(), self.args))


class _Wait(_Span):
    """A span in which the thread is off its CPU waiting for something
    named: charged (wall less the thread CPU burnt inside) to the account
    of the lane, when the lane is this thread's own. A wait for the device
    also stands in ``_open_waits`` while it lasts, where the watch thread
    finds it (and sets ``flagged`` once it has written it down)."""

    __slots__ = ("kind", "cpu0", "flagged")

    def __init__(self, lane, kind, name, trace_id, args):
        _Span.__init__(self, lane, name, trace_id, args)
        self.kind = kind
        self.flagged = False

    def __enter__(self):
        self.cpu0 = time.thread_time()
        self._begin()
        if self.kind == DEVICE_WAIT:
            _open_waits[id(self)] = self
        return self

    def __exit__(self, *exc):
        t1 = time.monotonic_ns()
        self._leave()
        lane = self.lane
        if self.kind == DEVICE_WAIT:
            _open_waits.pop(id(self), None)
            if self.flagged:
                self.note(stalled=True)
                m = lane.metrics
                m.device_stall_max_ms = max(m.device_stall_max_ms, (t1 - self.t0) / 1e6)
        own = lane is _tls.lane  # a prefetch worker waits on the task's behalf
        if own:
            off = (t1 - self.t0) / 1e9 - (time.thread_time() - self.cpu0)
            if off > 0.0:
                lane.metrics.account[self.kind] += off
        if t1 - self.t0 >= WAIT_SPAN_MIN_NS:
            _ring().append((self.name, lane.ident, self.trace_id, self.t0, t1,
                            self.args))
        if own:
            lane.account(t1)
        return False


def span(name: str, trace_id: Optional[int] = None,
         lane: Optional[Lane] = None, **args):
    """``with span("agg.dispatch"): ...`` — one record in the thread's ring
    and, in a profiler session, one ``arroyo.<name>`` event in the host
    plane. ``lane``: the task the work belongs to when another thread (a
    prefetch worker) does it."""
    lane = lane or _tls.lane
    return NO_SPAN if lane is None else _Span(lane, name, trace_id, args)


def open_span(name: str, trace_id: Optional[int] = None, **args):
    """A span whose ``with`` block is only its beginning: it is recorded
    when ``.end()`` is called, by any thread (``agg.close``: dispatched by
    the task, its rows land on a prefetch worker)."""
    lane = _tls.lane
    return NO_SPAN if lane is None else _Span(lane, name, trace_id, args, True)


def wait(kind: str, name: str, lane: Optional[Lane] = None,
         trace_id: Optional[int] = None, **args):
    """``with wait(DEVICE_WAIT, "agg.fetch", program="jit_go"): ...`` around
    a blocking call. ``lane``: the task the work belongs to when another
    thread (a prefetch worker) does it — recorded under the task's name,
    charged to nobody; ``trace_id`` is then the window the work was begun
    for (the lane's own has moved on). ``program``, on a wait for the
    device: the jitted program whose output it waits for."""
    lane = lane or _tls.lane
    return NO_SPAN if lane is None else _Wait(lane, kind, name, trace_id, args)


def mark(name: str, trace_id: Optional[int] = None, **args) -> None:
    lane = _tls.lane
    if lane is not None:
        now = time.monotonic_ns()
        _ring().append((name, lane.ident,
                        lane.trace_id if trace_id is None else trace_id,
                        now, now, args or None))


def close_left(trace_id: int, woke: bool) -> None:
    """An in-flight close's rows leave the operator: on a completion wake
    (``Operator.drain_ready``; also a ``close.wake`` mark), or at the
    operator's next input or a forced drain. The two counters say whether
    the wake engages where input is sparse."""
    lane = _tls.lane
    if lane is not None:
        if woke:
            lane.metrics.add("arroyo_worker_closes_on_wake")
            mark("close.wake", trace_id)
        else:
            lane.metrics.add("arroyo_worker_closes_on_input")


def join_prewarmed(lane: Optional[Lane], pair: tuple,
                   error: Optional[BaseException] = None) -> None:
    """A warm-up of the join's next probe size (operators/joins.py
    _prewarm) ended: compiled, or given up with ``error`` (then also a job
    event: nothing else says why). Called by the fetch worker that ran it,
    for the join task whose ``lane`` this is; warm-ups run one at a time
    and the task's own thread writes neither counter."""
    if lane is None:
        return
    lane.metrics.add("arroyo_worker_join_prewarms_failed" if error is not None
                     else "arroyo_worker_join_probes_prewarmed")
    if error is not None:
        from .events import recorder as events

        job, node, subtask = lane.ident
        events.record(
            job, "WARN", "JOIN_PREWARM_FAILED",
            message=f"the join's probe for {pair[0]:,} x {pair[1]:,} rows was not "
                    f"compiled ahead: {error!r}",
            node=node, subtask=subtask,
            data={"left": pair[0], "right": pair[1], "error": repr(error)})


def join_probe(trace_id: Optional[int], left: int, right: int,
               caps: Optional[tuple] = None, windows: int = 1):
    """A windowed join probes a window that has both sides (``windows`` of
    them in one fused probe): a ``join.probe`` span of the join's task and
    one count a window. ``caps``: the bucket pair of a probe on the device;
    the ``with`` block is then only its dispatch, and whoever lands the
    pairs on the host notes ``pairs`` and calls ``.end()``. None: the numpy
    probe on the task's own thread (a side under ``device.join-min-rows``,
    or no device), which would otherwise fall to the host unseen."""
    lane = _tls.lane
    if lane is None:
        return NO_SPAN
    on = "host" if caps is None else "device"
    lane.metrics.add("arroyo_worker_join_probes_host" if caps is None
                     else "arroyo_worker_join_probes_device", windows)
    l_cap, r_cap = caps or (0, 0)
    args = dict(left=int(left), right=int(right), l_cap=l_cap, r_cap=r_cap, on=on)
    if windows > 1:
        args["windows"] = windows
    return _Span(lane, "join.probe", trace_id, args, deferred=caps is not None)


def step_dispatched(rows: int, batches: int, shards: int = 0, room: int = 0,
                    lane_bytes: int = 0, rows_in: int = 0, made: bool = False):
    """A window aggregate hands one step to the device (ops/slot_agg.py
    _update_chunk; parallel/sharded_agg.py update): the ``agg.dispatch``
    span, with the rows the step carries and the inbox batches it was made
    of (a window operator takes what its inbox holds, up to a step's width,
    before it dispatches), and the task's two counters, whose ratio says
    how often that engages. A step of the sharded aggregate also says over
    how many ``shards`` it is dealt, the rows it has ``room`` for (``shards``
    times the per-shard batch: ``rows`` over ``room`` is how full the mesh
    step is) and the bytes of one row's accumulator lanes. A keyless
    aggregate stages partials (windows/tumbling.py RowStage): its step's
    ``rows`` are one a bin, ``rows_in`` the rows of the inbox they were
    combined from, which the task's third counter adds up; a step of rows
    (``rows_in`` 0) writes its ``rows`` there. ``made``: who made the step's
    inputs to the device's shapes, ``native`` (one pass of the host library
    over the staged batches, cpp ah_step_make: the span then covers the
    jitted call alone; the task's fourth counter) or ``numpy`` (the hook's
    passes, then ``_dispatch_step``'s fills and casts inside this span);
    the ``agg.make`` span in front of the step's ``agg.directory`` is the
    making, on either side."""
    lane = _tls.lane
    if lane is None:
        return NO_SPAN
    lane.metrics.add("arroyo_worker_steps_dispatched")
    lane.metrics.add("arroyo_worker_batches_staged", batches)
    if rows_in:
        lane.metrics.add("arroyo_worker_rows_precombined", int(rows_in))
    if made:
        lane.metrics.add("arroyo_worker_steps_made_native")
    args = dict(rows=int(rows), batches=int(batches), rows_in=int(rows_in or rows),
                made="native" if made else "numpy")
    if shards:
        args.update(shards=int(shards), room=int(room), lane_bytes=int(lane_bytes))
    return _Span(lane, "agg.dispatch", None, args)


def directory_step(span, rows: int, misses: int, native: bool,
                   fell_back: bool = False) -> None:
    """The slot directory gave one step's rows their slots (ops/slot_agg.py
    _resolve_slots, whose ``agg.directory`` span this is given): the ``rows``
    it resolved, the first-seen (bin, key) groups among them (``misses``)
    and where those were placed (``on``: ``native``, two calls into the
    library, or ``numpy``, ``lookup_or_assign``). A step that ``fell_back``
    went through ``lookup_or_assign`` although the library is loaded (its
    misses span more bins than a claim takes, or a probe wrapped); the
    task's two counters say how many of its steps did."""
    span.note(rows=int(rows), misses=int(misses), on="native" if native else "numpy")
    lane = _tls.lane
    if lane is not None:
        lane.metrics.add("arroyo_worker_directory_steps")
        if fell_back:
            lane.metrics.add("arroyo_worker_directory_fallback_steps")


def pane_combine(trace_id: int, bins):
    """A sliding aggregate closes one window on the host (windows/sliding.py
    _close_window): the ``agg.combine`` span around the making of the
    window's rows from its ``bins`` bins (those that held rows, ``width /
    slide`` at most; a number, or a call that counts them, made only where a
    span is recorded) and of its output columns, under the window's end as
    ``trace_id`` like its close; on the task's own thread and so part of its
    own time, no wait. The caller says how it went through
    ``pane_combined``."""
    lane = _tls.lane
    if lane is None:
        return NO_SPAN
    return _Span(lane, "agg.combine", trace_id,
                 dict(bins=int(bins() if callable(bins) else bins)))


def pane_combined(span, rows_in: int, rows: int, running: bool) -> None:
    """How the window ``span`` (a ``pane_combine``) covers was made. ``on``:
    ``full``, its bins concatenated and ``combine_by_key`` over their
    ``rows_in`` rows, or ``running``, the last window's rows slid by one bin
    in one native call, ``rows_in`` the rows of the bin that came in and of
    the one that went out. ``rows``: the rows the window emits. The task's
    counters add up ``rows_in``, ``rows`` and the closes of each kind:
    ``rows`` over the events that came in is what a close hands downstream."""
    span.note(rows_in=int(rows_in), rows=int(rows), on="running" if running else "full")
    lane = _tls.lane
    if lane is not None:
        lane.metrics.add("arroyo_worker_window_rows_combined", int(rows_in))
        lane.metrics.add("arroyo_worker_window_rows_emitted", int(rows))
        lane.metrics.add("arroyo_worker_pane_closes_running" if running
                         else "arroyo_worker_pane_closes_full")


def distinct_pairs(rows: int) -> None:
    """The first level of a distinct split (sql/planner.py
    _plan_distinct_split) emits ``rows`` rows of closed windows, one a
    (window, group keys, value): the pairs its table held for them."""
    lane = _tls.lane
    if lane is not None:
        lane.metrics.add("arroyo_worker_distinct_pairs", int(rows))


def window_rank(trace_id: int, rows_in: int, limit: int):
    """The SQL window function computes one bucket (operators/window_fn.py
    _compute_and_emit): the ``wf.rank`` span around the ordering of its
    ``rows_in`` rows (or, under a ``limit``, the selection of each
    partition's first N) and the making of its output columns, under the
    window's end as ``trace_id`` like the close that fed it (the bucket's
    timestamp where the rows carry no window); on the task's own thread and
    so part of its own time, no wait. ``limit``: the N of a window top-N, 0
    where whole partitions are ranked. The caller says what left through
    ``window_ranked``."""
    lane = _tls.lane
    if lane is None:
        return NO_SPAN
    return _Span(lane, "wf.rank", trace_id, dict(rows_in=int(rows_in), limit=int(limit)))


def window_ranked(span, rows_in: int, rows_out: int, partitions: int) -> None:
    """What the bucket ``span`` (a ``window_rank``) covers put out:
    ``rows_out`` rows (at most ``limit`` a partition under a limit, else
    ``rows_in``) over ``partitions`` partitions. The task's two counters add
    up the rows in and out: rows in over the events that came in is what a
    close hands the ranking."""
    span.note(rows_out=int(rows_out), partitions=int(partitions))
    lane = _tls.lane
    if lane is not None:
        lane.metrics.add("arroyo_worker_window_fn_rows_in", int(rows_in))
        lane.metrics.add("arroyo_worker_window_fn_rows_out", int(rows_out))


def pane_cache(bins_per_window: int, cached_rows: int, closes: str) -> None:
    """The bins a sliding aggregate holds on the host, extracted off the
    device and still feeding windows to come (windows/sliding.py
    _bin_cache): the rows every checkpoint has to write beside the device
    table's. The task's gauge, beside the table's, as each drain leaves the
    cache: the bins that landed in, the windows that left taken off.
    ``closes``: how the aggregate closes a window, ``running`` or ``full
    (<why it cannot slide>)``."""
    lane = _tls.lane
    if lane is not None:
        lane.metrics.panes = {"bins_per_window": int(bins_per_window),
                              "cached_rows": int(cached_rows), "closes": closes}


def table_state(span, capacity: int, live_slots: int,
                probe_rounds: Optional[int] = None,
                narrow_steps: Optional[int] = None) -> None:
    """The slot table's capacity (a sharded table's: all its shards') and
    the slots live when it closes a window, takes a snapshot or grows (the
    moments it is fullest, just before closing bins give their regions
    back): args ``cap`` and ``live`` of the span that covers the moment,
    and the task's gauges. A sharded table's close or snapshot also says
    how many ``probe_rounds`` its steps ran since the last one said (the
    shard that ran the most; ``device.max-probes`` a step at most): over
    the ``agg.dispatch`` spans between the two, the rounds a step; and how
    many of those steps ran behind their exchange at a narrow width on every
    shard (``narrow_steps``; parallel/sharded_agg.py ``_rungs``)."""
    args = dict(cap=int(capacity), live=int(live_slots))
    if probe_rounds is not None:
        args["probe_rounds"] = int(probe_rounds)
    if narrow_steps is not None:
        args["narrow_steps"] = int(narrow_steps)
    span.note(**args)
    _set_table(capacity, live_slots)


def _set_table(capacity: int, live_slots: int) -> None:
    lane = _tls.lane
    if lane is not None:
        lane.metrics.table = {"capacity": int(capacity), "live_slots": int(live_slots)}


def table_grew(span, before: int, after: int, live_slots: int) -> None:
    """The slot table ran out of regions and grew (ops/slot_agg.py _grow,
    whose agg.grow span this is given; it holds both capacities already):
    the counter, the gauges and a job event."""
    span.note(live=int(live_slots))
    _set_table(after, live_slots)
    lane = _tls.lane
    if lane is None:
        return
    lane.metrics.add("arroyo_worker_table_grows")
    from .events import recorder as events

    job, node, subtask = lane.ident
    events.record(
        job, "INFO", "TABLE_GROWN",
        message=f"slot table grew from {before:,} to {after:,} slots "
                f"({live_slots:,} live)",
        node=node, subtask=subtask,
        data={"capacity_before": before, "capacity_after": after,
              "live_slots": live_slots})


class window:
    """``with window(end_micros): agg.extract_start(...)`` — spans opened
    below take this trace_id unless they give one."""

    __slots__ = ("trace_id", "lane", "before")

    def __init__(self, trace_id: int):
        self.trace_id = trace_id

    def __enter__(self):
        self.lane = _tls.lane
        if self.lane is not None:
            self.before, self.lane.trace_id = self.lane.trace_id, self.trace_id
        return self

    def __exit__(self, *exc):
        if self.lane is not None:
            self.lane.trace_id = self.before
        return False


# ------------------------------------------------------- the watch thread

_open_waits: dict = {}   # id(wait) -> the _Wait for the device, while it lasts
_bound: dict = {}        # Lane -> the thread it is bound to
_watch_lock = threading.Lock()
_watch: Optional["_Watch"] = None
_WATCH_IDENT = (None, "watch", None)  # whose records the pulse's marks are
_stall_events: dict = {}  # job -> [its last DEVICE_STALLED's stamp, stalls since]
_CLK_TCK = os.sysconf("SC_CLK_TCK") if hasattr(os, "sysconf") else 100


def _sched_now() -> dict:
    """What the kernel says it kept from this process so far, each left out
    where it cannot be read: ``run_delay_ms``, the time on a run queue with
    no CPU given (/proc/self/schedstat); ``invol_switches``, the process's
    involuntary context switches (getrusage); ``steal_ms``, the time the
    machine's CPUs ran another guest (/proc/stat, all CPUs); and ``cpu_ms``,
    the CPU time of all the process's threads, which a sandbox that hides
    the others still counts: none over a gap says the process was held off
    its CPUs, one thread's worth that one thread ran. The watch differences
    two readings."""
    out = {"cpu_ms": time.process_time() * 1e3}
    try:
        with open("/proc/self/schedstat") as f:
            out["run_delay_ms"] = int(f.read().split()[1]) / 1e6
    except (OSError, IndexError, ValueError):
        pass
    if resource is not None:
        out["invol_switches"] = resource.getrusage(resource.RUSAGE_SELF).ru_nivcsw
    try:
        with open("/proc/stat") as f:
            cpu = f.readline().split()
        if cpu[0] == "cpu" and len(cpu) > 8:
            out["steal_ms"] = int(cpu[8]) * 1e3 / _CLK_TCK
    except (OSError, IndexError, ValueError):
        pass
    return out


def _sched_delta(now: dict, before: dict) -> dict:
    return {k: round(v - before[k], 3) for k, v in now.items() if k in before}


def _device_memory() -> tuple:
    """(``bytes_in_use`` per local device, the milliseconds the asking
    took): an allocator call, as a probe of the runtime. Back in
    microseconds while a buffer's ``is_ready`` stays false, it says the
    runtime lives and its queue does not move; where it blocks, the caller
    writes its mark late and the second number says by how much."""
    jax = sys.modules.get("jax")  # a wait for the device has imported it
    t0 = time.monotonic_ns()
    in_use = None
    if jax is not None:
        try:
            in_use = [(d.memory_stats() or {}).get("bytes_in_use")
                      for d in jax.local_devices()]
        except Exception:  # noqa: BLE001 - a probe: what it raises is not the watch's to raise
            pass
    return in_use, (time.monotonic_ns() - t0) / 1e6


def _threads_now() -> list:
    """[thread name, "dir/file.py:line function"] of every Python thread's
    innermost frame, and behind ``<`` the nearest frame below it that is not
    the standard library's or an installed package's (a thread asleep in
    ``threading.py`` says whose sleep it is); at most _MAX_THREADS_NAMED,
    the tasks' threads first, then the engine's other threads."""
    def where(frame) -> str:
        code = frame.f_code
        return ("/".join(code.co_filename.split(os.sep)[-2:])
                + f":{frame.f_lineno} {code.co_name}")

    names = {t.ident: t.name for t in threading.enumerate()}
    with _watch_lock:
        tasks = {t.name for t in _bound.values()}
    out = []
    for ident, frame in sys._current_frames().items():
        text, caller = where(frame), frame
        for _ in range(16):
            if caller is None or os.sep + "lib" + os.sep + "python" not in caller.f_code.co_filename:
                break
            caller = caller.f_back
        if caller is not None and caller is not frame:
            text += " < " + where(caller)
        out.append([names.get(ident, str(ident)), text])
    out.sort(key=lambda e: (e[0] not in tasks, not e[0].startswith("arroyo"), e[0]))
    return out[:_MAX_THREADS_NAMED]


class _Watch(threading.Thread):
    """The process's one watch thread (module docstring). It holds no lock
    a task takes: the table and the lanes' ``open`` are read as they are."""

    def __init__(self):
        threading.Thread.__init__(self, name="arroyo-watch", daemon=True)
        self.stop = threading.Event()
        self.late_max = 0        # ns, over the second being counted
        self.late_before = 0     # ns, the second before it
        self.ticks = 0
        self.sched: dict = {}    # the scheduler's counters as the last watch.tick found them

    def run(self) -> None:
        ring = _ring()
        self.sched = _sched_now()
        due = time.monotonic_ns() + WATCH_TICK_NS
        second = due + 1_000_000_000
        while not _nap(self.stop, (due - time.monotonic_ns()) / 1e9):
            now = time.monotonic_ns()
            late = max(0, now - due)
            self.late_max = max(self.late_max, late)
            self.ticks += 1
            try:
                self.look(ring, now, late)
            except Exception:  # noqa: BLE001 - the watch outlives a mark it could not write
                _log.exception("the watch could not write a stall down")
            if now >= second:
                sched = _sched_now()
                ring.append(("watch.tick", _WATCH_IDENT, None, now, now,
                             dict(late_max_ms=self.late_max / 1e6, ticks=self.ticks,
                                  **_sched_delta(sched, self.sched))))
                self.late_before, self.late_max, self.ticks = self.late_max, 0, 0
                self.sched, second = sched, now + 1_000_000_000
                with _watch_lock:  # a thread that ended without unbind()
                    for lane in [ln for ln, t in _bound.items() if not t.is_alive()]:
                        del _bound[lane]
                    if not _bound:
                        self.stop.set()
            due = max(due, time.monotonic_ns()) + WATCH_TICK_NS  # no burst after a long sleep

    def look(self, ring, now: int, late: int) -> None:
        """Flag every wait for the device that has been open for STALL_NS
        and is not flagged yet."""
        waits = list(_open_waits.values())
        for w in waits:
            if now - w.t0 >= STALL_NS and not w.flagged:
                w.flagged = True
                self.flag(ring, w, waits, now, late)

    def flag(self, ring, w, waits: list, now: int, late: int) -> None:
        job, node, subtask = w.lane.ident
        with _watch_lock:
            lanes = list(_bound)
        # every other open wait, then what each task's own thread is inside of
        spans_open = [(x.lane.ident[1], x.name, x.t0) for x in waits if x is not w]
        spans_open += [(ln.ident[1],) + o for ln in lanes for o in (ln.open,)
                       if o is not None and o != (w.name, w.t0)]
        in_use, asked_ms = _device_memory()
        args = dict(
            waited=w.name, program=(w.args or {}).get("program"),
            age_ms=(now - w.t0) / 1e6,
            # this tick's, and the worst of this second and the last
            watch_late_ms=[late / 1e6, max(self.late_max, self.late_before) / 1e6],
            open=[[n, name, (now - t0) / 1e6] for n, name, t0 in dict.fromkeys(spans_open)],
            # the scheduler's counters since the last watch.tick, a second ago at most
            threads=_threads_now(), sched=_sched_delta(_sched_now(), self.sched),
            bytes_in_use=in_use, memory_stats_ms=asked_ms)
        stamp = time.monotonic_ns()  # after the probe: a blocked one shows here
        ring.append(("device.stall", w.lane.ident, w.trace_id, now, stamp, args))
        m = w.lane.metrics
        m.add("arroyo_worker_device_stalls")
        m.device_stall_max_ms = max(m.device_stall_max_ms, args["age_ms"])
        last = _stall_events.setdefault(job, [None, 0])
        last[1] += 1
        if last[0] is None or now - last[0] >= STALL_EVENT_NS:
            from .events import recorder as events

            events.record(
                job, "WARN", "DEVICE_STALLED",
                message=f"{w.name} has waited {args['age_ms']:,.0f} ms for the device"
                        f" ({args['program'] or 'program not named'}); "
                        f"{last[1]} such wait(s) since the last of these events",
                node=node, subtask=subtask,
                data={"stalls": last[1], "waited": w.name, "program": args["program"],
                      "age_ms": args["age_ms"], "watch_late_ms": args["watch_late_ms"][0]})
            last[0], last[1] = now, 0


def _nap(stop: threading.Event, seconds: float) -> bool:
    """The watch's sleep; True when it is to leave."""
    return stop.wait(max(0.0, seconds))


# ------------------------------------------------------- reading the ring


def spans(name: Optional[str] = None, t0: Optional[int] = None,
          t1: Optional[int] = None, node: Optional[str] = None,
          job: Optional[str] = None) -> list[Span]:
    """The recorded spans and marks of every thread, oldest first; those
    named ``name``, of operator ``node`` (of job ``job``), that overlap
    [t0, t1] (``time.monotonic_ns()``). Safe to call while tasks run."""
    with _rings_lock:
        rings = [r for _t, r in _rings]
    out = []
    for ring in rings:
        for _ in range(8):
            try:
                records = list(ring)
                break
            except RuntimeError:  # appended to while copied
                continue
        else:
            records = []
        for n, ident, trace_id, a, b, args in records:
            if ((name is None or n == name)
                    and (node is None or ident[1] == node)
                    and (job is None or ident[0] == job)
                    and (t0 is None or b >= t0) and (t1 is None or a <= t1)):
                out.append(Span(n, ident[0], ident[1], ident[2], trace_id, a, b, args))
    out.sort(key=lambda s: s.t0_ns)
    return out


def account_over(node: str, t0: Optional[int] = None, t1: Optional[int] = None,
                 job: Optional[str] = None) -> Optional[dict]:
    """One operator's time account over [t0, t1]: its last ``task.account``
    mark inside less its first (summed over its subtasks), with ``wall``,
    the seconds between the two. ``cpu`` is thread CPU, the waits are
    TaskMetrics.account's, ``self_time``/``self_cpu`` the profiler's sums;
    wall - cpu - inbox_wait - put_wait - device_wait is the time the thread
    could have run and did not. None without two marks."""
    by_sub: dict = {}
    for s in spans("task.account", t0, t1, node, job):
        by_sub.setdefault(s.subtask, []).append(s)
    out: dict = {}
    for marks in by_sub.values():
        if len(marks) >= 2:
            first, last = marks[0], marks[-1]
            out["wall"] = out.get("wall", 0.0) + (last.t0_ns - first.t0_ns) / 1e9
            for k, v in last.args.items():
                out[k] = out.get(k, 0.0) + v - first.args[k]
    return out or None


def stamps(name: str, node: str, job: Optional[str] = None,
           t0: Optional[int] = None) -> list[tuple]:
    """(trace_id, t0_ns, t1_ns) of every ``name`` record of one operator
    that carries a trace_id, oldest first; with ``t0``, those not over
    before it."""
    return [(s.trace_id, s.t0_ns, s.t1_ns)
            for s in spans(name, t0, None, node, job) if s.trace_id is not None]


def crossings(name: str, node, values: Iterable[int], job: Optional[str] = None,
              t0: Optional[int] = None) -> list[Optional[int]]:
    """Per value, the start (ns) of the first ``name`` record of ``node``
    (after ``t0``) whose trace_id is at or past it; None where none is. For
    a window that ends at E: ``crossings("wm.in", node, [E])`` is when its
    closing watermark reached the operator. ``node`` may be several
    operators: then the moment the value had crossed at the last of them."""
    values = list(values)
    if not isinstance(node, str):
        per_node = [crossings(name, n, values, job, t0) for n in node]
        return [None if None in ts else max(ts) for ts in zip(*per_node)]
    recs = stamps(name, node, job, t0)
    return [next((t for tid, t, _ in recs if tid >= v), None) for v in values]
