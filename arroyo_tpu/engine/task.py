"""Physical subtask run loop.

Equivalent of the reference's operator_run_behavior
(crates/arroyo-operator/src/operator.rs:863-996): a select-loop over control
messages, the fused input stream, and a tick interval; handles
SignalMessage::{Barrier, Watermark, Stop, EndOfData} (:624-676); aligned
barriers block inputs that already delivered the current epoch's barrier
(:966-975, CheckpointCounter lib.rs:71); watermark merge is the min over
per-input watermarks with Idle short-circuit (context.rs:33-84
WatermarkHolder).
"""

from __future__ import annotations

import queue as _queue
import threading
import time
import traceback
from collections import deque
from typing import Optional, Union

from ..batch import KEY_FIELD, TIMESTAMP_FIELD, Batch
from ..faults import fault_point
from ..obs import trace as _trace
from ..operators.base import Operator, OperatorContext, SourceOperator
from ..operators.collector import Collector
from ..types import (
    CheckpointBarrier,
    CheckpointEvent,
    ControlMessage,
    ControlResp,
    Signal,
    SignalKind,
    SourceFinishType,
    TaskInfo,
    Watermark,
)
from .queues import TaskInbox


class WatermarkHolder:
    """Min-merge of per-input watermarks (reference context.rs:33-84)."""

    def __init__(self, n_inputs: int):
        self._wms: dict[int, Optional[Watermark]] = {i: None for i in range(n_inputs)}

    def set(self, input_index: int, wm: Watermark) -> None:
        if input_index in self._wms:
            self._wms[input_index] = wm

    def remove(self, input_index: int) -> None:
        self._wms.pop(input_index, None)

    def merged(self) -> Optional[Watermark]:
        """None until every live input has reported; Idle only if all idle."""
        if not self._wms:
            return None
        values = list(self._wms.values())
        if any(v is None for v in values):
            return None
        non_idle = [v.value for v in values if not v.is_idle]
        if not non_idle:
            return Watermark.idle()
        return Watermark.event_time(min(non_idle))


class SourceContext:
    """What a SourceOperator.run sees: control polling + checkpoint helper
    (reference SourceContext / start_checkpoint, operator.rs:313-341)."""

    def __init__(self, task: "Task"):
        self._task = task
        self.ctx = task.ctx

    def poll_control(self) -> Optional[ControlMessage]:
        # connector run loops poll between batches, so this doubles as the
        # source-task liveness beat (Engine.heartbeat) AND the time-based
        # coalescing flush point for source emissions
        self._task.last_progress = time.monotonic()
        self._task.collector.flush_expired(self._task.last_progress)
        if self._task.profiler is not None:
            # incremental self-time: live snapshots must show a streaming
            # source's busy%, not wait for run() to return
            self._task.profiler.source_tick()
            self._task.profiler.refresh()
        if self._task.lane is not None:
            self._task.lane.account()
        try:
            return self._task.control_queue.get_nowait()
        except _queue.Empty:
            return None

    def start_checkpoint(self, barrier: CheckpointBarrier) -> None:
        self._task.run_source_checkpoint(barrier)


class Task:
    def __init__(
        self,
        task_info: TaskInfo,
        operator: Union[Operator, SourceOperator],
        inbox: Optional[TaskInbox],
        collector: Collector,
        ctx: OperatorContext,
        resp_queue: "_queue.Queue[ControlResp]",
        n_inputs: int = 0,
    ):
        self.task_info = task_info
        self.operator = operator
        self.inbox = inbox
        self.collector = collector
        self.ctx = ctx
        self.resp_queue = resp_queue
        self.n_inputs = n_inputs
        self.control_queue: "_queue.Queue[ControlMessage]" = _queue.Queue()
        self.thread: Optional[threading.Thread] = None
        self.is_source = isinstance(operator, SourceOperator)
        # liveness beat: updated every run-loop iteration / control poll /
        # backpressure wait; a hung task stops beating (Engine.heartbeat)
        self.last_progress = time.monotonic()  # concurrency: single-writer — monotonic heartbeat timestamp owned by the task thread; watchdog reads are GIL-atomic float snapshots and only ever see a slightly stale beat
        # epoch being snapshotted right now (None otherwise): an exception
        # mid-checkpoint stamps its OPERATOR_PANIC event with the epoch
        self._ckpt_epoch: Optional[int] = None
        # True when the run loop drained cleanly (graceful EOF or
        # checkpoint-then-stop): only such finishes carry final/durable
        # state and may stand in for epoch coverage (ControlResp.clean)
        self.finished_clean = True
        from ..metrics import registry as _metrics_registry

        self.metrics = _metrics_registry.task(
            task_info.job_id, task_info.node_id, task_info.subtask_index
        )
        # cost attribution (obs/profile.py): self-time wrapping for every
        # operator hook, state-size gauges, and the key-skew sketch. None
        # when profile.enabled is off — the run loop then does zero extra
        # work. Built AFTER the table-manager restore (Engine.build runs
        # restore before constructing the Task) so the sketch resumes the
        # exact summary the checkpoint persisted.
        from ..obs.profile import make_profiler

        self.profiler = make_profiler(self.metrics, task_info,
                                      ctx.table_manager, operator)
        # the span ring (obs/trace.py): bound on the task's own thread,
        # under the same switch as the profiler
        self.lane: Optional[_trace.Lane] = None
        # one key space per sketch: an operator that keyed-shuffles its
        # OUTPUT is observed at the collector's shuffle boundary (the new
        # routing keys — what a re-keying operator is about to melt a
        # downstream subtask with); only operators that do NOT shuffle
        # observe their keyed INPUT (window/join insert paths). Feeding
        # both would mix two hash spaces and double-count pass-throughs.
        from ..graph import EdgeType as _EdgeType

        self.observe_input_keys = not any(
            len(e.dests) > 1 and e.edge_type != _EdgeType.FORWARD
            for e in collector.out_edges)
        if inbox is not None:
            self.metrics.queue_size = inbox.row_budget * inbox.n_inputs
            # an idle queue is an EMPTY queue, not a full one
            self.metrics.queue_rem = self.metrics.queue_size
            inbox.metrics = self.metrics  # consumer-side transit histogram
            # a fetch worker whose close has landed wakes this task's loop
            ctx.wake = inbox.wake
        collector.metrics = self.metrics
        # terminal operators (sinks) observe end-to-end event latency
        self._terminal = not collector.out_edges

    def _observe_sink_latency(self, batch: Batch) -> None:
        """Sink-side end-to-end latency: wall clock at arrival minus the
        batch's newest event timestamp (seconds)."""
        if TIMESTAMP_FIELD not in batch:
            return
        ts_max = batch[TIMESTAMP_FIELD].max()
        self.metrics.sink_event_latency.observe(
            max(0.0, time.time() - float(ts_max) / 1e6))

    # ------------------------------------------------------------------ API

    def start(self) -> None:
        name = f"{self.task_info.node_id}-{self.task_info.subtask_index}"
        self.thread = threading.Thread(target=self._run_guarded, name=name, daemon=True)
        self.thread.start()

    def join(self, timeout: Optional[float] = None) -> None:
        if self.thread:
            self.thread.join(timeout)

    def _resp(self, kind: str, **kw) -> None:
        self.resp_queue.put(
            ControlResp(kind=kind, node_id=self.task_info.node_id,
                        subtask_index=self.task_info.subtask_index, **kw)
        )

    # ------------------------------------------------------------- run loops

    def _beat(self) -> None:
        self.last_progress = time.monotonic()

    def _run_guarded(self) -> None:
        try:
            # a producer blocked on a full inbox is backpressured, not hung:
            # the inbox's budget wait loop beats through this thread hook
            threading.current_thread().arroyo_beat = self._beat  # type: ignore[attr-defined]
            if self.profiler is not None:
                self.lane = _trace.bind(
                    self.task_info.job_id, self.task_info.node_id,
                    self.task_info.subtask_index, self.metrics)
            self._resp("task_started")
            if self.is_source:
                self._run_source()
            else:
                self._run_operator()
            _trace.unbind()
            self._resp("task_finished", clean=self.finished_clean)
        except Exception:
            _trace.unbind()
            tb = traceback.format_exc()
            # structured event BEFORE the failure propagates: the job event
            # feed names the operator/subtask (+ epoch when the panic hit
            # mid-checkpoint) with a stable traceback digest, so a crashed
            # pipeline is diagnosable from `logs` without stderr archaeology
            from ..obs.events import recorder as _events
            from ..obs.events import traceback_digest

            dig = traceback_digest(tb)
            _events.record(
                self.task_info.job_id, "ERROR", "OPERATOR_PANIC",
                message=dig["error"] or "operator raised",
                node=self.task_info.node_id,
                subtask=self.task_info.subtask_index,
                epoch=self._ckpt_epoch,
                data={"digest": dig["digest"],
                      "operator": self.task_info.operator_name},
            )
            self._resp("task_failed", error=tb)

    def _run_source(self) -> None:
        op: SourceOperator = self.operator  # type: ignore[assignment]
        prof = self.profiler
        op.on_start(self.ctx)
        sctx = SourceContext(self)
        if prof is None:
            finish = op.run(sctx, self.collector)
            op.on_close(self.ctx, self.collector)
        else:
            # thread-CPU accumulates incrementally via source_tick (the
            # connector poll path) so LIVE snapshots carry the source's
            # busy%; this first tick just stamps the mark, the final one
            # catches the tail after run() returns
            prof.source_tick()
            finish = op.run(sctx, self.collector)
            prof.source_tick()
            t0 = prof.begin()
            op.on_close(self.ctx, self.collector)
            prof.end("close", t0)
            prof.refresh(force=True)
        if finish == SourceFinishType.GRACEFUL:
            # persist the drained offset so a restore from ANY later epoch
            # does not replay this source (state is constant after EOF and
            # all emitted data precedes downstream epoch barriers)
            if prof is not None:
                prof.checkpoint_sketch()
            self.ctx.table_manager.checkpoint("final", self.ctx.watermark())
            self.collector.broadcast(Signal.end_of_data())
        elif finish == SourceFinishType.IMMEDIATE:
            # stopped/aborted: no final snapshot exists, so this exit must
            # NOT count as epoch coverage (a restore would replay from zero)
            self.finished_clean = False
            self.collector.broadcast(Signal.stop())
        # FINAL: checkpoint-then-stop already broadcast the barrier; end data.
        if finish == SourceFinishType.FINAL:
            self.collector.broadcast(Signal.end_of_data())

    def run_source_checkpoint(self, barrier: CheckpointBarrier) -> None:
        """Checkpoint table state then broadcast the barrier downstream
        (reference operator.rs:313-341)."""
        self._resp("checkpoint_event", checkpoint_event=CheckpointEvent(
            barrier.epoch, self.task_info.node_id, self.task_info.subtask_index,
            int(time.time() * 1e6), "started_checkpointing"))
        self._ckpt_epoch = barrier.epoch
        prof = self.profiler
        t0 = prof.begin() if prof is not None else None
        if prof is not None:
            prof.checkpoint_sketch()
        meta = self.ctx.table_manager.checkpoint(barrier.epoch, self.ctx.watermark())
        if prof is not None:
            prof.end("checkpoint", t0)
            # the snapshot CPU is attributed above; the source's rolling
            # process clock must not count it again
            prof.source_reset()
            prof.refresh(force=True)
        # chaos hook: a crash HERE is the worst case — state files for this
        # epoch are on disk but the epoch never completes (no job metadata),
        # so recovery must ignore them and restore the previous epoch
        fault_point("worker", barrier=barrier.epoch,
                    node=self.task_info.node_id,
                    subtask=self.task_info.subtask_index)
        self.collector.broadcast(Signal.barrier_of(barrier))
        self._ckpt_epoch = None
        self._resp("checkpoint_completed", epoch=barrier.epoch, subtask_metadata=meta)

    def _run_operator(self) -> None:
        op: Operator = self.operator  # type: ignore[assignment]
        prof = self.profiler
        lane = self.lane
        op.on_start(self.ctx)
        # whole-segment compilation (engine/segment.py): a chained run
        # marked compilable at plan time processes batches through ONE
        # jitted call instead of the per-member hook loop; the runner owns
        # compile/verify/fallback and delegates to op.process_batch when
        # the segment is (or becomes) interpreted. On a mesh-marked
        # segment over a sharded aggregate the runner goes one further:
        # the traced prefix AND the keyed exchange/merge run as one
        # shard_map'd jitted program per micro-batch, so the device never
        # round-trips rows to the host between segment and aggregate.
        # Signals below always take the interpreted hooks — a checkpoint
        # barrier snapshots through the operator, which reads back
        # canonical (placement-independent) state, keeping fused
        # and host-path checkpoints byte-identical.
        from .segment import runner_for

        runner = runner_for(op, self.ctx, self.metrics)
        process = op.process_batch if runner is None else runner.process_batch
        holder = WatermarkHolder(self.n_inputs)
        finished: set[int] = set()
        blocked: set[int] = set()
        held: dict[int, deque] = {}
        barrier_inputs: set[int] = set()
        current_barrier: Optional[CheckpointBarrier] = None
        pending: deque[tuple[int, Union[Batch, Signal]]] = deque()
        last_merged: Optional[Watermark] = None
        stopping = False
        stop_epoch: Optional[int] = None

        tick_us = op.tick_interval_micros()
        tick_s = tick_us / 1e6 if tick_us else None
        last_tick = time.monotonic()
        # an operator that stages its input (the window aggregates take what
        # the inbox holds, up to a device step's width, before they run
        # their hook) is told when the inbox has run dry
        stages = type(op).flush_staged is not Operator.flush_staged

        def flush_staged():
            t0 = prof.begin() if prof is not None else None
            op.flush_staged(self.ctx, self.collector)
            if prof is not None:
                prof.end("process", t0)

        def merged_watermark_changed():
            nonlocal last_merged
            merged = holder.merged()
            if merged is not None and merged != last_merged:
                last_merged = merged
                self.ctx.last_watermark = merged
                if not merged.is_idle:
                    # watermark-lag gauge: lag (processing time minus this
                    # value) is derived at metrics-export time
                    self.metrics.watermark_micros = merged.value
                    # the watermark trail: when this value reached the task
                    _trace.mark("wm.in", merged.value)
                # watermark handling (window closes) is data-path work
                # driven by the stream: it attributes to "process"
                t0 = prof.begin() if prof is not None else None
                out = op.handle_watermark(merged, self.ctx, self.collector)
                if prof is not None:
                    prof.end("process", t0)
                if out is not None:
                    self.collector.broadcast(Signal.watermark_of(out))

        def run_checkpoint(b: CheckpointBarrier):
            self._resp("checkpoint_event", checkpoint_event=CheckpointEvent(
                b.epoch, self.task_info.node_id, self.task_info.subtask_index,
                int(time.time() * 1e6), "started_checkpointing"))
            self._ckpt_epoch = b.epoch
            t0 = prof.begin() if prof is not None else None
            op.handle_checkpoint(b, self.ctx, self.collector)
            if prof is not None:
                prof.checkpoint_sketch()
            meta = self.ctx.table_manager.checkpoint(b.epoch, self.ctx.watermark())
            if prof is not None:
                prof.end("checkpoint", t0)
                # barrier time is when host tables mirror device state:
                # the freshest moment for the state-size gauges
                prof.refresh(force=True)
            # chaos hook: mirror of run_source_checkpoint — crash with this
            # subtask's epoch state written but the epoch incomplete
            fault_point("worker", barrier=b.epoch,
                        node=self.task_info.node_id,
                        subtask=self.task_info.subtask_index)
            self.collector.broadcast(Signal.barrier_of(b))
            self._ckpt_epoch = None
            self._resp("checkpoint_completed", epoch=b.epoch, subtask_metadata=meta)

        def try_complete_alignment():
            """If every live input delivered the barrier, checkpoint and
            unblock held inputs; honors checkpoint-then-stop."""
            nonlocal current_barrier, stopping, stop_epoch
            if current_barrier is None:
                return
            live = set(range(self.n_inputs)) - finished
            if live <= barrier_inputs:
                run_checkpoint(current_barrier)
                if current_barrier.then_stop:
                    stopping = True
                    stop_epoch = current_barrier.epoch
                current_barrier = None
                barrier_inputs.clear()
                blocked.clear()
                # drain held items back through the loop, preserving
                # per-input order (budget released as they process)
                for i in sorted(held):
                    pending.extend(held[i])
                held.clear()

        def drain_control():
            """Out-of-band engine->task messages; commits arrive here after
            the epoch's job-level metadata is durable (reference
            ControlMessage::Commit via WorkerGrpc, operator.rs:1157)."""
            while True:
                try:
                    msg = self.control_queue.get_nowait()
                except _queue.Empty:
                    return
                if msg.kind == "commit" and msg.epoch is not None:
                    op.handle_commit(msg.epoch, self.ctx)

        while True:
            self.last_progress = time.monotonic()
            drain_control()
            # time-based coalescing flush: between items, pending sub-
            # threshold rows older than max-delay-ms go out
            self.collector.flush_expired(self.last_progress)
            if lane is not None:
                lane.account()
            if pending:
                idx, item = pending.popleft()
            else:
                if stages and not (self.inbox is not None and self.inbox.has_items()):
                    # nothing more to take: no row waits in the operator
                    # while its task sleeps
                    flush_staged()
                # an idle task still drops its time account into the span
                # ring on time
                timeout = 0.5 if lane is None else lane.account_due_s()
                if tick_s is not None:
                    timeout = min(timeout, max(tick_s - (time.monotonic() - last_tick), 0.0))
                deadline_f = self.collector.flush_deadline()
                if deadline_f is not None:
                    # wake exactly at the pending rows' delay deadline —
                    # waiting a full max-delay from NOW would stretch the
                    # worst-case hold to ~2x the knob
                    timeout = min(timeout, max(deadline_f - time.monotonic(), 0.0))
                if self.inbox is None:
                    got = None
                elif lane is None or self.inbox.has_items():
                    got = self.inbox.get(timeout=timeout)
                else:
                    # starved: nothing to do until upstream sends
                    with _trace.wait(_trace.INBOX_WAIT, "task.inbox_wait"):
                        got = self.inbox.get(timeout=timeout)
                if got is None:
                    if self.inbox is not None and self.inbox.closed:
                        self.finished_clean = False
                        return  # engine aborted the pipeline
                    if op.closes_in_flight():
                        # a close's host copy landed (TaskInbox.wake): its
                        # rows and the watermark held behind them leave now,
                        # not at the operator's next input. Legal between
                        # any two items: every hook opens with this drain,
                        # and a barrier force-drains before its snapshot
                        t0 = prof.begin() if prof is not None else None
                        op.drain_ready(self.ctx, self.collector)
                        if prof is not None:
                            prof.end("process", t0)
                    if tick_s is not None and time.monotonic() - last_tick >= tick_s:
                        t0 = prof.begin() if prof is not None else None
                        op.handle_tick(self.ctx, self.collector)
                        if prof is not None:
                            prof.end("tick", t0)
                        last_tick = time.monotonic()
                    if prof is not None:
                        # idle wait: the throttled state-gauge/late-row sweep
                        prof.refresh()
                    if self.n_inputs == 0 or len(finished) == self.n_inputs:
                        break
                    continue
                idx, item = got
            if idx in blocked:
                held.setdefault(idx, deque()).append((idx, item))
                continue

            if isinstance(item, Batch):
                self.metrics.add("arroyo_worker_batches_recv")
                self.metrics.add("arroyo_worker_messages_recv", item.num_rows)
                self.metrics.add("arroyo_worker_bytes_recv", item.nbytes())
                if prof is None:
                    process(item, self.ctx, self.collector, input_index=idx)
                else:
                    if self.observe_input_keys and KEY_FIELD in item:
                        # keyed-insert boundary of the skew sketch
                        # (shuffling operators feed at the collector's
                        # shuffle boundary instead — never both)
                        self.metrics.observe_keys(item.keys)
                    t0 = prof.begin()
                    process(item, self.ctx, self.collector, input_index=idx)
                    prof.end("process", t0)
                if self._terminal and item.num_rows:
                    self._observe_sink_latency(item)
                self.inbox.release(idx, item)
                self.metrics.queue_rem = self.metrics.queue_size - self.inbox.used_rows()
                continue

            sig: Signal = item
            if sig.kind == SignalKind.WATERMARK:
                holder.set(idx, sig.watermark)
                merged_watermark_changed()
            elif sig.kind == SignalKind.BARRIER:
                b = sig.barrier
                if current_barrier is not None and b.epoch < current_barrier.epoch:
                    # stale barrier of a subsumed epoch straggling in after
                    # the controller's stuck-checkpoint retry: a newer
                    # alignment is already in progress — joining the old one
                    # would skew this input's epoch tracking permanently
                    continue
                if current_barrier is not None and b.epoch > current_barrier.epoch:
                    # a retried epoch overtook a wedged alignment (the
                    # controller subsumed the old epoch after its
                    # checkpoint.timeout-ms): abandon it and replay the held
                    # traffic — the blocked inputs' own newer barriers sit at
                    # the front of their held queues and re-join below
                    current_barrier = None
                    barrier_inputs.clear()
                    blocked.clear()
                    for i in sorted(held):
                        pending.extend(held[i])
                    held.clear()
                if current_barrier is None:
                    current_barrier = b
                    self._resp("checkpoint_event", checkpoint_event=CheckpointEvent(
                        b.epoch, self.task_info.node_id, self.task_info.subtask_index,
                        int(time.time() * 1e6), "started_alignment"))
                barrier_inputs.add(idx)
                blocked.add(idx)
                try_complete_alignment()
            elif sig.kind == SignalKind.END_OF_DATA:
                finished.add(idx)
                holder.remove(idx)
                merged_watermark_changed()
                if len(finished) == self.n_inputs:
                    t0 = prof.begin() if prof is not None else None
                    op.on_close(self.ctx, self.collector)
                    if prof is not None:
                        prof.end("close", t0)
                        prof.refresh(force=True)
                    self.collector.broadcast(Signal.end_of_data())
                    break
                # a pending alignment may now be complete
                try_complete_alignment()
            elif sig.kind == SignalKind.STOP:
                # hard stop: state since the last barrier is NOT persisted
                self.finished_clean = False
                # nothing drains this inbox from here on, and the producers
                # of the task's other inputs run until their own STOP
                # arrives: one that waited for room here (a source under
                # back-pressure cannot poll for its stop) would wait forever
                if stages:
                    flush_staged()  # the rows before the stop, as ever
                self.inbox.close()
                self.collector.broadcast(Signal.stop())
                break
            if stopping:
                # checkpoint-then-stop: everything after the stopping barrier
                # (held items, EndOfData) is post-snapshot and must NOT be
                # processed — it would mutate state past what was persisted.
                # Committing operators first wait for the engine's commit of
                # the stopping epoch (reference: CheckpointStopping sends
                # commits before workers exit) or their phase-1 data would
                # never be finalized.
                if op.is_committing() and stop_epoch is not None:
                    deadline = time.monotonic() + 30
                    committed = False
                    while time.monotonic() < deadline and not committed:
                        try:
                            msg = self.control_queue.get(timeout=0.1)
                        except _queue.Empty:
                            continue
                        if msg.kind == "commit" and msg.epoch is not None:
                            # honor EVERY commit (a straggling earlier epoch
                            # may land here too); done once the stopping
                            # epoch itself is committed
                            op.handle_commit(msg.epoch, self.ctx)
                            if msg.epoch == stop_epoch:
                                committed = True
                break
