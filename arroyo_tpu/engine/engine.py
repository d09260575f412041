"""Engine: logical graph -> physical tasks -> running pipeline.

Equivalent of crates/arroyo-worker/src/engine.rs: Program::from_logical (:214,
node x parallelism -> SubtaskNode; Forward = 1:1 queue, Shuffle/LeftJoin/
RightJoin = full bipartite queues :319-357), Engine::start (:521), and
construct_operator (:770-901, OperatorName -> constructor mapping). Single
process; the multi-host data plane arrives with the C++/DCN runtime, while
keyed exchange inside a TPU slice is lowered separately (arroyo_tpu.parallel).

The engine also plays the reference controller's checkpoint-coordination role
for SINGLE-worker runs (job_controller/mod.rs:325 start_checkpoint,
checkpoint_state.rs): it injects ControlMessage::Checkpoint into source tasks,
collects per-subtask checkpoint metadata, and writes the job-level metadata
marker once every subtask reports. Under an ``assignment`` (multi-worker
mode) the engine is a pure participant: it relays per-subtask acks upward
through ``coordinator_events`` and accepts externally-injected commits via
``deliver_commit`` — epoch completion is owned by the control plane's
CheckpointCoordinator (controller/checkpoint_state.py), so no worker can
finalize phase 2 against an epoch another worker never made durable.
"""

from __future__ import annotations

import queue as _queue
import threading
import time
from dataclasses import dataclass
from typing import Callable, Optional

from ..batch import Schema
from ..config import config
from ..graph import EdgeType, Graph, Node, OpName
from ..operators.base import Operator, OperatorContext, SourceOperator
from ..operators.collector import Collector, OutEdge
from ..state.tables import (
    TableManager,
    cleanup_checkpoints,
    compact_job,
    latest_complete_checkpoint,
    write_job_checkpoint_metadata,
)
from ..obs.events import recorder as events_recorder
from ..obs.trace import recorder as trace_recorder
from ..obs.trace import now_us, timeline_report
from ..types import CheckpointBarrier, ControlMessage, ControlResp, TaskInfo
from .queues import TaskInbox
from .task import Task

# op name -> constructor(node_config, node, subtask ctx...) registered by the
# operator modules (reference engine.rs:867-879 construct_operator match).
_CONSTRUCTORS: dict[OpName, Callable[[dict], object]] = {}


def register_operator(op: OpName):
    def deco(fn):
        _CONSTRUCTORS[op] = fn
        return fn

    return deco


def construct_operator(op: OpName, cfg: dict):
    if op not in _CONSTRUCTORS:
        raise ValueError(f"no constructor registered for operator {op}")
    return _CONSTRUCTORS[op](cfg)


@dataclass(frozen=True)
class CheckpointWait:
    """Outcome of Engine.checkpoint_and_wait. Truthy only when the epoch
    actually completed, so ``assert eng.checkpoint_and_wait(...)`` keeps
    working — but callers can now tell a drained pipeline ("finished") from
    a stuck barrier ("timeout", with the subtasks that never acked)."""

    outcome: str  # "completed" | "finished" | "timeout"
    missing: tuple = ()  # (node_id, subtask) pairs unacked at timeout
    # timeout only: the epoch's trace timeline (obs.trace.timeline_report),
    # naming the exact subtask whose barrier never arrived / never acked —
    # a chaos failure asserting on this repr is self-diagnosing
    report: str = ""

    def __bool__(self) -> bool:
        return self.outcome == "completed"

    def __repr__(self) -> str:
        if self.outcome == "timeout" and self.missing:
            base = (f"CheckpointWait(timeout, never acked: "
                    f"{list(self.missing)})")
            return f"{base}\n{self.report}" if self.report else base
        return f"CheckpointWait({self.outcome})"


class Engine:
    def __init__(
        self,
        graph: Graph,
        job_id: str = "job",
        storage_url: Optional[str] = None,
        restore_epoch: Optional[int] = None,
        assignment: Optional[dict] = None,
        worker_index: int = 0,
        network=None,
    ):
        """assignment: {(node_id, subtask) -> worker_index} places subtasks
        on workers (reference compute_assignments, states/scheduling.rs:56);
        None runs everything in this engine. Remote edges ride ``network``
        (engine.network.NetworkManager over the C++ data plane)."""
        # chaos: a configured fault plan (faults.plan / ARROYO_TPU__FAULTS__
        # PLAN) activates with fresh counters per engine incarnation, so a
        # restarted worker replays its faults deterministically
        from ..faults import install_from_config

        install_from_config()
        # plan fingerprint of the logical (pre-chaining) graph — the same
        # graph the control plane planned, so controller and worker agree on
        # the hash stamped into checkpoint metadata regardless of the
        # chaining setting. Computed before chain_graph rewrites node ids.
        self.plan_hash = self._fingerprint(graph)
        if config().get("pipeline.chaining.enabled"):
            from ..optimizer import chain_graph

            graph = chain_graph(graph)
        if assignment is not None:
            # assignments computed against a differently-chained graph would
            # silently place fused subtasks on worker 0; reject instead
            unknown = {nid for nid, _ in assignment} - set(graph.nodes)
            if unknown:
                raise ValueError(
                    f"assignment references node ids not in the (post-chaining) "
                    f"graph: {sorted(unknown)}; compute assignments against the "
                    f"same pipeline.chaining.enabled setting"
                )
        self.graph = graph
        self.job_id = job_id
        self.storage_url = storage_url or config().get("checkpoint.storage-url")
        self.restore_epoch = restore_epoch
        self.assignment = assignment
        self.worker_index = worker_index
        self.network = network
        # multi-worker mode: epoch completion is controller-owned; this
        # engine only relays acks up and accepts injected commits
        self.coordinated = assignment is not None
        self.coordinator_events: "_queue.Queue[dict]" = _queue.Queue()
        self._committed_through = restore_epoch or 0
        self.delivered_commits: list[int] = []
        # stable numeric node ids for Quad addressing
        self._node_index = {nid: i for i, nid in enumerate(sorted(graph.nodes))}
        self.resp_queue: "_queue.Queue[ControlResp]" = _queue.Queue()
        # concurrency: single-writer — tasks/_inboxes are populated by build() before start() spawns the collector; Thread.start() is the happens-before edge, after which nobody mutates the dicts
        self.tasks: dict[tuple[str, int], Task] = {}
        self._inboxes: dict[tuple[str, int], TaskInbox] = {}  # concurrency: single-writer — same build()-then-start() discipline as tasks
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._finished_tasks: set[tuple[str, int]] = set()
        # the subset that drained CLEANLY (graceful EOF / checkpoint-then-
        # stop): only these have final/durable state and may stand in for
        # epoch coverage; stop/abort exits must not, or an epoch could go
        # "complete" with a subtask's snapshot missing and a restore would
        # replay its source from zero
        self._clean_finished: set[tuple[str, int]] = set()
        # concurrency: single-writer — appended only by the collector thread; join()'s unlocked reads are GIL-atomic list snapshots (truthiness + element 0)
        self._failed: list[ControlResp] = []
        self._checkpoints: dict[int, dict[tuple[str, int], dict]] = {}
        self._completed_epochs: set[int] = set()
        self._resp_thread: Optional[threading.Thread] = None
        # concurrency: single-writer — set by build() before the collector thread exists (see tasks above)
        self._n_tasks = 0
        self.restored_watermark: Optional[int] = None
        # triggers that arrived before build() populated the source tasks —
        # replayed by start(); without this, a checkpoint trigger racing a
        # slow build (cold compile, big restore) is silently LOST and the
        # epoch wedges from birth
        self._running = False
        self._pending_triggers: list[tuple[int, bool]] = []
        # set by _abort(): distinguishes a torn-down engine from a drained
        # one — an externally-killed worker must not report "finished"
        self._aborted = False
        # armed by build() when restoring through an evolution mapping in
        # single-worker mode: the first durable epoch is the blue/green
        # cutover barrier (commits withheld until then)
        # concurrency: single-writer — armed by build() pre-thread; cleared only by the collector under _lock
        self._evolve_cutover_pending = False
        # obs relay (worker subprocesses only; relay_obs set by the worker
        # CLI): epoch-lifecycle spans AND structured job events recorded in
        # this process are forwarded over the JSON-lines protocol so the
        # CONTROLLER's recorders hold the whole job's timeline + event feed.
        # All worker->controller streams drain through ONE helper
        # (drain_relay) so a new event kind never grows a new hand-rolled
        # drain with its own ordering bugs.
        self.relay_obs = False
        self.span_events: "_queue.Queue[dict]" = _queue.Queue()
        # relay cursors: job-event seq and epochs already reported
        self._relay_event_seq = events_recorder.last_seq(job_id)
        self._relay_reported_epochs: set[int] = set()

    def _span(self, epoch: int, event: str, node: Optional[str] = None,
              subtask: Optional[int] = None, worker: Optional[int] = None,
              t_us: Optional[int] = None) -> None:
        t = now_us() if t_us is None else int(t_us)
        trace_recorder.record(self.job_id, epoch, event, node, subtask,
                              worker, t)
        if self.relay_obs:
            self.span_events.put({
                "event": "span", "epoch": epoch, "name": event, "node": node,
                "subtask": subtask, "worker": worker, "t_us": t,
            })

    def drain_relay(self, include_metrics: bool = False) -> list[dict]:
        """ONE drain for every worker->controller relay stream, in the
        order the controller must observe them (the PR 6 drain-ordering bug
        class, fixed structurally):

          1. epoch-lifecycle span events — must land in the controller's
             trace recorder BEFORE the coordinator ack that completes
             global coverage, or the persisted epoch trace misses the
             final ack span;
          2. structured job events (obs.events) recorded in this process
             since the last drain — a task's OPERATOR_PANIC precedes the
             worker's terminal "failed" event, which the CLI loop emits
             only after draining;
          3. the per-second metrics snapshot (caller-throttled: it rides
             the heartbeat cadence and its chaos drop);
          4. coordinator acks / completed epochs, strictly last.

        A fourth relayed event kind slots in here — never as a fourth
        hand-rolled drain in the CLI loop."""
        out: list[dict] = []
        while True:
            try:
                out.append(self.span_events.get_nowait())
            except _queue.Empty:
                break
        if self.relay_obs:
            evs = events_recorder.events(self.job_id,
                                         after_seq=self._relay_event_seq)
            if evs:
                self._relay_event_seq = evs[-1]["seq"]
                out.extend({"event": "log", "data": e} for e in evs)
        if include_metrics:
            from ..metrics import registry as _metrics_registry

            out.append({"event": "metrics",
                        "data": _metrics_registry.job_metrics(self.job_id)})
        if self.coordinated:
            while True:
                try:
                    out.append(self.coordinator_events.get_nowait())
                except _queue.Empty:
                    break
        else:
            with self._lock:
                completed = sorted(
                    self._completed_epochs - self._relay_reported_epochs)
            for ep in completed:
                self._relay_reported_epochs.add(ep)
                out.append({"event": "checkpoint_completed", "epoch": ep})
        return out

    # -------------------------------------------------------------- building

    @staticmethod
    def _fingerprint(graph: Graph) -> Optional[str]:
        """analysis.plan_diff.plan_fingerprint, degraded to None when the
        analysis package cannot run here (it instantiates operators; a
        worker built before _load_operators simply skips stamping rather
        than stamping a hash the controller would never match)."""
        try:
            from ..analysis.plan_diff import plan_fingerprint

            return plan_fingerprint(graph)
        except Exception:
            return None

    def _is_mine(self, nid: str, sub: int) -> bool:
        if self.assignment is None:
            return True
        return self.assignment.get((nid, sub), 0) == self.worker_index

    def _worker_of(self, nid: str, sub: int) -> int:
        if self.assignment is None:
            return self.worker_index
        return self.assignment.get((nid, sub), 0)

    def build(self) -> None:
        g = self.graph
        self.evolution_mapping: Optional[dict] = None
        if self.restore_epoch is not None:
            from ..state.tables import (read_evolution_mapping,
                                        read_job_checkpoint_metadata)

            meta = read_job_checkpoint_metadata(
                self.storage_url, self.job_id, self.restore_epoch
            )
            mapping = read_evolution_mapping(
                self.storage_url, self.job_id, self.restore_epoch
            )
            # plan-fingerprint gate (degrade-not-corrupt): checkpointed
            # bytes are typed by the plan that wrote them. A hash mismatch
            # without a proven evolution mapping means this graph would
            # misread them — fail loudly instead.
            meta_hash = (meta or {}).get("plan_hash")
            if (meta_hash and self.plan_hash
                    and meta_hash != self.plan_hash):
                if mapping is None:
                    raise RuntimeError(
                        f"checkpoint epoch {self.restore_epoch} was written "
                        f"by plan {meta_hash} but this graph is plan "
                        f"{self.plan_hash} and no evolution mapping covers "
                        f"the change — restoring would misread state; run "
                        f"the evolve API so the plan-diff pass can prove "
                        f"(or reject) the carry-over"
                    )
                if (mapping.get("old_plan_hash") != meta_hash
                        or mapping.get("new_plan_hash") != self.plan_hash):
                    raise RuntimeError(
                        f"evolution mapping for epoch {self.restore_epoch} "
                        f"covers {mapping.get('old_plan_hash')} -> "
                        f"{mapping.get('new_plan_hash')} but the restore is "
                        f"{meta_hash} -> {self.plan_hash}; refusing a "
                        f"mapping proven for a different plan pair"
                    )
            if mapping is not None:
                self.evolution_mapping = mapping
                # blue/green: a single-worker engine self-commits, so IT
                # owns the cutover barrier — withhold phase-2 commits
                # until the evolved plan's first epoch goes durable
                # (coordinated sets gate in the controller instead)
                self._evolve_cutover_pending = not self.coordinated
            # operators the epoch holds state for that this graph lacks:
            # under an evolution mapping those explicitly dropped or carried
            # into a renamed successor are expected; anything else is a
            # silent state drop and rejected
            stale = set((meta or {}).get("operators", ())) - set(g.nodes)
            if mapping is not None:
                expected_gone = set(mapping.get("dropped", ()))
                expected_gone |= {
                    str(m.get("from")) for m in mapping.get("nodes", {}).values()
                    if m.get("from")
                }
                stale -= expected_gone
            if stale:
                raise RuntimeError(
                    f"checkpoint epoch {self.restore_epoch} holds state for "
                    f"operators {sorted(stale)} that do not exist in this graph "
                    f"— restoring across a pipeline.chaining.enabled change (or "
                    f"a graph edit) would silently drop their state"
                )
        queue_size = config().get("worker.queue-size")
        # flat-input layout per node: in-edge order, then upstream subtask
        in_layout: dict[str, list[tuple[int, int]]] = {}  # node -> [(edge_i, parallelism)]
        for nid, node in g.nodes.items():
            edges = g.in_edges(nid)
            in_layout[nid] = [(i, g.nodes[e.src].parallelism) for i, e in enumerate(edges)]
            n_inputs = sum(p for _, p in in_layout[nid])
            for s in range(node.parallelism):
                if n_inputs and self._is_mine(nid, s):
                    self._inboxes[(nid, s)] = TaskInbox(n_inputs, queue_size)

        # register network receivers for my tasks' remote inputs. Quads are
        # (edge_index, src_subtask, dst_node, dst_subtask) — the EDGE index
        # (not src node) disambiguates parallel edges between one node pair
        # (e.g. self-join / union-with-self shapes).
        edge_index = {id(e): i for i, e in enumerate(g.edges)}
        if self.network is not None:
            for nid, node in g.nodes.items():
                base = 0
                for e in g.in_edges(nid):
                    src_p = g.nodes[e.src].parallelism
                    for s in range(node.parallelism):
                        if not self._is_mine(nid, s):
                            continue
                        for u in range(src_p):
                            if not self._is_mine(e.src, u):
                                quad = (edge_index[id(e)], u,
                                        self._node_index[nid], s)
                                self.network.register_receiver(
                                    quad, self._inboxes[(nid, s)], base + u
                                )
                    base += src_p
            self.network.start()

        for nid, node in g.nodes.items():
            in_edges = g.in_edges(nid)
            n_inputs = sum(g.nodes[e.src].parallelism for e in in_edges)

            def edge_of_input(i, _edges=in_edges, _g=g):
                base = 0
                for ei, e in enumerate(_edges):
                    p = _g.nodes[e.src].parallelism
                    if i < base + p:
                        return (ei, i - base)
                    base += p
                raise IndexError(i)

            for s in range(node.parallelism):
                if not self._is_mine(nid, s):
                    continue
                ti = TaskInfo(self.job_id, nid, node.op.value, s, node.parallelism)
                out_edges = []
                for e in g.out_edges(nid):
                    dst_node = g.nodes[e.dst]
                    # flat input base for this edge at the destination
                    base = 0
                    for de in g.in_edges(e.dst):
                        if de is e:
                            break
                        base += g.nodes[de.src].parallelism
                    dests = []
                    for d in range(dst_node.parallelism):
                        if self._is_mine(e.dst, d):
                            dests.append(self._inboxes[(e.dst, d)])
                        else:
                            from .network import RemoteDest

                            quad = (edge_index[id(e)], s,
                                    self._node_index[e.dst], d)
                            dests.append(RemoteDest(
                                self.network, self._worker_of(e.dst, d), quad
                            ))
                    idxs = [base + s] * dst_node.parallelism
                    etype = e.edge_type
                    if etype == EdgeType.FORWARD and dst_node.parallelism != node.parallelism:
                        etype = EdgeType.SHUFFLE
                    out_edges.append(OutEdge(etype, dests, idxs))
                collector = Collector(out_edges, s)
                tm = TableManager(ti, self.storage_url)
                operator = construct_operator(node.op, node.config)
                prepare = getattr(operator, "prepare", None)  # sources have none
                if prepare is not None:
                    prepare()
                ctx = OperatorContext(
                    ti,
                    out_schema=g.out_edges(nid)[0].schema if g.out_edges(nid) else None,
                    table_manager=tm,
                    in_edge_of_input=edge_of_input,
                )
                if self.restore_epoch is not None:
                    node_map = (self.evolution_mapping or {}).get(
                        "nodes", {}).get(nid)
                    wm = tm.restore(self.restore_epoch, operator.tables(),
                                    mapping=node_map)
                    if wm is not None:
                        self.restored_watermark = (
                            wm if self.restored_watermark is None else min(self.restored_watermark, wm)
                        )
                task = Task(
                    ti,
                    operator,
                    self._inboxes.get((nid, s)),
                    collector,
                    ctx,
                    self.resp_queue,
                    n_inputs=n_inputs,
                )
                self.tasks[(nid, s)] = task
        self._n_tasks = len(self.tasks)

    # -------------------------------------------------------------- running

    def start(self) -> None:
        if not self.tasks:
            self.build()
        self._resp_thread = threading.Thread(target=self._collect_resps, daemon=True)
        self._resp_thread.start()
        # start sinks-to-sources so consumers are ready before producers
        for node in reversed(self.graph.topo_order()):
            for s in range(node.parallelism):
                task = self.tasks.get((node.node_id, s))
                if task is not None:  # remote subtasks belong to other workers
                    task.start()
        with self._lock:
            self._running = True
            pending, self._pending_triggers = self._pending_triggers, []
        for epoch, then_stop in pending:
            self.trigger_checkpoint(epoch, then_stop=then_stop)

    def _collect_resps(self) -> None:
        while True:
            try:
                resp = self.resp_queue.get(timeout=0.25)
            except _queue.Empty:
                with self._lock:
                    if len(self._finished_tasks) + len(self._failed) >= self._n_tasks and self._n_tasks:
                        return
                continue
            if resp.kind == "checkpoint_event" and resp.checkpoint_event:
                ce = resp.checkpoint_event
                name = {"started_alignment": "align_start",
                        "started_checkpointing": "snapshot_start"}.get(
                            ce.event_type)
                if name:
                    self._span(ce.checkpoint_epoch, name, node=resp.node_id,
                               subtask=resp.subtask_index,
                               t_us=ce.time_micros)
                continue
            if resp.kind == "checkpoint_completed":
                self._span(resp.epoch, "ack", node=resp.node_id,
                           subtask=resp.subtask_index)
            with self._lock:
                key = (resp.node_id, resp.subtask_index)
                if resp.kind == "task_finished":
                    self._finished_tasks.add(key)
                    if resp.clean:
                        self._clean_finished.add(key)
                        if self.coordinated:
                            # only CLEAN drains are relayed as coverage;
                            # stop/abort exits have no durable final state
                            self.coordinator_events.put({
                                "event": "subtask_finished",
                                "node": key[0], "subtask": key[1],
                            })
                    self._finish_ready_epochs()
                elif resp.kind == "task_failed":
                    self._failed.append(resp)
                    # propagate: unstick every surviving task so producers
                    # blocked on a dead consumer's row budget unwind
                    # (reference: ControlResp::TaskFailed -> controller stops
                    # the job; here the embedded engine aborts directly)
                    self._abort()
                elif resp.kind == "checkpoint_completed":
                    ep = self._checkpoints.setdefault(resp.epoch, {})
                    ep[key] = resp.subtask_metadata
                    if self.coordinated:
                        from ..state.integrity import fold_integrity

                        # the subtask's artifact envelopes ride the ack so
                        # the controller's marker can fold the per-epoch
                        # integrity manifest without re-reading storage
                        self.coordinator_events.put({
                            "event": "subtask_acked", "epoch": resp.epoch,
                            "node": key[0], "subtask": key[1],
                            "integrity": fold_integrity(
                                [resp.subtask_metadata or {}]),
                        })
                    self._finish_ready_epochs()
                self._cond.notify_all()

    def _finish_ready_epochs(self) -> None:
        """An epoch is complete once every task has snapshotted it or
        finished outright (a drained source can't take part in a barrier —
        its state is final; reference CheckpointState handles TaskFinished
        the same way). Caller holds the lock.

        Only the single-worker engine decides this locally. In assignment
        mode the per-subtask acks were already relayed upward (above): the
        controller's CheckpointCoordinator owns global coverage, writes the
        job-level metadata marker, and injects commits via deliver_commit —
        a local task count can never prematurely finalize an epoch that
        other workers are still snapshotting."""
        if self.coordinated:
            return
        for epoch, ep in self._checkpoints.items():
            if epoch in self._completed_epochs or not ep:
                continue
            covered = set(ep) | self._clean_finished
            if len(covered) >= self._n_tasks:
                extra = {"operators": list({k[0] for k in ep})}
                if self.plan_hash:
                    extra["plan_hash"] = self.plan_hash
                from ..state.integrity import fold_integrity

                integ = fold_integrity(m for m in ep.values() if m)
                if integ:
                    extra["integrity"] = integ
                write_job_checkpoint_metadata(
                    self.storage_url, self.job_id, epoch, extra,
                )
                self._span(epoch, "metadata_durable")
                if self._evolve_cutover_pending:
                    # blue/green cutover barrier (single-worker live
                    # evolution): this is the evolved plan's first durable
                    # epoch — it proves the new set caught up past the
                    # carried offsets. The `evolve_cutover` chaos site
                    # fires between durability and the commit release.
                    self._evolve_cutover_pending = False
                    from ..faults import fault_point

                    try:
                        fault_point("evolve_cutover", epoch=epoch,
                                    key=self.job_id)
                    except Exception as exc:  # noqa: BLE001 - injected
                        # crash AT the barrier: the epoch's metadata is
                        # durable but every commit stays withheld. The
                        # restarted incarnation restores from THIS epoch
                        # (same plan hash, no mapping needed) and the
                        # sink re-commits its staged output idempotently
                        # — exactly one committed lineage
                        self._failed.append(ControlResp(
                            kind="task_failed", node_id="<evolve_cutover>",
                            error=f"injected crash at the evolve cutover "
                                  f"barrier (epoch {epoch}): {exc}"))
                        self._abort()
                        return
                self._completed_epochs.add(epoch)
                # two-phase commit: metadata is durable, tell committing
                # sinks to finalize (reference send_commit_messages,
                # job_controller/mod.rs:838)
                for key, task in self.tasks.items():
                    if key in self._finished_tasks:
                        continue
                    opv = getattr(task, "operator", None)
                    if opv is not None and getattr(opv, "is_committing", lambda: False)():
                        # lint: waive LR403 — control_queue is an unbounded queue.Queue; put() never blocks, so holding _lock across it cannot stall
                        task.control_queue.put(
                            ControlMessage(kind="commit", epoch=epoch)
                        )
                self._span(epoch, "commit_delivered", worker=self.worker_index)

    def deliver_commit(self, epoch: int) -> None:
        """Phase-2 entry point in assignment mode: the control plane calls
        this once ``epoch``'s job-level metadata is durable across ALL
        workers. Marks the epoch (and any earlier ones whose commit message
        was lost — chaos site ``commit`` drops them on purpose) complete and
        forwards per-epoch commit messages to local committing operators, in
        epoch order. Cumulative delivery is what makes a dropped phase-2
        message re-delivered on the next epoch instead of lost."""
        to_commit: list[tuple[Task, int]] = []
        with self._lock:
            if epoch <= self._committed_through:
                return
            lo = self._committed_through
            self._committed_through = epoch
            # the carried epoch is durable by the coordinator's ordering
            # invariant; intermediates are marked only if this worker acked
            # them — an epoch the watchdog subsumed (and nobody acked here)
            # must not surface as "completed" to compact()/cleanup() callers
            self._completed_epochs.add(epoch)
            delivered = []
            for e in sorted(self._checkpoints):
                if not (lo < e <= epoch):
                    continue
                self._completed_epochs.add(e)
                self.delivered_commits.append(e)
                delivered.append(e)
                for key, task in self.tasks.items():
                    if key not in self._checkpoints[e] or key in self._finished_tasks:
                        continue
                    opv = getattr(task, "operator", None)
                    if opv is not None and getattr(opv, "is_committing", lambda: False)():
                        to_commit.append((task, e))
            self._cond.notify_all()
        for task, e in to_commit:
            task.control_queue.put(ControlMessage(kind="commit", epoch=e))
        # stamp every epoch this call made durable-and-committed, not just
        # the carried one: a re-delivered dropped commit for epoch E must
        # close E's commit span or the trace shows E wedged forever
        for e in delivered:
            if e != epoch:
                self._span(e, "commit_delivered", worker=self.worker_index)
                # a lost phase-2 commit recovered by cumulative delivery is
                # an operational fact worth a feed entry, not just a span
                events_recorder.record(
                    self.job_id, "WARN", "COMMIT_REDELIVERED",
                    message=f"phase-2 commit for epoch {e} re-delivered "
                            f"cumulatively with epoch {epoch}",
                    worker=self.worker_index, epoch=e)
        self._span(epoch, "commit_delivered", worker=self.worker_index)

    def heartbeat(self) -> float:
        """Liveness derived from actual engine progress: the stalest
        still-running task's last run-loop beat (tasks beat every loop
        iteration, sources via poll_control, backpressured producers from
        the inbox wait loop). A wedged subtask — hung in an operator or a
        stalled storage call — stops beating and ages this value out, which
        is what lets the controller's heartbeat timeout catch a hung
        embedded engine (a thread's mere existence proves nothing). The
        flip side: one process_batch call is one beat interval, so
        ``pipeline.worker-heartbeat-timeout-ms`` must stay above the
        worst-case single-batch latency (the 30s default leaves plenty of
        headroom for cold jit compiles and retry backoff)."""
        beats = []
        with self._lock:
            for key, t in self.tasks.items():
                if key in self._finished_tasks:
                    continue
                if t.thread is not None and t.thread.is_alive():
                    beats.append(t.last_progress)
        return min(beats) if beats else time.monotonic()

    # -------------------------------------------------------------- control

    def source_tasks(self) -> list[Task]:
        return [t for t in self.tasks.values() if t.is_source]

    def trigger_checkpoint(self, epoch: int, then_stop: bool = False) -> None:
        """Reference job_controller/mod.rs:325: checkpoint starts at sources.
        Triggers arriving before the engine is running are buffered and
        replayed by start() — never dropped."""
        self._span(epoch, "trigger")
        with self._lock:
            if not self._running:
                self._pending_triggers.append((epoch, then_stop))
                return
        barrier = CheckpointBarrier(epoch=epoch, timestamp=int(time.time() * 1e6), then_stop=then_stop)
        for t in self.source_tasks():
            t.control_queue.put(ControlMessage(kind="checkpoint", barrier=barrier))

    def checkpoint_and_wait(self, epoch: int, timeout: float = 60.0,
                            then_stop: bool = False) -> CheckpointWait:
        """Trigger ``epoch`` and wait. Returns a CheckpointWait whose
        outcome distinguishes the three exits callers used to have to
        guess apart: "completed" (truthy — every subtask snapshotted; in
        assignment mode, globally durable and committed), "finished" (the
        pipeline drained before the barrier — a stop, not a failure), and
        "timeout" (a stuck barrier, with the subtasks that never acked in
        ``missing`` for the diagnostic)."""
        self.trigger_checkpoint(epoch, then_stop=then_stop)
        deadline = time.monotonic() + timeout
        with self._lock:
            while epoch not in self._completed_epochs:
                if self._failed:
                    raise RuntimeError(f"task failed during checkpoint: {self._failed[0].error}")
                if len(self._finished_tasks) >= self._n_tasks:
                    return CheckpointWait("finished")
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    acked = set(self._checkpoints.get(epoch, ()))
                    missing = tuple(sorted(
                        set(self.tasks) - acked - self._finished_tasks))
                    expected = set(self.tasks) - self._finished_tasks
                    report = timeline_report(
                        self.job_id, epoch,
                        trace_recorder.events(self.job_id, epoch),
                        expected=expected)
                    return CheckpointWait("timeout", missing, report)
                self._cond.wait(timeout=min(remaining, 0.5))
        return CheckpointWait("completed")

    def compact(self, epoch: int) -> int:
        """Merge the epoch's per-subtask state shards (reference: controller
        compact_state trigger, job_controller/mod.rs:382). Safe only for
        completed epochs."""
        with self._lock:
            if epoch not in self._completed_epochs:
                raise ValueError(f"epoch {epoch} is not a completed checkpoint")
        return compact_job(self.storage_url, self.job_id, epoch)

    def cleanup(self, min_epoch: int) -> int:
        """Drop checkpoints below min_epoch (controller epoch GC). Refuses
        to delete past the newest restorable checkpoint."""
        with self._lock:
            newest = max(self._completed_epochs, default=None)
        if newest is None:
            newest = latest_complete_checkpoint(self.storage_url, self.job_id)
        if newest is None or min_epoch > newest:
            raise ValueError(
                f"cleanup(min_epoch={min_epoch}) would delete every restorable "
                f"checkpoint (newest complete epoch: {newest})"
            )
        return cleanup_checkpoints(self.storage_url, self.job_id, min_epoch)

    def stop(self) -> None:
        for t in self.source_tasks():
            # lint: waive LR403 — control_queue is an unbounded queue.Queue; put() never blocks (flagged via the _abort -> stop() reach under _lock)
            t.control_queue.put(ControlMessage(kind="stop"))

    def _abort(self) -> None:
        """Hard-stop after a task failure: stop sources and close every
        inbox so blocked producers/consumers exit."""
        self._aborted = True
        self.stop()
        for inbox in self._inboxes.values():
            inbox.close()

    def join(self, timeout: Optional[float] = None) -> None:
        deadline = time.monotonic() + timeout if timeout else None
        while True:
            if self._failed:
                # give surviving tasks a moment to unwind after the abort
                for t in self.tasks.values():
                    t.join(2.0)
                raise RuntimeError(f"pipeline task failed:\n{self._failed[0].error}")
            alive = [t for t in self.tasks.values() if t.thread and t.thread.is_alive()]
            if not alive:
                break
            if deadline is not None and time.monotonic() > deadline:
                raise TimeoutError(
                    f"{len(alive)} tasks still running after join timeout"
                )
            alive[0].join(0.2)
        # every task thread has exited, but the final task_finished /
        # task_failed responses may still be in flight on the resp queue —
        # wait for the accounting to catch up, or a failure posted just
        # before a thread died would be silently swallowed and a crashed
        # pipeline would report success
        catchup = time.monotonic() + 5.0
        with self._lock:
            while (self._n_tasks
                   and len(self._finished_tasks) + len(self._failed) < self._n_tasks
                   and time.monotonic() < catchup):
                self._cond.wait(timeout=0.1)
        if self._failed:
            raise RuntimeError(f"pipeline task failed:\n{self._failed[0].error}")

    def run_to_completion(self, timeout: Optional[float] = 120.0) -> None:
        self.start()
        self.join(timeout)


def run_graph(graph: Graph, job_id: str = "job", timeout: float = 120.0, **kw) -> Engine:
    """Convenience: build, run to completion, return the finished engine."""
    eng = Engine(graph, job_id=job_id, **kw)
    eng.run_to_completion(timeout)
    return eng
