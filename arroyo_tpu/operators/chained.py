"""Operator chaining: fuse Forward-edge neighbors into one task.

Equivalent of the reference's ChainingOptimizer + ChainedOperator
(crates/arroyo-datastream/src/optimizers.rs:40-105 — merge when Forward edge,
equal parallelism, single in/out, not source/sink — and
crates/arroyo-operator/src/operator.rs:424-428 ChainedOperator with
ChainedCollector threading output of op N into op N+1 in place :370-422).

On this engine a chain collapses per-batch queue hops and thread handoffs —
the host-side analog of XLA op fusion, and a direct throughput lever since
every hop costs a bounded-queue put/get plus a GIL switch. A chained run
marked compilable at plan time additionally runs its data path as ONE
jitted call per micro-batch (engine/segment.py whole-segment compilation);
this class stays the interpreted ground truth the compiled path verifies
against and falls back to.

Interplay with micro-batch coalescing (operators/collector.py): member-to-
member hops are plain in-process calls, so there is deliberately NO
coalescing buffer between chain members — only the chain's terminal
collector (the task's real Collector) coalesces, right where the queue/
data-plane overhead being amortized actually lives. Signal flushing is
inherited from that terminal collector: a watermark threaded through
ChainCollector.broadcast ends at Collector.broadcast, which flushes pending
rows ahead of the signal."""

from __future__ import annotations

from dataclasses import replace
from typing import Optional

from ..engine.engine import construct_operator, register_operator
from ..graph import OpName
from ..operators.base import Operator, OperatorContext
from ..types import Signal, SignalKind, Watermark


class PrefixedTables:
    """Namespaces one chain member's state tables inside the shared
    TableManager so two members' same-named tables cannot collide."""

    def __init__(self, inner, prefix: str):
        self._inner = inner
        self._prefix = prefix

    def global_keyed(self, name: str):
        return self._inner.global_keyed(self._prefix + name)

    def expiring_time_key(self, name: str, retention_micros: int = 0):
        return self._inner.expiring_time_key(self._prefix + name, retention_micros)

    def __getattr__(self, item):
        return getattr(self._inner, item)


class ChainCollector:
    """Collector handed to chain member i: data flows into member i+1's
    process_batch in place; watermark broadcasts thread through member i+1's
    handle_watermark (so holds/adjustments still apply); other signals pass
    through untouched (barriers originate in the task loop, not members)."""

    def __init__(self, op: Operator, ctx: OperatorContext, next_collector):
        self.op = op
        self.ctx = ctx
        self.next = next_collector

    def collect(self, batch) -> None:
        self.op.process_batch(batch, self.ctx, self.next)

    def broadcast(self, signal: Signal) -> None:
        if signal.kind == SignalKind.WATERMARK:
            self.ctx.last_watermark = signal.watermark
            out = self.op.handle_watermark(signal.watermark, self.ctx, self.next)
            if out is not None:
                self.next.broadcast(Signal.watermark_of(out))
        else:
            self.next.broadcast(signal)


class ChainedOperator(Operator):
    """config: members = [(op_name_value, member_config), ...] in data order."""

    def __init__(self, cfg: dict):
        self.members: list[Operator] = [
            construct_operator(OpName(op), c) for op, c in cfg["members"]
        ]
        # raw member (op, config) pairs + the optimizer's plan-time
        # compilability marking: engine/segment.py keys its compile cache
        # off these and traces the marked prefix into one jitted call
        self.cfg_members: list = list(cfg["members"])
        self.compile_marking: Optional[dict] = cfg.get("compile")
        # plan-time "not compilable: <reason>" (optimizer.chain_graph):
        # runner_for copies it into the task metrics so top/explain can
        # render the reject next to the [compiled] marker
        self.compile_reject: Optional[str] = cfg.get("compile_reject")
        self._ctxs: Optional[list[OperatorContext]] = None
        self._cols = None
        # only members that declared a tick interval get ticked: the chain
        # ticks at the MINIMUM member interval, and waking every member at
        # the fastest member's cadence is wasted hot-loop work
        self._tickers = [i for i, m in enumerate(self.members)
                         if m.tick_interval_micros() is not None]
        # members that hand window closes to the fetch pool: a member's
        # wake is the chain's task's wake, and only these are asked to drain
        self._closers = [i for i, m in enumerate(self.members)
                         if type(m).drain_ready is not Operator.drain_ready]
        # members that stage their input (the window aggregates)
        self._stagers = [i for i, m in enumerate(self.members)
                         if type(m).flush_staged is not Operator.flush_staged]

    def name(self) -> str:
        return "+".join(m.name() for m in self.members)

    @property
    def late_rows(self) -> int:
        """Chain-wide late/expired-row drops (obs/profile.py exports this
        per task, so a chain reports its members' sum)."""
        return sum(int(getattr(m, "late_rows", 0) or 0) for m in self.members)

    def state_sizes(self) -> dict[str, tuple[int, int]]:
        """Members' live-store gauges, namespaced like their state tables
        (PrefixedTables uses the same ``c{i}.`` prefix)."""
        out: dict[str, tuple[int, int]] = {}
        for i, m in enumerate(self.members):
            fn = getattr(m, "state_sizes", None)
            if fn is not None:
                for name, v in fn().items():
                    out[f"c{i}.{name}"] = v
        return out

    def spill_stats(self):
        """Members' tiered-state counters folded into one chain-level
        block (state/spill.py merge: counters sum, histograms add)."""
        from ..state.spill import merge_spill_stats

        return merge_spill_stats(
            [fn() for m in self.members
             for fn in (getattr(m, "spill_stats", None),) if fn is not None])

    def mesh_stats(self):
        """Fused-mesh residency of the chain's window member, if any (the
        sharded aggregate lives on exactly one member — obs/profile.py
        exports this as the arroyo_mesh_* series)."""
        for m in self.members:
            fn = getattr(m, "mesh_stats", None)
            if fn is not None:
                stats = fn()
                if stats is not None:
                    return stats
        return None

    def tables(self):
        specs = []
        for i, m in enumerate(self.members):
            for t in m.tables():
                specs.append(replace(t, name=f"c{i}.{t.name}"))
        return specs

    def on_start(self, ctx: OperatorContext) -> None:
        # collectors are rebuilt on first process_batch (on_start has none);
        # member on_start only needs the namespaced tables
        self._setup_ctx_only(ctx)
        for i, m in enumerate(self.members):
            m.on_start(self._ctxs[i])

    def _setup_ctx_only(self, ctx: OperatorContext) -> None:
        if self._ctxs is None:
            self._ctxs = [
                OperatorContext(
                    ctx.task_info,
                    ctx.out_schema if i == len(self.members) - 1 else None,
                    PrefixedTables(ctx.table_manager, f"c{i}."),
                    in_edge_of_input=ctx._in_edge_of_input,
                )
                for i in range(len(self.members))
            ]
            for c in self._ctxs:
                c.wake = ctx.wake

    def _chain_cols(self, collector):
        if self._cols is None or self._outer is not collector:
            cols = [None] * len(self.members)
            nxt = collector
            for i in range(len(self.members) - 1, -1, -1):
                cols[i] = nxt
                if i > 0:
                    nxt = ChainCollector(self.members[i], self._ctxs[i], nxt)
            self._cols = cols
            self._outer = collector
        return self._cols

    def process_batch(self, batch, ctx, collector, input_index=0) -> None:
        cols = self._chain_cols(collector)
        self.members[0].process_batch(batch, self._ctxs[0], cols[0], input_index=input_index)

    def handle_watermark(self, watermark: Watermark, ctx, collector) -> Optional[Watermark]:
        cols = self._chain_cols(collector)
        w: Optional[Watermark] = watermark
        for i, m in enumerate(self.members):
            self._ctxs[i].last_watermark = w
            w = m.handle_watermark(w, self._ctxs[i], cols[i])
            if w is None:
                return None
        return w

    def handle_checkpoint(self, barrier, ctx, collector) -> None:
        cols = self._chain_cols(collector)
        for i, m in enumerate(self.members):
            m.handle_checkpoint(barrier, self._ctxs[i], cols[i])

    def handle_commit(self, epoch: int, ctx) -> None:
        for i, m in enumerate(self.members):
            m.handle_commit(epoch, self._ctxs[i])

    def is_committing(self) -> bool:
        return any(m.is_committing() for m in self.members)

    def tick_interval_micros(self) -> Optional[int]:
        ticks = [t for m in self.members if (t := m.tick_interval_micros()) is not None]
        return min(ticks) if ticks else None

    def handle_tick(self, ctx, collector) -> None:
        cols = self._chain_cols(collector)
        for i in self._tickers:
            self.members[i].handle_tick(self._ctxs[i], cols[i])

    def closes_in_flight(self) -> bool:
        return any(self.members[i].closes_in_flight() for i in self._closers)

    def drain_ready(self, ctx, collector) -> None:
        cols = self._chain_cols(collector)
        for i in self._closers:
            if self.members[i].closes_in_flight():
                self.members[i].drain_ready(self._ctxs[i], cols[i])

    def flush_staged(self, ctx, collector) -> None:
        # in data order: what a member lets go reaches the next one first
        cols = self._chain_cols(collector)
        for i in self._stagers:
            self.members[i].flush_staged(self._ctxs[i], cols[i])

    def on_close(self, ctx, collector) -> None:
        cols = self._chain_cols(collector)
        for i, m in enumerate(self.members):
            m.on_close(self._ctxs[i], cols[i])


@register_operator(OpName.CHAINED)
def _make_chained(cfg: dict):
    return ChainedOperator(cfg)
