"""Operator trait boundary.

TPU-native equivalent of the reference's operator layer
(crates/arroyo-operator/src/operator.rs — ArrowOperator :1074, SourceOperator
:294, OperatorConstructor :55). Operators consume/produce columnar Batches;
window/join operator bodies dispatch into the jax runtime (arroyo_tpu.ops)
instead of DataFusion exec plans.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import TYPE_CHECKING, Optional

from ..batch import Batch, Schema
from ..types import (
    CheckpointBarrier,
    SourceFinishType,
    TaskInfo,
    Watermark,
)

if TYPE_CHECKING:
    from ..state.tables import TableManager
    from .collector import Collector


@dataclass
class TableSpec:
    """Declares a state table (reference operator.rs:1077 tables())."""

    name: str
    kind: str  # "global_keyed" | "expiring_time_key" | "key_time"
    retention_micros: int = 0
    schema: Optional[Schema] = None


class OperatorContext:
    """Per-subtask context handed to operator hooks
    (reference: arroyo-operator/src/context.rs OperatorContext)."""

    def __init__(
        self,
        task_info: TaskInfo,
        out_schema: Optional[Schema],
        table_manager: "TableManager",
        in_edge_of_input=None,
    ):
        self.task_info = task_info
        self.out_schema = out_schema
        self.table_manager = table_manager
        self.last_watermark: Optional[Watermark] = None
        # maps flat input index -> (edge_index, upstream_subtask)
        self._in_edge_of_input = in_edge_of_input or (lambda i: (0, i))
        # pokes the owning task out of its inbox wait (TaskInbox.wake; set
        # by the Task): the on_done of a close handed to the fetch pool
        self.wake = None

    def edge_of_input(self, input_index: int) -> int:
        return self._in_edge_of_input(input_index)[0]

    def watermark(self) -> Optional[int]:
        """Current event-time watermark in micros (None if idle/unset)."""
        if self.last_watermark is None:
            return None
        return self.last_watermark.value


def persist_mark(ctx: "OperatorContext", table: str, value) -> None:
    """Write this subtask's scalar meta mark (late-data barrier, event-time
    high-water, ...) into a global_keyed table — called UNCONDITIONALLY at
    every barrier, because a mark carried as a column on a state batch is
    silently dropped whenever the partial snapshot happens to be empty."""
    ctx.table_manager.global_keyed(table).insert(
        ctx.task_info.subtask_index, value)


def restore_marks(ctx: "OperatorContext", table: str) -> list:
    """Every prior subtask's non-None mark from a meta table. The merge is
    the caller's: ``max`` for watermark-aligned boundaries (aligned barriers
    mean all subtasks saw the same watermark, so max is rescale-safe);
    data-derived per-subtask marks should prefer their OWN entry
    (``global_keyed(table).get(subtask_index)``) and fall back to a merge
    only on rescale."""
    return [v for _k, v in ctx.table_manager.global_keyed(table).items()
            if v is not None]


class Operator:
    """Mid-pipeline operator (reference ArrowOperator, operator.rs:1074-1183).

    Hooks are called from the task run loop (engine/task.py) which owns
    barrier alignment, watermark merging, and end-of-data accounting.
    """

    def name(self) -> str:
        return type(self).__name__

    def tables(self) -> list[TableSpec]:
        return []

    def prepare(self) -> None:
        """Called once by Engine.build, on the process that will run the
        operator and before any task starts: the place to compile device
        programs whose compile would otherwise stall the stream at its first
        batch. An operator built only to be looked at (analysis/) is never
        prepared."""

    def on_start(self, ctx: OperatorContext) -> None:
        pass

    def process_batch(
        self, batch: Batch, ctx: OperatorContext, collector: "Collector", input_index: int = 0
    ) -> None:
        raise NotImplementedError

    def handle_watermark(
        self, watermark: Watermark, ctx: OperatorContext, collector: "Collector"
    ) -> Optional[Watermark]:
        """Return the watermark to forward downstream, or None to hold it
        (reference operator.rs:1138)."""
        return watermark

    def handle_checkpoint(
        self, barrier: CheckpointBarrier, ctx: OperatorContext, collector: "Collector"
    ) -> None:
        """Flush in-flight device/host state into state tables before the
        table manager snapshots them (reference operator.rs handle_checkpoint)."""

    def handle_commit(self, epoch: int, ctx: OperatorContext) -> None:
        pass

    def is_committing(self) -> bool:
        return False

    def tick_interval_micros(self) -> Optional[int]:
        """If set, handle_tick is invoked at roughly this period
        (reference operator.rs:1167 handle_tick)."""
        return None

    def handle_tick(self, ctx: OperatorContext, collector: "Collector") -> None:
        pass

    def closes_in_flight(self) -> bool:
        """True while a window close handed to the fetch pool
        (ops/prefetch.py, ``on_done=ctx.wake``) has not left yet."""
        return False

    def drain_ready(self, ctx: OperatorContext, collector: "Collector") -> None:
        """The task was woken with no input to handle and closes are in
        flight: emit those that have landed, in program order, each held
        watermark after its rows — the non-forcing drain that opens
        process_batch and handle_watermark, only sooner."""

    def flush_staged(self, ctx: OperatorContext, collector: "Collector") -> None:
        """The task found its inbox empty and is about to wait (or was told
        to stop): an operator that stages the batches it is handed, to run
        its hook once over several (the window aggregates, when a backlog
        feeds them), runs it now over what it holds. No row waits in an
        operator while its task sleeps."""

    def on_close(self, ctx: OperatorContext, collector: "Collector") -> None:
        """All inputs reached end-of-data; emit any remaining state."""


class SourceOperator:
    """Source (reference SourceOperator, operator.rs:294-342).

    ``run`` drives the source; it must call ``ctx_poll`` helpers frequently:
    the run loop passes a SourceContext whose ``poll_control`` surfaces
    checkpoint/stop commands from the engine.
    """

    def name(self) -> str:
        return type(self).__name__

    def tables(self) -> list[TableSpec]:
        return []

    def on_start(self, ctx: OperatorContext) -> None:
        pass

    def is_committing(self) -> bool:
        """True if this source defers side effects (e.g. broker acks) to the
        engine's post-checkpoint commit message; the engine then delivers
        ``ControlMessage(kind="commit", epoch=...)`` via poll_control once
        the epoch's job-level metadata is durable."""
        return False

    def run(self, ctx: OperatorContext, collector: "Collector") -> SourceFinishType:
        raise NotImplementedError

    def on_close(self, ctx: OperatorContext, collector: "Collector") -> None:
        pass
