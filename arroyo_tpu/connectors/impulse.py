"""Impulse source: synthetic counter stream at a configured rate
(reference crates/arroyo-connectors/src/impulse/mod.rs:104-183).

Schema: counter uint64, subtask_index uint64, _timestamp. Offsets checkpoint
into a global-keyed table so restore resumes exactly where the snapshot was
taken (exactly-once source semantics).

``event_rate`` pacing is RELATIVE to the resume point: a restored subtask
continues at the configured rate from where its snapshot left off, instead
of sleeping out the already-elapsed run against an absolute counter (which
a rescale would also re-mean against the new per-task rate).
"""

from __future__ import annotations

import time
from typing import Optional

import numpy as np

from ..batch import TIMESTAMP_FIELD, Batch, Field, Schema
from ..config import config
from ..operators.base import SourceOperator, TableSpec
from ..types import SourceFinishType
from . import register_source

IMPULSE_SCHEMA = Schema.of(
    [Field("counter", "uint64"), Field("subtask_index", "uint64"), Field(TIMESTAMP_FIELD, "int64")]
)


class ImpulseSource(SourceOperator):
    """config: event_rate (rows/s total, 0 = unthrottled), message_count
    (per subtask; None = unbounded), interval_micros (event-time step;
    default derived from event_rate or 1ms), start_time_micros."""

    def __init__(self, cfg: dict):
        self.event_rate = float(cfg.get("event_rate") or 0)
        self.message_count = (None if cfg.get("message_count") is None
                              else int(cfg["message_count"]))
        start = cfg.get("start_time_micros")
        self.start_time_micros = (int(time.time() * 1e6) if start is None
                                  else int(start))
        if cfg.get("interval_micros") is not None:
            self.interval_micros = int(cfg["interval_micros"])
        elif self.event_rate:
            self.interval_micros = max(int(1e6 / self.event_rate), 1)
        else:
            self.interval_micros = 1000

    def tables(self):
        return [TableSpec("s", "global_keyed")]

    def run(self, sctx, collector) -> SourceFinishType:
        ctx = sctx.ctx
        sub = ctx.task_info.subtask_index
        p = ctx.task_info.parallelism
        tbl = ctx.table_manager.global_keyed("s")
        batch_size = config().get("pipeline.source-batch-size")
        rate_per_task = self.event_rate / p if self.event_rate else 0
        started = time.monotonic()
        counter = tbl.get(sub, 0)
        pace_base = counter  # pacing is relative to the resume point (header)

        def control() -> Optional[SourceFinishType]:
            msg = sctx.poll_control()
            if msg is None:
                return None
            if msg.kind == "checkpoint":
                tbl.insert(sub, counter)
                sctx.start_checkpoint(msg.barrier)
                if msg.barrier.then_stop:
                    return SourceFinishType.FINAL
            elif msg.kind == "stop":
                return SourceFinishType.IMMEDIATE
            return None

        while self.message_count is None or counter < self.message_count:
            r = control()
            if r is not None:
                return r
            n = batch_size
            if self.message_count is not None:
                n = min(n, self.message_count - counter)
            idx = np.arange(counter, counter + n, dtype=np.uint64)
            ts = self.start_time_micros + idx.astype(np.int64) * self.interval_micros
            collector.collect(
                Batch(
                    {
                        "counter": idx,
                        "subtask_index": np.full(n, sub, dtype=np.uint64),
                        TIMESTAMP_FIELD: ts,
                    }
                )
            )
            counter += n
            if not rate_per_task:
                continue
            target = started + (counter - pace_base) / rate_per_task
            while True:
                delay = target - time.monotonic()
                if delay <= 0:
                    break
                r = control()
                if r is not None:
                    return r
                time.sleep(min(delay, 0.05))
        # keep the offset table current for the run loop's final snapshot
        tbl.insert(sub, counter)
        return SourceFinishType.GRACEFUL


register_source("impulse")(ImpulseSource)
