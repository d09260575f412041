"""Task inbox with per-input row-budget backpressure.

The reference gives every input edge an unbounded channel guarded by an
atomic row-count budget (crates/arroyo-operator/src/context.rs:113-205
``batch_bounded``; default ``worker.queue-size = 8192`` rows). Here each task
owns ONE multiplexed inbox; producers tag items with their flat input index
and block while that input's outstanding row budget is exhausted. Budget is
released when the consumer finishes processing the item, so batches held for
barrier alignment keep exerting backpressure upstream — reproducing aligned-
checkpoint backpressure (operator.rs:966-975).

Signals (watermarks, barriers, stop, end-of-data) never block: they must be
able to overtake a full queue exactly as in the reference.

``wake()`` is not an item: a fetch worker whose in-flight window close has
landed (ops/prefetch.py) pokes the consumer out of ``get`` so that it drains
the close now rather than at its next input. It takes no row budget, is
never held during barrier alignment and never reorders the queue.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Optional, Union

from ..batch import Batch
from ..faults import fault_point
from ..obs import trace as _trace
from ..obs.lockorder import make_lock
from ..types import Signal

QueueItem = Union[Batch, Signal]


class TaskInbox:
    def __init__(self, n_inputs: int, row_budget: int):
        self.n_inputs = max(n_inputs, 1)
        self.row_budget = row_budget
        # items carry their enqueue wall time: the consumer-side pop feeds
        # the queue-transit latency histogram (coalescing instrumentation)
        self._queue: deque[tuple[int, QueueItem, float]] = deque()
        self._used = [0] * self.n_inputs
        self._lock = make_lock("TaskInbox._lock")
        self._not_empty = make_lock("TaskInbox._lock", kind="cond",
                                    lock=self._lock)
        self._budget_freed = make_lock("TaskInbox._lock", kind="cond",
                                       lock=self._lock)
        self._closed = False
        # sticky: a close can land between the operator's is_ready() check
        # and the consumer's going to sleep, and a bare notify there is lost
        self._woken = False
        self.metrics = None  # TaskMetrics of the consuming task

    def put(self, input_index: int, item: QueueItem) -> None:
        """Blocks while this input's row budget is exhausted (data only)."""
        # chaos hook: delay models a stalled consumer (backpressure builds
        # upstream through the blocked producer); fail kills the producer
        fault_point("queue.put", input=input_index)
        rows = item.num_rows if isinstance(item, Batch) else 0
        # healthy-but-backpressured producers must keep their liveness beat
        # (Task sets this hook on its own thread); a task truly hung inside
        # an operator never reaches this wait loop, so it still goes stale
        beat = getattr(threading.current_thread(), "arroyo_beat", None)
        with self._lock:
            if rows and self._over_budget(input_index, rows):
                # blocked: the wait is charged to the PRODUCING task (the
                # calling thread's lane), under the consumer's name
                with _trace.wait(_trace.PUT_WAIT, "task.put_wait",
                                 dest=self.metrics.node_id if self.metrics else None):
                    while self._over_budget(input_index, rows):
                        if beat is not None:
                            beat()
                        self._budget_freed.wait(timeout=0.5)
            if self._closed:
                return
            self._used[input_index] += rows
            self._queue.append((input_index, item, time.monotonic()))
            self._not_empty.notify()

    def _over_budget(self, input_index: int, rows: int) -> bool:
        return (self._used[input_index] > 0
                and self._used[input_index] + rows > self.row_budget
                and not self._closed)

    def has_items(self) -> bool:
        """Unlocked peek for the consumer: would get() return at once."""
        return bool(self._queue)

    def wake(self) -> None:
        """Make the consumer's ``get`` return None now, or at once the next
        time it finds the queue empty. Called from any thread; a no-op on a
        closed inbox."""
        with self._lock:
            if not self._closed:
                self._woken = True
                self._not_empty.notify()

    def get(self, timeout: Optional[float] = None) -> Optional[tuple[int, QueueItem]]:
        """Pop next item; None on timeout, wake or close-with-empty-queue."""
        with self._lock:
            if not self._queue and not self._woken:
                self._not_empty.wait(timeout=timeout)
            if not self._queue:
                self._woken = False
                return None
            idx, item, t_enq = self._queue.popleft()
        if self.metrics is not None and isinstance(item, Batch):
            self.metrics.queue_transit.observe(time.monotonic() - t_enq)
        return idx, item

    def release(self, input_index: int, item: QueueItem) -> None:
        """Consumer finished processing; return the rows to the budget."""
        if not isinstance(item, Batch):
            return
        with self._lock:
            self._used[input_index] -= item.num_rows
            self._budget_freed.notify_all()

    def close(self) -> None:
        with self._lock:
            self._closed = True
            self._not_empty.notify_all()
            self._budget_freed.notify_all()

    @property
    def closed(self) -> bool:
        return self._closed

    def depth(self) -> int:
        with self._lock:
            return len(self._queue)

    def used_rows(self) -> int:
        with self._lock:
            return sum(self._used)
