"""Background materialization of device->host fetches.

A device->host fetch is a sync point: even when the copy was started with
``copy_to_host_async``, materializing it waits for the device to reach it
and for the transfer to land. Materializing on the operator thread
therefore stalls the hot loop once per window close.

This module gives operators a small shared fetch pool: extraction handles
are submitted right after dispatch and a worker thread blocks on the round
trip (numpy/jax release the GIL during the transfer). When the copy has
landed the worker calls the submitter's ``on_done`` — the owning task's
``TaskInbox.wake`` — and the task, on its own thread, runs the operator's
``drain_ready``: completed closes leave in program order
(``Future.is_ready()`` is a plain Event check), a few milliseconds after
their dispatch instead of at the operator's next input. Nothing is emitted
from a worker thread: the collector belongs to the task. The reference has
no analog (its operators and state share one address space); this is the
host-runtime half of SURVEY §7's "host-side async stages feeding device
steps".
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Callable, Optional

from ..obs import trace as _trace


def wait_buffers_ready(bufs, deadline_s: float = 30.0, waiting=_trace.NO_SPAN) -> None:
    """Poll device buffers' is_ready before materializing: a blocking
    np.asarray on a buffer whose async copy is still in flight was found to
    stall far longer than a short is_ready poll followed by the asarray
    once the copy has landed. Bounded: past the deadline the caller's
    blocking asarray still raises if the device actually failed (a bare
    poll loop would spin forever); ``waiting``, the ``trace.wait`` the
    caller polls under, then says ``gave_up`` on its record."""
    limit = time.monotonic() + deadline_s  # lint: waive LR109 — device-fetch wait deadline, not self-measurement
    for buf in bufs:
        if buf is None:
            continue
        while not buf.is_ready():
            if time.monotonic() > limit:  # lint: waive LR109 — device-fetch wait deadline, not self-measurement
                waiting.note(gave_up=True)
                return
            time.sleep(0.0002)


class Future:
    def __init__(self, fn: Callable, on_done: Optional[Callable[[], None]] = None,
                 program: Optional[str] = None):
        self._fn = fn
        self._on_done = on_done
        self._program = program
        self._done = threading.Event()
        self._value = None
        self._exc: Optional[BaseException] = None

    def is_ready(self) -> bool:
        return self._done.is_set()

    def result(self):
        if not self._done.is_set():
            # a forced drain: the calling task waits for the device
            with _trace.wait(_trace.DEVICE_WAIT, "agg.drain", program=self._program):
                self._done.wait()
        if self._exc is not None:
            raise self._exc
        return self._value

    def _run(self) -> None:
        try:
            self._value = self._fn()
        except BaseException as e:  # noqa: BLE001 - re-raised at result()
            self._exc = e
        self._done.set()
        if self._on_done is not None:
            # also when fn raised: the error surfaces at the task's drain
            self._on_done()


_WORKERS = 8  # the shared pool's size


class Prefetcher:
    """A small daemon pool draining a submit queue. Concurrent fetches
    overlap their waits, so multiple workers matter even though each just
    blocks on a copy.
    Submitted callables must not mutate shared aggregator state
    (SlotExtractHandle.result reads only snapshotted identities + device
    buffers); completion order is unconstrained — consumers pop their own
    queues in program order and check ``is_ready`` per future."""

    def __init__(self, workers: int = _WORKERS):
        self._q: "queue.Queue[Future]" = queue.Queue()
        self._workers = workers
        self._threads: list[threading.Thread] = []
        self._lock = threading.Lock()

    def _ensure_threads(self) -> None:
        if len(self._threads) < self._workers:
            with self._lock:
                while len(self._threads) < self._workers:
                    t = threading.Thread(
                        target=self._loop,
                        name=f"arroyo-prefetch-{len(self._threads)}",
                        daemon=True,
                    )
                    t.start()
                    self._threads.append(t)

    def _loop(self) -> None:
        while True:
            self._q.get()._run()

    def submit(self, fn: Callable, on_done: Optional[Callable[[], None]] = None,
               program: Optional[str] = None) -> Future:
        """``on_done`` runs on the worker once the future is ready; it only
        pokes the waiting task (``ctx.wake``), and must not raise.
        ``program``: the jitted program whose output ``fn`` waits for, for
        the record of a task that has to wait for ``fn`` in turn."""
        self._ensure_threads()
        fut = Future(fn, on_done, program)
        self._q.put(fut)
        return fut


_shared: Optional[Prefetcher] = None
_shared_lock = threading.Lock()


def shared_prefetcher() -> Prefetcher:
    global _shared
    if _shared is None:
        with _shared_lock:
            if _shared is None:
                _shared = Prefetcher()
    return _shared
