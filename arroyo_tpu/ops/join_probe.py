"""Device-lowered hash-join index computation for windowed joins.

Reference behavior being replaced: the per-bin DataFusion join execs of
crates/arroyo-worker/src/arrow/instant_join.rs:38. The join's heavy phase —
sorting the build side and binary-searching every probe key — runs on the
device as one jitted program; only the data-dependent pair expansion (whose
output size XLA cannot represent statically) stays on host, where it is a
cheap repeat/cumsum.

Shapes are bucketed to powers of two so each (probe, build) size pair
compiles once; results stream back through copy_to_host_async and a
JoinHandle, so windowed-join operators can dispatch the close for window t,
hand ``JoinHandle.result`` to the fetch pool (ops/prefetch.py) and emit when
woken, without blocking the hot loop (same pipelining discipline as
ops/slot_agg.py window closes).

A bucket pair's program is compiled when it is first called, which on a
chip takes seconds. A probe that fills more than half of a bucket therefore
names the next pair (``next_pairs``) and the join runs it once on scratch
arrays on a fetch worker (``prewarm``), so a window whose key count crosses
a power of two finds its program compiled and its close does not wait on a
compiler.
"""

from __future__ import annotations

import functools
import threading

import numpy as np

from ..obs import trace as _trace

_SENTINEL = np.iinfo(np.int64).max
_SMALLEST_BUCKET = 64  # rows a side is padded to at least (_bucket)


def host_join_indices(
    left_keys: np.ndarray, right_keys: np.ndarray
) -> tuple[np.ndarray, np.ndarray]:
    """Host (numpy) inner-join row index pairs (li, ri) where keys match:
    sort the right side once, binary-search each left key, expand ranges.
    The same sort/search phase the device path runs via _probe_jit."""
    order = np.argsort(right_keys, kind="stable")
    rk = right_keys[order]
    lo = np.searchsorted(rk, left_keys, side="left")
    hi = np.searchsorted(rk, left_keys, side="right")
    counts = hi - lo
    li = np.repeat(np.arange(len(left_keys)), counts)
    # for each left row, offsets lo[l]..hi[l] into the sorted right
    if len(li):
        within = np.arange(len(li)) - np.repeat(
            np.cumsum(counts) - counts, counts
        )
        ri = order[np.repeat(lo, counts) + within]
    else:
        ri = np.empty(0, dtype=np.int64)
    return li, ri


def fused_join_indices(
    left_keys: np.ndarray,
    right_keys: np.ndarray,
    l_bounds: np.ndarray,
    r_bounds: np.ndarray,
) -> tuple[np.ndarray, np.ndarray]:
    """Inner-join pairs for W independent partitions (windows) in one call:
    partition w spans left rows l_bounds[w]:l_bounds[w+1] and right rows
    r_bounds[w]:r_bounds[w+1]. Each partition is probed with the shared
    sort/search join on its slice (still a Python loop over W — a true
    (partition, key) lexsort probe is a possible follow-up); the win is in
    the OUTPUT: pairs come back as GLOBAL row indices so the caller
    gathers and emits once for all windows instead of W tiny batches."""
    lis: list[np.ndarray] = []
    ris: list[np.ndarray] = []
    for w in range(len(l_bounds) - 1):
        l0, l1 = int(l_bounds[w]), int(l_bounds[w + 1])
        r0, r1 = int(r_bounds[w]), int(r_bounds[w + 1])
        li, ri = host_join_indices(left_keys[l0:l1], right_keys[r0:r1])
        if len(li):
            lis.append(li + l0)
            ris.append(ri + r0)
    if not lis:
        return (np.empty(0, dtype=np.int64), np.empty(0, dtype=np.int64))
    return np.concatenate(lis), np.concatenate(ris)


@functools.lru_cache(maxsize=1)
def _probe_jit():
    # one jitted callable; jax specializes per bucketed input shape
    import jax
    import jax.numpy as jnp

    def probe(lk, rk):
        order = jnp.argsort(rk)
        rk_s = rk[order]
        # a build side of one smallest bucket (q7's and q5's: the window's one
        # global row) is counted, not searched: the build keys below each
        # probe key are the same lo and hi, 64 compares a row in place of six
        # dependent gathers, and a program a v5e's host compiles in 1.4 s where
        # the two search loops take 5.4 s at 131,072 probe rows (PERF.md, PR 33)
        method = "compare_all" if rk.shape[0] <= _SMALLEST_BUCKET else "scan"
        lo = jnp.searchsorted(rk_s, lk, side="left", method=method)
        hi = jnp.searchsorted(rk_s, lk, side="right", method=method)
        return order.astype(jnp.int32), lo.astype(jnp.int32), hi.astype(jnp.int32)

    return jax.jit(probe)


def _bucket(n: int) -> int:
    c = _SMALLEST_BUCKET
    while c < n:
        c <<= 1
    return c


def bucket_pair(n_l: int, n_r: int) -> tuple[int, int]:
    """The padded (probe, build) sizes a device join of these rows runs at."""
    return _bucket(n_l), _bucket(n_r)


class JoinHandle:
    """In-flight device join for one window: order/lo/hi are streaming to
    host; result() expands them into (li, ri) inner-join index pairs."""

    program = "jit_probe"  # what result() waits for (ops/prefetch.py submit)

    def __init__(self, n_l: int, n_r: int, order, lo, hi):
        self._n_l = n_l
        self._n_r = n_r
        self._bufs = (order, lo, hi)
        # the join task that dispatched the probe, and the window it is
        # for: result() runs on a fetch worker
        self._lane, self._trace_id = _trace.current_window()

    def result(self) -> tuple[np.ndarray, np.ndarray]:
        from .prefetch import wait_buffers_ready

        with _trace.wait(_trace.DEVICE_WAIT, "join.fetch", lane=self._lane,
                         trace_id=self._trace_id, program=self.program) as waiting:
            wait_buffers_ready(self._bufs, waiting=waiting)
        order, lo, hi = (np.asarray(b) for b in self._bufs)
        n_l, n_r = self._n_l, self._n_r
        lo = lo[:n_l].astype(np.int64)
        hi = hi[:n_l].astype(np.int64)
        counts = hi - lo
        li = np.repeat(np.arange(n_l), counts)
        if len(li):
            within = np.arange(len(li)) - np.repeat(np.cumsum(counts) - counts, counts)
            ri = order[np.repeat(lo, counts) + within].astype(np.int64)
            # padded build rows sort to the tail; a probe key equal to the
            # sentinel could reference them — drop those pairs exactly
            keep = ri < n_r
            if not keep.all():
                li, ri = li[keep], ri[keep]
        else:
            ri = np.empty(0, dtype=np.int64)
        return li, ri


# bucket pairs this process has probed with or asked a fetch worker to warm
_pairs_met: set[tuple[int, int]] = set()
_pairs_lock = threading.Lock()


def next_pairs(n_l: int, n_r: int) -> list[tuple[int, int]]:
    """The bucket pairs a probe of these sizes wants compiled ahead, each
    named once per process: a side that fills more than half of its bucket
    may pass it at a later close, so the pairs with that side doubled (and,
    where both do, with both). A probe under half on both sides names none."""
    l_cap, r_cap = bucket_pair(n_l, n_r)
    l_next = 2 * l_cap if 2 * n_l > l_cap else l_cap
    r_next = 2 * r_cap if 2 * n_r > r_cap else r_cap
    wanted = {(l_next, r_cap), (l_cap, r_next), (l_next, r_next)}
    with _pairs_lock:
        new = sorted(wanted - _pairs_met - {(l_cap, r_cap)})
        _pairs_met.update(new)
    return new


def prewarm(pair: tuple[int, int]) -> None:
    """Compile the probe for a bucket pair by running it on scratch arrays
    (all sentinel: a sort of equal keys); returns when the device is done."""
    from .prefetch import wait_buffers_ready

    lk = np.full(pair[0], _SENTINEL, dtype=np.int64)
    rk = np.full(pair[1], _SENTINEL, dtype=np.int64)
    wait_buffers_ready(_probe_jit()(lk, rk))


def device_join_start(left_keys: np.ndarray, right_keys: np.ndarray) -> JoinHandle:
    """Dispatch the sort/search phase for an inner join on int64 keys;
    returns a JoinHandle whose result() yields (li, ri) pairs."""
    n_l, n_r = len(left_keys), len(right_keys)
    l_cap, r_cap = bucket_pair(n_l, n_r)
    _pairs_met.add((l_cap, r_cap))
    lk = np.full(l_cap, _SENTINEL, dtype=np.int64)
    lk[:n_l] = left_keys
    rk = np.full(r_cap, _SENTINEL, dtype=np.int64)
    rk[:n_r] = right_keys
    order, lo, hi = _probe_jit()(lk, rk)
    for b in (order, lo, hi):
        b.copy_to_host_async()
    return JoinHandle(n_l, n_r, order, lo, hi)
