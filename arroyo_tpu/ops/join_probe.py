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
"""

from __future__ import annotations

import functools

import numpy as np

_SENTINEL = np.iinfo(np.int64).max


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
        lo = jnp.searchsorted(rk_s, lk, side="left")
        hi = jnp.searchsorted(rk_s, lk, side="right")
        return order.astype(jnp.int32), lo.astype(jnp.int32), hi.astype(jnp.int32)

    return jax.jit(probe)


def _bucket(n: int) -> int:
    c = 64
    while c < n:
        c <<= 1
    return c


class JoinHandle:
    """In-flight device join for one window: order/lo/hi are streaming to
    host; result() expands them into (li, ri) inner-join index pairs."""

    def __init__(self, n_l: int, n_r: int, order, lo, hi):
        self._n_l = n_l
        self._n_r = n_r
        self._bufs = (order, lo, hi)

    def result(self) -> tuple[np.ndarray, np.ndarray]:
        from .prefetch import wait_buffers_ready

        wait_buffers_ready(self._bufs)
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


def device_join_start(left_keys: np.ndarray, right_keys: np.ndarray) -> JoinHandle:
    """Dispatch the sort/search phase for an inner join on int64 keys;
    returns a JoinHandle whose result() yields (li, ri) pairs."""
    n_l, n_r = len(left_keys), len(right_keys)
    l_cap, r_cap = _bucket(n_l), _bucket(n_r)
    lk = np.full(l_cap, _SENTINEL, dtype=np.int64)
    lk[:n_l] = left_keys
    rk = np.full(r_cap, _SENTINEL, dtype=np.int64)
    rk[:n_r] = right_keys
    order, lo, hi = _probe_jit()(lk, rk)
    for b in (order, lo, hi):
        b.copy_to_host_async()
    return JoinHandle(n_l, n_r, order, lo, hi)
