"""The NEXmark stream as the benchmark defines it: which events are bids,
which auction and price each carries, by event number and seed.

A copy of the arithmetic of the ``nexmark`` connector (``_generate``,
``_rng``, ``splitmix64``) at commit f33500c, restricted to the columns the
configurations read. The oracle is fed from this copy, never from the
program, so a connector that drifts makes ``correct`` false instead of
moving the oracle with it. ``tests/test_stream.py`` holds the copy to the
connector as it is today.
"""

from __future__ import annotations

import numpy as np

PROPORTION = 50          # events per epoch: 1 person, 3 auctions, 46 bids
NOT_BIDS = 4             # the person and the auctions come first
FIRST_AUCTION_ID = 1000
AUCTIONS_PER_EPOCH = 3
HOT_AUCTION_RATIO = 100

_C1 = np.uint64(0x9E3779B97F4A7C15)
_C2 = np.uint64(0xBF58476D1CE4E5B9)
_C3 = np.uint64(0x94D049BB133111EB)


def _splitmix64(x: np.ndarray) -> np.ndarray:
    z = x + _C1
    z = (z ^ (z >> np.uint64(30))) * _C2
    z = (z ^ (z >> np.uint64(27))) * _C3
    return z ^ (z >> np.uint64(31))


def _rng(n: np.ndarray, salt: int, seed: int) -> np.ndarray:
    word = (salt * 0x9E3779B97F4A7C15) ^ (seed * 0xBF58476D1CE4E5B9)
    return _splitmix64(n ^ np.uint64((word | 1) & ((1 << 64) - 1)))


def generate(lo: int, hi: int, seed: int) -> dict[str, np.ndarray]:
    """Events ``lo <= n < hi``: ``bid`` (bool), ``auction`` and ``price``
    (0 where the event is no bid)."""
    with np.errstate(over="ignore"):
        n = np.arange(lo, hi, dtype=np.uint64)
        epoch = (n // np.uint64(PROPORTION)).astype(np.int64)
        bid = (n % np.uint64(PROPORTION)).astype(np.int64) >= NOT_BIDS
        max_auction = FIRST_AUCTION_ID + epoch * AUCTIONS_PER_EPOCH
        r0, r1 = _rng(n, 1, seed), _rng(n, 2, seed)
        hot = np.maximum(
            max_auction - 1 - (r0 % np.uint64(HOT_AUCTION_RATIO)).astype(np.int64),
            FIRST_AUCTION_ID)
        cold = FIRST_AUCTION_ID + (
            r0.astype(np.int64) % np.maximum(max_auction - FIRST_AUCTION_ID, 1))
        is_hot = (r1 % np.uint64(100)).astype(np.int64) < 90
        auction = np.where(bid, np.where(is_hot, hot, cold), 0)
        price = np.where(bid, (100 + (r1 % np.uint64(9_999_900))).astype(np.int64), 0)
    return {"bid": bid, "auction": auction, "price": price}


def bids_before(n: int) -> int:
    """How many of the events ``0 <= i < n`` are bids."""
    full, rest = divmod(int(n), PROPORTION)
    return full * (PROPORTION - NOT_BIDS) + max(0, rest - NOT_BIDS)
