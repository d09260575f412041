"""The NEXmark stream as the benchmark defines it: which events are persons,
auctions and bids, and every integer column of each, by event number and
seed.

A copy of the arithmetic of the ``nexmark`` connector (``_generate``,
``_rng``, ``splitmix64`` of ``arroyo_tpu/connectors/nexmark.py``) for the
integer columns of all three event kinds; strings stay out, ``correct`` is
integers only. The oracle is fed from this copy, never from the program, so
a connector that drifts makes ``correct`` false instead of moving the
oracle with it. ``tests/test_stream.py`` holds every column of the copy to
the connector as it is today.
"""

from __future__ import annotations

import numpy as np

PROPORTION = 50          # events per epoch: 1 person, 3 auctions, 46 bids
PERSONS_PER_EPOCH = 1
AUCTIONS_PER_EPOCH = 3
NOT_BIDS = PERSONS_PER_EPOCH + AUCTIONS_PER_EPOCH  # the person and the auctions come first
FIRST_PERSON_ID = 1000
FIRST_AUCTION_ID = 1000
HOT_AUCTION_RATIO = 100
HOT_BIDDER_RATIO = 100

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


def _hot_or_cold(hot_lane, ratio: int, cold_lane, so_far, first_id: int) -> np.ndarray:
    """The connector's choice of an id among those so far: nine times in
    ten (by ``cold_lane``) one of the newest ``ratio``, else any of them."""
    hot = np.maximum(so_far - 1 - (hot_lane % np.uint64(ratio)).astype(np.int64), first_id)
    cold = first_id + hot_lane.astype(np.int64) % np.maximum(so_far - first_id, 1)
    return np.where((cold_lane % np.uint64(100)).astype(np.int64) < 90, hot, cold)


def generate(lo: int, hi: int, seed: int) -> dict[str, np.ndarray]:
    """Events ``lo <= n < hi``, a column each, 0 where the event is not of
    the column's kind:

      event                           the event's number
      is_person, is_auction, bid      which kind it is (bool)
      person.id
      auction.id, auction.seller
      auction, price, bid.bidder      the bid's auction, price and bidder

    ``bid``, ``auction`` and ``price`` keep the names the first references
    were written against; the auction *event's* columns are dotted, as the
    connector names them."""
    with np.errstate(over="ignore"):
        n = np.arange(lo, hi, dtype=np.uint64)
        epoch = (n // np.uint64(PROPORTION)).astype(np.int64)
        offset = (n % np.uint64(PROPORTION)).astype(np.int64)
        is_person = offset < PERSONS_PER_EPOCH
        bid = offset >= NOT_BIDS
        is_auction = ~(is_person | bid)
        # ids so far, the current epoch's left out
        people = FIRST_PERSON_ID + epoch * PERSONS_PER_EPOCH
        auctions = FIRST_AUCTION_ID + epoch * AUCTIONS_PER_EPOCH
        r1, r2, r3, r4 = (_rng(n, salt, seed) for salt in (1, 2, 3, 4))
        auction = np.where(
            bid, _hot_or_cold(r1, HOT_AUCTION_RATIO, r2, auctions, FIRST_AUCTION_ID), 0)
        price = np.where(bid, (100 + (r2 % np.uint64(9_999_900))).astype(np.int64), 0)
        bidder = np.where(
            bid, _hot_or_cold(r3, HOT_BIDDER_RATIO, r4, people, FIRST_PERSON_ID), 0)
        seller = np.where(
            is_auction,
            FIRST_PERSON_ID + r1.astype(np.int64) % np.maximum(people - FIRST_PERSON_ID, 1), 0)
    return {
        "event": n.astype(np.int64), "is_person": is_person, "is_auction": is_auction,
        "bid": bid,
        "person.id": np.where(is_person, FIRST_PERSON_ID + epoch, 0),
        "auction.id": np.where(is_auction, auctions + offset - PERSONS_PER_EPOCH, 0),
        "auction.seller": seller,
        "auction": auction, "price": price, "bid.bidder": bidder,
    }


def _before(n: int, first: int, count: int) -> int:
    """How many of the events ``0 <= i < n`` sit at offsets ``first <=
    offset < first + count`` of their epoch."""
    full, rest = divmod(int(n), PROPORTION)
    return full * count + min(max(0, rest - first), count)


def persons_before(n: int) -> int:
    """How many of the events ``0 <= i < n`` are persons."""
    return _before(n, 0, PERSONS_PER_EPOCH)


def auctions_before(n: int) -> int:
    """How many of the events ``0 <= i < n`` are auctions."""
    return _before(n, PERSONS_PER_EPOCH, AUCTIONS_PER_EPOCH)


def bids_before(n: int) -> int:
    """How many of the events ``0 <= i < n`` are bids."""
    return _before(n, NOT_BIDS, PROPORTION - NOT_BIDS)
