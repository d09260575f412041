"""The benchmark's copy of the stream against the connector as it is today,
and the two plain references on hand-made windows."""

import numpy as np
import pytest

from harness import stream
from harness.cells import Cell


# the copy's column -> the connector's
COLUMNS = {"is_person": "person", "is_auction": "auction", "bid": "bid",
           "person.id": "person.id", "auction.id": "auction.id",
           "auction.seller": "auction.seller", "auction": "bid.auction",
           "price": "bid.price", "bid.bidder": "bid.bidder"}


@pytest.mark.parametrize("seed", [0, 7, 4242424242])
def test_copy_equals_connector(seed):
    from arroyo_tpu.connectors.nexmark import NexmarkSource

    src = NexmarkSource({"inter_event_micros": 100, "first_event_micros": 0, "seed": seed,
                         "include_strings": False, "columns": sorted(set(COLUMNS.values()))})
    # from the stream's first event (one person, no auction so far) and from
    # deep inside it
    for lo, hi in ((0, 10_000), (123_450, 133_450)):
        b = src._generate(np.arange(lo, hi, dtype=np.uint64))
        mine = stream.generate(lo, hi, seed)
        assert set(mine) == set(COLUMNS) | {"event"}
        assert np.array_equal(mine["event"], np.arange(lo, hi))
        for column, theirs in COLUMNS.items():
            assert mine[column].dtype == np.asarray(b[theirs]).dtype, column
            assert np.array_equal(mine[column], np.asarray(b[theirs])), column


def test_events_before():
    for n in (0, 1, 2, 4, 5, 49, 50, 51, 52, 54, 55, 100_003):
        events = stream.generate(0, n, 0)
        assert stream.persons_before(n) == int(events["is_person"].sum())
        assert stream.auctions_before(n) == int(events["is_auction"].sum())
        assert stream.bids_before(n) == int(events["bid"].sum())
        assert stream.persons_before(n) + stream.auctions_before(n) \
            + stream.bids_before(n) == n


def window(rows):
    """rows: (bid?, auction, price)"""
    a = np.array(rows, dtype=np.int64).reshape(-1, 3)
    return {"bid": a[:, 0].astype(bool), "auction": a[:, 1], "price": a[:, 2]}


def test_q7_reference():
    ref = Cell("q7-sat").reference
    w = window([(1, 1001, 500), (1, 1002, 900), (0, 0, 0), (1, 1003, 900),
                (1, 1002, 100), (1, 1003, 900)])
    assert ref.rows(w) == [(1002, 900), (1003, 900)]
    assert ref.rows(window([(0, 0, 0)])) == []


def test_q5_reference():
    ref = Cell("q5-sat").reference
    w = window([(1, 1001, 5), (1, 1002, 5), (1, 1001, 5), (0, 0, 0), (1, 1002, 5), (1, 1003, 5)])
    assert ref.rows(w) == [(1001, 2), (1002, 2)]
    assert ref.ingested(100) == 92
