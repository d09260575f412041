"""The benchmark's copy of the stream against the connector as it is today,
and the two plain references on hand-made windows."""

import numpy as np
import pytest

from harness import stream
from harness.cells import Cell


@pytest.mark.parametrize("seed", [0, 7, 4242424242])
def test_copy_equals_connector(seed):
    from arroyo_tpu.connectors.nexmark import NexmarkSource

    src = NexmarkSource({"inter_event_micros": 100, "first_event_micros": 0, "seed": seed,
                         "include_strings": False, "columns": ["bid.auction", "bid.price"]})
    lo, hi = 123_450, 133_450
    b = src._generate(np.arange(lo, hi, dtype=np.uint64))
    mine = stream.generate(lo, hi, seed)
    assert np.array_equal(mine["bid"], np.asarray(b["bid"]))
    assert np.array_equal(mine["auction"], np.asarray(b["bid.auction"]))
    assert np.array_equal(mine["price"], np.asarray(b["bid.price"]))


def test_bids_before():
    for n in (0, 1, 4, 5, 49, 50, 51, 54, 55, 100_003):
        assert stream.bids_before(n) == int(stream.generate(0, n, 0)["bid"].sum())


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
