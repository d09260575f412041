"""Plain reference of NEXmark Query 15, the bidding statistics report, as the
benchmark runs it: of one window's bids, how many there are, how many
distinct bidders and how many distinct auctions, each in all and in the
three price bands. Numpy and plain Python only; shares nothing with the
engine. The plan splits each count(DISTINCT) into a first-level aggregate
keyed by the distinct column, so the reference answers for each by what the
plan keys it on."""

import numpy as np

BIDDERS, AUCTIONS = ("bid.bidder",), ("bid.auction",)
COLUMN = {BIDDERS: "bid.bidder", AUCTIONS: "auction"}  # harness.stream's names


def _bands(price: np.ndarray) -> list[np.ndarray]:
    """Which of the bids fall in each of the report's three price bands."""
    return [price < 10_000, (price >= 10_000) & (price < 1_000_000), price >= 1_000_000]


def _pairs(window: dict, column: str) -> np.ndarray:
    """Rows (value, its bids, its bids in each band), by value: what the
    first level of the split holds for the window."""
    bid = window["bid"]
    values, price = window[column][bid], window["price"][bid]
    ids, inv = np.unique(values, return_inverse=True)
    lanes = [np.bincount(inv, minlength=len(ids))]
    lanes += [np.bincount(inv[band], minlength=len(ids)) for band in _bands(price)]
    return np.column_stack([ids, *lanes]).astype(np.int64)


def partials(window: dict) -> dict:
    """``window`` holds the columns of the events of one whole window
    (``harness.stream.generate``)."""
    return {key: _pairs(window, column) for key, column in COLUMN.items()}


def rows(window: dict) -> list[tuple]:
    """The window's one row of twelve integers, counted with sets."""
    bid = window["bid"]
    price = window["price"][bid]
    if not len(price):
        return []
    masks = [np.ones(len(price), dtype=bool)] + _bands(price)
    row = [int(m.sum()) for m in masks]
    for column in COLUMN.values():
        values = window[column][bid]
        row += [len(set(values[m].tolist())) for m in masks]
    return [tuple(row)]


def ingested(events_sent: int) -> dict:
    """Rows each first-level aggregate has to have received once the scan
    has handed over ``events_sent`` events: both are fed the bids."""
    from harness.stream import bids_before

    return {BIDDERS: bids_before(events_sent), AUCTIONS: bids_before(events_sent)}
