"""Plain reference of the two-stream fixture: the persons who registered in
a window and opened an auction in it, with how often they did either.
Numpy only; shares nothing with the engine. Its two first-level aggregates
emit rows of the same width and receive different events, so it answers
for each by what the plan keys it on."""

import numpy as np

PERSONS, SELLERS = ("person.id",), ("auction.seller",)


def _counts(window: dict, kind: str, column: str) -> np.ndarray:
    """Rows (id, how often among the events of ``kind``), sorted by id."""
    ids, n = np.unique(window[column][window[kind]], return_counts=True)
    return np.column_stack([ids, n]).astype(np.int64)


def _persons(window: dict) -> np.ndarray:
    return _counts(window, "is_person", "person.id")


def _sellers(window: dict) -> np.ndarray:
    return _counts(window, "is_auction", "auction.seller")


def partials(window: dict) -> dict:
    """``window`` holds the columns of the events of one whole window
    (``harness.stream.generate``). By key columns: per person registered
    in it how often (once), per seller the auctions opened in it."""
    return {PERSONS: _persons(window), SELLERS: _sellers(window)}


def rows(window: dict) -> list[tuple]:
    opened = dict(_sellers(window).tolist())
    return sorted((i, n, opened[i]) for i, n in _persons(window).tolist() if i in opened)


def ingested(events_sent: int) -> dict:
    """Rows each first-level aggregate has to have received once its scan
    has handed over ``events_sent`` events: one keeps the persons (1 of
    every 50 events), the other the auctions (3 of 50)."""
    from harness.stream import auctions_before, persons_before

    return {PERSONS: persons_before(events_sent), SELLERS: auctions_before(events_sent)}
