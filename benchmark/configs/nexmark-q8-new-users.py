"""Plain reference of NEXmark Query 8 as the benchmark runs it: the persons
who registered in a 10 s window and opened an auction in it, with how often
they did either. Numpy only; shares nothing with the engine. The query's
two first-level aggregates emit rows of the same width and receive
different events (the persons, 1 of every 50; the auctions, 3 of 50), so
the reference answers for each by what the plan keys it on."""

import numpy as np

PERSONS, SELLERS = ("person.id",), ("auction.seller",)


def _counts(window: dict, kind: str, column: str) -> np.ndarray:
    """Rows (id, how often among the window's events of ``kind``), by id."""
    ids, n = np.unique(window[column][window[kind]], return_counts=True)
    return np.column_stack([ids, n]).astype(np.int64)


def partials(window: dict) -> dict:
    """``window`` holds the columns of the events of one whole window
    (``harness.stream.generate``). By key columns: per person registered
    in it how often (once), per seller the auctions opened in it."""
    return {PERSONS: _counts(window, "is_person", "person.id"),
            SELLERS: _counts(window, "is_auction", "auction.seller")}


def rows(window: dict) -> list[tuple]:
    """(id, registered, opened) of every person of the window who is also
    one of its sellers."""
    persons = _counts(window, "is_person", "person.id")
    opened = dict(_counts(window, "is_auction", "auction.seller").tolist())
    return sorted((i, n, opened[i]) for i, n in persons.tolist() if i in opened)


def ingested(events_sent: int) -> dict:
    """Rows each first-level aggregate has to have received once its scan
    has handed over ``events_sent`` events."""
    from harness.stream import auctions_before, persons_before

    return {PERSONS: persons_before(events_sent), SELLERS: auctions_before(events_sent)}
