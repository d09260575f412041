"""Plain reference of NEXmark Query 5: the rows (auction, bids) of the
auctions with the most bids in one window. Numpy only; shares nothing
with the engine."""

import numpy as np


def rows(window: dict) -> list[tuple]:
    """``window`` holds the columns of the events of one whole window."""
    auction = window["auction"][window["bid"]]
    if not len(auction):
        return []
    ids, counts = np.unique(auction, return_counts=True)
    most = counts.max()
    return sorted((int(a), int(most)) for a in ids[counts == most])


def partials(window: dict) -> dict:
    """What the query's first-level aggregates emit for the window, by the
    number of columns a row has: per auction its bids. Rows sorted."""
    ids, counts = np.unique(window["auction"][window["bid"]], return_counts=True)
    return {2: np.column_stack([ids, counts.astype(np.int64)])}


def ingested(events_sent: int) -> int:
    """Rows a first-level aggregate has to have received once its scan has
    handed over ``events_sent`` events: the query keeps the bids."""
    from harness.stream import bids_before

    return bids_before(events_sent)
