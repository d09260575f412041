"""Plain reference of NEXmark Query 7 for the four-chip deployment: the
rows (auction, price) of the bids that carry the highest price of one
window. The query does not change with the number of chips its state is
sharded over, so this is nexmark-q7-highest-bid's reference, the cell's own
copy of it. Numpy only; shares nothing with the engine."""

import numpy as np


def rows(window: dict) -> list[tuple]:
    """``window`` holds the columns of the events of one whole window."""
    bid = window["bid"]
    auction, price = window["auction"][bid], window["price"][bid]
    if not len(price):
        return []
    top = price == price.max()
    # one row per auction whose own maximum is the window's maximum
    return sorted((int(a), int(price.max())) for a in np.unique(auction[top]))


def partials(window: dict) -> dict:
    """What the query's first-level aggregates emit for the window, by the
    number of columns a row has: per auction its highest price, and the
    window's highest price alone. Rows sorted."""
    bid = window["bid"]
    auction, price = window["auction"][bid], window["price"][bid]
    ids, inv = np.unique(auction, return_inverse=True)
    mx = np.zeros(len(ids), dtype=np.int64)
    np.maximum.at(mx, inv, price)
    return {2: np.column_stack([ids, mx]), 1: np.array([[price.max()]], dtype=np.int64)}


def ingested(events_sent: int) -> int:
    """Rows a first-level aggregate has to have received once its scan has
    handed over ``events_sent`` events: the query keeps the bids."""
    from harness.stream import bids_before

    return bids_before(events_sent)
