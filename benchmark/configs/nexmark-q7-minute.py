"""Plain reference of NEXmark Query 7 at the specification's one-minute
window: the rows (auction, price) of the bids that carry the highest price
of one window, and what the query's two first-level aggregates emit for it.
The window's width is the caller's: it hands over the events of one whole
window. Numpy only; its own copy, nothing shared with the engine or with
the 10 s configuration's reference."""

import numpy as np


def _bids(window: dict):
    keep = window["bid"]
    return window["auction"][keep], window["price"][keep]


def rows(window: dict) -> list[tuple]:
    """``window`` holds the columns of the events of one whole window."""
    auction, price = _bids(window)
    if not len(price):
        return []
    highest = int(price.max())
    # one row per auction that received a bid at the window's highest price
    return sorted((int(a), highest) for a in set(auction[price == highest].tolist()))


def partials(window: dict) -> dict:
    """The first-level aggregates' output for the window, by the number of
    columns a row has: 2, per auction its highest price (rows sorted by
    auction); 1, the window's highest price alone."""
    auction, price = _bids(window)
    order = np.lexsort((price, auction))
    auction, price = auction[order], price[order]
    # sorted by auction, then price: an auction's last row carries its maximum
    last = np.append(auction[1:] != auction[:-1], True)
    return {2: np.column_stack([auction[last], price[last]]).astype(np.int64),
            1: np.array([[price.max()]], dtype=np.int64)}


def ingested(events_sent: int) -> int:
    """Rows a first-level aggregate has to have received once its scan has
    handed over ``events_sent`` events: the query keeps the bids, 46 of
    every 50 events, the first four of each 50 being the person and the
    auctions."""
    full, rest = divmod(int(events_sent), 50)
    return full * 46 + max(0, rest - 4)
