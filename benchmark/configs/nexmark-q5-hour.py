"""Plain reference of NEXmark Query 5 at the specification's sixty slides
to a window: the rows (auction, bids) of the auctions with the most bids in
one window, and what the query's per-auction aggregates emit for it. The
query's semantics do not change with the window: the caller hands over the
events of one whole window, whatever its width, and the sixty slides it was
binned into on the way leave no mark on its result. Numpy only; the cell's
own copy, nothing shared with the engine or with the 10 s configuration's
reference."""

import numpy as np


def _bids_per_auction(window: dict):
    return np.unique(window["auction"][window["bid"]], return_counts=True)


def rows(window: dict) -> list[tuple]:
    """``window`` holds the columns of the events of one whole window."""
    ids, counts = _bids_per_auction(window)
    if not len(ids):
        return []
    most = int(counts.max())
    return sorted((int(a), most) for a in ids[counts == most])


def partials(window: dict) -> dict:
    """What the query's first-level aggregates emit for the window, by the
    number of columns a row has: per auction its bids (both aggregates: the
    one the join reads and the one under the window's maximum). Rows sorted."""
    ids, counts = _bids_per_auction(window)
    return {2: np.column_stack([ids, counts.astype(np.int64)])}


def ingested(events_sent: int) -> int:
    """Rows a first-level aggregate has to have received once its scan has
    handed over ``events_sent`` events: the query keeps the bids."""
    from harness.stream import bids_before

    return bids_before(events_sent)
