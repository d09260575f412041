"""Plain reference of upstream Arroyo's first pipeline: the five auctions
with the most bids in one window, ranked, and what the query's one
per-auction aggregate emits for it. The caller hands over the events of one
whole window, whatever its width: the thirty slides it was binned into on
the way leave no mark on its result. Ties in the count go to the lower
auction id, as the query's ORDER BY num DESC, auction ASC says. Numpy only;
the cell's own copy, nothing shared with the engine or with another
configuration's reference."""

import numpy as np

TOP = 5


def _bids_per_auction(window: dict):
    return np.unique(window["auction"][window["bid"]], return_counts=True)


def rows(window: dict) -> list[tuple]:
    """``window`` holds the columns of the events of one whole window: the
    rows (auction, bids, rank) of its five busiest auctions, fewer where it
    has fewer."""
    ids, counts = _bids_per_auction(window)
    first = np.lexsort((ids, -counts))[:TOP]  # by count descending, then by id
    return [(int(ids[i]), int(counts[i]), place + 1) for place, i in enumerate(first)]


def partials(window: dict) -> dict:
    """What the query's first-level aggregate emits for the window, by the
    number of columns a row has: per auction its bids. Rows sorted."""
    ids, counts = _bids_per_auction(window)
    return {2: np.column_stack([ids, counts.astype(np.int64)])}


def ingested(events_sent: int) -> int:
    """Rows the first-level aggregate has to have received once its scan
    has handed over ``events_sent`` events: the query keeps the bids."""
    from harness.stream import bids_before

    return bids_before(events_sent)
