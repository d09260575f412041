"""SQL window-function (OVER clause) operator.

Reference behavior: crates/arroyo-worker/src/arrow/window_fn.rs:34 — rows
buffer per event-time bucket (upstream windowed operators stamp the window
start); when the watermark passes a bucket, rows are partitioned and sorted
and the window-function plan runs, emitting the input columns plus the
computed function columns.

Supported functions: row_number, rank, dense_rank, plus unbounded-partition
aggregates (sum/count/min/max/avg). Everything is vectorized: one lexsort per
bucket, segment boundaries via flatnonzero, per-partition reductions via
reduceat broadcast back with repeat.

A window top-N (``row_number() <= N`` a SELECT above, which sql/planner.py
hands down as ``limit``) is a selection and not a sort of everything: at
most N rows a partition leave the operator, and only the candidates are
ever ordered (``_first_n``). The rows are those the whole-partition path and
the filter would have left, in their order, bit for bit.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

from ..batch import KEY_FIELD, TIMESTAMP_FIELD, Batch
from ..engine.engine import register_operator
from ..expr import Expr, eval_expr
from ..graph import OpName
from ..hashing import hash_columns
from ..obs import trace as _trace
from ..operators.base import Operator, TableSpec, persist_mark, restore_marks
from ..windows.tumbling import WINDOW_END


def _sortable(col: np.ndarray, desc: bool) -> np.ndarray:
    """Map a column to an ascending-sortable numeric key. Descending order
    negates a rank transform for everything but floats — negating raw
    unsigned columns wraps (0 would sort first) and int64 min overflows."""
    if col.dtype == object:
        import pandas as pd

        codes, uniques = pd.factorize(col, use_na_sentinel=True)
        order = np.argsort(np.asarray(uniques, dtype=object), kind="stable")
        rank_of = np.empty(len(uniques) + 1, dtype=np.int64)
        rank_of[order] = np.arange(len(uniques))
        rank_of[-1] = -1  # None sorts first
        key = rank_of[codes]
    elif col.dtype == np.bool_:
        key = col.astype(np.int64)
    elif col.dtype.kind in "iu":
        _u, key = np.unique(col, return_inverse=True)
        key = key.astype(np.int64)
    else:
        key = col
    return -key if desc else key


def _selectable(col: np.ndarray, desc: bool) -> Optional[np.ndarray]:
    """A column as a key that ``np.partition`` can select on, ascending in
    the order ``_sortable`` gives it and equal where that is equal, without
    the rank transform's sort over every row. None where there is none: a
    string column, whose NULLs compare with nothing."""
    if col.dtype == np.bool_:
        col = col.view(np.uint8)
    if col.dtype.kind in "iu":
        return ~col if desc else col  # -x - 1: no wrap, no overflow at the ends
    if col.dtype.kind == "f":
        return -col if desc else col
    return None


def _starts(values: np.ndarray) -> np.ndarray:
    """Where each run of equal values begins: the partitions of rows sorted
    by partition, the windows of a batch."""
    brk = np.ones(len(values), dtype=bool)
    brk[1:] = values[1:] != values[:-1]
    return np.flatnonzero(brk)


class WindowFunctionOperator(Operator):
    """config: partition_fields: [str], order_by: [(Expr, asc_bool)],
    functions: [(out_name, kind, Expr|None)], retain_fields: [str]|None
    (input columns to carry through; default all), limit: int|None (the N
    of a window top-N: each partition's first N rows in the ORDER BY order
    leave, every function a row_number)."""

    def __init__(self, cfg: dict):
        self.partition_fields: list[str] = list(cfg.get("partition_fields", ()))
        self.order_by: list[tuple[Expr, bool]] = list(cfg.get("order_by", ()))
        self.functions: list[tuple[str, str, Optional[Expr]]] = list(cfg["functions"])
        self.retain_fields = cfg.get("retain_fields")
        self.limit = int(cfg.get("limit") or 0)
        if self.limit and any(kind != "row_number" for _n, kind, _e in self.functions):
            raise ValueError("a limited window function ranks by row_number alone: "
                             "rank, dense_rank and aggregates need whole partitions")
        self.buf: dict[int, list[Batch]] = {}
        self.emitted_before: Optional[int] = None
        self.late_rows = 0  # state: ephemeral — observability counter (obs/profile.py export); never read into emitted data

    def tables(self):
        return [
            TableSpec("input", "expiring_time_key"),
            TableSpec("e", "global_keyed"),  # late-data barrier
        ]

    def on_start(self, ctx):
        tbl = ctx.table_manager.expiring_time_key("input")
        for b in tbl.all_batches():
            self._buffer(b)
        tbl.replace_all([])
        barriers = restore_marks(ctx, "e")
        if barriers:
            self.emitted_before = max(barriers)

    def _buffer(self, batch: Batch) -> None:
        """Each timestamp's rows to its bucket, in the order they came. A
        window operator hands its windows over one after the other, so the
        buckets of a batch are slices of it; rows out of that order are
        brought into it by one stable sort."""
        ts = batch.timestamps
        starts = _starts(ts)
        if (ts[starts[1:]] < ts[starts[1:] - 1]).any():
            batch = batch.take(np.argsort(ts, kind="stable"))
            ts = batch.timestamps
            starts = _starts(ts)
        for lo, hi in zip(starts.tolist(), [*starts[1:].tolist(), len(ts)]):
            self.buf.setdefault(int(ts[lo]), []).append(batch.slice(lo, hi))

    def process_batch(self, batch, ctx, collector, input_index=0):
        if self.emitted_before is not None:
            late = batch.timestamps < self.emitted_before
            if late.any():
                self.late_rows += int(late.sum())
                if late.all():
                    return
                batch = batch.filter(~late)
        self._buffer(batch)

    def handle_watermark(self, watermark, ctx, collector):
        if not watermark.is_idle:
            self._emit_closed(watermark.value, collector)
        return watermark

    def on_close(self, ctx, collector):
        self._emit_closed(None, collector)

    def _emit_closed(self, before: Optional[int], collector) -> None:
        for t in sorted(k for k in self.buf if before is None or k < before):
            self._compute_and_emit(t, Batch.concat(self.buf.pop(t)), collector)
        if before is not None and (
            self.emitted_before is None or before > self.emitted_before
        ):
            self.emitted_before = before

    def _one_partition(self, b: Batch) -> bool:
        for f in self.partition_fields:
            col = np.asarray(b[f])
            if not (col == col[0]).all():
                return False
        return True

    def _sort_keys(self, by: list[np.ndarray], rows=slice(None)) -> list[np.ndarray]:
        """The ORDER BY columns ``by`` (of ``rows`` alone) as ``np.lexsort``
        takes them: ascending-sortable keys, the last expression first."""
        return [_sortable(col[rows], not asc)
                for col, (_e, asc) in zip(reversed(by), reversed(self.order_by))]

    def _first_n(self, b: Batch, n: int, by: list[np.ndarray]) -> Optional[np.ndarray]:
        """The rows of a one-partition bucket that a row_number up to
        ``limit`` keeps, in their order, by a selection: the limit-th value
        of the leading ORDER BY key (``np.partition``), the rows that reach
        it, ties included, and the full key list over those few. A stable
        sort of a subset in its rows' first order leaves them as the sort of
        everything would. None where the bucket has to be ordered whole: more
        partitions than one, a leading key no selection reads (a string), or
        a limit-th value that is a NaN."""
        k = self.limit
        if n <= k or not self._one_partition(b):
            return None
        if not by:
            return np.arange(k)
        lead = _selectable(by[0], not self.order_by[0][1])
        if lead is None:
            return None
        reach = np.partition(lead, k - 1)[k - 1]
        if reach != reach:
            return None
        found = np.flatnonzero(lead <= reach)
        return found[np.lexsort(tuple(self._sort_keys(by, found)))[:k]]

    def _compute_and_emit(self, t: int, b: Batch, collector) -> None:
        n = b.num_rows
        if n == 0:
            return
        end = b.columns.get(WINDOW_END)
        with _trace.window_rank(t if end is None else int(end[0]), n, self.limit) as span:
            out, partitions = self._rank(b, n)
            _trace.window_ranked(span, n, out.num_rows, partitions)
        collector.collect(out)

    def _rank(self, b: Batch, n: int) -> tuple[Batch, int]:
        """-> the bucket's output rows and the partitions they fall in."""
        by = [np.asarray(eval_expr(e, b.columns, n)) for e, _asc in self.order_by]
        order = self._first_n(b, n, by) if self.limit else None
        if order is not None:
            sort_keys: list[np.ndarray] = []
            starts = np.zeros(1, dtype=np.int64)
        else:
            # sort: partition hash first, then order-by keys
            sort_keys = self._sort_keys(by)
            if self.partition_fields:
                part = hash_columns([np.asarray(b[f]) for f in self.partition_fields])
                part_signed = part.view(np.int64)
            else:
                part_signed = np.zeros(n, dtype=np.int64)
            order = np.lexsort(tuple(sort_keys + [part_signed]))
            starts = _starts(part_signed[order])
            if self.limit:
                # cut to each partition's first N before any column is gathered
                ahead = np.arange(n) - np.repeat(starts, np.diff(np.append(starts, n)))
                order = order[ahead < self.limit]
                starts = _starts(part_signed[order])
        n = len(order)
        sb = b.take(order)
        counts = np.diff(np.append(starts, n))
        part_start = np.repeat(starts, counts)  # per-row partition start idx
        pos = np.arange(n)
        # order-key change points (for rank/dense_rank ties) — reuse the
        # already-built sort keys, permuted into sorted order
        brk = np.zeros(n, dtype=bool)
        brk[starts] = True
        if self.order_by:
            obrk = brk.copy()
            for k in sort_keys:
                k_sorted = k[order]
                obrk[1:] |= k_sorted[1:] != k_sorted[:-1]
        else:
            obrk = brk
        cols = dict(sb.columns)
        if self.retain_fields is not None:
            keep = set(self.retain_fields) | {TIMESTAMP_FIELD}
            if KEY_FIELD in cols:
                keep.add(KEY_FIELD)
            cols = {k: v for k, v in cols.items() if k in keep}
        for out_name, kind, e in self.functions:
            if kind == "row_number":
                cols[out_name] = pos - part_start + 1
            elif kind == "rank":
                # index of the first row of the tie-group, relative to partition
                tie_start = pos[obrk]
                cols[out_name] = np.repeat(tie_start, np.diff(np.append(np.flatnonzero(obrk), n))) - part_start + 1
            elif kind == "dense_rank":
                new_in_part = np.cumsum(obrk) - 1
                first_of_part = (np.cumsum(obrk) - 1)[part_start]
                cols[out_name] = new_in_part - first_of_part + 1
            elif kind in ("sum", "count", "min", "max", "avg"):
                if kind == "count" or e is None:
                    vals = np.ones(n, dtype=np.int64)
                else:
                    vals = np.asarray(eval_expr(e, sb.columns, n))
                if kind in ("sum", "count"):
                    red = np.add.reduceat(vals, starts)
                elif kind == "min":
                    red = np.minimum.reduceat(vals, starts)
                elif kind == "max":
                    red = np.maximum.reduceat(vals, starts)
                else:
                    s = np.add.reduceat(vals.astype(np.float64), starts)
                    red = s / counts
                cols[out_name] = np.repeat(red, counts)
            else:
                raise NotImplementedError(f"window function {kind}")
        return Batch(cols), len(starts)

    def handle_checkpoint(self, barrier, ctx, collector):
        tbl = ctx.table_manager.expiring_time_key("input")
        tbl.replace_all([b for lst in self.buf.values() for b in lst])
        persist_mark(ctx, "e", self.emitted_before)


@register_operator(OpName.WINDOW_FUNCTION)
def _make_window_fn(cfg: dict):
    return WindowFunctionOperator(cfg)
