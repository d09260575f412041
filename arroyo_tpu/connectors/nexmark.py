"""Nexmark benchmark source.

Deterministic, splittable generator for the NEXMark auction benchmark
(reference: crates/arroyo-connectors/src/nexmark/operator.rs — event kinds
:68-160, GeneratorConfig :431, deterministic event-number scheme :514-530,
split() across subtasks :493). Re-designed vectorized: a whole micro-batch of
events is derived from its event numbers with numpy uint64 lanes (splitmix64
counter RNG), so generation keeps up with a TPU consumer; subtask i of p owns
event numbers n with n % p == i.

Event mix per 50 events (standard NEXMark proportions): 1 person, 3 auctions,
46 bids. The three entity types are flattened into presence-flagged column
groups ("person.*", "auction.*", "bid.*" with boolean "person"/"auction"/
"bid" presence columns) instead of Arrow struct columns; SQL predicates like
``bid IS NOT NULL`` resolve against the presence columns.

A scan synthesises the columns its table declares (``CREATE TABLE nexmark
("bid" BOOLEAN, "bid.auction" BIGINT, ...)``: the planner hands them over as
``columns``), beside the three presence flags and ``_timestamp``, which are
always built; a table declared with no column gets all 22 of
``NEXMARK_SCHEMA``. Every column is a function of the event number alone, so
its values do not depend on which others are built, nor on how a batch's event
numbers are cut: ``run`` builds a batch of more than ``_LOCK_KEPT_ROWS`` rows
in pieces (``_build``).
"""

from __future__ import annotations

import time

import numpy as np

from ..batch import TIMESTAMP_FIELD, Batch, Field, Schema
from ..config import config
from ..hashing import splitmix64
from ..obs import trace as _trace
from ..operators.base import SourceOperator, TableSpec
from ..types import SourceFinishType
from . import register_source

FIRST_PERSON_ID = 1000
FIRST_AUCTION_ID = 1000
FIRST_CATEGORY_ID = 10
PERSON_PROPORTION = 1
AUCTION_PROPORTION = 3
BID_PROPORTION = 46
TOTAL_PROPORTION = PERSON_PROPORTION + AUCTION_PROPORTION + BID_PROPORTION  # 50
HOT_AUCTION_RATIO = 100
HOT_BIDDER_RATIO = 100

NEXMARK_SCHEMA = Schema.of(
    [
        Field("event_type", "int32"),  # 0=person 1=auction 2=bid
        Field("person", "bool"),
        Field("person.id", "int64"),
        Field("person.name", "string"),
        Field("person.email_address", "string"),
        Field("person.city", "string"),
        Field("person.state", "string"),
        Field("auction", "bool"),
        Field("auction.id", "int64"),
        Field("auction.item_name", "string"),
        Field("auction.initial_bid", "int64"),
        Field("auction.reserve", "int64"),
        Field("auction.expires", "int64"),
        Field("auction.seller", "int64"),
        Field("auction.category", "int64"),
        Field("bid", "bool"),
        Field("bid.auction", "int64"),
        Field("bid.bidder", "int64"),
        Field("bid.price", "int64"),
        Field("bid.channel", "string"),
        Field("bid.datetime", "int64"),
        Field(TIMESTAMP_FIELD, "int64"),
    ]
)

_US_STATES = np.array(["AZ", "CA", "ID", "OR", "WA", "WY"], dtype=object)
_CITIES = np.array(
    ["Phoenix", "Los Angeles", "San Francisco", "Boise", "Portland", "Bend",
     "Redmond", "Seattle", "Kent", "Cheyenne"],
    dtype=object,
)
_CHANNELS = np.array(["Google", "Facebook", "Baidu", "Apple"], dtype=object)
_STRING_COLUMNS = frozenset(f.name for f in NEXMARK_SCHEMA.fields if f.dtype == "string")

# numpy keeps the interpreter lock through an inner loop of at most this many
# elements and lets go of it around a longer one (numpy/_core/include/numpy/
# ndarraytypes.h: ``#define NPY_BEGIN_THREADS_THRESHOLDED(loop_size) do { if
# ((loop_size) > 500)``). A scan that lets go at each of its ~45 numpy calls a
# batch spends its time taking the lock back from the job's other task
# threads, so no call on the way to a batch sees more rows than this.
_LOCK_KEPT_ROWS = 500
# event numbers 0.._LOCK_KEPT_ROWS: np.arange lets go of the lock at any length
# (NPY_BEGIN_THREADS_DESCR around its fill), a slice of this plus a scalar does not
_RAMP = np.arange(_LOCK_KEPT_ROWS, dtype=np.uint64)
_RAMP.setflags(write=False)


def _rng(n: np.ndarray, salt: int, seed: int = 0) -> np.ndarray:
    # seed 0 leaves the word, and so the stream, what it was before seeds
    word = (salt * 0x9E3779B97F4A7C15) ^ (seed * 0xBF58476D1CE4E5B9)
    return splitmix64(n ^ np.uint64((word | 1) & ((1 << 64) - 1)))


def _number(cfg: dict, key: str, default=None):
    """A numeric option; SQL ``WITH`` values arrive quoted as often as not."""
    v = cfg.get(key, default)
    if isinstance(v, str):
        v = float(v) if "." in v else int(v)
    return v


class NexmarkSource(SourceOperator):
    """config: event_rate (events/s across all subtasks, 0 = unthrottled),
    event_count (total; None = unbounded), first_event_micros,
    inter_event_micros (event-time step; default from event_rate or 1000us),
    seed (varies the random draws — which auction, bidder, price; event
    kinds and timestamps are fixed by the event number. Default 0),
    columns (the columns to synthesise: the SQL planner passes the table's
    declared ones, a hand-built graph its own; absent = all of
    NEXMARK_SCHEMA), include_strings (False, from a hand-built graph, leaves
    the string columns out of that set. Default True)."""

    def __init__(self, cfg: dict):
        self.event_rate = _number(cfg, "event_rate", 0)
        self.event_count = _number(cfg, "event_count")
        self.first_event_micros = _number(cfg, "first_event_micros", 1_600_000_000_000_000)
        self.seed = _number(cfg, "seed", 0)
        if cfg.get("inter_event_micros") is not None:
            self.inter_event_micros = _number(cfg, "inter_event_micros")
        elif self.event_rate:
            self.inter_event_micros = max(int(1e6 / self.event_rate), 1)
        else:
            self.inter_event_micros = 1000
        # projection pushdown: sql/planner.py _plan_source passes the columns
        # the table declares, hand-built graphs (chip_smoke.py, the tests) the
        # ones they read; presence flags + timestamp are always generated
        names = set(cfg.get("columns") or NEXMARK_SCHEMA.names())
        if not cfg.get("include_strings", True):
            names -= _STRING_COLUMNS
        self.columns = frozenset(names)

    def tables(self):
        return [TableSpec("s", "global_keyed")]

    def _generate(self, numbers: np.ndarray) -> Batch:
        """Vectorized event synthesis for the given absolute event numbers.

        Only ``self.columns`` are built (projection pushdown, like
        DataFusion's into table scans), and of the random lanes and running
        id counts only those a built column reads; presence flags and the
        timestamp are always produced."""
        n = numbers.astype(np.uint64)
        want = self.columns.__contains__
        epoch = (n // np.uint64(TOTAL_PROPORTION)).astype(np.int64)
        offset = (n % np.uint64(TOTAL_PROPORTION)).astype(np.int64)
        is_person = offset < PERSON_PROPORTION
        is_auction = (~is_person) & (offset < PERSON_PROPORTION + AUCTION_PROPORTION)
        is_bid = ~(is_person | is_auction)
        ts = self.first_event_micros + n.astype(np.int64) * self.inter_event_micros

        lanes: dict[int, np.ndarray] = {}

        def r(salt: int) -> np.ndarray:
            # one random lane per salt, drawn when the first column reads it
            if salt not in lanes:
                lanes[salt] = _rng(n, salt, self.seed)
            return lanes[salt]

        # ids so far (exclusive of current epoch, conservative "active" sets)
        def people_so_far() -> np.ndarray:
            return FIRST_PERSON_ID + epoch * PERSON_PROPORTION

        auction_id = None
        if want("auction.id") or want("auction.item_name"):
            auction_id = np.where(
                is_auction, FIRST_AUCTION_ID + epoch * AUCTION_PROPORTION + (offset - PERSON_PROPORTION), 0
            ).astype(np.int64)

        cols: dict[str, np.ndarray] = {
            "person": is_person,
            "auction": is_auction,
            "bid": is_bid,
            TIMESTAMP_FIELD: ts,
        }
        if want("event_type"):
            cols["event_type"] = np.where(is_person, 0, np.where(is_auction, 1, 2)).astype(np.int32)
        if want("person.id"):
            cols["person.id"] = np.where(is_person, FIRST_PERSON_ID + epoch, 0).astype(np.int64)
        if want("auction.id"):
            cols["auction.id"] = auction_id
        if want("bid.auction"):
            # bids: hot auctions with ratio 1/HOT of uniform traffic
            r0 = r(1)
            max_auction = FIRST_AUCTION_ID + epoch * AUCTION_PROPORTION
            recent_window = np.maximum(max_auction - FIRST_AUCTION_ID, 1)
            hot_auction = np.maximum(
                max_auction - 1 - (r0 % np.uint64(HOT_AUCTION_RATIO)).astype(np.int64), FIRST_AUCTION_ID)
            cold_auction = FIRST_AUCTION_ID + (r0.astype(np.int64) % recent_window)
            cols["bid.auction"] = np.where(
                is_bid,
                np.where((r(2) % np.uint64(100)).astype(np.int64) < 90, hot_auction, cold_auction),
                0,
            )
        if want("bid.bidder"):
            r2 = r(3)
            max_person = people_so_far()
            recent_people = np.maximum(max_person - FIRST_PERSON_ID, 1)
            hot_bidder = np.maximum(
                max_person - 1 - (r2 % np.uint64(HOT_BIDDER_RATIO)).astype(np.int64), FIRST_PERSON_ID)
            cold_bidder = FIRST_PERSON_ID + (r2.astype(np.int64) % recent_people)
            cols["bid.bidder"] = np.where(
                is_bid,
                np.where((r(4) % np.uint64(100)).astype(np.int64) < 90, hot_bidder, cold_bidder),
                0,
            )
        if want("bid.price"):
            cols["bid.price"] = np.where(is_bid, (100 + (r(2) % np.uint64(9_999_900))).astype(np.int64), 0)
        if want("auction.initial_bid"):
            cols["auction.initial_bid"] = np.where(is_auction, 100 + (r(2) % np.uint64(1000)).astype(np.int64), 0)
        if want("auction.reserve"):
            cols["auction.reserve"] = np.where(is_auction, 500 + (r(3) % np.uint64(2000)).astype(np.int64), 0)
        if want("auction.expires"):
            cols["auction.expires"] = np.where(
                is_auction, ts + (1 + (r(4) % np.uint64(60))).astype(np.int64) * 1_000_000, 0)
        if want("auction.seller"):
            cols["auction.seller"] = np.where(
                is_auction, FIRST_PERSON_ID + (r(1).astype(np.int64) % np.maximum(people_so_far() - FIRST_PERSON_ID, 1)), 0
            )
        if want("auction.category"):
            cols["auction.category"] = np.where(is_auction, FIRST_CATEGORY_ID + (r(1).astype(np.int64) % 5), 0)
        if want("bid.datetime"):
            cols["bid.datetime"] = np.where(is_bid, ts // 1000, 0)
        if want("person.name"):
            cols["person.name"] = np.where(
                is_person, np.char.add("person-", epoch.astype(str)).astype(object), None
            )
        if want("person.email_address"):
            cols["person.email_address"] = np.where(
                is_person, np.char.add(np.char.add("p", epoch.astype(str)), "@example.com").astype(object), None
            )
        if want("person.city"):
            cols["person.city"] = np.where(is_person, _CITIES[(r(2) % np.uint64(len(_CITIES))).astype(np.int64)], None)
        if want("person.state"):
            cols["person.state"] = np.where(is_person, _US_STATES[(r(3) % np.uint64(len(_US_STATES))).astype(np.int64)], None)
        if want("auction.item_name"):
            cols["auction.item_name"] = np.where(
                is_auction, np.char.add("item-", auction_id.astype(str)).astype(object), None
            )
        if want("bid.channel"):
            cols["bid.channel"] = np.where(is_bid, _CHANNELS[(r(3) % np.uint64(len(_CHANNELS))).astype(np.int64)], None)
        return Batch(cols)

    def _build(self, first: int, rows: int, p: int, sub: int) -> tuple[Batch, int]:
        """Events ``first .. first + rows`` of subtask ``sub`` of ``p``'s
        stream as one batch, and the number of pieces it was built in. A
        batch of more than ``_LOCK_KEPT_ROWS`` rows is generated in
        near-equal runs of at most that many, each written into columns
        allocated once for the batch (a slice assignment of a piece keeps
        the lock; ``np.concatenate`` of the whole would drop it again). A
        column is a function of the event number alone, so a piece is the
        same rows of the whole, bit for bit."""
        def numbers(lo: int, hi: int) -> np.ndarray:
            local = _RAMP[: hi - lo] + np.uint64(first + lo)
            return local * np.uint64(p) + np.uint64(sub)

        pieces = -(-rows // _LOCK_KEPT_ROWS)
        if pieces == 1:
            return self._generate(numbers(0, rows)), 1
        out: dict[str, np.ndarray] = {}
        for j in range(pieces):
            lo, hi = rows * j // pieces, rows * (j + 1) // pieces
            for name, col in self._generate(numbers(lo, hi)).columns.items():
                if j == 0:
                    out[name] = np.empty(rows, dtype=col.dtype)
                out[name][lo:hi] = col
        return Batch(out), pieces

    def run(self, sctx, collector) -> SourceFinishType:
        ctx = sctx.ctx
        sub = ctx.task_info.subtask_index
        p = ctx.task_info.parallelism
        tbl = ctx.table_manager.global_keyed("s")
        i = tbl.get(sub, 0)  # index within this subtask's event-number stream
        batch_size = config().get("pipeline.source-batch-size")
        per_task_count = None
        if self.event_count is not None:
            per_task_count = (self.event_count - sub + p - 1) // p
        rate_per_task = self.event_rate / p if self.event_rate else 0
        started = time.monotonic()

        def control():
            msg = sctx.poll_control()
            if msg is None:
                return None
            if msg.kind == "checkpoint":
                tbl.insert(sub, i)
                sctx.start_checkpoint(msg.barrier)
                if msg.barrier.then_stop:
                    return SourceFinishType.FINAL
            elif msg.kind == "stop":
                return SourceFinishType.IMMEDIATE
            return None

        while per_task_count is None or i < per_task_count:
            r = control()
            if r is not None:
                return r
            b = batch_size
            if per_task_count is not None:
                b = min(b, per_task_count - i)
            with _trace.span("source.generate", first_event=i, rows=b) as sp:
                batch, pieces = self._build(i, b, p, sub)
                sp.note(cols=len(batch.columns), pieces=pieces)
            # in a paced stream, when the schedule wanted the batch's first
            # event out: lateness is source.emit's start less due_ns
            due = {"due_ns": int((started + i / rate_per_task) * 1e9)} \
                if rate_per_task else {}
            with _trace.span("source.emit", first_event=i, rows=b, **due):
                collector.collect(batch)
            i += b
            if rate_per_task:
                target = started + i / rate_per_task
                while True:
                    delay = target - time.monotonic()
                    if delay <= 0:
                        break
                    r = control()
                    if r is not None:
                        return r
                    # ahead of the schedule: the source's kind of starving
                    with _trace.wait(_trace.INBOX_WAIT, "source.pace"):
                        time.sleep(min(delay, 0.05))
        # keep the offset table current for the run loop's final snapshot
        tbl.insert(sub, i)
        return SourceFinishType.GRACEFUL


register_source("nexmark")(NexmarkSource)
