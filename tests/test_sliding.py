"""Sliding (hop) window aggregate: overlap semantics, watermark-driven
emission, device vs numpy backends, checkpoint/restore."""

import numpy as np
import pytest

from arroyo_tpu.batch import TIMESTAMP_FIELD, Schema
from arroyo_tpu.engine import Engine, run_graph
from arroyo_tpu.expr import BinOp, Col, Lit
from arroyo_tpu.graph import EdgeType, Graph, Node, OpName

DUMMY = Schema.of([("x", "int64"), (TIMESTAMP_FIELD, "int64")])


def sliding_graph(rows, backend, count=1000, width=1_000_000, slide=250_000,
                  parallelism=1, agg_parallelism=1):
    g = Graph()
    g.add_node(Node("src", OpName.SOURCE, {
        "connector": "impulse", "message_count": count,
        "interval_micros": 1000, "start_time_micros": 0}, parallelism))
    g.add_node(Node("wm", OpName.WATERMARK, {"expr": Col(TIMESTAMP_FIELD)}, parallelism))
    g.add_node(Node("key", OpName.KEY,
                    {"keys": [("k", BinOp("%", Col("counter"), Lit(5)))]}, parallelism))
    g.add_node(Node("agg", OpName.SLIDING_AGGREGATE, {
        "width_micros": width,
        "slide_micros": slide,
        "key_fields": ["k"],
        "aggregates": [("cnt", "count", None), ("total", "sum", Col("counter"))],
        "input_dtype_of": lambda e: np.dtype(np.int64),
        "backend": backend,
    }, agg_parallelism))
    g.add_node(Node("sink", OpName.SINK, {"connector": "vec", "rows": rows}, 1))
    g.add_edge("src", "wm", EdgeType.FORWARD, DUMMY)
    g.add_edge("wm", "key", EdgeType.FORWARD, DUMMY)
    g.add_edge("key", "agg", EdgeType.SHUFFLE, DUMMY)
    g.add_edge("agg", "sink", EdgeType.SHUFFLE, DUMMY)
    return g


def expected_sliding(count=1000, width=1_000_000, slide=250_000, interval=1000,
                     scale=1):
    """counter c: ts=c*interval, key=c%5. Window starting at s covers
    [s, s+width). Windows emitted for any start s=j*slide with data."""
    out = {}
    for c in range(count):
        ts = c * interval
        k = c % 5
        # windows containing ts: starts s with s <= ts < s + width, s = j*slide
        j_hi = ts // slide
        j_lo = (ts - width) // slide + 1
        for j in range(j_lo, j_hi + 1):
            s = j * slide
            cnt, tot = out.get((s, k), (0, 0))
            out[(s, k)] = (cnt + scale, tot + c * scale)
    return out


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_sliding_count_sum(backend):
    rows: list = []
    g = sliding_graph(rows, backend)
    run_graph(g, job_id=f"sw-{backend}", timeout=120)
    got = {(r["window_start"], r["k"]): (r["cnt"], r["total"]) for r in rows}
    exp = expected_sliding()
    assert got == exp
    for r in rows:
        assert r["window_end"] - r["window_start"] == 1_000_000


def test_sliding_parallel():
    rows: list = []
    g = sliding_graph(rows, "numpy", count=2000, parallelism=2, agg_parallelism=2)
    run_graph(g, job_id="swp", timeout=120)
    got = {(r["window_start"], r["k"]): (r["cnt"], r["total"]) for r in rows}
    # two identical sources double every count/sum
    exp = {}
    for (s, k), (c, t) in expected_sliding(2000).items():
        exp[(s, k)] = (c * 2, t * 2)
    assert got == exp


def test_sliding_incremental_emission():
    """Windows close as the watermark passes, across many small batches."""
    from arroyo_tpu.config import update

    update({"pipeline.source-batch-size": 100})
    rows: list = []
    g = sliding_graph(rows, "numpy", count=3000, width=400_000, slide=100_000)
    run_graph(g, job_id="sw-incr", timeout=120)
    got = {(r["window_start"], r["k"]): (r["cnt"], r["total"]) for r in rows}
    assert got == expected_sliding(3000, width=400_000, slide=100_000)


def test_width_must_be_multiple_of_slide():
    from arroyo_tpu.windows.sliding import SlidingAggregate

    with pytest.raises(ValueError):
        SlidingAggregate({
            "width_micros": 1_000_000, "slide_micros": 300_000,
            "aggregates": [("cnt", "count", None)],
        })


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_sliding_checkpoint_restore(backend):
    rows1: list = []
    count, width, slide = 2000, 500_000, 125_000
    g1 = sliding_graph(rows1, backend, count=count, width=width, slide=slide)
    run_graph(g1, job_id=f"sref-{backend}", timeout=120)
    expected = {(r["window_start"], r["k"]): (r["cnt"], r["total"]) for r in rows1}

    rows2: list = []
    g2 = sliding_graph(rows2, backend, count=count, width=width, slide=slide)
    g2.nodes["src"].config["event_rate"] = 2000
    eng = Engine(g2, job_id=f"sckpt-{backend}")
    eng.start()
    assert eng.checkpoint_and_wait(1, timeout=30)
    eng.stop()
    eng.join(timeout=30)

    rows3: list = []
    g3 = sliding_graph(rows3, backend, count=count, width=width, slide=slide)
    eng3 = Engine(g3, job_id=f"sckpt-{backend}", restore_epoch=1)
    eng3.run_to_completion(timeout=120)
    merged = {}
    for r in rows2 + rows3:
        merged[(r["window_start"], r["k"])] = (r["cnt"], r["total"])
    assert merged == expected


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_sliding_mixed_key_transport_restore(backend):
    """Mixed group-by keys: the numeric column rides aggregate-store lanes,
    the string column rides the host KeyDictionary (r5 split) — both must
    survive checkpoint/restore with exact per-window results."""
    from arroyo_tpu.expr import BinOp, Case, Col, Lit

    def graph(rows, event_rate=None):
        g = Graph()
        cfg = {"connector": "impulse", "message_count": 1500,
               "interval_micros": 1000, "start_time_micros": 0}
        if event_rate:
            cfg["event_rate"] = event_rate
        g.add_node(Node("src", OpName.SOURCE, cfg, 1))
        g.add_node(Node("wm", OpName.WATERMARK, {"expr": Col(TIMESTAMP_FIELD)}, 1))
        # key: (counter % 3 as int lane, parity name as dict string)
        parity = Case(((BinOp("==", BinOp("%", Col("counter"), Lit(2)), Lit(0)),
                        Lit("even")),), Lit("odd"))
        g.add_node(Node("key", OpName.KEY, {"keys": [
            ("k", BinOp("%", Col("counter"), Lit(3))), ("p", parity)]}, 1))
        g.add_node(Node("agg", OpName.SLIDING_AGGREGATE, {
            "width_micros": 500_000, "slide_micros": 125_000,
            "key_fields": ["k", "p"],
            "aggregates": [("cnt", "count", None), ("total", "sum", Col("counter"))],
            "input_dtype_of": lambda e: np.dtype(np.int64),
            "backend": backend,
        }, 1))
        g.add_node(Node("sink", OpName.SINK, {"connector": "vec", "rows": rows}, 1))
        g.add_edge("src", "wm", EdgeType.FORWARD, DUMMY)
        g.add_edge("wm", "key", EdgeType.FORWARD, DUMMY)
        g.add_edge("key", "agg", EdgeType.SHUFFLE, DUMMY)
        g.add_edge("agg", "sink", EdgeType.FORWARD, DUMMY)
        return g

    rows1: list = []
    run_graph(graph(rows1), job_id=f"smix-{backend}", timeout=120)
    expected = {(r["window_start"], r["k"], r["p"]): (r["cnt"], r["total"])
                for r in rows1}
    assert expected, "reference run emitted nothing"
    assert {r["p"] for r in rows1} == {"even", "odd"}

    rows2: list = []
    eng = Engine(graph(rows2, event_rate=2000), job_id=f"smix-ck-{backend}")
    eng.start()
    assert eng.checkpoint_and_wait(1, timeout=30)
    eng.stop()
    eng.join(timeout=30)
    rows3: list = []
    eng3 = Engine(graph(rows3), job_id=f"smix-ck-{backend}", restore_epoch=1)
    eng3.run_to_completion(timeout=120)
    merged = {}
    for r in rows2 + rows3:
        merged[(r["window_start"], r["k"], r["p"])] = (r["cnt"], r["total"])
    assert merged == expected


def test_count_distinct_in_a_hop_window_through_the_distinct_split():
    """count(DISTINCT <integer>) [FILTER] in a hop window plans onto the
    device (sql/planner.py _plan_distinct_split): pairs in panes combine by
    adding counts. Against a plain computation over the impulse's counters."""
    from arroyo_tpu.sql import plan_query
    from arroyo_tpu.sql.lexer import SqlError

    select = """SELECT hop(interval '250 milliseconds', interval '1 second') AS w,
      counter %% 3 AS k, count(DISTINCT %s) AS d,
      count(DISTINCT counter %% 7) FILTER (WHERE counter %% 2 = 0) AS even, count(*) AS n
    FROM impulse GROUP BY w, k;"""
    ddl = """CREATE TABLE impulse (counter BIGINT UNSIGNED) WITH (
      connector = 'impulse', message_count = 2000, interval_micros = 1000,
      start_time_micros = 0);"""
    pp = plan_query(ddl + select % "counter % 7")
    ops = [n.op.value for n in pp.graph.nodes.values() if n.config.get("distinct")]
    assert ops == ["sliding_aggregate", "tumbling_aggregate"]  # pairs in panes, counts by window
    Engine(pp.graph, job_id="hop-distinct").run_to_completion(timeout=120)
    want: dict = {}
    for c in range(2000):
        ts = c * 1000
        for j in range((ts - 1_000_000) // 250_000 + 1, ts // 250_000 + 1):
            d, even, n = want.setdefault((j * 250_000, c % 3), (set(), set(), 0))
            d.add(c % 7)
            if c % 2 == 0:
                even.add(c % 7)
            want[(j * 250_000, c % 3)] = (d, even, n + 1)
    got = {(r["window_start"], r["k"]): (r["d"], r["even"], r["n"]) for r in pp.sinks[0].rows}
    assert got == {k: (len(d), len(even), n) for k, (d, even, n) in want.items()}
    assert len(got) == 3 * (2000 // 250 + 3)
    # where the split does not apply the hop window still refuses, and says why
    with pytest.raises(SqlError, match="supported in session and tumbling windows only.*"
                                       "is float64, not an integer"):
        plan_query(ddl + select % "CAST(counter AS DOUBLE)")


# ------------------------------------------------- the window that slides
#
# A sliding aggregate whose accumulators can be retracted (sum and count
# over 8-byte integers) closes window w from window w - 1's rows, one bin
# out and one bin in, in one native call (windows/sliding.py _slide); every
# other close combines all the window's bins anew, which is also the oracle
# here: the same stream through the same operator with the slide forced off.

S = 1_000_000  # micros a slide; a window is PANE_NB of them
PANE_NB = 4


class _Recorder:
    """A collector that keeps the batches it is handed, in order."""

    def __init__(self):
        self.batches: list = []

    def collect(self, batch):
        self.batches.append(batch)

    def broadcast(self, signal):
        pass


def _pane_op(aggregates, storage, slides: bool, restore_epoch=None):
    from arroyo_tpu.metrics import TaskMetrics
    from arroyo_tpu.operators.base import OperatorContext
    from arroyo_tpu.state.tables import TableManager
    from arroyo_tpu.types import TaskInfo
    from arroyo_tpu.windows.sliding import SlidingAggregate

    op = SlidingAggregate({
        "width_micros": PANE_NB * S, "slide_micros": S, "key_fields": ["k"],
        "aggregates": [(n, kind, Col(e) if e else None) for n, kind, e in aggregates],
        "input_dtype_of": lambda e: np.dtype(np.float64 if e.name == "f" else np.int64),
        "backend": "jax"})
    if not slides:
        op._full_why = "forced off"
    ti = TaskInfo("pane", "agg", op.name(), 0, 1)
    tm = TableManager(ti, storage)
    if restore_epoch is not None:
        tm.restore(restore_epoch, op.tables())
    ctx = OperatorContext(ti, None, tm)
    op.on_start(ctx)
    return op, ctx, _Recorder(), TaskMetrics("pane", "agg", 0)


def _bin_rows(b: int, keyed: dict):
    """Bin ``b``'s rows: ``keyed`` is key -> the values of its rows."""
    from arroyo_tpu.batch import KEY_FIELD, Batch
    from arroyo_tpu.hashing import hash_columns

    k = np.array([key for key, vals in keyed.items() for _ in vals], dtype=np.int64)
    v = np.array([val for vals in keyed.values() for val in vals], dtype=np.int64)
    ts = b * S + (np.arange(len(k), dtype=np.int64) * 7919) % S
    return Batch({TIMESTAMP_FIELD: ts, "k": k, "v": v, "f": v / 3.0,
                  KEY_FIELD: hash_columns([k])})


def _drive(bins: dict, aggregates, storage, slides: bool, barrier_after=None,
           native: bool = True):
    """The stream ``bins`` (bin -> key -> values; a bin it lacks holds no
    row) through one sliding aggregate, a watermark behind every bin, with a
    checkpoint behind bin ``barrier_after`` from which a second operator goes
    on. -> (every emitted batch as [(column, dtype, bytes)], the snapshot's
    batches likewise, the task's counters)."""
    from test_slot_agg import _without_native

    from arroyo_tpu.obs import trace
    from arroyo_tpu.types import CheckpointBarrier, Watermark

    def as_bytes(batches):
        return [[(c, str(a.dtype), a.tobytes()) for c, a in b.columns.items()]
                for b in batches]

    def run():
        op, ctx, col, metrics = _pane_op(aggregates, storage, slides)
        out, snapshot, counters = [], None, {}
        trace.bind("pane", "agg", 0, metrics)
        try:
            for b in range(min(bins), max(bins) + 1):
                if b in bins:
                    op.process_batch(_bin_rows(b, bins[b]), ctx, col)
                op.handle_watermark(Watermark.event_time((b + 1) * S), ctx, col)
                if b == barrier_after:
                    op.handle_checkpoint(CheckpointBarrier(epoch=1), ctx, col)
                    snapshot = as_bytes(ctx.table_manager.expiring_time_key("t").all_batches())
                    ctx.table_manager.checkpoint(1, None)
                    out += col.batches
                    for name, n in metrics.counters.items():
                        counters[name] = counters.get(name, 0) + n
                    trace.unbind()
                    op, ctx, col, metrics = _pane_op(aggregates, storage, slides, restore_epoch=1)
                    trace.bind("pane", "agg", 0, metrics)
            op.on_close(ctx, col)
        finally:
            trace.unbind()
        for name, n in metrics.counters.items():
            counters[name] = counters.get(name, 0) + n
        return as_bytes(out + col.batches), snapshot, counters

    if native:
        return run()
    with _without_native():
        return run()


def _random_bins(n_bins: int, keys: int = 9, seed: int = 5) -> dict:
    rng = np.random.default_rng(seed)
    return {b: {int(k): rng.integers(-40, 40, rng.integers(1, 4)).tolist()
                for k in range(-3, keys) if rng.random() < 0.6}
            for b in range(n_bins)}


def _an_empty_bin_in_the_middle():
    bins = _random_bins(14)
    del bins[6], bins[7]
    return bins


def _a_key_leaves_and_comes_back():
    bins = _random_bins(16)
    for b in bins:
        bins[b].pop(4, None)
    for b in (0, 1, 7, 13, 14):  # away for longer than a window, twice
        bins[b][4] = [b + 1]
    return bins


def _a_sum_through_zero_and_below():
    bins = _random_bins(14)
    for b, v in zip(bins, [5, -5, -7, 7, 0, 0, -1, 1, 0, 3, -3, 0, 2, -2]):
        bins[b][1] = [v]  # in every bin: it never leaves, its sum passes 0
    return bins


def _first_and_last_bin_both_absent():
    bins = _random_bins(16)
    for b in (3, 3 + PANE_NB, 9, 9 + PANE_NB, 10 + PANE_NB):
        del bins[b]
    return bins


def _a_gap_longer_than_a_window():
    bins = _random_bins(8)
    bins.update({b + 3 * PANE_NB + 8: keyed for b, keyed in _random_bins(7, seed=6).items()})
    return bins


COUNT_SUM = [("cnt", "count", None), ("total", "sum", "v")]
# case -> (bins, aggregates, keyword arguments of _drive, seeding closes or
# None where every close combines anew)
PANE_CASES = {
    "a-stream-of-random-bins": (lambda: _random_bins(20), COUNT_SUM, {}, 1),
    "an-empty-bin-in-the-middle": (_an_empty_bin_in_the_middle, COUNT_SUM, {}, 1),
    "a-key-leaves-and-comes-back": (_a_key_leaves_and_comes_back, COUNT_SUM, {}, 1),
    "a-sum-through-zero-and-below": (_a_sum_through_zero_and_below, COUNT_SUM, {}, 1),
    "first-and-last-bin-both-absent": (_first_and_last_bin_both_absent, COUNT_SUM, {}, 1),
    "a-gap-longer-than-a-window": (_a_gap_longer_than_a_window, COUNT_SUM, {}, 2),
    "a-checkpoint-and-a-restore-inside-a-window": (
        lambda: _random_bins(16), COUNT_SUM, {"barrier_after": 8}, 2),
    "a-count-alone": (lambda: _random_bins(12), [("cnt", "count", None)], {}, 1),
    "max-takes-full": (lambda: _random_bins(12),
                       [("cnt", "count", None), ("mx", "max", "v")], {}, None),
    "min-takes-full": (lambda: _random_bins(12), [("mn", "min", "v")], {}, None),
    "a-float-sum-takes-full": (lambda: _random_bins(12), [("fs", "sum", "f")], {}, None),
    "an-average-takes-full": (lambda: _random_bins(12), [("av", "avg", "v")], {}, None),
    "a-host-without-the-library-takes-full": (
        lambda: _random_bins(12), COUNT_SUM, {"native": False}, None),
}


@pytest.fixture
def _closes_land_at_once(monkeypatch):
    """A bin's extraction handed to the fetch pool lands before ``submit``
    returns: which drain a window leaves in does not depend on a worker
    thread's luck, so two runs can be compared batch for batch."""
    from arroyo_tpu.ops.prefetch import Future, Prefetcher

    def submit(self, fn, on_done=None, program=None):
        fut = Future(fn, on_done)
        fut._run()
        return fut

    monkeypatch.setattr(Prefetcher, "submit", submit)


@pytest.mark.parametrize("case", list(PANE_CASES))
def test_a_window_slid_equals_the_window_combined_anew(case, tmp_path, _closes_land_at_once):
    """The same stream with the slide on and with it forced off: every
    emitted batch equal column for column and row for row in order, dtypes
    and bytes; the snapshot a checkpoint writes byte for byte the same (the
    running window is in no snapshot); and the two counters say which closes
    slid: all but the seeding ones where the accumulators can be retracted,
    none elsewhere."""
    from arroyo_tpu import native

    if not native.available():
        pytest.skip("native library unavailable")
    make, aggregates, kw, seeds = PANE_CASES[case]
    bins = make()
    got, snap_got, counters = _drive(bins, aggregates, str(tmp_path / "on"), True, **kw)
    want, snap_want, forced = _drive(bins, aggregates, str(tmp_path / "off"), False, **kw)
    assert got == want and len(got) > len(bins) // 2
    assert snap_got == snap_want and (snap_got is None) == ("barrier_after" not in kw)
    closes = sum(len(np.unique(np.frombuffer(dict((c, raw) for c, _d, raw in b)["window_start"],
                                             dtype=np.int64))) for b in got)
    running, full = (counters[f"arroyo_worker_pane_closes_{on}"] for on in ("running", "full"))
    assert running + full == closes
    assert full == (closes if seeds is None else seeds)
    assert forced["arroyo_worker_pane_closes_running"] == 0
    assert forced["arroyo_worker_pane_closes_full"] == closes
    assert counters["arroyo_worker_window_rows_emitted"] \
        == forced["arroyo_worker_window_rows_emitted"]
    if seeds is not None:
        # a slide reads two bins where the combine reads the window's four
        assert counters["arroyo_worker_window_rows_combined"] \
            < forced["arroyo_worker_window_rows_combined"]


def _cell_shaped(slides: bool, nb: int = 60, per_bin: int = 1_600):
    """A sliding aggregate that holds ``nb`` + 2 extracted bins of
    ``per_bin`` keys out of 400,000 (q5-hour-sat's close: sixty bins, ~82,000
    keys a window), a count and the key's column, its first window closed."""
    from arroyo_tpu.windows.sliding import SlidingAggregate

    op = SlidingAggregate({"width_micros": nb * S, "slide_micros": S, "key_fields": ["k"],
                           "aggregates": [("n", "count", None)], "backend": "jax"})
    op.lane_key_fields = ["k"]
    op.acc_kinds, op.acc_dtypes = ("count", "max"), (np.dtype(np.int64),) * 2
    op.base_bin, op.next_window, op._target_window = 0, 0, 0
    if not slides:
        op._full_why = "forced off"
    rng = np.random.default_rng(1)
    for b in range(nb + 2):
        keys = np.sort(rng.choice(400_000, per_bin, replace=False).astype(np.int64))
        op._bin_cache[b] = (keys.view(np.uint64), [rng.integers(1, 4, per_bin), keys.copy()])
    def close(w):
        return op._close_window(w, [op._bin_cache[b] for b in range(w, w + nb)], nb)

    close(0)
    return op, close


def test_a_sliding_close_hands_the_interpreter_lock_over_four_times():
    """What a close costs its thread on a host where a dozen threads want
    the lock is each hand-over, not its CPU. Between ``agg.combine``'s start
    and its end a close that slides lets go four times at the cell's shape:
    the one native call, and ``_window_cols``' three fills of 82,000 rows
    (window start, window end, timestamp). The parent's close, all sixty
    bins combined anew, let go 19 times (three concatenates, the argsort,
    the gathers through it, the ``reduceat``s, the casts and the same three
    fills), which this pins only as more."""
    from interpreter_lock import hand_overs

    from arroyo_tpu import native

    if not native.available():
        pytest.skip("native library unavailable")

    def the_second_window_again(op, close):
        first = op._pane

        def again():
            op._pane = first
            close(1)

        return again

    # a hand-over shorter than the waiter's wake is missed, on a busy
    # machine most of the time: the same close again until all four are seen
    slid = hand_overs(the_second_window_again(*_cell_shaped(slides=True)), runs=40, until=5)
    assert slid == 4
    assert hand_overs(the_second_window_again(*_cell_shaped(slides=False)), runs=10, until=5) > 4


@pytest.mark.parametrize("fault", ["a-bin-out-of-order", "a-key-twice-in-a-bin",
                                   "a-retiring-key-the-state-lacks",
                                   "a-key-column-that-differs"])
def test_a_slide_that_meets_what_it_cannot_merge_combines_anew(fault):
    """The native pass checks the order it relies on as it walks: where a
    bin is not one row a key in key order, a retiring row's key is not in
    the state or a key's own column differs from the state's, the close is
    the full combine over the bins as they are, counted as ``full``, and it
    seeds the state again: the next close slides."""
    from arroyo_tpu import native
    from arroyo_tpu.metrics import TaskMetrics
    from arroyo_tpu.obs import trace
    from arroyo_tpu.ops.aggregate import combine_by_key

    if not native.available():
        pytest.skip("native library unavailable")
    nb = 5
    op, close = _cell_shaped(slides=True, nb=nb, per_bin=50)
    keys, (counts, column) = op._bin_cache[nb][0], op._bin_cache[nb][1]
    if fault == "a-bin-out-of-order":
        op._bin_cache[nb] = (keys[::-1].copy(), [counts[::-1].copy(), column[::-1].copy()])
    elif fault == "a-key-twice-in-a-bin":
        op._bin_cache[nb] = (np.repeat(keys, 2), [np.repeat(counts, 2), np.repeat(column, 2)])
    elif fault == "a-retiring-key-the-state-lacks":
        gone, (c, k) = op._pane.first
        op._pane = op._pane._replace(first=(gone + np.uint64(400_001), [c, k]))
    else:  # keys the window holds already, with another value of their column
        keys, (counts, column) = op._bin_cache[2]
        op._bin_cache[nb] = (keys, [counts, column + 1])
    job = f"refused-{fault}"
    metrics = TaskMetrics(job, "agg", 0)
    trace.bind(job, "agg", 0, metrics)
    try:
        cols = close(1)
        parts = [op._bin_cache[b] for b in range(1, 1 + nb)]
        want_keys, want = combine_by_key(
            op.acc_kinds, np.concatenate([p[0] for p in parts]),
            [np.concatenate([p[1][i] for p in parts]) for i in range(2)])
        assert cols["n"].tobytes() == want[0].tobytes()
        assert cols["k"].tobytes() == want[1].tobytes() and len(want_keys) == len(cols["k"])
        assert metrics.counters["arroyo_worker_pane_closes_full"] == 1
        assert metrics.counters["arroyo_worker_pane_closes_running"] == 0
        assert op._pane.start == 1
        op._bin_cache[nb + 1] = op._bin_cache[0]
        close(2)
        assert metrics.counters["arroyo_worker_pane_closes_running"] == 1
        spans = trace.spans("agg.combine", job=job)
        assert [s.args["on"] for s in spans] == ["full", "running"]
    finally:
        trace.unbind()
