"""Runtime cost attribution (ISSUE 7): per-operator self-time, state-size
gauges, key-skew sketches, the /profile snapshot, and EXPLAIN ANALYZE.

Covers the determinism contract (identical replays — and checkpoint/restore
replays — rebuild identical sketch summaries), state-gauge accuracy against
``total_rows()`` ground truth, late-row export, and the profile export/merge
path shared by single- and multi-worker jobs. The 2-worker merged /profile
assertion lives with the process-scheduler set test in test_controller.py.
"""

from __future__ import annotations

import heapq
import json
import time

import numpy as np
import pytest

import arroyo_tpu
from arroyo_tpu.batch import TIMESTAMP_FIELD, Batch
from arroyo_tpu.metrics import registry
from arroyo_tpu.obs.sketch import KeySketch, merge_topk
from interpreter_lock import lets_go_of_the_lock


# ------------------------------------------------------------- sketch unit


def test_sketch_batch_boundary_invariance():
    """sample_every=1 counts rows exactly, so ANY re-batching of the same
    row stream (what coalescing does under timing jitter) yields the same
    summary — the replay-determinism foundation."""
    rng = np.random.default_rng(7)
    keys = rng.integers(0, 50, size=10_000, dtype=np.uint64)
    a, b = KeySketch(capacity=64), KeySketch(capacity=64)
    a.observe(keys)
    for lo in range(0, len(keys), 137):
        b.observe(keys[lo:lo + 137])
    assert a.topk(10) == b.topk(10)
    assert a.total == b.total == 10_000


def test_sketch_heavy_hitter_detection_under_eviction():
    """A Zipf-ish hot key survives eviction pressure (capacity << keyspace)
    and its count-error lower bound stays a true floor."""
    rng = np.random.default_rng(1)
    cold = rng.integers(1000, 100_000, size=20_000, dtype=np.uint64)
    hot = np.full(5_000, 42, dtype=np.uint64)
    mixed = np.concatenate([cold, hot])
    rng.shuffle(mixed)
    sk = KeySketch(capacity=32)
    for lo in range(0, len(mixed), 997):
        sk.observe(mixed[lo:lo + 997])
    top = sk.topk(1)[0]
    assert top["key"] == 42
    assert top["count"] - top["error"] <= 5_000 <= top["count"]
    assert top["share"] == pytest.approx(5_000 / 25_000, abs=0.05)


def test_sketch_state_roundtrip_and_merge():
    rng = np.random.default_rng(3)
    keys = rng.integers(0, 30, size=5_000, dtype=np.uint64)
    orig = KeySketch(capacity=64)
    orig.observe(keys)
    restored = KeySketch(capacity=64)
    restored.merge_state(json.loads(json.dumps(orig.state())))  # DB roundtrip
    assert restored.topk(10) == orig.topk(10)
    assert restored.total == orig.total
    # rescale-style fold of two disjoint summaries never under-counts
    s1, s2 = KeySketch(capacity=8), KeySketch(capacity=8)
    s1.observe(np.full(100, 7, dtype=np.uint64))
    s2.observe(np.full(50, 7, dtype=np.uint64))
    s1.merge_state(s2.state())
    assert s1.topk(1)[0]["count"] >= 150


def test_merge_topk_across_subtasks():
    t1 = [{"key": "00000000000000aa", "count": 100, "error": 0, "share": 0.5}]
    t2 = [{"key": "00000000000000aa", "count": 60, "error": 5, "share": 0.3},
          {"key": "00000000000000bb", "count": 40, "error": 0, "share": 0.2}]
    merged = merge_topk([t1, t2], total=400, k=2)
    assert merged[0] == {"key": "00000000000000aa", "count": 160,
                         "error": 5, "share": 0.4}
    assert merged[1]["key"] == "00000000000000bb"


# ---------------------------------------- the sketch against its plain form


class _DictSketch:
    """The plain reference: the summary as a dict of counts and a dict of
    errors, a batch folded in key by key and the smallest thrown out again
    with a heap. What ``KeySketch`` was until it kept its summary in arrays,
    and what wrote every ``__sketch`` state checkpointed before then."""

    def __init__(self, capacity=64, sample_every=1, seed=0):
        self.capacity = max(1, int(capacity))
        self.sample_every = max(1, int(sample_every))
        self.counts, self.errors = {}, {}
        self.threshold = self.total = 0
        self._tick = int(seed) % self.sample_every

    def observe(self, keys):
        self._tick += 1
        if self._tick % self.sample_every or len(keys) == 0:
            return
        scale = self.sample_every
        self.total += len(keys) * scale
        u, c = np.unique(np.asarray(keys, dtype=np.uint64), return_counts=True)
        thr = self.threshold
        for k, add in zip(u.tolist(), c.tolist()):
            add *= scale
            if k in self.counts:
                self.counts[k] += add
            else:
                self.counts[k] = add + thr
                if thr:
                    self.errors[k] = thr
        self._evict()

    def _evict(self):
        over = len(self.counts) - self.capacity
        if over <= 0:
            return
        for k, v in heapq.nsmallest(over, self.counts.items(),
                                    key=lambda kv: (kv[1], kv[0])):
            self.threshold = max(self.threshold, v)
            del self.counts[k]
            self.errors.pop(k, None)

    def topk(self, k=8):
        order = sorted(self.counts.items(), key=lambda kv: (-kv[1], kv[0]))
        total = self.total or 1
        return [{"key": key, "count": cnt, "error": self.errors.get(key, 0),
                 "share": round(cnt / total, 4)} for key, cnt in order[:k]]

    def state(self):
        return {"counts": dict(self.counts), "errors": dict(self.errors),
                "threshold": self.threshold, "total": self.total,
                "tick": self._tick, "sample_every": self.sample_every}

    def merge_state(self, state):
        if not state:
            return
        other_counts = {int(k): int(v) for k, v in state.get("counts", {}).items()}
        other_errors = {int(k): int(v) for k, v in state.get("errors", {}).items()}
        other_thr = int(state.get("threshold", 0))
        mine = self.counts
        merged_fresh = not mine and not self.total
        for k, v in other_counts.items():
            if k in mine:
                mine[k] += v
                if other_errors.get(k) or self.errors.get(k):
                    self.errors[k] = self.errors.get(k, 0) + other_errors.get(k, 0)
            else:
                mine[k] = v + self.threshold
                err = other_errors.get(k, 0) + self.threshold
                if err:
                    self.errors[k] = err
        if other_thr:
            for k in mine:
                if k not in other_counts:
                    mine[k] += other_thr
                    self.errors[k] = self.errors.get(k, 0) + other_thr
        self.threshold += other_thr
        self.total += int(state.get("total", 0))
        if merged_fresh:
            self._tick = int(state.get("tick", self._tick))
        self._evict()


_HASH = np.uint64(0x9E3779B97F4A7C15)  # spreads small ids over all 64 bits


def _bid_rule(rows):
    """This repo's bids (benchmark/configs, B19): nine in ten to the last
    100 auctions, one in ten uniform over all ~14.5k, the hot set moving on
    as auctions open. ~145 distinct keys in 471 rows."""
    def stream(rng):
        for b in range(40):
            newest = 14_500 + 3 * b
            hot = newest - rng.integers(0, 100, size=rows)
            ids = np.where(rng.random(rows) < 0.9, hot, rng.integers(0, newest, size=rows))
            yield ids.astype(np.uint64) * _HASH
    return stream


def _all_distinct(rng):
    for b in range(12):
        yield (np.arange(b * 700, b * 700 + 1_000, dtype=np.uint64)[::-1]) * _HASH


def _one_key(rng):
    for _ in range(20):
        yield np.full(7_750, 42, dtype=np.uint64) * _HASH


def _runs_of_a_few_keys(rng):
    """A second-level aggregate's and a join side's batches: thousands of
    rows, one key a window, a batch now and then across two or three."""
    for b in range(12):
        lengths = rng.integers(1, 2_000, size=rng.integers(1, 4))
        ids = np.repeat(np.arange(b, b + len(lengths)), lengths)
        yield ids.astype(np.uint64) * _HASH


def _ties_at_the_edge(rng):
    """Every key of a batch once or twice, fresh keys below, between and
    above the held ones, and keys that come back: what is thrown out is
    decided among equal counts, by key."""
    for b in range(60):
        ids = np.concatenate([rng.permutation(24)[:rng.integers(1, 20)],
                              rng.integers(0, 200, size=rng.integers(0, 6)),
                              np.repeat(rng.integers(0, 24, size=3), 2)])
        yield ids.astype(np.uint64) * _HASH if b % 2 else ids.astype(np.uint64)


def _with_empty_batches(rng):
    for b, keys in enumerate(_bid_rule(120)(rng)):
        yield keys
        if b % 3 == 0:
            yield np.empty(0, dtype=np.uint64)


_STREAMS = {"bids-471": _bid_rule(471), "bids-4096": _bid_rule(4_096),
            "all-distinct": _all_distinct, "one-key": _one_key, "runs": _runs_of_a_few_keys,
            "ties": _ties_at_the_edge, "empty-batches": _with_empty_batches}


@pytest.mark.parametrize("sample_every,seed", [(1, 0), (3, 0), (3, 1)])
@pytest.mark.parametrize("capacity", [1, 8, 64])
@pytest.mark.parametrize("stream", sorted(_STREAMS))
def test_sketch_is_the_plain_summary_after_every_batch(stream, capacity, sample_every, seed):
    """The arrays hold, entry for entry, what the dict-and-heap form holds:
    ``state()`` and ``topk()`` equal after every batch of every stream, and
    at the end each restores the state the other wrote."""
    new = KeySketch(capacity, sample_every, seed)
    ref = _DictSketch(capacity, sample_every, seed)
    for keys in _STREAMS[stream](np.random.default_rng(seed + 11)):
        new.observe(keys)
        ref.observe(keys)
        assert new.state() == ref.state()
        assert new.topk(capacity + 1) == ref.topk(capacity + 1)
    json.dumps(new.state())  # plain ints throughout: the table's store takes it
    # the old checkpoint: a state the plain form wrote restores into the
    # arrays, and the reverse, tick and all
    for writer, reader in ((ref, KeySketch), (new, _DictSketch)):
        restored = reader(capacity, sample_every, seed)
        restored.merge_state(json.loads(json.dumps(writer.state())))
        assert restored.state() == ref.state()
        assert restored.topk(capacity + 1) == ref.topk(capacity + 1)


@pytest.mark.parametrize("capacity", [1, 8, 64])
@pytest.mark.parametrize("a,b", [("bids-471", "ties"), ("ties", "bids-471"),
                                 ("all-distinct", "one-key"), ("ties", "ties"),
                                 ("one-key", "empty-batches")])
def test_sketch_merges_two_summaries_as_the_plain_form_does(a, b, capacity):
    """A rescale's fold: hits, misses, both thresholds' compensation and the
    eviction behind them, over summaries that evicted and ones that never
    did; then the merged summary goes on counting."""
    pairs = []
    for cls in (KeySketch, _DictSketch):
        mine, other = cls(capacity), cls(capacity if a != b else 4 * capacity)
        for sk, name, seed in ((mine, a, 5), (other, b, 6)):
            for keys in _STREAMS[name](np.random.default_rng(seed)):
                sk.observe(keys)
        pairs.append((mine, other))
    (new, new_other), (ref, ref_other) = pairs
    assert new_other.state() == ref_other.state()
    new.merge_state(new_other.state())
    ref.merge_state(ref_other.state())
    assert new.state() == ref.state()
    new.merge_state({})
    new.merge_state(None)
    assert new.state() == ref.state()
    for keys in _STREAMS[b](np.random.default_rng(7)):
        new.observe(keys)
        ref.observe(keys)
    assert new.state() == ref.state()
    assert new.topk(5) == ref.topk(5)


def test_sketch_evicts_without_a_heap(monkeypatch):
    """The mechanism by what can be observed: 10,000 distinct keys go into a
    64-entry summary with no selection in Python to throw them out again."""
    def no_heap(*a, **k):
        raise AssertionError("a heap over the summary's entries")
    monkeypatch.setattr(heapq, "nsmallest", no_heap)
    sk = KeySketch(capacity=64)
    sk.observe(np.arange(10_000, dtype=np.uint64) * _HASH)
    assert len(sk.state()["counts"]) == 64 and sk.threshold == 1 and sk.total == 10_000
    with pytest.raises(AssertionError):
        _DictSketch(64).observe(np.arange(100, dtype=np.uint64))


def test_sketch_keeps_the_interpreter_lock_while_it_folds_a_batch():
    """What the fold costs a task's thread is the lock: every call that lets
    go of it waits for a dozen other threads to give it back. At the cells'
    shapes (a 2,700-row batch of ~350 distinct keys, a 471-row one, one key
    in 7,750 rows, q8's 30 rows) ``observe`` makes no such call, twenty
    batches running; a sort, which the summary was built on, does."""
    big = np.arange(1 << 20, dtype=np.uint64)[::-1]
    assert lets_go_of_the_lock(lambda: np.sort(big))
    rng = np.random.default_rng(5)
    one_key = np.full(7_750, 42, dtype=np.uint64)
    for rows in (30, 471, 2_700):
        sk = KeySketch(capacity=64)
        stream = _bid_rule(rows)(rng)
        for _ in range(3):
            sk.observe(next(stream))  # full, and evicting
        batches = [next(stream) for _ in range(20)] + [one_key]

        def fold():
            for keys in batches:
                sk.observe(keys)

        assert not lets_go_of_the_lock(fold), rows
        assert sk.threshold > 0 or rows == 30


def test_sketch_is_read_from_other_threads_while_its_task_feeds_it():
    """The metrics export reads ``topk`` and a checkpoint ``state`` while the
    task's thread folds batches in: keys enter and leave under the reader,
    and it never sees arrays of two lengths."""
    import sys
    import threading

    sk = KeySketch(capacity=16)
    rng = np.random.default_rng(9)
    batches = [rng.integers(0, 400, size=64).astype(np.uint64) * _HASH for _ in range(64)]
    stop, failures, reads = threading.Event(), [], [0]

    def read():
        try:
            while not stop.is_set():
                top, state = sk.topk(20), sk.state()
                assert len(top) <= 16 and len(state["counts"]) <= 16
                assert all(e["count"] >= e["error"] >= 0 for e in top)
                assert set(state["errors"]) <= set(state["counts"])
                reads[0] += 1
        except Exception as e:  # noqa: BLE001 - handed to the asserting thread
            failures.append(e)

    readers = [threading.Thread(target=read) for _ in range(8)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for t in readers:
            t.start()
        deadline = time.monotonic() + 1.0
        while time.monotonic() < deadline and not failures:
            for keys in batches:
                sk.observe(keys)
    finally:
        stop.set()
        sys.setswitchinterval(interval)
        for t in readers:
            t.join(10)
    assert not failures and reads[0] > 0 and not any(t.is_alive() for t in readers)


# -------------------------------------------------------- engine integration


def _keyed_sql(tmp_path, n=4000, keys=7):
    src = tmp_path / "in.json"
    with open(src, "w") as f:
        for i in range(n):
            f.write(json.dumps({"k": f"u{i % keys}", "x": i,
                                "_timestamp": i * 1000}) + "\n")
    return f"""
    CREATE TABLE t (k TEXT, x BIGINT) WITH (connector='single_file',
      path='{src}', format='json', type='source');
    SELECT k, count(*) AS n, tumble(interval '1 second') AS w
    FROM t GROUP BY k, w;
    """


def _run_sql(sql, job_id):
    from arroyo_tpu.engine.engine import run_graph
    from arroyo_tpu.sql import plan_query

    arroyo_tpu._load_operators()
    pp = plan_query(sql)
    run_graph(pp.graph, job_id=job_id, timeout=120)
    return pp


def _agg_entry(jm):
    op = next(o for o in jm if "tumbling" in o or "agg" in o)
    return op, jm[op]


def test_self_time_state_and_sketch_export(tmp_path, _storage):
    registry.clear_job("prof-export")
    sql = _keyed_sql(tmp_path)
    pp = _run_sql(sql, "prof-export")
    jm = registry.job_metrics("prof-export")
    op, agg = _agg_entry(jm)
    # self-time attributed, busy% and cost-per-row derived at export
    assert agg["self_time"]["process"] > 0
    assert agg["busy_pct"] > 0
    assert agg["self_us_per_row"] > 0
    per = agg["per_subtask"]["0"]
    assert set(per["self_time"]) == {"process", "tick", "close", "checkpoint"}
    # the keyed insert path fed the sketch: 7 uniform keys at ~1/7 share
    hot = agg["hot_keys"]
    assert len(hot) >= 5
    assert all(len(e["key"]) == 16 for e in hot)  # fixed-width hex
    assert hot[0]["share"] == pytest.approx(1 / 7, abs=0.02)
    # prometheus exposition carries the new families
    text = registry.prometheus_text()
    assert f'arroyo_worker_self_time_seconds{{job="prof-export",operator="{op}"' \
           in text
    assert "# TYPE arroyo_state_rows gauge" in text
    # sinks/sources without state report no state tables; the watermark
    # operator's global table rides the gauges
    wm_op = next(o for o in jm if "watermark" in o)
    assert "s" in jm[wm_op]["state_rows"]


def test_sketch_identical_across_replays(tmp_path, _storage):
    """Two identical runs (fresh registry each) export identical hot-key
    summaries — seeded, no randomness, row-exact counting."""
    sql = _keyed_sql(tmp_path)
    tops = []
    for run in range(2):
        registry.clear_job("prof-replay")
        _run_sql(sql, "prof-replay")
        _op, agg = _agg_entry(registry.job_metrics("prof-replay"))
        tops.append(agg["hot_keys"])
        assert agg["sketch_total"] > 0
    assert tops[0] == tops[1]


def test_sketch_checkpoint_restore_continuity(tmp_path, _storage):
    """A run that checkpoints mid-stream and a restored run that finishes
    the stream rebuild the same summary an uninterrupted run produces:
    the __sketch table restores the exact space-saving state + sampling
    phase. Drives the engine directly so the checkpoint lands at a
    deterministic row boundary."""
    from arroyo_tpu.engine.engine import Engine
    from arroyo_tpu.sql import plan_query

    arroyo_tpu._load_operators()
    from arroyo_tpu import config as cfg

    cfg.update({"testing.source-read-delay-micros": 2000})
    sql = _keyed_sql(tmp_path, n=3000)

    registry.clear_job("prof-ckpt")
    pp = plan_query(sql)
    eng = Engine(pp.graph, job_id="prof-ckpt")
    eng.start()
    assert eng.checkpoint_and_wait(1, timeout=60)
    eng.join(120)
    _op, agg = _agg_entry(registry.job_metrics("prof-ckpt"))
    uninterrupted = agg["hot_keys"]

    # restore from epoch 1: replays the remainder; fresh sketch merges the
    # checkpointed state, so the final summary matches the full run
    registry.clear_job("prof-ckpt")
    pp2 = plan_query(sql)
    eng2 = Engine(pp2.graph, job_id="prof-ckpt", restore_epoch=1)
    eng2.run_to_completion(120)
    _op, agg2 = _agg_entry(registry.job_metrics("prof-ckpt"))
    assert agg2["hot_keys"] == uninterrupted
    cfg.update({"testing.source-read-delay-micros": 0})


def test_state_gauges_match_total_rows_ground_truth(_storage, tmp_path):
    """Profiler refresh vs the tables' own accounting."""
    from arroyo_tpu.obs.profile import TaskProfiler
    from arroyo_tpu.operators.base import Operator
    from arroyo_tpu.state.tables import TableManager
    from arroyo_tpu.types import TaskInfo

    ti = TaskInfo("gauge-job", "op", "value", 0, 1)
    tm = TableManager(ti, str(tmp_path / "ck"))
    exp = tm.expiring_time_key("t", retention_micros=10**9)
    exp.insert(Batch({TIMESTAMP_FIELD: np.arange(500, dtype=np.int64),
                      "x": np.arange(500, dtype=np.int64)}))
    exp.insert(Batch({TIMESTAMP_FIELD: np.arange(250, dtype=np.int64),
                      "x": np.arange(250, dtype=np.int64)}))
    g = tm.global_keyed("offsets")
    for i in range(10):
        g.insert(i, {"pos": i})
    registry.clear_job("gauge-job")
    m = registry.task("gauge-job", "op", 0)
    prof = TaskProfiler(m, Operator(), tm)
    prof.refresh(force=True)
    assert m.state_rows["t"] == exp.total_rows() == 750
    assert m.state_bytes["t"] == sum(b.nbytes() for b in exp.batches) > 0
    assert m.state_rows["offsets"] == 10
    assert m.state_bytes["offsets"] > 0
    registry.clear_job("gauge-job")


def test_join_side_store_gauges_and_expiry_late_rows(_storage):
    """The updating join reports LIVE _SideStore sizes (overriding the
    barrier-time host tables) and counts TTL-expired drops as late rows."""
    from arroyo_tpu.operators.joins import JoinWithExpiration
    from arroyo_tpu.types import Watermark

    op = JoinWithExpiration({
        "join_type": "inner",
        "left_names": [("lx", "lx")], "right_names": [("rx", "rx")],
        "ttl_micros": 1000,
    })
    keys = np.arange(100, dtype=np.uint64)
    op.stores[0].append(keys.view(np.int64),
                        np.zeros(100, dtype=np.int64),
                        [np.arange(100).astype(object)],
                        np.zeros(100, dtype=np.int64), False)
    sizes = op.state_sizes()
    assert sizes["left"][0] == 100 and sizes["left"][1] > 0
    assert sizes["right"][0] == 0
    # watermark far past TTL expires everything buffered -> late_rows
    out = op.handle_watermark(Watermark.event_time(10_000), None, None)
    assert out is not None
    assert op.late_rows == 100
    assert op.state_sizes()["left"][0] == 0


def test_chained_operator_aggregates_members(_storage):
    from arroyo_tpu.operators.chained import ChainedOperator

    class _M:
        late_rows = 3

        def state_sizes(self):
            return {"t": (5, 80)}

    chain = ChainedOperator.__new__(ChainedOperator)
    chain.members = [_M(), _M()]
    assert chain.late_rows == 6
    assert chain.state_sizes() == {"c0.t": (5, 80), "c1.t": (5, 80)}


def test_late_rows_exported_from_window_operator(tmp_path, _storage):
    """Rows behind an emitted window drop AND surface as
    arroyo_late_rows_total — counting only, goldens untouched."""
    src = tmp_path / "in.json"
    with open(src, "w") as f:
        # ride event time far ahead, then inject stragglers behind the
        # closed windows (watermark interval defaults: every row advances)
        for i in range(2000):
            f.write(json.dumps({"k": "a", "x": i,
                                "_timestamp": i * 10_000}) + "\n")
        for i in range(50):
            f.write(json.dumps({"k": "a", "x": i, "_timestamp": 0}) + "\n")
    sql = f"""
    CREATE TABLE t (k TEXT, x BIGINT) WITH (connector='single_file',
      path='{src}', format='json', type='source');
    SELECT k, count(*) AS n, tumble(interval '1 second') AS w
    FROM t GROUP BY k, w;
    """
    registry.clear_job("prof-late")
    _run_sql(sql, "prof-late")
    jm = registry.job_metrics("prof-late")
    _op, agg = _agg_entry(jm)
    assert agg["late_rows"] == 50
    assert 'arroyo_late_rows_total{job="prof-late"' in registry.prometheus_text()


# ------------------------------------------------------ profile + explain


def test_job_profile_and_render_explain(tmp_path, _storage):
    from arroyo_tpu.obs.profile import job_profile, render_explain

    registry.clear_job("prof-view")
    sql = _keyed_sql(tmp_path)
    pp = _run_sql(sql, "prof-view")
    prof = job_profile(registry.job_metrics("prof-view"))
    op = next(o for o in prof if "tumbling" in o or "agg" in o)
    assert prof[op]["busy_pct"] > 0
    assert prof[op]["hot_keys"]
    assert "0" in prof[op]["per_subtask"]
    nodes = [{"id": n.node_id, "op": n.op.value,
              "description": n.description or n.op.value,
              "parallelism": n.parallelism} for n in pp.graph.nodes.values()]
    edges = [{"src": e.src, "dst": e.dst} for e in pp.graph.edges]
    text = render_explain(nodes, edges, prof,
                          {"id": "prof-view", "state": "Finished"})
    assert "EXPLAIN ANALYZE job prof-view" in text
    # sink-first plan, every operator present, annotated
    assert text.index("sink") < text.index("source")
    for nid in pp.graph.nodes:
        assert nid in text
    assert "busy" in text and "hot keys:" in text and "state:" in text
    registry.clear_job("prof-view")


def test_profile_api_endpoint_embedded(tmp_path, _storage, capsys):
    """GET /api/v1/jobs/<id>/profile serves the controller-persisted
    snapshot, and `python -m arroyo_tpu explain --api` renders the plan
    annotated from it."""
    import urllib.request

    from arroyo_tpu.api import ApiServer
    from arroyo_tpu.controller import ControllerServer, Database
    from arroyo_tpu.controller.scheduler import EmbeddedScheduler

    arroyo_tpu._load_operators()
    db = Database()
    api = ApiServer(db, port=0).start()
    ctl = ControllerServer(db, EmbeddedScheduler()).start()
    try:
        sql = _keyed_sql(tmp_path)
        pid = db.create_pipeline("prof", sql, 1)
        jid = db.create_job(pid)
        ctl.wait_for_state(jid, "Finished", timeout=120)

        def fetch():
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{api.port}/api/v1/jobs/{jid}/profile",
                    timeout=10) as r:
                return json.load(r)["data"]

        # the controller's terminal tick flushes the final registry snapshot
        # right after the state flip; poll past that race
        deadline = time.monotonic() + 10
        prof = fetch()
        while time.monotonic() < deadline:
            ops = [o for o in (prof or {}) if "tumbling" in o or "agg" in o]
            if ops and prof[ops[0]]["self_time"]["process"] > 0:
                break
            time.sleep(0.1)
            prof = fetch()
        assert prof, "no profile served"
        op = next(o for o in prof if "tumbling" in o or "agg" in o)
        assert prof[op]["self_time"]["process"] > 0
        assert db.get_profile(jid) is not None
        # the full CLI path: plan via /pipelines/<id>/graph, numbers via
        # /profile, rendered sink-first with annotations
        from arroyo_tpu.cli import main as cli_main

        rc = cli_main(["explain", jid, "--api",
                       f"http://127.0.0.1:{api.port}"])
        out = capsys.readouterr().out
        assert rc == 0
        assert f"EXPLAIN ANALYZE job {jid}" in out
        assert op in out and "busy" in out and "hot keys:" in out
    finally:
        ctl.stop()
        api.stop()


def test_profile_disabled_zero_surface(tmp_path, _storage):
    """profile.enabled=false: no sketch, no self-time, run still correct."""
    from arroyo_tpu import config as cfg

    cfg.update({"profile.enabled": False})
    try:
        registry.clear_job("prof-off")
        sql = _keyed_sql(tmp_path, n=500)
        _run_sql(sql, "prof-off")
        jm = registry.job_metrics("prof-off")
        _op, agg = _agg_entry(jm)
        assert sum(agg["self_time"].values()) == 0
        assert "hot_keys" not in agg
        assert agg["busy_pct"] == 0
    finally:
        cfg.update({"profile.enabled": True})
        registry.clear_job("prof-off")