"""Replay-soundness auditor tests (arroyo_tpu.analysis.state_audit).

Four layers:
- per-rule AST fixtures: one positive (fires) and one negative (clean)
  class per LR201-LR204, plus the classification edge shapes (barrier-
  flushed, lazy-memo vs monotone-advance, helper-method resolution);
- waiver grammar: ``# state: ephemeral — why`` / ``# effect: idempotent —
  why`` / ``# lint: waive LR2xx — why``, and the no-justification rule;
- AR008 plan-pass fixtures (duplicate TableSpec names, TTL mismatch);
- the runtime cross-check: drive real operators through a real
  TableManager checkpoint/restore roundtrip on smoke-family-shaped data
  and diff every attribute the auditor classifies as *covered* — the
  static verdict and the engine must agree, in both directions (a
  deliberately-broken restore must make the diff non-empty).
"""

from __future__ import annotations

import numpy as np
import pytest

from arroyo_tpu.analysis import (
    Severity,
    analyze_graph,
    audit_package,
    audit_source,
    render_json,
)
from arroyo_tpu.batch import KEY_FIELD, TIMESTAMP_FIELD, Batch, Schema
from arroyo_tpu.expr import Col
from arroyo_tpu.graph import EdgeType, Graph, Node, OpName
from arroyo_tpu.state.tables import TableManager
from arroyo_tpu.types import CheckpointBarrier, TaskInfo, Watermark

DUMMY = Schema.of([("x", "int64"), (TIMESTAMP_FIELD, "int64")])


def ids_of(diags):
    return {d.rule_id for d in diags}


def audit(src: str):
    return audit_source(src, "operators/fixture.py")


# ------------------------------------------------------------------- LR201


LR201_BAD = """
from arroyo_tpu.operators.base import Operator, TableSpec

class C(Operator):
    def __init__(self, cfg):
        self._cache = {}

    def tables(self):
        return [TableSpec("t", "global_keyed")]

    def process_batch(self, batch, ctx, collector, input_index=0):
        self._cache[1] = batch

    def on_start(self, ctx):
        ctx.table_manager.global_keyed("t")

    def handle_checkpoint(self, barrier, ctx, collector):
        ctx.table_manager.global_keyed("t").insert(0, 1)
"""


def test_lr201_unregistered_mutable_state_fires():
    diags = audit(LR201_BAD)
    assert "LR201" in ids_of(diags)
    d = next(d for d in diags if d.rule_id == "LR201")
    assert d.severity == Severity.ERROR and "_cache" in d.message


def test_lr201_restored_attr_is_covered():
    src = LR201_BAD.replace(
        "        ctx.table_manager.global_keyed(\"t\")",
        "        self._cache = dict(ctx.table_manager.global_keyed(\"t\").items())",
    )
    assert "LR201" not in ids_of(audit(src))


def test_lr201_helper_method_mutation_counts():
    # the mutation moved into a helper reachable from process_batch: the
    # whole-class closure still sees it
    src = LR201_BAD.replace(
        "        self._cache[1] = batch",
        "        self._grow(batch)",
    ) + """
    def _grow(self, batch):
        self._cache[1] = batch
"""
    assert "LR201" in ids_of(audit(src))


def test_lr201_barrier_flushed_buffer_is_clean():
    src = """
from arroyo_tpu.operators.base import Operator, TableSpec

class Sink(Operator):
    def __init__(self, cfg):
        self.buf = []

    def tables(self):
        return [TableSpec("p", "global_keyed")]

    def is_committing(self):
        return True

    def on_start(self, ctx):
        saved = ctx.table_manager.global_keyed("p").get(0)

    def process_batch(self, batch, ctx, collector, input_index=0):
        self.buf.extend([batch])

    def handle_checkpoint(self, barrier, ctx, collector):
        ctx.table_manager.global_keyed("p").insert(0, list(self.buf))
        self.buf = []
"""
    assert "LR201" not in ids_of(audit(src))


def test_lr201_lazy_memo_clean_but_monotone_advance_fires():
    memo = """
from arroyo_tpu.operators.base import Operator

class C(Operator):
    def __init__(self, cfg):
        self._agg = None

    def process_batch(self, batch, ctx, collector, input_index=0):
        if self._agg is None:
            self._agg = object()
"""
    assert ids_of(audit(memo)) == set()
    # `is None or <progress>` is the monotone-advance shape (the tumbling
    # late-boundary bug): NOT a memo, must fire
    advance = memo.replace(
        "        if self._agg is None:\n            self._agg = object()",
        "        if self._agg is None or batch.num_rows > self._agg:\n"
        "            self._agg = batch.num_rows",
    )
    assert "LR201" in ids_of(audit(advance))


def test_lr201_state_ephemeral_waiver_grammar():
    waived = LR201_BAD.replace(
        "        self._cache = {}",
        "        self._cache = {}  # state: ephemeral — derived per-epoch scratch, rebuilt by replay",
    )
    assert "LR201" not in ids_of(audit(waived))
    # a waiver with no justification text does not suppress
    empty = LR201_BAD.replace(
        "        self._cache = {}",
        "        self._cache = {}  # state: ephemeral",
    )
    assert "LR201" in ids_of(audit(empty))
    # the generic lint-waive form works too, on a mutation line
    generic = LR201_BAD.replace(
        "        self._cache[1] = batch",
        "        self._cache[1] = batch  # lint: waive LR201 — scratch",
    )
    assert "LR201" not in ids_of(audit(generic))


# ------------------------------------------------------------------- LR202


LR202_BAD = """
from arroyo_tpu.operators.base import Operator

class Sink(Operator):
    def __init__(self, cfg):
        self.producer = cfg["producer"]

    def is_committing(self):
        return True

    def process_batch(self, batch, ctx, collector, input_index=0):
        self.producer.produce("topic", batch)

    def handle_commit(self, epoch, ctx):
        pass
"""


def test_lr202_effect_in_hot_path_of_committing_class():
    diags = audit(LR202_BAD)
    assert "LR202" in ids_of(diags)


def test_lr202_effect_under_handle_commit_is_clean():
    src = """
from arroyo_tpu.operators.base import Operator

class Sink(Operator):
    def __init__(self, cfg):
        self.producer = cfg["producer"]
        self.pending = {}

    def is_committing(self):
        return True

    def process_batch(self, batch, ctx, collector, input_index=0):
        self.pending.setdefault(0, []).append(batch)  # state: ephemeral — staged then committed

    def handle_commit(self, epoch, ctx):
        for p in self.pending.pop(epoch, []):
            self.producer.produce("topic", p)
"""
    assert "LR202" not in ids_of(audit(src))


def test_lr202_non_committing_class_is_out_of_scope():
    src = LR202_BAD.replace("return True", "return False")
    assert "LR202" not in ids_of(audit(src))


def test_lr202_idempotent_waiver():
    src = LR202_BAD.replace(
        "        self.producer.produce(\"topic\", batch)",
        "        # effect: idempotent — keyed upsert, replay overwrites\n"
        "        self.producer.produce(\"topic\", batch)",
    )
    assert "LR202" not in ids_of(audit(src))


# ------------------------------------------------------------------- LR203


def test_lr203_written_but_undeclared_table():
    src = """
from arroyo_tpu.operators.base import Operator, TableSpec

class C(Operator):
    def tables(self):
        return [TableSpec("a", "global_keyed")]

    def on_start(self, ctx):
        ctx.table_manager.global_keyed("a").get(0)

    def handle_checkpoint(self, barrier, ctx, collector):
        ctx.table_manager.global_keyed("a").insert(0, 1)
        ctx.table_manager.global_keyed("b").insert(0, 2)
"""
    diags = [d for d in audit(src) if d.rule_id == "LR203"]
    assert any("'b'" in d.message and d.severity == Severity.ERROR
               for d in diags)


def test_lr203_declared_but_unwired_is_warning():
    src = """
from arroyo_tpu.operators.base import Operator, TableSpec

class C(Operator):
    def tables(self):
        return [TableSpec("dead", "global_keyed")]

    def process_batch(self, batch, ctx, collector, input_index=0):
        pass
"""
    diags = [d for d in audit(src) if d.rule_id == "LR203"]
    assert len(diags) == 1 and diags[0].severity == Severity.WARNING


def test_lr203_symmetric_class_is_clean():
    src = """
from arroyo_tpu.operators.base import Operator, TableSpec

class C(Operator):
    def tables(self):
        return [TableSpec("t", "expiring_time_key")]

    def on_start(self, ctx):
        ctx.table_manager.expiring_time_key("t").all_batches()

    def handle_checkpoint(self, barrier, ctx, collector):
        ctx.table_manager.expiring_time_key("t").replace_all([])
"""
    assert "LR203" not in ids_of(audit(src))


# ------------------------------------------- tiered-state spill manifests


SPILL_SOUND = """
from arroyo_tpu.operators.base import Operator, TableSpec
from arroyo_tpu.state.spill import checkpoint_manifest, restore_manifest

class SpillSound(Operator):
    def tables(self):
        return [TableSpec("s__spill", "global_keyed")]

    def on_start(self, ctx):
        self.annex = build_annex(ctx)
        self.annex.adopt(restore_manifest(ctx, "s__spill"))

    def process_batch(self, batch, ctx, collector, input_index=0):
        self.annex.lookup_many([1])
        self.annex.spill(0, [])

    def handle_checkpoint(self, barrier, ctx, collector):
        checkpoint_manifest(ctx, "s__spill", self.annex)
"""


def test_spill_annex_checkpoint_covered_is_clean():
    """The positive half of the manifest pair: annex probed/spilled on the
    hot path, manifest checkpointed at the barrier and re-adopted in
    on_start — covered, symmetric, convention-following."""
    assert not audit(SPILL_SOUND)


def test_spill_annex_unchreckpointed_manifest_fires_lr201():
    """The negative half: the annex mutates on the hot path (probes
    tombstone what they promote; spills move ownership) but nothing ever
    checkpoints or restores its manifest — a restore silently forgets
    which runs exist and every spilled key resurrects stale or vanishes."""
    src = """
from arroyo_tpu.operators.base import Operator

class SpillLeaky(Operator):
    def process_batch(self, batch, ctx, collector, input_index=0):
        self.annex.lookup_many([1, 2])
"""
    diags = audit(src)
    assert any(d.rule_id == "LR201" and "annex" in d.message for d in diags)


def test_spill_manifest_name_convention_fires_lr203():
    """A manifest persisted under a table name without the ``__spill``
    suffix checkpoints fine but is invisible to spill-run GC liveness —
    the convention is enforced, both directions (write and restore)."""
    src = """
from arroyo_tpu.operators.base import Operator, TableSpec
from arroyo_tpu.state.spill import checkpoint_manifest, restore_manifest

class C(Operator):
    def tables(self):
        return [TableSpec("manifest", "global_keyed")]

    def on_start(self, ctx):
        self.annex = build_annex(ctx)
        self.annex.adopt(restore_manifest(ctx, "manifest"))

    def process_batch(self, batch, ctx, collector, input_index=0):
        self.annex.lookup_many([1])

    def handle_checkpoint(self, barrier, ctx, collector):
        checkpoint_manifest(ctx, "manifest", self.annex)
"""
    diags = audit(src)
    hits = [d for d in diags if d.rule_id == "LR203" and "__spill" in d.message]
    assert hits, diags


# ------------------------------------------------------------------- LR204


LR204_BAD = """
from arroyo_tpu.operators.base import Operator

class C(Operator):
    def __init__(self, cfg):
        self.state = {}

    def process_batch(self, batch, ctx, collector, input_index=0):
        out = []
        for k, v in self.state.items():
            out.append(v)
        collector.collect(out)
"""


def test_lr204_dict_attr_iteration_feeding_emit():
    assert "LR204" in ids_of(audit(LR204_BAD))


def test_lr204_sorted_iteration_is_clean():
    src = LR204_BAD.replace("self.state.items()", "sorted(self.state.items())")
    assert "LR204" not in ids_of(audit(src))


def test_lr204_comprehension_over_set_attr():
    src = """
from arroyo_tpu.operators.base import Operator

class C(Operator):
    def __init__(self, cfg):
        self.dirty = set()

    def process_batch(self, batch, ctx, collector, input_index=0):
        rows = [k for k in self.dirty]
        collector.collect(rows)
"""
    assert "LR204" in ids_of(audit(src))
    clean = src.replace("[k for k in self.dirty]",
                        "sorted(k for k in self.dirty)")
    assert "LR204" not in ids_of(audit(clean))


def test_lr204_annassign_attr_and_bare_iteration():
    # `self.buf: dict[...] = {}` is this repo's universal init style, and
    # bare `for t in self.buf:` iteration must be caught without an
    # .items()/.keys() call in the loop header
    src = """
from arroyo_tpu.operators.base import Operator

class C(Operator):
    def __init__(self, cfg):
        self.buf: dict[int, list] = {}

    def process_batch(self, batch, ctx, collector, input_index=0):
        for t in self.buf:
            collector.collect(self.buf[t])
"""
    assert "LR204" in ids_of(audit(src))
    assert "LR204" not in ids_of(audit(src.replace(
        "for t in self.buf:", "for t in sorted(self.buf):")))


def test_lr204_local_deterministic_dict_is_clean():
    src = """
from arroyo_tpu.operators.base import Operator

class C(Operator):
    def process_batch(self, batch, ctx, collector, input_index=0):
        cols = {}
        cols["a"] = 1
        out = [v for k, v in cols.items()]
        collector.collect(out)
"""
    assert "LR204" not in ids_of(audit(src))


def test_lr204_non_emitting_method_is_out_of_scope():
    src = LR204_BAD.replace("        collector.collect(out)\n", "")
    assert "LR204" not in ids_of(audit(src))


# ----------------------------------------------------------- determinism


def test_audit_output_deterministic_and_json_stable():
    a = audit(LR201_BAD + LR204_BAD.replace("class C", "class D"))
    b = audit(LR201_BAD + LR204_BAD.replace("class C", "class D"))
    assert [d.render() for d in a] == [d.render() for d in b]
    assert render_json(a) == render_json(b)
    assert all(set(d.to_dict()) == {"rule", "severity", "site", "message",
                                    "hint"} for d in a)


def test_same_named_classes_in_different_modules_both_audited():
    # review-round regression: the sweep keys classes by qualified name —
    # a name collision across modules must not silently drop one class
    from arroyo_tpu.analysis.state_audit import audit_modules
    from arroyo_tpu.analysis.repo_lint import _parse

    clean = """
from arroyo_tpu.operators.base import Operator

class Twin(Operator):
    def process_batch(self, batch, ctx, collector, input_index=0):
        pass
"""
    dirty = """
from arroyo_tpu.operators.base import Operator

class Twin(Operator):
    def __init__(self, cfg):
        self._cache = {}

    def process_batch(self, batch, ctx, collector, input_index=0):
        self._cache[1] = batch
"""
    diags, audits = audit_modules([
        _parse(clean, "operators/a.py"), _parse(dirty, "operators/b.py")])
    assert "LR201" in ids_of(diags)  # the SECOND Twin is still audited
    assert {"operators/a.py:Twin", "operators/b.py:Twin"} <= set(audits)


def test_repo_audit_clean():
    """The gate this PR's sweep earns: the whole package audits clean —
    every hot-path-mutated attribute is covered, flushed, or carries a
    justified waiver."""
    diags, audits = audit_package()
    assert diags == [], "\n".join(d.render() for d in diags)
    # and the sweep actually saw the fleet (not a silently-empty walk)
    names = {a.cls for a in audits.values()}
    assert {"TumblingAggregate", "SlidingAggregate", "UpdatingAggregate",
            "InstantJoin", "LookupJoin", "KafkaSink"} <= names


# ------------------------------------------------------------------ AR008


def _register_fixture_connectors():
    from arroyo_tpu.connectors import _SOURCES, register_source
    from arroyo_tpu.connectors.vec import VecSink
    from arroyo_tpu.operators.base import SourceOperator, TableSpec

    if "audit_dup_tables" not in _SOURCES:
        class DupTables(SourceOperator):
            def __init__(self, cfg):
                pass

            def tables(self):
                return [TableSpec("s", "global_keyed"),
                        TableSpec("s", "expiring_time_key")]

        register_source("audit_dup_tables")(DupTables)
    if "audit_ttl_mismatch" not in _SOURCES:
        class TtlMismatch(SourceOperator):
            def __init__(self, cfg):
                pass

            def tables(self):
                # retention hard-coded to 1s regardless of configured TTL
                return [TableSpec("x", "expiring_time_key",
                                  retention_micros=1_000_000)]

        register_source("audit_ttl_mismatch")(TtlMismatch)


def _source_graph(cfg: dict) -> Graph:
    g = Graph()
    g.add_node(Node("src", OpName.SOURCE, cfg, 1))
    g.add_node(Node("sink", OpName.SINK, {"connector": "blackhole"}, 1))
    g.add_edge("src", "sink", EdgeType.FORWARD, DUMMY)
    return g


def test_ar008_duplicate_table_specs_rejected():
    _register_fixture_connectors()
    diags = analyze_graph(_source_graph({"connector": "audit_dup_tables"}))
    d = [d for d in diags if d.rule_id == "AR008"]
    assert d and d[0].severity == Severity.ERROR and "'s'" in d[0].message


def test_ar008_ttl_mismatch_rejected_and_match_clean():
    _register_fixture_connectors()
    diags = analyze_graph(_source_graph(
        {"connector": "audit_ttl_mismatch", "ttl_micros": 3_600_000_000}))
    assert any(d.rule_id == "AR008" and "ttl" in d.message.lower()
               for d in diags)
    # matching TTL is clean
    diags = analyze_graph(_source_graph(
        {"connector": "audit_ttl_mismatch", "ttl_micros": 1_000_000}))
    assert "AR008" not in ids_of(diags)


def test_ar008_real_operators_consistent():
    """The production operators declare TTL-consistent specs: a join with
    a configured TTL plans clean."""
    g = Graph()
    g.add_node(Node("src", OpName.SOURCE,
                    {"connector": "impulse", "message_count": 10}, 1))
    g.add_node(Node("j", OpName.JOIN_WITH_EXPIRATION,
                    {"left_names": [("a", "a")], "right_names": [("b", "b")],
                     "ttl_micros": 60_000_000}, 1))
    g.add_node(Node("sink", OpName.SINK, {"connector": "blackhole"}, 1))
    g.add_edge("src", "j", EdgeType.FORWARD, DUMMY)
    g.add_edge("j", "sink", EdgeType.FORWARD, DUMMY)
    assert "AR008" not in ids_of(analyze_graph(g))


# ----------------------------------------------- runtime cross-check


class _Collector:
    def __init__(self):
        self.batches: list[Batch] = []
        self.signals: list = []

    def collect(self, b):
        self.batches.append(b)

    def broadcast(self, s):
        self.signals.append(s)


def _ctx(storage_url: str, node_id: str = "op"):
    from arroyo_tpu.operators.base import OperatorContext

    ti = TaskInfo("xcheck", node_id, node_id, 0, 1)
    tm = TableManager(ti, storage_url)
    return OperatorContext(ti, None, tm), tm


_SKIP_TYPES = ("ThreadPoolExecutor",)


def _norm(v, depth=0):
    """Replay-equivalence normal form: numpy to python, containers sorted
    where identity-ordered, aggregator objects via their snapshot, lists
    of Batch as their concatenated row sequence."""
    assert depth < 12
    if type(v).__name__ in _SKIP_TYPES:
        return "<skipped>"
    if isinstance(v, Batch):
        return [sorted(r.items(), key=str) for r in v.to_pylist()]
    if isinstance(v, np.ndarray):
        return v.tolist()
    if isinstance(v, np.generic):
        return v.item()
    if isinstance(v, dict):
        return sorted(((str(k), _norm(x, depth + 1)) for k, x in v.items()),
                      key=str)
    if isinstance(v, (set, frozenset)):
        return sorted(v, key=str)
    if isinstance(v, (list, tuple)):
        if v and all(isinstance(e, Batch) for e in v):
            return _norm(Batch.concat(list(v)), depth + 1)
        return [_norm(e, depth + 1) for e in v]
    if isinstance(v, (int, float, str, bytes, bool, type(None))):
        return v
    snap = getattr(v, "snapshot", None)
    if callable(snap):
        return _norm(snap(), depth + 1)
    if hasattr(v, "__dict__"):
        return _norm(vars(v), depth + 1)
    slots = getattr(type(v), "__slots__", None)
    if slots:
        return _norm({s: getattr(v, s, None) for s in slots}, depth + 1)
    return repr(v)


def _covered_attrs(op) -> list[str]:
    from arroyo_tpu.analysis import coverage_for_class

    audit_entry = coverage_for_class(type(op))
    assert audit_entry is not None, f"{type(op).__name__} not in the audit"
    return audit_entry.covered_attrs()


def _roundtrip_diff(make_op, drive, epoch: int, storage_url: str,
                    node_id: str) -> tuple[list[str], list[str]]:
    """Drive a fresh operator, checkpoint through a REAL TableManager,
    restore a second fresh operator from the files, and diff every
    audited-covered attribute. Returns (covered, mismatched)."""
    op = make_op()
    ctx, tm = _ctx(storage_url, node_id)
    col = _Collector()
    op.on_start(ctx)
    drive(op, ctx, col)
    op.handle_checkpoint(CheckpointBarrier(epoch=epoch), ctx, col)
    tm.checkpoint(epoch, watermark_micros=None)

    op2 = make_op()
    ctx2, tm2 = _ctx(storage_url, node_id)
    tm2.restore(epoch, op2.tables())
    op2.on_start(ctx2)

    covered = _covered_attrs(op)
    mism = []
    for a in covered:
        v1 = _norm(getattr(op, a, "<unset>"))
        v2 = _norm(getattr(op2, a, "<unset>"))
        if v1 != v2:
            mism.append(f"{type(op).__name__}.{a}: {v1!r} != {v2!r}")
    return covered, mism


def _kv_batch(ks, vs, ts):
    from arroyo_tpu.hashing import hash_columns

    k = np.asarray(ks, dtype=np.int64)
    return Batch({
        "k": k,
        "v": np.asarray(vs, dtype=np.int64),
        KEY_FIELD: hash_columns([k]),
        TIMESTAMP_FIELD: np.asarray(ts, dtype=np.int64),
    })


def test_runtime_cross_check_tumbling(_storage):
    """The smoke tumbling family's operator, checkpoint mid-stream (no
    window closed yet, so every covered attribute must round-trip
    bit-for-bit through the parquet state files)."""
    from arroyo_tpu.windows.tumbling import TumblingAggregate

    W = 1_000_000

    def make():
        return TumblingAggregate({
            "width_micros": W,
            "key_fields": ["k"],
            "aggregates": [("total", "sum", Col("v")), ("n", "count", None)],
            "input_dtype_of": lambda e: np.dtype(np.int64),
            "backend": "numpy",
        })

    def drive(op, ctx, col):
        op.process_batch(_kv_batch([1, 2, 1], [10, 20, 30],
                                   [100, 200, 300]), ctx, col)
        op.process_batch(_kv_batch([2, 3], [5, 7],
                                   [W + 100, W + 200]), ctx, col)

    covered, mism = _roundtrip_diff(make, drive, 1, _storage, "tumbling")
    assert not mism, "\n".join(mism)
    # the attrs at the heart of this PR's fix are in the covered set
    assert {"emitted_before_rel", "base_bin", "open_bins",
            "_agg"} <= set(covered)


def test_runtime_cross_check_detects_a_broken_restore(_storage):
    """The harness has teeth: an operator whose restore 'forgets' one
    covered attribute must produce a non-empty diff — this is exactly the
    disagreement between static verdict and runtime behavior the
    cross-check exists to catch."""
    from arroyo_tpu.windows.tumbling import TumblingAggregate

    class Amnesiac(TumblingAggregate):
        def on_start(self, ctx):
            super().on_start(ctx)
            self.open_bins = set()  # "forgets" restored state

    def make():
        return Amnesiac({
            "width_micros": 1_000_000,
            "key_fields": ["k"],
            "aggregates": [("total", "sum", Col("v"))],
            "input_dtype_of": lambda e: np.dtype(np.int64),
            "backend": "numpy",
        })

    def drive(op, ctx, col):
        op.process_batch(_kv_batch([1], [10], [100]), ctx, col)

    # the subclass inherits TumblingAggregate's audit via name match
    op = make()
    from arroyo_tpu.analysis import coverage_for_class

    base_audit = coverage_for_class(TumblingAggregate)
    assert "open_bins" in base_audit.covered_attrs()
    _, mism = _roundtrip_diff(make, drive, 1, _storage, "amnesiac")
    # the fabricated bug can only be visible in open_bins
    assert any("open_bins" in m for m in mism), mism


def test_runtime_cross_check_tumbling_late_boundary(_storage):
    """Behavioral leg of the LR201 fix: after a window closes and the
    epoch round-trips, the restored operator must drop a late row exactly
    like the original would — pre-fix, the restored operator re-opened the
    closed bin and re-emitted the window."""
    from arroyo_tpu.types import Signal, SignalKind
    from arroyo_tpu.windows.tumbling import TumblingAggregate

    W = 1_000_000

    def make():
        return TumblingAggregate({
            "width_micros": W,
            "key_fields": ["k"],
            "aggregates": [("total", "sum", Col("v"))],
            "input_dtype_of": lambda e: np.dtype(np.int64),
            "backend": "numpy",
        })

    op = make()
    ctx, tm = _ctx(_storage, "late")
    col = _Collector()
    op.on_start(ctx)
    op.process_batch(_kv_batch([1, 1], [10, 20], [100, W + 100]), ctx, col)
    # watermark past the first window closes and emits it
    out = op.handle_watermark(Watermark.event_time(W + 1), ctx, col)
    assert out is not None and len(col.batches) == 1
    op.handle_checkpoint(CheckpointBarrier(epoch=1), ctx, col)
    tm.checkpoint(1, watermark_micros=W + 1)
    assert op.emitted_before_rel is not None

    op2 = make()
    ctx2, tm2 = _ctx(_storage, "late")
    tm2.restore(1, op2.tables())
    op2.on_start(ctx2)
    # rel boundaries are anchored to each incarnation's base_bin (the
    # restored base is the snapshot's min bin): compare the ABSOLUTE bin
    assert op2.emitted_before_rel is not None
    assert (op2.emitted_before_rel + op2.base_bin
            == op.emitted_before_rel + op.base_bin)

    # a late row behind the emitted window: BOTH incarnations must drop it
    late = _kv_batch([1], [99], [200])
    col_a, col_b = _Collector(), _Collector()
    op.process_batch(late, ctx, col_a)
    op2.process_batch(late, ctx2, col_b)
    # the task's call when its inbox runs dry: the staged rows meet the hook
    op.flush_staged(ctx, col_a)
    op2.flush_staged(ctx2, col_b)
    assert op.late_rows == 1 and op2.late_rows == 1
    op.on_close(ctx, col_a)
    op2.on_close(ctx2, col_b)
    assert [_norm(b) for b in col_a.batches] == [_norm(b) for b in col_b.batches]


def test_runtime_cross_check_tumbling_empty_snapshot_keeps_boundary(_storage):
    """Review-round regression: when EVERY window has closed by the
    barrier, the partial snapshot is empty — the late-data boundary must
    survive anyway (it rides the 'e' global table, not a column on the
    't' batch), and the restored operator must still drop late rows."""
    from arroyo_tpu.windows.tumbling import TumblingAggregate

    W = 1_000_000

    def make():
        return TumblingAggregate({
            "width_micros": W,
            "key_fields": ["k"],
            "aggregates": [("total", "sum", Col("v"))],
            "input_dtype_of": lambda e: np.dtype(np.int64),
            "backend": "numpy",
        })

    op = make()
    ctx, tm = _ctx(_storage, "empty")
    col = _Collector()
    op.on_start(ctx)
    op.process_batch(_kv_batch([1], [10], [100]), ctx, col)
    # watermark closes the ONLY window: partial state is now empty
    op.handle_watermark(Watermark.event_time(2 * W), ctx, col)
    assert len(col.batches) == 1 and not op.open_bins
    op.handle_checkpoint(CheckpointBarrier(epoch=1), ctx, col)
    tm.checkpoint(1, watermark_micros=2 * W)

    op2 = make()
    ctx2, tm2 = _ctx(_storage, "empty")
    tm2.restore(1, op2.tables())
    op2.on_start(ctx2)
    assert op2.emitted_before_rel is not None
    col2 = _Collector()
    op2.process_batch(_kv_batch([1], [99], [200]), ctx2, col2)  # late row
    op2.flush_staged(ctx2, col2)  # the task's call when its inbox runs dry
    assert op2.late_rows == 1
    op2.on_close(ctx2, col2)
    assert col2.batches == [], "restored op re-emitted an already-closed window"


def test_runtime_cross_check_updating_aggregate(_storage):
    from arroyo_tpu.operators.updating_aggregate import UpdatingAggregate

    def make():
        return UpdatingAggregate({
            "key_fields": ["k"],
            "aggregates": [("total", "sum", Col("v")), ("n", "count", None)],
            "input_dtype_of": lambda e: np.dtype(np.int64),
            "ttl_micros": 3_600_000_000,
            "backend": "numpy",
        })

    def drive(op, ctx, col):
        op.process_batch(_kv_batch([1, 2, 1], [10, 20, 30],
                                   [100, 200, 9_000_000]), ctx, col)
        op.handle_tick(ctx, col)  # flush -> `emitted` mirrors downstream
        op.process_batch(_kv_batch([2], [5], [9_500_000]), ctx, col)

    covered, mism = _roundtrip_diff(make, drive, 1, _storage, "upd")
    assert not mism, "\n".join(mism)
    assert {"state", "key_values", "max_event_time"} <= set(covered)


def test_runtime_cross_check_instant_join(_storage):
    from arroyo_tpu.operators.joins import InstantJoin

    def make():
        return InstantJoin({
            "join_type": "inner",
            "left_names": [("lv", "v")],
            "right_names": [("rv", "v")],
            "backend": "numpy",
        })

    class Ctx2:
        pass

    def drive(op, ctx, col):
        # edge_of_input maps flat input index -> side
        ctx._in_edge_of_input = lambda i: (i, 0)
        op.process_batch(_kv_batch([1, 2], [10, 20], [100, 100]),
                         ctx, col, input_index=0)
        op.process_batch(_kv_batch([1], [7], [100]), ctx, col, input_index=1)

    covered, mism = _roundtrip_diff(make, drive, 1, _storage, "ij")
    assert not mism, "\n".join(mism)
    assert "buf" in covered and "emitted_before" in covered


def test_runtime_cross_check_lookup_join_cache(_storage):
    """The table the audit found declared-but-unwired (LR203): the lookup
    cache now checkpoints into 'c' and restores, so replayed batches
    resolve from the same cache state the original run had."""
    from arroyo_tpu.operators.joins import LookupJoin

    class Src:
        def __init__(self):
            self.calls = 0

        def lookup(self, keys):
            self.calls += 1
            return {k: {"name": f"row-{int(k)}"} for k in keys}

    src = Src()

    def make():
        return LookupJoin({
            "connector": src,
            "key_exprs": [Col("k")],
            "right_names": [("name", "name")],
            "join_type": "left",
        })

    def drive(op, ctx, col):
        op.process_batch(_kv_batch([1, 2], [0, 0], [100, 100]), ctx, col)

    covered, mism = _roundtrip_diff(make, drive, 1, _storage, "lj")
    assert not mism, "\n".join(mism)
    assert "cache" in covered
    # and the restored cache actually serves: replaying the same batch
    # must not re-ask the external source
    op2 = make()
    ctx2, tm2 = _ctx(_storage, "lj")
    tm2.restore(1, op2.tables())
    op2.on_start(ctx2)
    calls_before = src.calls
    col = _Collector()
    op2.process_batch(_kv_batch([1, 2], [0, 0], [100, 100]), ctx2, col)
    op2.handle_checkpoint(CheckpointBarrier(epoch=2), ctx2, col)
    assert src.calls == calls_before, "restored cache did not serve replay"
    assert len(col.batches) == 1 and "name" in col.batches[0].columns


def test_runtime_cross_check_watermark_generator(_storage):
    from arroyo_tpu.operators.builtin import WatermarkGenerator

    def make():
        return WatermarkGenerator({"expr": Col(TIMESTAMP_FIELD)})

    def drive(op, ctx, col):
        op.process_batch(_kv_batch([1], [1], [5_000]), ctx, col)

    covered, mism = _roundtrip_diff(make, drive, 1, _storage, "wm")
    assert not mism, "\n".join(mism)
    assert {"max_watermark", "last_emitted"} <= set(covered)
