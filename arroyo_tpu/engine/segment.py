"""Whole-segment XLA compilation: one jitted call per micro-batch.

A chained run of shuffle-free operators (optimizer.chain_graph) still costs
N Python hook dispatches per micro-batch, each bailing to numpy — the
profiler (obs/profile.py) can attribute that overhead per operator but
nothing removes it. This module traces the chain's data path — ValueOperator
projections/filters, KeyOperator key calculation + routing hash, the
WatermarkGenerator's per-batch max, and the window operators' insert prep
(bins + accumulator inputs) — into ONE ``jax.jit`` batch-in/batch-out
function, compiled once per (segment, input schema) and cached process-wide.

Design rules (correctness first — compilation must never be a risk):

  - **Masked, padded execution.** Filters cannot change array shapes under
    XLA, so the trace threads a validity mask instead of compacting; inputs
    pad to the next power of two so varying batch sizes reuse a handful of
    compiled shapes instead of retracing per batch (the LR111 bug class).
    The host compacts once, after the traced call — the same single filter
    pass the interpreted path pays.
  - **State stays where it was.** Member mutable state (watermark state
    machine, window aggregator tables, late-data boundaries) is NOT moved
    into the trace: the traced function is pure, and per-member host
    finishers feed its outputs into the members' existing state-mutation
    methods (``WatermarkGenerator.observe_batch_max``, the window
    operators' ``insert_arrays``). Checkpoint/restore therefore runs the
    exact interpreted code, byte for byte — the LR2xx state audit's class
    model is the carry contract, enforced by reuse instead of by a
    parallel implementation.
  - **Verify-then-trust.** The first batch of every freshly compiled
    (segment, schema) entry runs BOTH ways: the traced function and a pure
    numpy reference that mirrors the interpreted members exactly. Any
    difference — values or dtypes, bit for bit — falls the segment back to
    the interpreted path permanently (structured ``SEGMENT_FALLBACK``
    WARN), as does any trace failure. A fallback is never a job failure.
  - **Signals stay interpreted.** Watermarks, barriers, stop, and EOF take
    the existing ChainCollector path, so barrier alignment, coalescing
    flush rules, and checkpoint recovery are untouched.

Cache keys include the serialized member configs, the input column
(name, dtype) signature, and the node parallelism, so a schema or
parallelism change recompiles rather than mis-executes
(``segment.compile.cache-max`` bounds the LRU).

jax/XLA imports happen at trace time, not module import time: plan-time
marking (optimizer.chain_graph) must stay cheap enough for control-plane
processes that never run a batch.
"""

from __future__ import annotations

import hashlib
import json
import threading
import time
from collections import OrderedDict
from typing import Any, Callable, Optional

import numpy as np

from ..batch import KEY_FIELD, TIMESTAMP_FIELD, Batch
from ..config import config
from ..expr import (BinOp, Case, Cast, Col, Expr, Func, Lit, Neg, Not,
                    eval_expr)
from ..graph import OpName

# scalar functions whose jnp evaluation is bit-identical to the numpy path
# (elementwise, IEEE-exact or pure integer). Transcendentals (exp/ln/log10/
# power) and decimal-scaled round() are NOT listed: libm and XLA may round
# differently, which would break byte-exact goldens.
_TRACEABLE_FUNCS = {"abs", "floor", "ceil", "sqrt", "extract_epoch",
                    "date_trunc_micros", "to_timestamp_micros"}

_TRACEABLE_BINOPS = {"+", "-", "*", "/", "%", "==", "!=", "<", "<=", ">",
                     ">=", "and", "or"}

# ops implemented with BOTH numpy and jnp twins in expr.py yet deliberately
# kept out of the allowlist: their two implementations are not bit-exact
# (libm vs XLA rounding for the transcendentals; decimal-scaled round).
# Trace-safety rule LR303 audits the three sets against expr.py — an
# allowlisted op with no trace builder is an ERROR, a dual-implemented op
# in neither this set nor the allowlist is a WARN (silently uncompiled),
# and an op in both sets is a contradiction. The allowlisted set itself is
# proven bit-exact across the dtype matrix by the runtime parity oracle
# (tests/test_trace_audit.py).
_KNOWN_DIVERGENT_FUNCS = {"ln", "log10", "exp", "power", "round"}

_KNOWN_DIVERGENT_BINOPS: set[str] = set()


def expr_traceable(e: Expr) -> Optional[str]:
    """None if ``e`` evaluates identically under eval_jnp, else the reason
    it cannot (used both for plan-time marking and the runtime gate)."""
    if isinstance(e, Col):
        return None
    if isinstance(e, Lit):
        if isinstance(e.value, (bool, int, float)):
            return None
        return f"non-numeric literal {e.value!r}"
    if isinstance(e, BinOp):
        if e.op not in _TRACEABLE_BINOPS:
            return f"operator {e.op!r}"
        return expr_traceable(e.left) or expr_traceable(e.right)
    if isinstance(e, (Not, Neg)):
        return expr_traceable(e.inner)
    if isinstance(e, Cast):
        if e.dtype == "string":
            return "cast to string"
        return expr_traceable(e.inner)
    if isinstance(e, Case):
        if e.otherwise is None:
            # numpy leaves unmatched rows holding the first branch's value,
            # jnp would yield NaN — don't trace the divergent shape
            return "CASE without ELSE"
        for c, v in e.branches:
            r = expr_traceable(c) or expr_traceable(v)
            if r:
                return r
        return expr_traceable(e.otherwise)
    if isinstance(e, Func):
        if e.name not in _TRACEABLE_FUNCS:
            return f"function {e.name}()"
        for a in e.args:
            r = expr_traceable(a)
            if r:
                return r
        return None
    return f"expression {type(e).__name__}"  # UdfExpr and anything unknown


def _referenced(exprs) -> set[str]:
    out: set[str] = set()
    for e in exprs:
        if e is not None:
            out |= e.columns()
    return out


# ------------------------------------------------------- plan-time marking

_WINDOW_OPS = (OpName.TUMBLING_AGGREGATE.value, OpName.SLIDING_AGGREGATE.value)


def _scan_members(members: list[tuple[str, dict]]) -> tuple[int, bool, str]:
    """(traceable prefix length, ends in a window insert, stop reason)."""
    k = 0
    insert = False
    stop = "end of chain"
    for op, cfg in members:
        reason = _member_traceable(op, cfg, first=k == 0)
        if reason is not None:
            stop = reason
            break
        k += 1
        if op in _WINDOW_OPS:
            insert = True
            stop = "window insert terminates the traced prefix"
            break
    return k, insert, stop


def segment_marking(members: list[tuple[str, dict]]) -> Optional[dict]:
    """Static compilability of a chained run: the maximal traceable PREFIX
    of the member list, judged by op kind and expression shape (runtime
    still gates on actual column dtypes and verifies the first batch).
    Returns ``{"prefix": k, "insert": bool, "stop": reason, "mesh": bool}``
    when the prefix is worth compiling (>= 2 members), else None."""
    k, insert, stop = _scan_members(members)
    if k < 2:
        return None
    return {"prefix": k, "insert": insert, "stop": stop,
            "mesh": insert and _mesh_refused(members, k) is None}


def _mesh_refused(members: list[tuple[str, dict]], k: int) -> Optional[str]:
    """Static half of the mesh-fusion gate: can this insert-terminated
    prefix run as ONE shard_map'd program feeding the sharded aggregate
    in-program? None when it can, else why not (``explain``'s ``mesh:``
    line says so). In-trace filters ban it — the fused step commits rows on
    device, so the host prologue (late split, open-bin bookkeeping) must
    see exactly the rows the program inserts. The LEADING member's filter
    is fine (the mesh path force-hoists it to the host); any later
    member's filter has nowhere to go."""
    for i, (op, cfg) in enumerate(members[1:k], 1):
        if op == OpName.VALUE.value and cfg.get("filter") is not None:
            return (f"a filter behind the chain's first member (member {i}; a SQL "
                    f"WHERE sits behind the watermark)")
    return None


def segment_reject_reason(members: list[tuple[str, dict]]) -> Optional[str]:
    """Human-readable ``not compilable: <reason>`` for a chained run that
    ``segment_marking`` declined to mark, or None when it IS marked.

    Attached to the chained node's config at plan time (optimizer.
    chain_graph) and surfaced by ``check`` (AR009 INFO), ``explain``,
    ``top``, and the executed-graph view — so an uncompiled segment is a
    plan-time explained fact, not an unexplained runtime fallback."""
    k, _insert, stop = _scan_members(members)
    if k >= 2:
        return None
    # the stop reason leads: narrow renderers (`top` truncates) must show
    # the actionable part, not a boilerplate prefix
    return f"not compilable: {stop} (traceable prefix {k} < 2)"


def _member_traceable(op: str, cfg: dict, first: bool = False) -> Optional[str]:
    if op == OpName.VALUE.value:
        # a FIRST member's filter is hoisted to the host (evaluated exactly
        # as interpreted, object columns and all), so only its projections
        # must trace
        exprs = ([] if first else [cfg.get("filter")]) + \
            [e for _n, e in (cfg.get("projections") or [])]
        for e in exprs:
            if e is None:
                continue
            r = expr_traceable(e)
            if r:
                return f"value: {r}"
        return None
    if op == OpName.KEY.value:
        for _n, e in cfg.get("keys", []):
            r = expr_traceable(e)
            if r:
                return f"key: {r}"
        return None
    if op == OpName.WATERMARK.value:
        r = expr_traceable(cfg["expr"])
        return f"watermark: {r}" if r else None
    if op in _WINDOW_OPS:
        for _n, kind, e in cfg.get("aggregates", []):
            if kind.startswith("udaf:") or kind in ("collect", "count_distinct"):
                return f"window: {kind} accumulator is host-resident"
            if e is not None:
                r = expr_traceable(e)
                if r:
                    return f"window: {r}"
        return None
    return f"operator {op} is not traceable"


# ------------------------------------------------------------ jnp helpers


def _splitmix64_jnp(x):
    import jax.numpy as jnp

    c1 = jnp.uint64(0x9E3779B97F4A7C15)
    c2 = jnp.uint64(0xBF58476D1CE4E5B9)
    c3 = jnp.uint64(0x94D049BB133111EB)
    z = x + c1
    z = (z ^ (z >> jnp.uint64(30))) * c2
    z = (z ^ (z >> jnp.uint64(27))) * c3
    return z ^ (z >> jnp.uint64(31))


def _hash_column_jnp(col):
    """Traced twin of hashing.hash_column for numeric/bool columns
    (differentially covered by the first-batch verification against the
    host path, which itself cross-checks the C++ kernel)."""
    import jax.numpy as jnp
    from jax import lax

    if col.dtype.kind == "f":
        col = jnp.where(col == 0.0, 0.0, col)  # canonicalize -0.0
        bits = lax.bitcast_convert_type(col.astype(jnp.float64), jnp.uint64)
        return _splitmix64_jnp(bits)
    if col.dtype == np.bool_:
        return _splitmix64_jnp(col.astype(jnp.uint64))
    bits = lax.bitcast_convert_type(col.astype(jnp.int64), jnp.uint64)
    return _splitmix64_jnp(bits)


def _hash_columns_jnp(cols):
    import jax.numpy as jnp

    h = _hash_column_jnp(cols[0])
    for c in cols[1:]:
        h2 = _hash_column_jnp(c)
        h = _splitmix64_jnp(h ^ (h2 + jnp.uint64(0x9E3779B97F4A7C15)))
    return h


def _as_full(v, p):
    """Broadcast a traced scalar to a full column the way eval_expr's
    np.full does (weak-typed python scalars promote identically under
    jax x64)."""
    import jax.numpy as jnp

    v = jnp.asarray(v)
    if v.ndim == 0:
        return jnp.broadcast_to(v, (p,))
    return v


def _dtype_floor(dt: np.dtype):
    """Identity element for a masked max of dtype ``dt``."""
    if np.issubdtype(dt, np.floating):
        return np.array(-np.inf, dtype=dt)
    return np.iinfo(dt).min


# ------------------------------------------------------------- stage plans
#
# A bound segment is a list of small stage records; ``_trace_fn`` folds them
# into one traced function and ``_reference`` executes the interpreted
# members' exact numpy logic for the first-batch verification. Both read the
# SAME records, so a drift between them is a verification failure, not a
# silent divergence.


class _Stage:
    __slots__ = ("kind", "member_index", "member")

    def __init__(self, kind: str, member_index: int, member):
        self.kind = kind  # "value" | "key" | "wm" | "insert"
        self.member_index = member_index
        self.member = member


class _SegmentPlan:
    """Static description of what the traced function consumes/produces."""

    def __init__(self):
        self.stages: list[_Stage] = []
        self.prefix = 0  # members covered (including an insert member)
        self.insert: Optional[_Stage] = None
        self.traced_in: list[str] = []  # input columns fed to the trace
        self.traced_out: list[str] = []  # traced output names, fixed order
        self.insert_has_key = False
        # final batch assembly: ordered (name, "host" | "traced")
        self.out_plan: list[tuple[str, str]] = []
        self.emits_batch = True  # False in insert mode
        self.wm_stages: list[_Stage] = []
        # leading-filter hoist: the FIRST member's filter evaluates on the
        # host (eval_expr, exactly the interpreted path — object columns
        # allowed) and the traced inputs compact BEFORE the trace. A
        # selective leading filter otherwise forces the whole trace to
        # compute on mostly-dead padded rows — measurably slower than
        # interpreted's compact-then-compute on e.g. q8's rare-event
        # branches. Filters in LATER members still trace as mask narrowing.
        self.prefilter: Optional[Expr] = None


class SegmentUntraceable(Exception):
    """Raised during binding when the actual batch makes the marked
    segment untraceable (object columns, host accumulators, ...)."""


# a leading filter keeping less than this fraction of rows is hoisted to
# the host: tracing a mostly-dead padded batch costs more than interpreted's
# compact-then-compute, while a high-survival filter fuses profitably
_HOIST_SELECTIVITY = 0.5


def _bind(members, prefix: int, batch: Batch, probe: bool = False,
          hoist: bool = False) -> _SegmentPlan:
    """Resolve the plan against the first batch's real columns: decide
    which inputs the trace consumes, the output assembly order, and gate
    every referenced column on a numeric/bool dtype. ``probe`` builds a
    plan only for a one-off ``_reference`` run (the insert member's
    key-transport setup), skipping the trace-only gates; ``hoist`` moves
    the leading member's filter out of the trace (see _HOIST_SELECTIVITY
    and SegmentRunner._should_hoist)."""
    from ..operators.builtin import (KeyOperator, ValueOperator,
                                     WatermarkGenerator)
    from ..windows.sliding import SlidingAggregate
    from ..windows.tumbling import TumblingAggregate

    plan = _SegmentPlan()
    plan.prefix = prefix
    # provenance: name -> None (verbatim input column) | "computed";
    # ``order`` mirrors the dict insertion order the interpreted members
    # produce, so the emitted Batch's column order is byte-identical
    prov: dict[str, Optional[str]] = {n: None for n in batch.columns}
    order: list[str] = list(batch.columns)
    referenced: set[str] = set()

    def ref(exprs):
        for name in _referenced(exprs):
            if name not in prov:
                raise SegmentUntraceable(
                    f"expression references unknown column {name!r}")
            if prov[name] is None:
                referenced.add(name)

    for i in range(prefix):
        m = members[i]
        if isinstance(m, ValueOperator):
            st = _Stage("value", i, m)
            if i == 0 and m.filter is not None and hoist:
                # hoisted: evaluated host-side pre-trace, never in-trace
                plan.prefilter = m.filter
                for name in m.filter.columns():
                    if name not in prov:
                        raise SegmentUntraceable(
                            f"filter references unknown column {name!r}")
                ref([e for _n, e in (m.projections or [])])
            else:
                ref([m.filter] + [e for _n, e in (m.projections or [])])
            if m.projections is not None:
                new_order: list[str] = []
                new_prov: dict[str, Optional[str]] = {}
                for name, _e in m.projections:
                    if name not in new_prov:
                        new_order.append(name)
                    new_prov[name] = "computed"
                if TIMESTAMP_FIELD not in new_prov:
                    if TIMESTAMP_FIELD not in prov:
                        raise SegmentUntraceable("batch has no _timestamp")
                    new_order.append(TIMESTAMP_FIELD)
                    new_prov[TIMESTAMP_FIELD] = prov[TIMESTAMP_FIELD]
                for carried in (KEY_FIELD, "_is_retract"):
                    if carried in prov and carried not in new_prov:
                        new_order.append(carried)
                        new_prov[carried] = prov[carried]
                order, prov = new_order, new_prov
        elif isinstance(m, KeyOperator):
            st = _Stage("key", i, m)
            ref([e for _n, e in m.keys])
            for name, _e in m.keys:
                if name not in prov:
                    order.append(name)
                prov[name] = "computed"
            if KEY_FIELD not in prov:
                order.append(KEY_FIELD)
            prov[KEY_FIELD] = "computed"
        elif isinstance(m, WatermarkGenerator):
            st = _Stage("wm", i, m)
            ref([m.expr])
            plan.wm_stages.append(st)
        elif isinstance(m, (TumblingAggregate, SlidingAggregate)):
            st = _Stage("insert", i, m)
            if m.lane_key_fields is None:
                raise SegmentUntraceable("window key transport unresolved")
            if m.dict_key_fields:
                raise SegmentUntraceable(
                    f"window group-by columns {m.dict_key_fields} are "
                    f"non-numeric (host key dictionary)")
            if "collect" in m.acc_kinds:
                raise SegmentUntraceable("collect accumulator is host-resident")
            ref([e for e in m.acc_inputs if e is not None])
            if TIMESTAMP_FIELD not in prov:
                raise SegmentUntraceable("window input has no _timestamp")
            if prov[TIMESTAMP_FIELD] is None:
                referenced.add(TIMESTAMP_FIELD)
            if KEY_FIELD in prov:
                plan.insert_has_key = True
                if prov[KEY_FIELD] is None:
                    referenced.add(KEY_FIELD)
            plan.insert = st
            plan.emits_batch = False
        else:
            raise SegmentUntraceable(f"member {m.name()} is not traceable")
        plan.stages.append(st)

    if not probe:
        # dtype gate: every input column the trace consumes must be numeric
        for name in sorted(referenced):
            dt = np.asarray(batch.columns[name]).dtype
            if dt.kind not in "biuf":
                raise SegmentUntraceable(f"column {name!r} has dtype {dt} "
                                         f"(only numeric/bool columns trace)")
        if not referenced:
            raise SegmentUntraceable("segment computes nothing traceable")
    plan.traced_in = sorted(referenced)
    if plan.emits_batch:
        for name in order:
            plan.out_plan.append(
                (name, "host" if prov.get(name) is None else "traced"))
        plan.traced_out = [n for n, src in plan.out_plan if src == "traced"]
    else:
        m = plan.insert.member
        plan.traced_out = ["__bins"]
        if plan.insert_has_key:
            plan.traced_out.append("__hash")
        plan.traced_out += [f"__val{i}" for i, inp in enumerate(m.acc_inputs)
                            if inp is not None]
    return plan


def _insert_step(member) -> int:
    """Bin width of a window insert: tumbling bins by the window width,
    sliding by the slide."""
    from ..windows.tumbling import TumblingAggregate

    return member.width if isinstance(member, TumblingAggregate) else member.slide


# ----------------------------------------------------------------- tracing


def _trace_fn(plan: _SegmentPlan) -> Callable:
    """Build the single traced function for a bound plan.

    Traced signature: ``fn(n, *in_arrays)``, every array padded to one
    static length P; returns ``(outs, mask, aux)`` where ``outs`` follow
    ``plan.traced_out`` order, ``mask`` selects valid rows (None when no
    member filters — the padding tail is then dropped by slicing), and
    ``aux`` carries one ``(batch_max, valid_count)`` pair per watermark
    stage."""
    import jax
    import jax.numpy as jnp

    # pin 64-bit jax semantics BEFORE the first trace: without it a chain
    # that never touches a device kernel (value/key/wm-only — nothing has
    # imported arroyo_tpu.ops) traces under default 32-bit jax, int64
    # inputs downcast, and every first-batch verification fails into a
    # permanent unexplained fallback (trace-safety rule LR304)
    from ..ops import require_x64

    require_x64()

    def fn(n, *arrays):
        p = arrays[0].shape[0]
        cols: dict[str, Any] = dict(zip(plan.traced_in, arrays))
        # dtype pinned: bare arange would follow the jax_enable_x64 flag
        # (int32 by default) while the numpy twin is fixed 64-bit (LR304)
        base = jnp.arange(p, dtype=jnp.int64) < n  # padding-tail invalidity
        valid = None  # narrows at each filter; None = all real rows valid
        aux: list[Any] = []
        outs: dict[str, Any] = {}
        for si, st in enumerate(plan.stages):
            m = st.member
            if st.kind == "value":
                hoisted = si == 0 and plan.prefilter is not None
                if m.filter is not None and not hoisted:
                    f = jnp.broadcast_to(
                        jnp.asarray(m.filter.eval_jnp(cols), dtype=bool), (p,))
                    valid = (base & f) if valid is None else (valid & f)
                if m.projections is not None:
                    new = {}
                    for name, e in m.projections:
                        new[name] = _as_full(e.eval_jnp(cols), p)
                    for carried in (TIMESTAMP_FIELD, KEY_FIELD, "_is_retract"):
                        if carried not in new and carried in cols:
                            new[carried] = cols[carried]
                    cols = new
            elif st.kind == "key":
                key_cols = []
                for name, e in m.keys:
                    c = _as_full(e.eval_jnp(cols), p)
                    cols[name] = c
                    key_cols.append(c)
                cols[KEY_FIELD] = _hash_columns_jnp(key_cols)
            elif st.kind == "wm":
                vals = _as_full(m.expr.eval_jnp(cols), p)
                eff = base if valid is None else valid
                floor = _dtype_floor(np.dtype(vals.dtype))
                aux.extend([jnp.max(jnp.where(eff, vals, floor)),
                            jnp.sum(eff)])
            else:  # insert
                outs["__bins"] = cols[TIMESTAMP_FIELD] // _insert_step(m)
                if plan.insert_has_key:
                    outs["__hash"] = cols[KEY_FIELD].astype(jnp.uint64)
                for i, (inp, dt) in enumerate(zip(m.acc_inputs, m.acc_dtypes)):
                    if inp is not None:
                        outs[f"__val{i}"] = _as_full(
                            inp.eval_jnp(cols), p).astype(dt)
        if plan.emits_batch:
            for name in plan.traced_out:
                outs[name] = cols[name]
        return tuple(outs[k] for k in plan.traced_out), valid, tuple(aux)

    jitted = jax.jit(fn)

    def run(n: int, arrays: list[np.ndarray]):
        out_tuple, mask, aux = jitted(np.int64(n), *arrays)
        return dict(zip(plan.traced_out, out_tuple)), mask, aux

    return run


# --------------------------------------------------------------- reference


def _reference(plan: _SegmentPlan, batch: Batch) -> dict:
    """Pure-numpy twin of the interpreted member hooks, mutating nothing:
    the oracle the compiled outputs must match bit for bit. Structure
    mirrors ValueOperator/KeyOperator/WatermarkGenerator and the window
    operators' process_batch exactly (compaction at each filter, eval_expr
    per expression, hash_columns for routing keys)."""
    from ..hashing import hash_columns

    cols = dict(batch.columns)
    n = batch.num_rows
    aux: list[tuple[Optional[int], int]] = []
    res: dict[str, Any] = {}
    for st in plan.stages:
        m = st.member
        if st.kind == "value":
            if m.filter is not None:
                fmask = np.asarray(eval_expr(m.filter, cols, n), dtype=bool)
                if not fmask.all():
                    cols = {k: v[fmask] for k, v in cols.items()}
                    n = int(fmask.sum())
            if m.projections is not None:
                new = {}
                for name, e in m.projections:
                    new[name] = eval_expr(e, cols, n)
                if TIMESTAMP_FIELD not in new:
                    new[TIMESTAMP_FIELD] = cols[TIMESTAMP_FIELD]
                if KEY_FIELD in cols and KEY_FIELD not in new:
                    new[KEY_FIELD] = cols[KEY_FIELD]
                if "_is_retract" in cols and "_is_retract" not in new:
                    new["_is_retract"] = cols["_is_retract"]
                cols = new
        elif st.kind == "key":
            key_cols = []
            for name, e in m.keys:
                c = eval_expr(e, cols, n)
                cols[name] = c
                key_cols.append(np.asarray(c))
            cols[KEY_FIELD] = (hash_columns(key_cols) if n
                               else np.zeros(0, dtype=np.uint64))
        elif st.kind == "wm":
            if n:
                vals = np.asarray(eval_expr(m.expr, cols, n))
                aux.append((int(vals.max()), n))
            else:
                aux.append((None, 0))
        else:  # insert
            res["__bins"] = np.asarray(cols[TIMESTAMP_FIELD]) // _insert_step(m)
            if plan.insert_has_key:
                res["__hash"] = np.asarray(cols[KEY_FIELD]).astype(np.uint64)
            for i, (inp, dt) in enumerate(zip(m.acc_inputs, m.acc_dtypes)):
                if inp is not None:
                    res[f"__val{i}"] = np.asarray(
                        eval_expr(inp, cols, n)).astype(dt)
    if plan.emits_batch:
        for name, _src in plan.out_plan:
            res[name] = np.asarray(cols[name])
    return {"cols": res, "aux": aux, "n": n}


# ----------------------------------------------------------- compiled entry


_PAD_QUANTUM = 4096


def _padded_size(n: int) -> int:
    """Static trace length for an n-row batch: next power of two below the
    quantum, then quantum multiples. Bounds the number of distinct compiled
    shapes (the retrace-per-batch bug) at ~log2(quantum) + max_rows/quantum
    while capping padding waste at one quantum (~12% worst case) — a pure
    pow2 schedule wasted up to 2x on just-over-a-power batch sizes, which
    showed up directly as compiled-vs-interpreted regression on the A/B."""
    if n <= 16:
        return 16
    if n < _PAD_QUANTUM:
        return 1 << (n - 1).bit_length()
    return -(-n // _PAD_QUANTUM) * _PAD_QUANTUM


class CompiledSegment:
    """One (segment, schema) cache entry: the bound plan + traced fn,
    shared by every subtask (and post-restore incarnation) of the node."""

    def __init__(self, plan: _SegmentPlan, fn: Callable, sig: tuple):
        self.plan = plan
        self.fn = fn
        self.sig = sig
        self._shapes: set[int] = set()
        self._lock = threading.Lock()

    def execute(self, batch: Batch, job_id: str, observe: bool = True,
                min_rows: int = 0) -> Optional[dict]:
        """Run the traced function on one batch; returns the same structure
        ``_reference`` produces (compacted numpy arrays + aux pairs), or
        None when fewer than ``min_rows`` rows survive the hoisted filter
        (too small to pay the jit dispatch — caller runs interpreted)."""
        fmask = None
        n = batch.num_rows
        if self.plan.prefilter is not None:
            fm = np.asarray(
                eval_expr(self.plan.prefilter, batch.columns, n), dtype=bool)
            if not fm.any():
                # the interpreted leading member emits nothing: downstream
                # stages never see this batch
                return {"cols": {}, "n": 0,
                        "aux": [(None, 0)] * len(self.plan.wm_stages)}
            if not fm.all():
                survivors = int(fm.sum())
                if survivors < min_rows:
                    # a selective filter left too few rows for the jit call
                    # to pay for itself: hand the batch back (the caller
                    # runs it interpreted; nothing was mutated here)
                    return None
                fmask = fm
                n = survivors
        p = _padded_size(n)
        arrays = []
        for name in self.plan.traced_in:
            a = np.asarray(batch.columns[name])
            if fmask is not None:
                # fused compact+pad: one pass per column (the same single
                # filter pass the interpreted member pays — a separate
                # compact-then-pad double copy showed up on the A/B)
                buf = np.zeros(p, dtype=a.dtype)
                np.compress(fmask, a, out=buf[:n])
                a = buf
            elif p > n:
                padded = np.zeros(p, dtype=a.dtype)
                padded[:n] = a
                a = padded
            arrays.append(a)
        with self._lock:
            new_shape = p not in self._shapes
            self._shapes.add(p)
        if new_shape and observe:
            # per-shape XLA compile (bucketed by the pow2 padding): timed
            # into arroyo_segment_compile_seconds so retraces stay visible
            t0 = time.perf_counter()
            outs, mask, aux = self.fn(n, arrays)
            from ..metrics import registry

            registry.observe_segment_compile(job_id, time.perf_counter() - t0)
        else:
            outs, mask, aux = self.fn(n, arrays)
        def host_col(name):
            # passthrough columns never enter the trace; they only pay the
            # hoisted filter's compaction, exactly like interpreted
            col = batch.columns[name]
            return col[fmask] if fmask is not None else col

        if mask is not None:
            idx = np.flatnonzero(np.asarray(mask))
            k = len(idx)
            res = {name: np.asarray(a)[idx] for name, a in outs.items()}
            if self.plan.emits_batch:
                for name, src in self.plan.out_plan:
                    if src == "host":
                        res[name] = host_col(name)[idx]
        else:
            k = n
            res = {name: np.asarray(a)[:n] for name, a in outs.items()}
            if self.plan.emits_batch:
                for name, src in self.plan.out_plan:
                    if src == "host":
                        res[name] = host_col(name)
        pairs = []
        it = iter(aux)
        for mx in it:
            cnt = int(next(it))
            pairs.append((int(mx) if cnt else None, cnt))
        return {"cols": res, "aux": pairs, "n": k}


def _outputs_equal(got: dict, want: dict) -> Optional[str]:
    """Bitwise comparison of an execute() result against the reference;
    returns a mismatch description or None."""
    if got["n"] != want["n"]:
        return f"row count {got['n']} != {want['n']}"
    if got["aux"] != want["aux"]:
        return f"watermark aux {got['aux']} != {want['aux']}"
    if got["n"] == 0 and not got["cols"]:
        return None  # hoisted filter killed the whole batch: nothing flows
    gc, wc = got["cols"], want["cols"]
    if set(gc) != set(wc):
        return f"column set {sorted(gc)} != {sorted(wc)}"
    for name in wc:
        g, w = np.asarray(gc[name]), np.asarray(wc[name])
        if g.dtype != w.dtype:
            return f"{name}: dtype {g.dtype} != {w.dtype}"
        if g.dtype == object:
            if len(g) != len(w) or any(
                    not (a is None and b is None) and a != b
                    for a, b in zip(g, w)):
                return f"{name}: object values differ"
        elif g.tobytes() != w.tobytes():
            return f"{name}: values differ"
    return None


# ------------------------------------------------------------ global cache


class _SegmentCache:
    """Process-wide LRU of compiled (and known-untraceable) segments, so
    the N subtasks of a node — and post-restore incarnations — share one
    compile."""

    def __init__(self):
        self._lock = threading.Lock()
        self._entries: "OrderedDict[tuple, Any]" = OrderedDict()

    def _max(self) -> int:
        return int(config().get("segment.compile.cache-max", 32) or 32)

    def lookup(self, key: tuple) -> tuple[bool, Any]:
        with self._lock:
            if key in self._entries:
                self._entries.move_to_end(key)
                return True, self._entries[key]
            return False, None

    def store(self, key: tuple, entry) -> None:
        with self._lock:
            self._entries[key] = entry
            self._entries.move_to_end(key)
            while len(self._entries) > self._max():
                self._entries.popitem(last=False)

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()


segment_cache = _SegmentCache()


class _Fallback:
    """Negative cache entry: this (segment, schema) is untraceable."""

    __slots__ = ("reason",)

    def __init__(self, reason: str):
        self.reason = reason


# ----------------------------------------------------------------- runner


# per-process micro-batch commit counts for mesh-armed runners: "fused" =
# committed through the ONE shard_map'd program, "host" = committed through
# the per-batch host path (first-batch verification, small batches, post-
# failure recovery). The mesh tests assert on these that the fused path
# actually engaged (a silently-host run would still be correct).
_MESH_DISPATCH = {"fused": 0, "host": 0}


def mesh_dispatch_counts() -> dict:
    return dict(_MESH_DISPATCH)


def reset_mesh_dispatch_counts() -> None:
    for k in _MESH_DISPATCH:
        _MESH_DISPATCH[k] = 0


class SegmentRunner:
    """Per-task driver: owns the compile/fallback decision for one chained
    operator and runs the compiled function per batch. The task run loop
    invokes ``process_batch`` in place of the chain's member hook loop."""

    def __init__(self, chain, ctx, metrics, marking: dict):
        self.chain = chain
        self.ctx = ctx
        self.metrics = metrics
        self.marking = marking
        self._entry: Optional[CompiledSegment] = None
        self._sig: Optional[tuple] = None
        self._fallback = False
        self._min_rows = int(config().get("segment.compile.min-rows", 8192))
        # cost demotion (not a fallback): a run of consecutive batches
        # whose hoisted-filter survivors stayed under min-rows proves the
        # stream too selective for the jit to pay; latch to interpreted so
        # later batches stop paying a throwaway filter evaluation
        self._small_streak = 0
        # mesh fusion (device.mesh-devices > 1 + a mesh-markable insert
        # prefix): the traced prefix runs per-shard inside the sharded
        # aggregate's ONE shard_map'd program instead of as a host jit
        # followed by a device exchange step. _mesh_n > 1 also forces the
        # leading-filter hoist (_should_hoist) — the fused program has no
        # mask output.
        mesh_n = int(config().get("device.mesh-devices", 0) or 0)
        self._mesh_n = mesh_n if mesh_n > 1 and marking.get("mesh") else 0
        self._mesh_prog = None  # jitted shard_map step (armed by _setup_mesh)
        self._mesh_agg = None
        self._mesh_member = None
        self._mesh_off = False  # latched: fusion declined/failed, host path only
        self._mesh_shapes: set[int] = set()
        # cache identity: the traced prefix's configs (tail members never
        # enter the trace — their configs may hold run-local objects) plus
        # the node's parallelism, so a rescale recompiles rather than
        # reusing a trace whose key semantics could differ. The mesh width
        # keys too: a resize changes the forced-hoist decision and the
        # owner-range layout the fused program bakes in.
        cfgs = [(op, _cfg_fingerprint(c))
                for op, c in chain.cfg_members[: int(marking["prefix"])]]
        self._seg_key = hashlib.sha1(json.dumps(
            [cfgs, ctx.task_info.parallelism, self._mesh_n], default=repr,
        ).encode()).hexdigest()[:16]

    # -- events ---------------------------------------------------------

    def _event(self, level: str, code: str, message: str, **data) -> None:
        from ..obs.events import recorder as _events

        ti = self.ctx.task_info
        _events.record(ti.job_id, level, code, message=message,
                       node=ti.node_id, subtask=ti.subtask_index,
                       data={"segment": self.chain.name(), **data})

    # -- per-batch entry point -----------------------------------------

    def process_batch(self, batch, ctx, collector, input_index=0) -> None:
        # segment.compile.min-rows: batches too small to amortize the jit
        # dispatch (sub-threshold coalescing flushes, selective-filter
        # survivors) run interpreted — the two paths are verified
        # interchangeable per batch, so mixing them is free
        if (self._fallback or batch.num_rows < max(1, self._min_rows)):
            self.chain.process_batch(batch, ctx, collector,
                                     input_index=input_index)
            return
        if self._entry is None or self._sig != _schema_sig(batch):
            verified = self._prepare(batch)
            if self._fallback:
                self.chain.process_batch(batch, ctx, collector,
                                         input_index=input_index)
                return
            if verified is not None:
                # fresh compile: the verification pass already executed
                # this batch — commit its (proven-equal) outputs instead
                # of paying a second jit dispatch
                self._commit(verified, collector)
                return
            if self._entry is None:
                # vacuous first batch (hoisted filter left no survivors):
                # a no-op on both paths; compile retries on the next batch
                return
        if self._mesh_prog is not None and self._mesh_execute(batch, collector):
            return
        try:
            # pure: a trace/XLA failure here (e.g. a new padded shape
            # compiling under memory pressure) has mutated nothing, so it
            # degrades like any other — never a job failure
            res = self._entry.execute(batch, ctx.task_info.job_id,
                                      min_rows=self._min_rows)
        except Exception as e:  # noqa: BLE001 - fallback, never a panic
            self._mark_fallback(f"{type(e).__name__}: {e}")
            self.chain.process_batch(batch, ctx, collector,
                                     input_index=input_index)
            return
        if res is None:
            self._small_streak += 1
            if self._small_streak >= 8:
                self._fallback = True  # cost latch; state paths unaffected
                self.metrics.segment_compiled = False
                self.metrics.segment_reason = (
                    "hoisted-filter survivors stayed under "
                    "segment.compile.min-rows (cost latch)")
            self.chain.process_batch(batch, ctx, collector,
                                     input_index=input_index)
            return
        self._small_streak = 0
        self._commit(res, collector)

    # -- compile --------------------------------------------------------

    def _prepare(self, batch: Batch) -> Optional[dict]:
        """Resolve/compile the entry for this batch's schema; on a FRESH
        compile, returns the verification pass's execute() result for this
        batch (proven bit-equal to the reference) so the caller can commit
        it without re-running; None on cache hit or fallback."""
        sig = _schema_sig(batch)
        key = (self._seg_key, sig)
        members = self.chain.members[: int(self.marking["prefix"])]
        # the insert member's key-transport split must exist before binding
        # (acc lanes extend acc_inputs); dtype-only, so deriving it from the
        # first batch matches what the first surviving batch would do
        err = self._setup_insert(members, batch)
        if err is not None:
            segment_cache.store(key, _Fallback(err))
            self._mark_fallback(err)
            return None
        from ..metrics import registry

        hit, entry = segment_cache.lookup(key)
        if hit:
            if isinstance(entry, _Fallback):
                # negative-cache reuse deliberately does NOT count as a
                # cache hit: the metric means "reused a COMPILED entry"
                self._mark_fallback(entry.reason)
                return None
            registry.add_segment_cache_hit(self.ctx.task_info.job_id)
            self._entry, self._sig = entry, sig
            self.metrics.segment_compiled = True
            self._setup_mesh(entry)
            # the event feed is per-job: a job served from the process-wide
            # cache must still be diagnosable as compiled from `logs` alone
            self._event(
                "INFO", "SEGMENT_COMPILED",
                f"segment {self.chain.name()} running compiled "
                f"({entry.plan.prefix}/{len(self.chain.members)} members, "
                f"cache hit)",
                members=entry.plan.prefix, cached=True,
                schema=[list(pair) for pair in sig])
            return None
        t0 = time.perf_counter()
        try:
            plan = _bind(members, len(members), batch,
                         hoist=self._should_hoist(members[0], batch))
            entry = CompiledSegment(plan, _trace_fn(plan), sig)
            # observe=False: the bind+trace+verify total below covers this
            # first shape's compile; later shapes self-report from execute
            got = entry.execute(batch, self.ctx.task_info.job_id,
                                observe=False)
            if got["n"] == 0 and not got["cols"]:
                # the hoisted filter killed the entire first batch: the
                # traced function never ran, so "verification" would be
                # vacuous. The batch is a no-op on both paths — do NOT
                # cache or adopt the unproven entry; retry the compile on
                # the next batch that has survivors
                return None
            want = _reference(plan, batch)
            mismatch = _outputs_equal(got, want)
            if mismatch is not None:
                raise SegmentUntraceable(f"verification failed: {mismatch}")
        except SegmentUntraceable as e:
            segment_cache.store(key, _Fallback(str(e)))
            self._mark_fallback(str(e))
            return None
        except Exception as e:  # noqa: BLE001 - tracing must never kill a job
            reason = f"{type(e).__name__}: {e}"
            segment_cache.store(key, _Fallback(reason))
            self._mark_fallback(reason)
            return None
        elapsed = time.perf_counter() - t0
        segment_cache.store(key, entry)
        registry.observe_segment_compile(self.ctx.task_info.job_id, elapsed)
        self._entry, self._sig = entry, sig
        self.metrics.segment_compiled = True
        self._setup_mesh(entry)
        self._event(
            "INFO", "SEGMENT_COMPILED",
            f"segment {self.chain.name()} compiled to one jitted call "
            f"({plan.prefix}/{len(self.chain.members)} members, "
            f"{elapsed * 1e3:.1f}ms, first batch verified)",
            members=plan.prefix, compile_ms=round(elapsed * 1e3, 2),
            schema=[list(pair) for pair in sig])
        return got

    def _should_hoist(self, m0, batch: Batch) -> bool:
        """Hoist the leading filter out of the trace when it must be (the
        expression or its columns cannot trace) or when the first batch
        shows it selective enough that compact-then-compute beats masked
        full-length tracing. Either choice is correct — the first-batch
        verification covers both shapes — so a wrong guess only costs
        performance."""
        from ..operators.builtin import ValueOperator

        if not isinstance(m0, ValueOperator) or m0.filter is None:
            return False
        if self._mesh_n > 1:
            # mesh fusion: the fused shard_map program has no mask output,
            # so a leading filter MUST run on the host. Cache keys include
            # the mesh width, so entries never cross hoist decisions.
            return True
        if expr_traceable(m0.filter) is not None:
            return True
        for name in m0.filter.columns():
            col = batch.columns.get(name)
            if col is None or np.asarray(col).dtype.kind not in "biuf":
                return True
        fm = np.asarray(
            eval_expr(m0.filter, batch.columns, batch.num_rows), dtype=bool)
        return bool(fm.mean() < _HOIST_SELECTIVITY)

    def _setup_insert(self, members, batch: Batch) -> Optional[str]:
        if not self.marking.get("insert"):
            return None
        m = members[-1]
        if m.lane_key_fields is not None:
            return None
        # the split must be derived from the member's OWN input — exactly
        # what process_batch would see — so run the prefix as a one-off
        # pure reference. (The chain input is NOT a substitute: a group-by
        # column name can shadow a differently-typed source column.)
        try:
            probe = _bind(members[:-1], len(members) - 1, batch, probe=True)
        except SegmentUntraceable as e:
            return str(e)
        inter = _reference(probe, batch)["cols"]
        missing = [f for f in m.key_fields if f not in inter]
        if missing:
            return (f"window group-by columns {missing} not produced by "
                    f"the traced prefix")
        m._setup_key_transport(Batch(inter))
        return None

    def _mark_fallback(self, reason: str) -> None:
        self._fallback = True
        self.metrics.segment_compiled = False
        self.metrics.segment_reason = reason
        self._event(
            "WARN", "SEGMENT_FALLBACK",
            f"segment {self.chain.name()} fell back to the interpreted "
            f"path: {reason}", reason=reason)

    # -- mesh fusion ----------------------------------------------------

    def _setup_mesh(self, entry: CompiledSegment) -> None:
        """Arm the fused mesh path for a freshly adopted entry: ONE
        shard_map'd jitted program that runs the traced prefix per shard
        and feeds the sharded aggregate's owner bucketing → all_to_all →
        sort_reduce/probe_merge directly in-program, so rows never
        round-trip to host between projection and state update. Fusion is
        an optimization on top of the verified per-batch path, not a mode
        switch: any gate failure quietly stays on the host path (no
        SEGMENT_FALLBACK — the segment is still compiled)."""
        self._mesh_prog = None
        self._mesh_agg = None
        self._mesh_member = None
        if self._mesh_n <= 1 or self._mesh_off:
            return
        plan = entry.plan
        if plan.insert is None:
            return self._decline_mesh("the compiled prefix has no window insert")
        # the member resolves BY INDEX against THIS chain (same rule as
        # _commit): a cache-hit entry was bound by another incarnation
        member = self.chain.members[plan.insert.member_index]
        from ..parallel.sharded_agg import ShardedAggregator

        # the window operators build their store lazily on first insert;
        # setup runs before the verified first batch commits, so force the
        # construction (same path an insert would take) to see its type
        agg_fn = getattr(member, "_aggregator", None)
        agg = agg_fn() if agg_fn is not None else getattr(member, "_agg", None)
        if not isinstance(agg, ShardedAggregator):
            # mesh-devices was toggled after the operator built its store,
            # or the backend fell back — the host path still works
            return self._decline_mesh("the window's store is not sharded")
        for si, st in enumerate(plan.stages):
            if (st.kind == "value" and st.member.filter is not None
                    and (si != 0 or plan.prefilter is None)):
                # an in-trace filter would desync the host prologue (late
                # split, open-bin bookkeeping) from the rows the program
                # inserts; _mesh_refused bans this statically, but a
                # cache entry bound under different config could disagree
                return self._decline_mesh("a filter inside the traced prefix")
        # the host prologue derives bins from the VERBATIM event time, so
        # the insert-time _timestamp must be the input column untouched: a
        # projection that redefines it (prov walk in _bind) cannot fuse —
        # and "in traced_in" alone doesn't prove it (an earlier stage may
        # have consumed the verbatim column before a projection shadowed it)
        ts_verbatim = TIMESTAMP_FIELD in plan.traced_in
        for st in plan.stages:
            if (st.kind == "value" and st.member.projections is not None
                    and any(name == TIMESTAMP_FIELD
                            for name, _e in st.member.projections)):
                ts_verbatim = False
        if not ts_verbatim:
            return self._decline_mesh("a projection redefines the event time")
        if getattr(member, "mesh_insert_begin", None) is None:
            return self._decline_mesh("the window operator has no fused insert")
        try:
            prefix_fn = self._build_mesh_prefix(plan, member)
            self._mesh_prog = agg.fused_step(
                prefix_fn, len(plan.traced_in), 2 * len(plan.wm_stages))
            self._mesh_agg = agg
            self._mesh_member = member
            self._mesh_shapes = set()
        except Exception as e:  # noqa: BLE001 - fusion is best-effort
            self._decline_mesh(f"{type(e).__name__}: {e}")
            self._event(
                "WARN", "SEGMENT_FALLBACK",
                f"segment {self.chain.name()} mesh fusion disabled "
                f"(compiled host path continues): {type(e).__name__}: {e}",
                reason=str(e), mesh=True)

    def _decline_mesh(self, why: str) -> None:
        """Latch the host path and say why (``explain``'s ``mesh:`` line)."""
        self._mesh_off = True
        self.metrics.mesh_reason = why

    def _build_mesh_prefix(self, plan: _SegmentPlan, member) -> Callable:
        """The traced prefix re-expressed as the sharded step's in-program
        prologue: a per-shard twin of ``_trace_fn`` minus filter stages
        (banned by the mesh gate), producing the insert columns the
        exchange+merge consumes.

        Contract (parallel.sharded_agg.ShardedAggregator.fused_step):
        ``prefix_fn(arrays, valid, base_bin, ontime) -> (key_i64,
        bins_i32, insert_valid, vals, aux)`` where ``valid`` masks this
        shard's padding rows, ``ontime`` masks host-detected late rows
        (insert only — the watermark observes PRE-late rows, matching the
        interpreted order where the generator sits upstream of the
        window), and ``aux`` is one (masked max, valid count) pair per
        watermark stage."""
        import jax.numpy as jnp
        from jax import lax

        from ..ops import require_x64

        require_x64()
        step_us = _insert_step(member)
        stages = list(plan.stages)
        traced_in = list(plan.traced_in)
        insert_has_key = plan.insert_has_key
        acc = list(zip(member.acc_inputs, member.acc_dtypes))

        def prefix_fn(arrays, valid, base_bin, ontime):
            p = arrays[0].shape[0]
            cols: dict[str, Any] = dict(zip(traced_in, arrays))
            aux: list[Any] = []
            key_i64 = None
            bins = None
            vals: list[Any] = []
            for st in stages:
                m = st.member
                if st.kind == "value":
                    if m.projections is not None:
                        new = {}
                        for name, e in m.projections:
                            new[name] = _as_full(e.eval_jnp(cols), p)
                        for carried in (TIMESTAMP_FIELD, KEY_FIELD,
                                        "_is_retract"):
                            if carried not in new and carried in cols:
                                new[carried] = cols[carried]
                        cols = new
                elif st.kind == "key":
                    key_cols = []
                    for name, e in m.keys:
                        c = _as_full(e.eval_jnp(cols), p)
                        cols[name] = c
                        key_cols.append(c)
                    cols[KEY_FIELD] = _hash_columns_jnp(key_cols)
                elif st.kind == "wm":
                    wvals = _as_full(m.expr.eval_jnp(cols), p)
                    floor = _dtype_floor(np.dtype(wvals.dtype))
                    aux.extend([jnp.max(jnp.where(valid, wvals, floor)),
                                jnp.sum(valid)])
                else:  # insert: rel bins in int32, like the host twins
                    bins = (cols[TIMESTAMP_FIELD] // step_us
                            - base_bin).astype(jnp.int32)
                    if insert_has_key:
                        # signed transport twin of the host .view(np.int64)
                        key_i64 = lax.bitcast_convert_type(
                            cols[KEY_FIELD].astype(jnp.uint64), jnp.int64)
                    for inp, dt in acc:
                        if inp is None:
                            vals.append(jnp.ones(p, dtype=dt))
                        else:
                            vals.append(
                                _as_full(inp.eval_jnp(cols), p).astype(dt))
            if key_i64 is None:
                key_i64 = jnp.zeros(p, dtype=jnp.int64)
            return key_i64, bins, valid & ontime, tuple(vals), tuple(aux)

        return prefix_fn

    def _mesh_execute(self, batch: Batch, collector) -> bool:
        """One fused micro-batch: host prologue (hoisted filter, late
        split, open-bin bookkeeping via the member's mesh_insert_begin),
        then ONE jitted shard_map dispatch running projection → key hash →
        owner exchange → merge entirely on device. Returns False to hand
        the batch to the per-batch host path, which recovers it exactly:
        a failed fused call never committed aggregate state, and the
        member prologue's bookkeeping (late counter, open-bin set) is
        idempotent under the host re-run."""
        plan = self._entry.plan
        member = self._mesh_member
        agg = self._mesh_agg
        n = batch.num_rows
        fmask = None
        if plan.prefilter is not None:
            fm = np.asarray(
                eval_expr(plan.prefilter, batch.columns, n), dtype=bool)
            if not fm.any():
                self._small_streak = 0
                return True  # nothing flows on either path
            if not fm.all():
                survivors = int(fm.sum())
                if survivors < max(1, self._min_rows):
                    return False  # host path owns the small-batch latch
                fmask = fm
                n = survivors
        try:
            ts = np.asarray(batch.columns[TIMESTAMP_FIELD])
            if fmask is not None:
                ts = ts[fmask]
            bins_abs = ts // _insert_step(member)
            mcols = self.chain._chain_cols(collector)
            ontime = member.mesh_insert_begin(
                bins_abs, mcols[plan.insert.member_index])
            p = _padded_size(n)
            if p % agg.n_dev:
                p = -(-p // agg.n_dev) * agg.n_dev
            shard = p // agg.n_dev
            arrays = []
            for name in plan.traced_in:
                a = np.asarray(batch.columns[name])
                buf = np.zeros(p, dtype=a.dtype)
                if fmask is not None:
                    np.compress(fmask, a, out=buf[:n])
                else:
                    buf[:n] = a
                arrays.append(buf.reshape(agg.n_dev, shard))
            ot = np.zeros(p, dtype=bool)
            ot[:n] = True if ontime is None else ontime
            ot = ot.reshape(agg.n_dev, shard)
            with self._entry._lock:
                new_shape = p not in self._mesh_shapes
                self._mesh_shapes.add(p)
            t0 = time.perf_counter()
            aux = agg.update_fused(
                self._mesh_prog, n,
                0 if member.base_bin is None else int(member.base_bin),
                ot, arrays)
            if new_shape:
                # per-shape XLA compile of the fused program, same series
                # as the host entry's retraces
                from ..metrics import registry

                registry.observe_segment_compile(
                    self.ctx.task_info.job_id, time.perf_counter() - t0)
        except Exception as e:  # noqa: BLE001 - fusion is best-effort
            self._mesh_prog = None
            self._decline_mesh(f"a fused step failed: {type(e).__name__}: {e}")
            self._event(
                "WARN", "SEGMENT_FALLBACK",
                f"segment {self.chain.name()} fused mesh step failed; "
                f"batches continue on the compiled host path: "
                f"{type(e).__name__}: {e}", reason=str(e), mesh=True)
            return False
        pairs = []
        it = iter(aux)
        for mx in it:
            cnt = np.asarray(next(it))
            total = int(cnt.sum())
            # exact across shards: empty shards report the dtype floor,
            # which never exceeds a real value
            pairs.append((int(np.asarray(mx).max()) if total else None, total))
        for st, (mx, cnt) in zip(reversed(plan.wm_stages), reversed(pairs)):
            if cnt:
                self.chain.members[st.member_index].observe_batch_max(
                    mx, mcols[st.member_index])
        self._small_streak = 0
        self.metrics.segment_mesh = True
        _MESH_DISPATCH["fused"] += 1
        return True

    # -- host finish ----------------------------------------------------

    def _commit(self, res: dict, collector) -> None:
        """Feed verified traced outputs into the members' own state
        mutation/emission methods, in the interpreted path's order: data
        first (terminal collect or window insert), then the watermark
        state machines innermost-first (a downstream generator's broadcast
        happens inside the upstream one's collect call).

        Members resolve BY INDEX against this runner's chain, never via
        the cached plan's stage objects: a cache-hit entry was bound by a
        different operator incarnation (another subtask, another run, a
        restore), and committing into ITS members would mutate dead state
        while this chain's operators — the ones that checkpoint — see
        nothing. The traced function itself is pure, so reusing it across
        incarnations is safe; only the state sinks must be re-resolved."""
        if self._mesh_n > 1:
            _MESH_DISPATCH["host"] += 1
        chain = self.chain
        cols = chain._chain_cols(collector)
        plan = self._entry.plan
        k = res["n"]
        if plan.insert is not None:
            if k:
                m = chain.members[plan.insert.member_index]
                vals = []
                for i, (inp, dt) in enumerate(zip(m.acc_inputs, m.acc_dtypes)):
                    vals.append(np.ones(k, dtype=dt) if inp is None
                                else res["cols"][f"__val{i}"])
                hashes = (res["cols"]["__hash"] if plan.insert_has_key
                          else np.zeros(k, dtype=np.uint64))
                m.insert_arrays(hashes, res["cols"]["__bins"], vals,
                                cols[plan.insert.member_index])
        elif k:
            out = {name: res["cols"][name] for name, _src in plan.out_plan}
            cols[plan.prefix - 1].collect(Batch(out))
        for st, (mx, cnt) in zip(reversed(plan.wm_stages),
                                 reversed(res["aux"])):
            if cnt:
                chain.members[st.member_index].observe_batch_max(
                    mx, cols[st.member_index])


def _schema_sig(batch: Batch) -> tuple:
    return tuple((name, np.asarray(c).dtype.str)
                 for name, c in batch.columns.items())


def _cfg_fingerprint(cfg: dict):
    """JSON-stable view of a member config (exprs as tagged trees; live
    callables dropped the way graph serialization drops them)."""
    from ..graph import _jsonable

    return _jsonable(cfg)


def runner_for(operator, ctx, metrics) -> Optional[SegmentRunner]:
    """The task run loop's hook: a SegmentRunner when ``operator`` is a
    chained run marked compilable at plan time and ``segment.compile.
    enabled`` is on; None means run the interpreted hook loop. On a mesh
    (``device.mesh-devices`` > 1) it also notes why a sharded aggregate of
    this task is fed by the host prefix and not by the fused program
    (``metrics.mesh_reason``: ``explain``'s ``mesh:`` line)."""
    from ..operators.chained import ChainedOperator

    runner = None
    if not config().get("segment.compile.enabled", True):
        refused = "segment.compile.enabled is off"
    elif not isinstance(operator, ChainedOperator):
        refused = "the aggregate is in no chained run (pipeline.chaining.enabled)"
    elif not operator.compile_marking:
        # plan-time reject (optimizer.chain_graph): record the reason so
        # `top`/`explain` show "not compiled: ..." instead of nothing
        reason = getattr(operator, "compile_reject", None)
        if reason:
            metrics.segment_reason = reason
        refused = reason or "the chained run is not compiled"
    else:
        marking = operator.compile_marking
        runner = SegmentRunner(operator, ctx, metrics, marking)
        if marking.get("mesh"):
            refused = None  # the runner says so if it declines at run time
        elif marking.get("insert"):
            refused = _mesh_refused(operator.cfg_members, int(marking["prefix"]))
        else:
            refused = f"the compiled prefix ends before the window: {marking['stop']}"
    if int(config().get("device.mesh-devices", 0) or 0) > 1:
        metrics.mesh_reason = refused
    return runner
