"""Trace-safety auditor: prove segment-compiled and device code is pure,
shape-stable, and numerically parity-safe.

The whole-segment compiler (engine/segment.py) and the device kernels
(ops/) rest on conventions jax cannot check for us: code that runs under
``jax.jit`` must be PURE (no host syncs, no Python control flow on traced
values, no member-state reads/writes), SHAPE-STABLE (no data-dependent
output shapes), and — because every traced path here has a bit-exact
numpy twin — NUMERICALLY PARITY-SAFE (the allowlist in segment.py, the
twin implementations in expr.py, and the dtype semantics of both paths
must agree). PR 12 discovered violations at runtime: the first-batch
verification caught them one (segment, schema) at a time and degraded to
the interpreted path. This module proves the same invariants statically,
repo-wide, at lint time (the LR3xx series — fourth engine on the shared
Diagnostic model) and at plan time (AR009).

**The trace-reachability model.** Trace roots are (a) every function
passed to ``jax.jit`` / ``pjit`` (including through wrappers:
``jax.jit(jax.shard_map(local_step, ...))`` roots ``local_step``) or to a
``jax.lax`` control-flow combinator (``fori_loop``/``scan``/...), and
(b) every ``eval_jnp`` method (the expression twins are only ever called
from inside a trace). The audited set is the call closure over those
roots, resolved through sweep-known functions by name (nested defs and
methods included) — the same closure-resolution idea as the LR2xx state
audit. Within the closure a per-function TAINT analysis marks traced
values: parameters (per-callsite), ``jnp.``/``jax.lax.`` results, and
calls into closure functions whose returns are traced. Static metadata
(``.dtype``/``.shape``/``.ndim``, ``jnp.issubdtype``, ``np.dtype``,
``is None`` identity tests) is explicitly NOT traced — branching on it
is ordinary trace-time specialization. A call into a function the sweep
cannot resolve launders taint by design: the callee is audited on its
own if it is trace-reachable, and a host helper that merely receives a
traced value is the callee's problem, not the callsite's.

Rule catalog:

    LR301 trace-impurity       host sync or impurity in trace-reachable
                               code: ``.item()``/``.tolist()``/
                               ``.block_until_ready()``, ``int()/float()/
                               bool()`` on traced values, ``np.*`` calls
                               on traced values, ``if``/``while`` on
                               traced booleans, and reads/writes of
                               mutable ``self`` state
    LR302 trace-shape-unstable data-dependent output shape in traced
                               code: ``jnp.nonzero``/``unique``/
                               ``flatnonzero``/``argwhere``/``compress``
                               without ``size=``, single-argument
                               ``jnp.where``, boolean-mask indexing
    LR303 allowlist-drift      segment.py's ``_TRACEABLE_FUNCS``/
                               ``_TRACEABLE_BINOPS`` vs expr.py's twin
                               implementations: an allowlisted op with no
                               trace builder raises at compile time and
                               silently falls back (ERROR); an op with
                               bit-exact-capable twins in neither the
                               allowlist nor ``_KNOWN_DIVERGENT_*`` is a
                               silently-uncompiled segment (WARN)
    LR304 dual-path-dtype      dtype divergence risks between the numpy
                               and traced paths: jnp constructors whose
                               default dtype follows ``jax_enable_x64``
                               (``arange``/``zeros``/... without
                               ``dtype=``), ``.astype(int/float/bool)``
                               with Python builtins, and jit-root modules
                               that never pin x64 before tracing (the
                               32-bit default silently downcasts every
                               int64 input)
    LR305 trace-time-side-effect print/logging/event/metric/clock calls
                               inside trace-reachable code: they execute
                               ONCE at trace time and never again — the
                               jitted replay silently drops them

Waivers: the repo-lint grammar, ``# lint: waive LR3xx — justification``
on the flagged line or the line above.

**AR009 (plan pass).** For every chained run the optimizer marked
compilable, propagate the input edge schema's dtypes through each traced-
prefix expression TWICE — empirically through the numpy evaluators, and
through a static model of jax-x64 semantics (weak Python scalars, the
int⊕float32 lattice divergence, the float-function dtype rules) — and
REJECT the pipeline at plan time when the traced program would compute
in a different dtype than the interpreted path (the same divergence the
first-batch verification would catch per batch, promoted to a plan
error). Chains the optimizer declined to mark carry their
``not compilable: <reason>`` string as an INFO diagnostic, so
``check``/``explain`` stop reporting fallback as an unexplained runtime
event. The jnp dtype model is pinned against real jitted dtypes by
tests/test_trace_audit.py, and the allowlist itself is proven bit-exact
across the dtype matrix by the runtime parity oracle in the same file.
"""

from __future__ import annotations

import ast
from dataclasses import dataclass, field
from typing import Iterable, Optional

import numpy as np

from .diagnostics import Diagnostic, Severity, finish
from .repo_lint import ModuleInfo, _call_name, _dotted, _parse

RULES = ("LR301", "LR302", "LR303", "LR304", "LR305")

# attribute loads that yield static (trace-time) metadata, not traced data
_STATIC_ATTRS = frozenset({
    "dtype", "shape", "ndim", "size", "kind", "itemsize", "names", "aval",
})

# library calls that return static metadata even when fed traced values
_METADATA_FNS = frozenset({
    "dtype", "issubdtype", "promote_types", "result_type", "can_cast",
    "iinfo", "finfo", "isdtype",
})

# builtins that pass taint through from their arguments
_PROPAGATING_BUILTINS = frozenset({
    "zip", "enumerate", "reversed", "sorted", "list", "tuple", "iter",
    "map", "filter", "next", "sum", "min", "max", "abs",
})

# jax.lax control-flow combinators whose function arguments run traced
_LAX_COMBINATORS = frozenset({
    "fori_loop", "scan", "while_loop", "cond", "switch", "map",
    "associative_scan", "custom_root",
})

_JIT_NAMES = ("jax.jit", "jit", "pjit", "jax.pjit",
              "jax.experimental.pjit.pjit")

# jnp/lax calls with data-dependent output shapes unless size= pins them
_SHAPE_UNSTABLE = frozenset({
    "nonzero", "flatnonzero", "argwhere", "unique", "unique_values",
    "unique_counts", "unique_inverse", "compress", "extract",
})

# jnp constructors whose default dtype follows the jax_enable_x64 flag
# while the numpy twin is fixed 64-bit: name -> index of the positional
# dtype argument (arange's sits after start/stop/step)
_DTYPE_DEFAULT_CTORS = {"zeros": 1, "ones": 1, "empty": 1, "full": 2,
                        "arange": 3, "linspace": 5}

_CLOCK_FNS = frozenset({
    "time", "monotonic", "perf_counter", "thread_time", "process_time",
    "monotonic_ns", "perf_counter_ns", "thread_time_ns", "process_time_ns",
    "sleep",
})

_LOG_METHODS = frozenset({"debug", "info", "warning", "error", "exception",
                          "critical"})

_MUTATORS = frozenset({
    "append", "appendleft", "add", "pop", "popleft", "popitem", "push",
    "extend", "extendleft", "update", "insert", "remove", "discard",
    "clear", "setdefault", "sort", "reverse", "rotate",
})


def _canon(mod: ModuleInfo, expr: ast.expr) -> str:
    return mod.canonical(_dotted(expr))


def _is_jnp(canon: str) -> bool:
    return canon.startswith(("jax.numpy.", "jnp.", "jax.lax.", "lax.")) \
        or canon.startswith("jax.")


# ----------------------------------------------------------- function index


@dataclass
class FnInfo:
    name: str
    relpath: str
    node: ast.AST  # FunctionDef | AsyncFunctionDef
    mod: ModuleInfo
    cls: Optional[str] = None  # owning class, for method self-state checks
    # taint state (fixpoint): which params are traced, does it return taint
    param_taint: set[str] = field(default_factory=set)
    all_params_tainted: bool = False
    returns_traced: bool = False
    taint: set[str] = field(default_factory=set)

    def key(self):
        return (self.relpath, id(self.node))

    def params(self) -> list[str]:
        a = self.node.args
        names = [p.arg for p in a.posonlyargs + a.args + a.kwonlyargs]
        if a.vararg:
            names.append(a.vararg.arg)
        if a.kwarg:
            names.append(a.kwarg.arg)
        return names


class _Index:
    """Every function/method (nested included) in the sweep, by bare name."""

    def __init__(self):
        self.by_name: dict[str, list[FnInfo]] = {}
        self.fns: list[FnInfo] = []
        # (relpath, class) -> attrs mutated outside __init__ (mutable state)
        self.class_mutable: dict[tuple[str, str], set[str]] = {}

    def add_module(self, mod: ModuleInfo) -> None:
        def walk(node: ast.AST, cls: Optional[str]):
            for child in ast.iter_child_nodes(node):
                if isinstance(child, ast.ClassDef):
                    self._mine_class(child, mod)
                    walk(child, child.name)
                elif isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    fi = FnInfo(child.name, mod.relpath, child, mod, cls)
                    self.fns.append(fi)
                    self.by_name.setdefault(child.name, []).append(fi)
                    walk(child, None)  # nested defs are not methods
                else:
                    walk(child, cls)

        walk(mod.tree, None)

    def _mine_class(self, cd: ast.ClassDef, mod: ModuleInfo) -> None:
        mutable: set[str] = set()
        for st in cd.body:
            if not isinstance(st, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    or st.name == "__init__":
                continue
            for n in ast.walk(st):
                if isinstance(n, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
                    targets = n.targets if isinstance(n, ast.Assign) \
                        else [n.target]
                    for t in targets:
                        a = _self_attr(t)
                        if a:
                            mutable.add(a)
                elif isinstance(n, ast.Call) and \
                        isinstance(n.func, ast.Attribute) and \
                        n.func.attr in _MUTATORS:
                    a = _self_attr(n.func.value)
                    if a:
                        mutable.add(a)
        self.class_mutable[(mod.relpath, cd.name)] = mutable

    def resolve(self, name: str, relpath: str) -> list[FnInfo]:
        cands = self.by_name.get(name, [])
        local = [c for c in cands if c.relpath == relpath]
        return local or cands


def _self_attr(t: ast.expr) -> Optional[str]:
    """'x' for a target/receiver rooted at ``self`` (``self.x``,
    ``self.x.y``, ``self.x[i]``)."""
    while isinstance(t, (ast.Subscript, ast.Attribute)):
        if isinstance(t, ast.Attribute) and isinstance(t.value, ast.Name) \
                and t.value.id == "self":
            return t.attr
        t = t.value
    return None


def _walk_own(root: ast.AST) -> Iterable[ast.AST]:
    """Walk a function body WITHOUT descending into nested function/class
    definitions: nested defs are separate closure entries with their own
    taint environment, so scanning them here would double-report findings
    under the wrong context."""
    stack = list(ast.iter_child_nodes(root))
    while stack:
        n = stack.pop()
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                          ast.ClassDef)):
            continue
        yield n
        stack.extend(ast.iter_child_nodes(n))


# ------------------------------------------------------------ root discovery


def _fn_args_of_call(call: ast.Call) -> list[str]:
    """Names passed as arguments (candidate traced callbacks/roots)."""
    return [a.id for a in call.args if isinstance(a, ast.Name)]


def _is_shard_map(canon: str) -> bool:
    """shard_map wraps its function argument for per-shard tracing, so a
    shard_map call site is a jit root exactly like jit()/pjit() — whether
    spelled jax.experimental.shard_map.shard_map, jax.shard_map, a bare
    import, or a leading-underscore alias. Without this the fused mesh
    step's per-shard body would escape LR301-LR305 entirely."""
    return canon.rsplit(".", 1)[-1].lstrip("_") == "shard_map"


def _find_roots(index: _Index, mods: list[ModuleInfo]
                ) -> tuple[list[FnInfo], set[str]]:
    """Trace roots + the set of relpaths containing a JIT call site (the
    modules LR304's x64-pin check applies to)."""
    roots: list[FnInfo] = []
    jit_modules: set[str] = set()

    def root_by_name(name: str, relpath: str):
        for fi in index.resolve(name, relpath):
            roots.append(fi)

    for mod in mods:
        for n in ast.walk(mod.tree):
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for dec in n.decorator_list:
                    d = dec.func if isinstance(dec, ast.Call) else dec
                    dc = _canon(mod, d)
                    if dc in _JIT_NAMES or _is_shard_map(dc):
                        root_by_name(n.name, mod.relpath)
                        jit_modules.add(mod.relpath)
            if not isinstance(n, ast.Call):
                continue
            canon = _canon(mod, n.func)
            if canon in _JIT_NAMES or canon.endswith((".jit", ".pjit")) \
                    or _is_shard_map(canon):
                jit_modules.add(mod.relpath)
                for a in n.args:
                    if isinstance(a, ast.Name):
                        root_by_name(a.id, mod.relpath)
                    elif isinstance(a, ast.Call):
                        # jit(wrapper(fn, ...)): the wrapped fn is traced
                        for name in _fn_args_of_call(a):
                            root_by_name(name, mod.relpath)
    for fi in index.by_name.get("eval_jnp", []):
        roots.append(fi)
    return roots, jit_modules


# ------------------------------------------------------------- taint engine


class _Taint:
    """Per-function forward taint over local names (flat scope)."""

    def __init__(self, fi: FnInfo, index: _Index, closure: dict):
        self.fi = fi
        self.index = index
        self.closure = closure  # key -> FnInfo for closure membership
        self.taint = set(fi.param_taint)
        if fi.all_params_tainted:
            self.taint |= {p for p in fi.params() if p not in ("self", "cls")}
        # (callee FnInfo, [tainted positional args]) observed at callsites
        self.callee_args: list[tuple[FnInfo, list[int], bool]] = []

    def tainted(self, e: ast.expr) -> bool:
        if isinstance(e, ast.Name):
            return e.id in self.taint
        if isinstance(e, ast.Attribute):
            if e.attr in _STATIC_ATTRS:
                return False
            return self.tainted(e.value)
        if isinstance(e, ast.Subscript):
            return self.tainted(e.value)
        if isinstance(e, ast.Call):
            return self.call_tainted(e)
        if isinstance(e, ast.Compare):
            if all(isinstance(op, (ast.Is, ast.IsNot)) for op in e.ops):
                return False  # trace-time identity (x is None)
            return self.tainted(e.left) or any(self.tainted(c)
                                               for c in e.comparators)
        if isinstance(e, ast.BoolOp):
            return any(self.tainted(v) for v in e.values)
        if isinstance(e, ast.BinOp):
            return self.tainted(e.left) or self.tainted(e.right)
        if isinstance(e, ast.UnaryOp):
            return self.tainted(e.operand)
        if isinstance(e, ast.IfExp):
            return self.tainted(e.body) or self.tainted(e.orelse)
        if isinstance(e, (ast.Tuple, ast.List, ast.Set)):
            return any(self.tainted(x) for x in e.elts)
        if isinstance(e, ast.Dict):
            return any(self.tainted(v) for v in e.values if v is not None)
        if isinstance(e, ast.Starred):
            return self.tainted(e.value)
        if isinstance(e, (ast.ListComp, ast.SetComp, ast.GeneratorExp)):
            return self.tainted(e.elt) or any(self.tainted(g.iter)
                                              for g in e.generators)
        if isinstance(e, ast.DictComp):
            return self.tainted(e.value) or any(self.tainted(g.iter)
                                                for g in e.generators)
        return False

    def call_tainted(self, call: ast.Call) -> bool:
        canon = _canon(self.fi.mod, call.func)
        name = _call_name(call)
        args_tainted = any(self.tainted(a) for a in call.args) or \
            any(self.tainted(k.value) for k in call.keywords)
        if name in _METADATA_FNS:
            return False
        if _is_jnp(canon):
            return True
        if name == "eval_jnp":
            return True
        if isinstance(call.func, ast.Name) and \
                name in _PROPAGATING_BUILTINS:
            return args_tainted
        # sweep-resolved callee: taint iff its returns are traced
        for fi in self._resolved(call):
            if fi.returns_traced:
                return True
        return False

    def _resolved(self, call: ast.Call) -> list[FnInfo]:
        name = _call_name(call)
        if isinstance(call.func, ast.Name):
            return [fi for fi in self.index.resolve(name, self.fi.relpath)
                    if fi.key() in self.closure]
        return []

    # -- statement walk -------------------------------------------------

    def run(self) -> None:
        for _ in range(4):  # small fixpoint: loops rarely nest deeper
            before = set(self.taint)
            self._walk(self.fi.node.body)
            if self.taint == before:
                break

    def _assign_target(self, t: ast.expr, tainted: bool) -> None:
        if isinstance(t, ast.Name):
            if tainted:
                self.taint.add(t.id)
        elif isinstance(t, (ast.Tuple, ast.List)):
            for e in t.elts:
                self._assign_target(e, tainted)
        elif isinstance(t, ast.Starred):
            self._assign_target(t.value, tainted)

    def _record_callsites(self, node: ast.AST) -> None:
        for n in _walk_own(node):
            if not isinstance(n, ast.Call):
                continue
            canon = _canon(self.fi.mod, n.func)
            # jax.lax combinators run their function args traced with
            # traced parameters — mark those callbacks fully tainted
            if canon.rsplit(".", 1)[-1] in _LAX_COMBINATORS and \
                    _is_jnp(canon):
                for an in _fn_args_of_call(n):
                    for fi in self.index.resolve(an, self.fi.relpath):
                        self.callee_args.append((fi, [], True))
                continue
            for fi in self._resolved(n):
                pos = [i for i, a in enumerate(n.args) if self.tainted(a)]
                kw = any(self.tainted(k.value) for k in n.keywords)
                self.callee_args.append((fi, pos, kw))

    def _walk(self, stmts: Iterable[ast.stmt]) -> None:
        for st in stmts:
            if isinstance(st, (ast.FunctionDef, ast.AsyncFunctionDef,
                               ast.ClassDef)):
                continue  # nested defs audited separately (if reachable)
            if isinstance(st, ast.Assign):
                t = self.tainted(st.value)
                for tgt in st.targets:
                    self._assign_target(tgt, t)
            elif isinstance(st, ast.AnnAssign) and st.value is not None:
                self._assign_target(st.target, self.tainted(st.value))
            elif isinstance(st, ast.AugAssign):
                if self.tainted(st.value) or self.tainted(st.target):
                    self._assign_target(st.target, True)
            elif isinstance(st, ast.For):
                it = st.iter
                # per-position taint through zip()/enumerate() so static
                # config zipped with traced state doesn't over-taint
                if isinstance(it, ast.Call) and isinstance(it.func, ast.Name) \
                        and it.func.id in ("zip", "enumerate") \
                        and isinstance(st.target, ast.Tuple) and it.args:
                    srcs = list(it.args)
                    if it.func.id == "enumerate":
                        srcs = [None] + srcs
                    for tgt, src in zip(st.target.elts, srcs):
                        self._assign_target(
                            tgt, src is not None and self.tainted(src))
                else:
                    self._assign_target(st.target, self.tainted(it))
                self._walk(st.body)
                self._walk(st.orelse)
                continue
            elif isinstance(st, (ast.If, ast.While)):
                self._walk(st.body)
                self._walk(st.orelse)
                continue
            elif isinstance(st, ast.With):
                self._walk(st.body)
                continue
            elif isinstance(st, ast.Try):
                self._walk(st.body)
                for h in st.handlers:
                    self._walk(h.body)
                self._walk(st.orelse)
                self._walk(st.finalbody)
                continue
            elif isinstance(st, ast.Return) and st.value is not None:
                if self.tainted(st.value):
                    self.fi.returns_traced = True


def _build_closure(index: _Index, roots: list[FnInfo]
                   ) -> dict[tuple, FnInfo]:
    """BFS over sweep-resolvable calls from the roots."""
    closure: dict[tuple, FnInfo] = {}
    todo = list(roots)
    for fi in roots:
        fi.all_params_tainted = True
    while todo:
        fi = todo.pop()
        if fi.key() in closure:
            continue
        closure[fi.key()] = fi
        for n in ast.walk(fi.node):
            if not isinstance(n, ast.Call):
                continue
            canon = _canon(fi.mod, n.func)
            names: list[str] = []
            if isinstance(n.func, ast.Name):
                names.append(n.func.id)
            if canon.rsplit(".", 1)[-1] in _LAX_COMBINATORS and _is_jnp(canon):
                names.extend(_fn_args_of_call(n))
            for name in names:
                for cand in index.resolve(name, fi.relpath):
                    if cand.key() not in closure:
                        todo.append(cand)
    return closure


def _taint_fixpoint(index: _Index, closure: dict[tuple, FnInfo]
                    ) -> dict[tuple, _Taint]:
    """Iterate per-function taint until param/return verdicts stabilize."""
    analyses: dict[tuple, _Taint] = {}
    for _ in range(6):
        changed = False
        for key, fi in closure.items():
            t = _Taint(fi, index, closure)
            t.run()
            t._record_callsites(fi.node)
            analyses[key] = t
            for callee, pos, kw_tainted in t.callee_args:
                if callee.key() not in closure:
                    continue
                params = [p for p in callee.params() if p not in ("self",)]
                if kw_tainted and not pos:
                    new = set(params)
                else:
                    new = {params[i] for i in pos if i < len(params)}
                    if kw_tainted:
                        new |= set(params)
                if not new <= callee.param_taint:
                    callee.param_taint |= new
                    changed = True
        if not changed:
            break
    return analyses


# ----------------------------------------------------------------- findings

# rule, relpath, line, msg, hint [, Severity] — severity defaults to ERROR
Finding = tuple


def _scan_closure(analyses: dict[tuple, _Taint]) -> list[Finding]:
    out: list[Finding] = []
    for key in sorted(analyses, key=lambda k: (k[0], analyses[k].fi.node.lineno)):
        t = analyses[key]
        fi = t.fi
        rel = fi.relpath
        ctx = f"trace-reachable {'method' if fi.cls else 'function'} " \
              f"{(fi.cls + '.') if fi.cls else ''}{fi.name}"
        mutable = _mutable_for(t, fi)
        for n in _walk_own(fi.node):
            # ---- LR301: host sync / impurity --------------------------
            if isinstance(n, ast.Call):
                name = _call_name(n)
                canon = _canon(fi.mod, n.func)
                if name in ("item", "tolist", "block_until_ready") and \
                        isinstance(n.func, ast.Attribute):
                    out.append((
                        "LR301", rel, n.lineno,
                        f".{name}() in {ctx}: forces a device->host sync — "
                        "under jit it either fails to trace or silently "
                        "degrades the whole segment to the interpreted path",
                        "keep the value traced; sync on the host side of "
                        "the jitted call"))
                elif isinstance(n.func, ast.Name) and \
                        n.func.id in ("int", "float", "bool") and \
                        any(t.tainted(a) for a in n.args):
                    out.append((
                        "LR301", rel, n.lineno,
                        f"{n.func.id}() on a traced value in {ctx}: "
                        "concretizes the tracer (TracerConversionError) or "
                        "freezes a trace-time constant into every batch",
                        "keep the computation in jnp; convert on the host "
                        "after the jitted call returns"))
                elif canon.startswith(("numpy.", "np.")) and \
                        canon.rsplit(".", 1)[-1] not in _METADATA_FNS and \
                        any(t.tainted(a) for a in n.args):
                    out.append((
                        "LR301", rel, n.lineno,
                        f"{canon}() on a traced value in {ctx}: numpy "
                        "evaluates eagerly on the host, so this either "
                        "fails to trace or silently pins a trace-time "
                        "constant",
                        "use the jnp twin of this call inside traced code"))
            if isinstance(n, (ast.If, ast.While)) and t.tainted(n.test):
                out.append((
                    "LR301", rel, n.lineno,
                    f"Python {'if' if isinstance(n, ast.If) else 'while'} "
                    f"on a traced value in {ctx}: trace-time control flow "
                    "cannot branch on batch data "
                    "(TracerBoolConversionError)",
                    "use jnp.where / lax.cond / a mask instead"))
            # self-state writes & mutable reads
            if fi.cls is not None:
                if isinstance(n, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
                    targets = n.targets if isinstance(n, ast.Assign) \
                        else [n.target]
                    for tgt in targets:
                        a = _self_attr(tgt)
                        if a:
                            out.append((
                                "LR301", rel, n.lineno,
                                f"write to self.{a} in {ctx}: traced code "
                                "must be pure — the store happens once at "
                                "trace time, then never again, so member "
                                "state silently diverges from the "
                                "interpreted path",
                                "return the value from the traced function "
                                "and commit it in a host finisher (the "
                                "segment runner's carry contract)"))
                elif isinstance(n, ast.Call) and \
                        isinstance(n.func, ast.Attribute) and \
                        n.func.attr in _MUTATORS:
                    a = _self_attr(n.func.value)
                    if a:
                        out.append((
                            "LR301", rel, n.lineno,
                            f"self.{a}.{n.func.attr}() in {ctx}: in-place "
                            "member mutation under trace runs once at "
                            "trace time only",
                            "thread the value through the traced return "
                            "and mutate on the host"))
                elif isinstance(n, ast.Attribute) and \
                        isinstance(n.ctx, ast.Load) and \
                        isinstance(n.value, ast.Name) and \
                        n.value.id == "self" and n.attr in (mutable or ()):
                    out.append((
                        "LR301", rel, n.lineno,
                        f"read of mutable member state self.{n.attr} in "
                        f"{ctx}: the value is frozen into the trace at "
                        "compile time, so later mutations never reach the "
                        "compiled segment",
                        "pass the value in as a traced argument, or keep "
                        "this expression out of the traced prefix"))
            # ---- LR302: shape instability -----------------------------
            if isinstance(n, ast.Call):
                canon = _canon(fi.mod, n.func)
                tail = canon.rsplit(".", 1)[-1]
                if _is_jnp(canon) and tail in _SHAPE_UNSTABLE and \
                        not any(k.arg == "size" for k in n.keywords):
                    out.append((
                        "LR302", rel, n.lineno,
                        f"{canon}() without size= in {ctx}: the output "
                        "shape depends on batch VALUES, which XLA cannot "
                        "compile — the trace fails or retraces per batch",
                        "pass size= (pad to a static bound) or move the "
                        "compaction to the host after the jitted call"))
                if _is_jnp(canon) and tail == "where" and \
                        len(n.args) == 1 and not n.keywords:
                    out.append((
                        "LR302", rel, n.lineno,
                        f"single-argument jnp.where() in {ctx} is "
                        "nonzero() in disguise: data-dependent output "
                        "shape",
                        "use the three-argument form, or size= via "
                        "jnp.nonzero"))
            if isinstance(n, ast.Subscript) and t.tainted(n.value):
                sl = n.slice
                if isinstance(sl, (ast.Compare, ast.BoolOp)) or \
                        (isinstance(sl, ast.UnaryOp) and
                         isinstance(sl.op, ast.Not)):
                    out.append((
                        "LR302", rel, n.lineno,
                        f"boolean-mask indexing in {ctx}: the result "
                        "length depends on how many rows match, which "
                        "XLA cannot compile",
                        "thread a validity mask (jnp.where) and compact "
                        "on the host, as the segment trace does"))
            # ---- LR304: dtype-defaulting construction -----------------
            if isinstance(n, ast.Call):
                canon = _canon(fi.mod, n.func)
                tail = canon.rsplit(".", 1)[-1]
                if _is_jnp(canon) and tail in _DTYPE_DEFAULT_CTORS:
                    pos = _DTYPE_DEFAULT_CTORS[tail]
                    has_dtype = any(k.arg == "dtype" for k in n.keywords) \
                        or (pos is not None and len(n.args) > pos)
                    if not has_dtype:
                        out.append((
                            "LR304", rel, n.lineno,
                            f"{canon}() without an explicit dtype in "
                            f"{ctx}: the default follows jax_enable_x64 "
                            "(int32/float32 when unset) while the numpy "
                            "twin is fixed 64-bit — the dual paths "
                            "silently diverge",
                            "pass dtype= explicitly (jnp.int64/"
                            "jnp.float64)"))
                if isinstance(n.func, ast.Attribute) and \
                        n.func.attr == "astype" and n.args and \
                        isinstance(n.args[0], ast.Name) and \
                        n.args[0].id in ("int", "float", "bool"):
                    out.append((
                        "LR304", rel, n.lineno,
                        f".astype({n.args[0].id}) in {ctx}: the Python "
                        "builtin maps to a platform/flag-dependent width "
                        "under jax while numpy pins 64-bit",
                        "name the dtype exactly (jnp.int64, jnp.float64, "
                        "jnp.bool_)"))
            # ---- LR305: trace-time-only side effects ------------------
            if isinstance(n, ast.Call):
                canon = _canon(fi.mod, n.func)
                recv = ""
                if isinstance(n.func, ast.Attribute):
                    v = n.func.value
                    recv = getattr(v, "id", getattr(v, "attr", "")) or ""
                effect = None
                if isinstance(n.func, ast.Name) and n.func.id == "print":
                    effect = "print()"
                elif isinstance(n.func, ast.Name) and n.func.id == "open":
                    effect = "open()"
                elif canon.startswith("logging."):
                    effect = canon + "()"
                elif isinstance(n.func, ast.Attribute) and \
                        n.func.attr in _LOG_METHODS and "log" in recv.lower():
                    effect = f"{recv}.{n.func.attr}()"
                elif canon.startswith("time.") and \
                        canon.rsplit(".", 1)[-1] in _CLOCK_FNS:
                    effect = canon + "()"
                elif isinstance(n.func, ast.Attribute) and (
                        (n.func.attr == "record"
                         and ("record" in recv.lower()
                              or "event" in recv.lower()))
                        or n.func.attr in ("_event", "_emit")):
                    effect = f"{recv}.{n.func.attr}()"
                if effect is not None:
                    out.append((
                        "LR305", rel, n.lineno,
                        f"{effect} in {ctx}: side effects under jit "
                        "execute ONCE at trace time and never again — "
                        "the compiled replay silently drops this call "
                        "on every subsequent batch",
                        "move it to the host wrapper around the jitted "
                        "call (events/metrics/logging belong outside the "
                        "trace)"))
    return out


def _mutable_for(t: _Taint, fi: FnInfo) -> set[str]:
    if fi.cls is None:
        return set()
    return t.index.class_mutable.get((fi.relpath, fi.cls), set())


# ------------------------------------------------------- LR304: the x64 pin


def _module_pins_x64(mod: ModuleInfo) -> bool:
    if "/ops/" in f"/{mod.relpath}" or mod.relpath.startswith("ops/"):
        return True  # arroyo_tpu.ops pins x64 at import
    for n in ast.walk(mod.tree):
        if isinstance(n, ast.Call):
            if _call_name(n) == "require_x64":
                return True
            for a in n.args:
                if isinstance(a, ast.Constant) and a.value == "jax_enable_x64":
                    return True
        elif isinstance(n, ast.ImportFrom):
            if n.module and ("ops" == n.module or n.module.startswith("ops.")
                             or n.module.endswith(".ops")
                             or ".ops." in n.module):
                return True
            # `from arroyo_tpu import ops` / `from .. import ops` bind the
            # pinning package by name rather than through n.module
            if any(a.name == "ops" for a in n.names):
                return True
        elif isinstance(n, ast.Import):
            if any("ops" in a.name.split(".") for a in n.names):
                return True
    return False


def _check_x64_pins(mods: dict[str, ModuleInfo], jit_modules: set[str]
                    ) -> list[Finding]:
    out: list[Finding] = []
    for rel in sorted(jit_modules):
        mod = mods[rel]
        if _module_pins_x64(mod):
            continue
        line = 1
        for n in ast.walk(mod.tree):
            if isinstance(n, ast.Call) and \
                    _canon(mod, n.func) in _JIT_NAMES:
                line = n.lineno
                break
        out.append((
            "LR304", rel, line,
            "module jits traced code without pinning jax_enable_x64 "
            "first: under the 32-bit default every int64 input silently "
            "downcasts and the uint64 routing hash truncates, so the "
            "first-batch verification fails into a permanent unexplained "
            "fallback",
            "call arroyo_tpu.ops.require_x64() (or import arroyo_tpu.ops) "
            "before building the jitted callable"))
    return out


# --------------------------------------------------- LR303: allowlist drift


def _set_literal(tree: ast.AST, varname: str) -> Optional[tuple[set, int]]:
    for n in ast.walk(tree):
        if isinstance(n, ast.Assign) and len(n.targets) == 1 and \
                isinstance(n.targets[0], ast.Name) and \
                n.targets[0].id == varname:
            value = n.value
        elif isinstance(n, ast.AnnAssign) and \
                isinstance(n.target, ast.Name) and n.target.id == varname \
                and n.value is not None:
            value = n.value  # `X: set[str] = {...}` parses like the bare form
        else:
            continue
        vals = set()
        if isinstance(value, (ast.Set, ast.Tuple, ast.List)):
            for e in value.elts:
                if isinstance(e, ast.Constant):
                    vals.add(e.value)
        elif isinstance(value, ast.Call):  # set(...) / frozenset(...)
            for a in value.args:
                if isinstance(a, (ast.Set, ast.Tuple, ast.List)):
                    for e in a.elts:
                        if isinstance(e, ast.Constant):
                            vals.add(e.value)
        else:
            continue
        return vals, n.lineno
    return None


def _dict_keys(tree: ast.AST, varname: str) -> set:
    out: set = set()
    for n in ast.walk(tree):
        if isinstance(n, ast.Assign):
            targets, value = n.targets, n.value
        elif isinstance(n, ast.AnnAssign) and n.value is not None:
            targets, value = [n.target], n.value
        else:
            continue
        if any(isinstance(t, ast.Name) and t.id == varname
               for t in targets) and isinstance(value, ast.Dict):
            for k in value.keys:
                if isinstance(k, ast.Constant):
                    out.add(k.value)
    return out


def _method_impl_names(cls_node: ast.ClassDef, method: str) -> set:
    """String constants a method dispatches on: ``name == "x"``,
    ``name in ("x", "y")``, plus every dict-literal key inside it."""
    out: set = set()
    for st in cls_node.body:
        if not (isinstance(st, ast.FunctionDef) and st.name == method):
            continue
        for n in ast.walk(st):
            if isinstance(n, ast.Compare) and \
                    isinstance(n.left, ast.Name) and n.left.id == "name":
                for comp in n.comparators:
                    if isinstance(comp, ast.Constant):
                        out.add(comp.value)
                    elif isinstance(comp, (ast.Tuple, ast.List, ast.Set)):
                        out |= {e.value for e in comp.elts
                                if isinstance(e, ast.Constant)}
            elif isinstance(n, ast.Dict):
                out |= {k.value for k in n.keys
                        if isinstance(k, ast.Constant)}
    return out


def _class_node(tree: ast.AST, name: str) -> Optional[ast.ClassDef]:
    for n in ast.walk(tree):
        if isinstance(n, ast.ClassDef) and n.name == name:
            return n
    return None


def _check_allowlist_drift(mods: dict[str, ModuleInfo]) -> list[Finding]:
    seg = next((m for m in mods.values()
                if _set_literal(m.tree, "_TRACEABLE_FUNCS") is not None
                and m.relpath.endswith("segment.py")), None)
    ex = next((m for m in mods.values()
               if _class_node(m.tree, "Func") is not None
               and m.relpath.endswith("expr.py")), None)
    if seg is None or ex is None:
        return []
    out: list[Finding] = []
    funcs, fline = _set_literal(seg.tree, "_TRACEABLE_FUNCS")
    binops, bline = _set_literal(seg.tree, "_TRACEABLE_BINOPS")
    divergent = (_set_literal(seg.tree, "_KNOWN_DIVERGENT_FUNCS")
                 or (set(), fline))[0]
    divergent_b = (_set_literal(seg.tree, "_KNOWN_DIVERGENT_BINOPS")
                   or (set(), bline))[0]

    func_cls = _class_node(ex.tree, "Func")
    np_impl = _method_impl_names(func_cls, "eval_np")
    jnp_impl = _method_impl_names(func_cls, "eval_jnp")
    np_bin = _dict_keys(ex.tree, "_NP_BINOPS")
    bin_cls = _class_node(ex.tree, "BinOp")
    jnp_bin = _method_impl_names(bin_cls, "eval_jnp") if bin_cls else set()

    for f in sorted(funcs - jnp_impl):
        out.append((
            "LR303", seg.relpath, fline,
            f"allowlisted func {f!r} (_TRACEABLE_FUNCS) has no jnp trace "
            "builder in expr.Func.eval_jnp: every segment using it "
            "compiles, raises NotImplementedError at trace time, and "
            "silently falls back to the interpreted path",
            "implement the eval_jnp twin (and prove it bit-exact in the "
            "parity oracle) or remove the op from the allowlist"))
    for f in sorted(funcs - np_impl):
        out.append((
            "LR303", seg.relpath, fline,
            f"allowlisted func {f!r} has no numpy implementation in "
            "expr.Func.eval_np: the interpreted path (and the first-batch "
            "verification reference) cannot evaluate it",
            "implement eval_np or remove the op from the allowlist"))
    for f in sorted((np_impl & jnp_impl) - funcs - divergent):
        out.append((
            "LR303", seg.relpath, fline,
            f"func {f!r} has BOTH numpy and jnp implementations but is in "
            "neither _TRACEABLE_FUNCS nor _KNOWN_DIVERGENT_FUNCS: segments "
            "using it silently never compile",
            "allowlist it if the twins are bit-exact (prove with the "
            "parity oracle) or declare it in _KNOWN_DIVERGENT_FUNCS with "
            "the reason", Severity.WARNING))
    for f in sorted(funcs & divergent):
        out.append((
            "LR303", seg.relpath, fline,
            f"func {f!r} is in both _TRACEABLE_FUNCS and "
            "_KNOWN_DIVERGENT_FUNCS: the allowlist claims bit-exactness "
            "the divergence set denies",
            "keep it in exactly one of the two sets"))
    for op in sorted(binops - jnp_bin):
        out.append((
            "LR303", seg.relpath, bline,
            f"allowlisted operator {op!r} (_TRACEABLE_BINOPS) has no jnp "
            "dispatch entry in expr.BinOp.eval_jnp",
            "add the jnp twin or remove the operator from the allowlist"))
    for op in sorted(binops - np_bin):
        out.append((
            "LR303", seg.relpath, bline,
            f"allowlisted operator {op!r} has no _NP_BINOPS entry",
            "add the numpy twin or remove the operator from the allowlist"))
    for op in sorted((np_bin & jnp_bin) - binops - divergent_b):
        out.append((
            "LR303", seg.relpath, bline,
            f"operator {op!r} has both numpy and jnp implementations but "
            "is in neither _TRACEABLE_BINOPS nor _KNOWN_DIVERGENT_BINOPS: "
            "segments using it silently never compile",
            "allowlist it if bit-exact, else declare it known-divergent",
            Severity.WARNING))
    return out


# -------------------------------------------------------------- entry points


def audit_trace_modules(mods: list[ModuleInfo]) -> list[Diagnostic]:
    """LR3xx over already-parsed modules (the lint sweep hands its own)."""
    index = _Index()
    by_rel: dict[str, ModuleInfo] = {}
    for mod in mods:
        by_rel.setdefault(mod.relpath, mod)
        index.add_module(mod)
    roots, jit_modules = _find_roots(index, mods)
    closure = _build_closure(index, roots)
    analyses = _taint_fixpoint(index, closure)

    findings: list[Finding] = []
    findings += _scan_closure(analyses)
    findings += _check_x64_pins(by_rel, jit_modules)
    findings += _check_allowlist_drift(by_rel)

    diags: list[Diagnostic] = []
    seen: set[tuple] = set()
    for rule, rel, line, msg, hint, *rest in findings:
        sev = rest[0] if rest else Severity.ERROR
        mod = by_rel.get(rel)
        if mod is not None and mod.waiver(line, rule):
            continue
        key = (rule, rel, line, msg)
        if key in seen:
            continue
        seen.add(key)
        diags.append(Diagnostic(rule, sev, f"{rel}:{line}", msg, hint))
    return finish(diags)


def audit_trace_source(source: str, relpath: str = "engine/fixture.py"
                       ) -> list[Diagnostic]:
    """Audit one file's text (test surface)."""
    return audit_trace_modules([_parse(source, relpath)])


def audit_trace_sources(named: list[tuple[str, str]]) -> list[Diagnostic]:
    """Audit several (source, relpath) files as one sweep (test surface
    for the cross-module rules, e.g. LR303's segment/expr pairing)."""
    return audit_trace_modules([_parse(src, rel) for src, rel in named])


# =========================================================================
# AR009 — plan-time dual-path dtype propagation
# =========================================================================


class _Weak:
    """A weak-typed Python scalar inside the jax dtype model."""

    __slots__ = ("kind",)

    def __init__(self, kind: str):  # "i" | "f" | "b"
        self.kind = kind


class _Unmodeled(Exception):
    """The dtype model does not cover this expression shape; AR009 skips
    it (the runtime first-batch verification still covers it)."""


def _jnp_promote(a, b):
    """jax-x64 binary promotion. Identical to numpy except the lattice's
    famous corner: integer x float32 stays float32 under jax where numpy
    widens to float64 (the divergence AR009 exists to reject)."""
    if isinstance(a, _Weak) and isinstance(b, _Weak):
        if "f" in (a.kind, b.kind):
            return _Weak("f")
        if "i" in (a.kind, b.kind):
            return _Weak("i")
        return _Weak("b")
    if isinstance(a, _Weak):
        a, b = b, a
    if isinstance(b, _Weak):
        if b.kind == "f":
            return a if a.kind == "f" else np.dtype(np.float64)
        if b.kind == "i":
            return np.dtype(np.int64) if a.kind == "b" else a
        return np.dtype(np.int64) if a.kind == "b" else a
    if (a.kind in "iu" and b == np.float32) or \
            (b.kind in "iu" and a == np.float32):
        return np.dtype(np.float32)
    return np.promote_types(a, b)


def _resolve_weak(d):
    if isinstance(d, _Weak):
        return np.dtype({"i": np.int64, "f": np.float64, "b": np.bool_}[d.kind])
    return d


def _jnp_dtype(expr, env: dict):
    """Static model of the dtype ``expr.eval_jnp`` computes under jax with
    x64 enabled. Pinned against real jitted dtypes by the model-fidelity
    test in tests/test_trace_audit.py — extend both together."""
    from ..expr import BinOp, Case, Cast, Col, Expr, Func, Lit, Neg, Not

    e = expr
    if isinstance(e, Col):
        d = env.get(e.name)
        if d is None or d == np.dtype(object):
            raise _Unmodeled(e.name)
        return d
    if isinstance(e, Lit):
        if isinstance(e.value, bool):
            return np.dtype(np.bool_)
        if isinstance(e.value, int):
            return _Weak("i")
        if isinstance(e.value, float):
            return _Weak("f")
        raise _Unmodeled(repr(e.value))
    if isinstance(e, BinOp):
        if e.op in ("==", "!=", "<", "<=", ">", ">=", "and", "or"):
            _jnp_dtype(e.left, env), _jnp_dtype(e.right, env)
            return np.dtype(np.bool_)
        l = _jnp_dtype(e.left, env)
        r = _jnp_dtype(e.right, env)
        out = _jnp_promote(l, r)
        if e.op == "/":
            li, ri = (isinstance(x, _Weak) and x.kind == "i"
                      or (not isinstance(x, _Weak) and x.kind in "iu")
                      for x in (l, r))
            if not (li and ri):
                # true division: float result
                rf = _resolve_weak(out)
                if rf.kind in "iub":
                    # int/int handled above; mixed int-float promoted
                    out = np.dtype(np.float64)
        return out
    if isinstance(e, Not):
        return np.dtype(np.bool_)
    if isinstance(e, Neg):
        return _jnp_dtype(e.inner, env)
    if isinstance(e, Cast):
        try:
            from ..batch import Field

            return np.dtype(Field("_", e.dtype).numpy_dtype())
        except Exception as err:
            raise _Unmodeled(e.dtype) from err
    if isinstance(e, Case):
        if e.otherwise is None:
            raise _Unmodeled("CASE without ELSE")
        out = _jnp_dtype(e.otherwise, env)
        for c, v in e.branches:
            _jnp_dtype(c, env)
            out = _jnp_promote(out, _jnp_dtype(v, env))
        return out
    if isinstance(e, Func):
        args = [_jnp_dtype(a, env) for a in e.args]
        if e.name == "abs":
            return args[0]
        if e.name in ("floor", "ceil", "sqrt"):
            a = args[0]
            if isinstance(a, _Weak):
                return np.dtype(np.float64)
            if a.kind == "f":
                return a
            if a.kind in "iu":
                # expr.py promotes integer inputs to float64 explicitly
                return np.dtype(np.float64)
            # bool: numpy computes float16, jnp has no exact twin —
            # model the jnp results so the comparison flags the mismatch
            return np.dtype(np.bool_) if e.name != "sqrt" \
                else np.dtype(np.float32)
        if e.name == "extract_epoch":
            return _jnp_promote(args[0], _Weak("i"))
        if e.name == "date_trunc_micros":
            return _jnp_promote(args[1], args[0])
        if e.name == "to_timestamp_micros":
            return np.dtype(np.int64)
        raise _Unmodeled(e.name)
    if isinstance(e, Expr):
        raise _Unmodeled(type(e).__name__)
    raise _Unmodeled(repr(e))


def _np_dtype_of(expr, env: dict):
    """The dtype the interpreted path actually computes — measured, not
    modeled: evaluate on zero-row columns through the real eval_np."""
    from ..expr import eval_expr

    cols = {name: np.empty(0, dtype=dt) for name, dt in env.items()}
    return np.asarray(eval_expr(expr, cols, 0)).dtype


def pass_segment_compile(ctx) -> None:
    """AR009: dual-path dtype parity of plan-marked-compilable segments,
    plus the ``not compilable: <reason>`` surfacing for chains the
    optimizer declined to mark.

    Deliberately ignores ``pipeline.chaining.enabled``: chaining is a
    deploy-time flag that can flip on a pipeline AFTER it was accepted
    (restores re-plan under the then-current config), so a plan accepted
    today must stay byte-exact under tomorrow's chained execution — the
    same reasoning that makes AR004 warn about unbounded state regardless
    of today's memory. ``segment.compile.enabled`` is the explicit
    opt-out: with compilation off, segments can never trace and the
    divergence cannot materialize, so the pass stands down entirely."""
    from ..batch import KEY_FIELD, TIMESTAMP_FIELD
    from ..config import config
    from ..graph import OpName
    from ..optimizer import chain_graph

    if not config().get("segment.compile.enabled", True):
        return  # segments never compile: the divergence cannot materialize
    try:
        g2 = chain_graph(ctx.graph)
    except Exception:
        return  # a malformed graph fails other passes; nothing to add here
    for nid in sorted(g2.nodes):
        node = g2.nodes[nid]
        if node.op != OpName.CHAINED:
            continue
        reject = node.config.get("compile_reject")
        if reject:
            ctx.add("AR009", Severity.INFO, node.node_id,
                    f"chained run is {reject}; it will execute interpreted",
                    "expected for chains ending at a sink or using "
                    "host-only expressions — see README \"why is my "
                    "segment not compiled\"")
            continue
        marking = node.config.get("compile")
        if not marking:
            continue
        env: dict = {}
        for e in g2.in_edges(node.node_id):
            for f in e.schema.fields:
                try:
                    env[f.name] = np.dtype(f.numpy_dtype())
                except Exception:
                    continue
        env.setdefault(TIMESTAMP_FIELD, np.dtype(np.int64))
        members = list(node.config.get("members", []))[: int(marking["prefix"])]

        def compare(label: str, expr, mi: int, op: str) -> None:
            refs = expr.columns()
            strings = sorted(r for r in refs
                             if env.get(r) == np.dtype(object))
            if strings:
                ctx.add(
                    "AR009", Severity.INFO, node.node_id,
                    f"compile-marked segment member {mi} references "
                    f"non-numeric column(s) {strings}: the segment will "
                    "fall back to the interpreted path at runtime (only "
                    "numeric/bool columns trace)",
                    "expected when projections carry strings; the "
                    "fallback is safe and permanent")
                return
            try:
                want = _np_dtype_of(expr, env)
                got = _resolve_weak(_jnp_dtype(expr, env))
            except Exception:
                return  # unmodeled shape: the first-batch verify covers it
            if np.dtype(got) != np.dtype(want):
                ctx.add(
                    "AR009", Severity.ERROR, node.node_id,
                    f"dual-path dtype divergence in {label} (chain member "
                    f"{mi}, {op}): the interpreted path computes {want} "
                    f"but the traced program would compute {np.dtype(got)}"
                    " — the byte-exactness contract cannot hold, so the "
                    "pipeline is rejected at plan time instead of failing "
                    "verification on the first batch",
                    "make the dtype explicit (e.g. CAST both operands to "
                    "DOUBLE) so both paths agree, or rewrite the "
                    "expression out of the compile-marked chain")

        for mi, (op, cfg) in enumerate(members):
            if op == OpName.VALUE.value:
                projections = cfg.get("projections")
                for n, e in projections or []:
                    compare(f"projection {n!r}", e, mi, op)
                if projections is not None:
                    nenv: dict = {}
                    for n, e in projections:
                        try:
                            nenv[n] = _np_dtype_of(e, env)
                        except Exception:
                            nenv[n] = np.dtype(object)  # host-only value
                    for carried in (TIMESTAMP_FIELD, KEY_FIELD,
                                    "_is_retract"):
                        if carried in env and carried not in nenv:
                            nenv[carried] = env[carried]
                    env = nenv
            elif op == OpName.KEY.value:
                for n, e in cfg.get("keys") or []:
                    compare(f"key {n!r}", e, mi, op)
                    try:
                        env[n] = _np_dtype_of(e, env)
                    except Exception:
                        env[n] = np.dtype(object)
                env[KEY_FIELD] = np.dtype(np.uint64)
            elif op == OpName.WATERMARK.value:
                if cfg.get("expr") is not None:
                    compare("watermark expression", cfg["expr"], mi, op)
            else:  # window insert: accumulator input expressions
                for n, _k, e in cfg.get("aggregates") or []:
                    if e is not None:
                        compare(f"aggregate input {n!r}", e, mi, op)
