"""Optimize, compile and cache lazy ds-array expression plans.

``compute()`` takes recorded ``Expr`` DAGs (see ``core.expr``) through three
stages:

1. **optimize** —
   (a) canonicalize + hash-cons (CSE): identical subexpressions become one
       node, so sibling reductions over the same operand evaluate it once
       and duplicate reductions collapse entirely;
   (b) transpose rules: ``T(T(x)) → x``; a Blockwise whose ds operands are
       all transposes hoists the transpose above the elementwise work (so
       chains keep fusing and the matmul fold below can fire);
       ``(A.T) @ B → MatMul(A, B, transpose_a=True)``, which lowers through
       the fused Pallas GEMM with the transpose folded into block-index
       maps — the transposed stacked tensor is never materialized;
   (c) blockwise fusion: runs of elementwise/map_blocks nodes with
       single-consumer intermediates compose into ONE per-block function,
       whose pad state is re-probed on the leaf pad constants — the eager
       layer's pad tracking, propagated symbolically across the whole plan,
       so a chain pays at most one remask at its consumer.

2. **compile** — the optimized DAG is lowered onto the eager block-native
   primitives (each node's ``lower``) inside a single ``jax.jit``; leaf
   arrays are the only runtime inputs.  A fused elementwise chain is one
   jitted body with one HBM write — the eager path dispatched every op
   separately.

3. **cache** — compiled plans are keyed by a structural hash (node kinds +
   static params + leaf signatures, NOT leaf data), so hot-loop bodies like
   the PCA power iteration compile once and replay.  The OPTIMIZER is
   cached the same way: a pre-optimization structural key (which also
   encodes leaf aliasing — two uses of the same array must keep CSE-ing)
   maps straight to the optimized plan key + input order, so re-recording a
   structurally-unchanged DAG (every ``compute()`` in a hot loop) skips
   canonicalize/fuse entirely — the remaining per-iteration recording cost
   the ROADMAP flagged after metadata memoization landed.

Block formats: a sparse (bcoo) ``Blockwise`` is a **fusion boundary** — its
fn consumes/produces BCOO block structures, which cannot compose with dense
per-block fns — but sparse nodes still CSE, and sparse plans cache by
structure + nse like any other.
"""

from __future__ import annotations

import contextlib
import hashlib
import os
import re
import sys
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence, Tuple

import jax

from repro.core import expr as _expr
from repro.core.dsarray import DsArray
from repro.core.expr import (ArrayLeaf, Blockwise, Expr, Leaf, MatMul,
                             Reduce, Transpose, _is_ds, _is_sparse)
from repro.obs import metrics as _metrics
from repro.obs import tracing as _tracing

# ---------------------------------------------------------------------------
# Optimizer
# ---------------------------------------------------------------------------


def emission_order(roots: Sequence[Expr]) -> List[Expr]:
    """Every DAG node in the naive emission order: the child-first,
    left-to-right DFS that ``Plan._make_run``'s ``ev`` memoization actually
    evaluates in.  The analysis layer's 'naive' schedule is exactly this."""
    seen = set()
    order: List[Expr] = []

    def visit(n: Expr) -> None:
        if id(n) in seen:
            return
        seen.add(id(n))
        for c in n.children:
            visit(c)
        order.append(n)

    for r in roots:
        visit(r)
    return order


def _count_nodes(roots: Sequence[Expr]) -> int:
    seen = set()

    def visit(n):
        if id(n) in seen:
            return
        seen.add(id(n))
        for c in n.children:
            visit(c)

    for r in roots:
        visit(r)
    return sum(1 for i in seen)


def _rules(n: Expr) -> Expr:
    """Local rewrite rules, applied bottom-up after children are canonical."""
    if isinstance(n, Transpose) and isinstance(n.children[0], Transpose):
        return n.children[0].children[0]
    if isinstance(n, MatMul) and not n.transpose_a \
            and isinstance(n.children[0], Transpose):
        return MatMul(n.children[0].children[0], n.children[1],
                      transpose_a=True)
    if isinstance(n, Blockwise) and n.elementwise and _is_ds(n.meta) \
            and n.children \
            and all(isinstance(c, Transpose) for c in n.children):
        # elementwise only: a position-dependent map_blocks fn does not
        # commute with the block transpose.  Transpose preserves pad
        # constants, so the resolved pad carries over unchanged.
        inner = Blockwise(n.fn, tuple(c.children[0] for c in n.children),
                          ("hoistT", n.key), pad=n.pad, elementwise=True)
        return Transpose(inner)
    return n


def _canonicalize(roots: Sequence[Expr]) -> List[Expr]:
    """Bottom-up rewrite + hash-consing (CSE) over the whole DAG."""
    memo: Dict[int, Expr] = {}
    cons: Dict[tuple, Expr] = {}

    def canon(node: Expr) -> Expr:
        if id(node) in memo:
            return memo[id(node)]
        kids = [canon(c) for c in node.children]
        n2 = node if all(a is b for a, b in zip(kids, node.children)) \
            else node.rebuild(kids)
        n2 = _rules(n2)
        if isinstance(n2, Leaf):
            key = ("leafid", id(n2.value))
        elif isinstance(n2, ArrayLeaf):
            key = ("aleafid", id(n2.value))
        else:
            key = (type(n2).__name__, n2.local_key(),
                   tuple(id(c) for c in n2.children))
        n2 = cons.setdefault(key, n2)
        memo[id(node)] = n2
        return n2

    return [canon(r) for r in roots]


def _use_counts(roots: Sequence[Expr]) -> Dict[int, int]:
    counts: Dict[int, int] = {}
    seen = set()

    def visit(n):
        for c in n.children:
            counts[id(c)] = counts.get(id(c), 0) + 1
            if id(c) not in seen:
                seen.add(id(c))
                visit(c)

    for r in roots:
        counts[id(r)] = counts.get(id(r), 0) + 1
        if id(r) not in seen:
            seen.add(id(r))
            visit(r)
    return counts


def _compose(parent_fn, specs):
    """One per-block function for a fused Blockwise: each spec is
    ('arg', slot) — pass input through — or ('call', child_fn, slots) —
    inline the child's computation."""

    def fused(*args):
        vals = []
        for kind, payload in specs:
            if kind == "arg":
                vals.append(args[payload])
            else:
                cfn, idxs = payload
                vals.append(cfn(*[args[i] for i in idxs]))
        return parent_fn(*vals)

    return fused


def _fuse(roots: Sequence[Expr]) -> Tuple[List[Expr], int]:
    """Fuse single-consumer Blockwise chains into composed Blockwise nodes."""
    counts = _use_counts(roots)
    memo: Dict[int, Expr] = {}
    fused_away = 0

    def fuse(node: Expr) -> Expr:
        nonlocal fused_away
        if id(node) in memo:
            return memo[id(node)]
        kids = [fuse(c) for c in node.children]
        out = node if all(a is b for a, b in zip(kids, node.children)) \
            else node.rebuild(kids)
        if isinstance(out, Blockwise) and _is_ds(out.meta):
            specs, new_children, key_parts = [], [], []
            slot_of: Dict[int, int] = {}
            inlined = 0

            def slot(child: Expr) -> int:
                if id(child) not in slot_of:
                    slot_of[id(child)] = len(new_children)
                    new_children.append(child)
                return slot_of[id(child)]

            for orig_c, new_c in zip(node.children, kids):
                # sparse nodes are fusion boundaries: a BCOO-consuming fn
                # cannot be inlined into a dense per-block body (or vice
                # versa) — data/indices structure is not elementwise state
                fusible = (isinstance(new_c, Blockwise)
                           and _is_ds(new_c.meta)
                           and not _is_sparse(new_c.meta)
                           and not _is_sparse(out.meta)
                           and not any(_is_sparse(gc.meta)
                                       for gc in new_c.children
                                       if _is_ds(gc.meta))
                           and counts.get(id(orig_c), 2) == 1
                           and new_c.meta.blocks.shape == out.meta.blocks.shape
                           and new_c.meta.grid == out.meta.grid)
                if fusible:
                    idxs = [slot(gc) for gc in new_c.children]
                    specs.append(("call", (new_c.fn, idxs)))
                    key_parts.append(("call", new_c.key, tuple(idxs)))
                    inlined += 1
                else:
                    s = slot(new_c)
                    specs.append(("arg", s))
                    key_parts.append(("arg", s))
            if inlined:
                fused_away += inlined
                # the fused node computes exactly what the outer node did,
                # so its pad is the outer node's RESOLVED pad — re-probing
                # the composed fn could wrongly upgrade an explicit DIRTY
                ew = out.elementwise and all(
                    c.elementwise for s, c in zip(specs, kids)
                    if s[0] == "call")
                out = Blockwise(
                    _compose(out.fn, specs), new_children,
                    ("fused", out.key, tuple(key_parts)), pad=out.pad,
                    elementwise=ew)
        memo[id(node)] = out
        return out

    new_roots = [fuse(r) for r in roots]
    return new_roots, fused_away


def optimize(roots: Sequence[Expr]) -> Tuple[List[Expr], Dict[str, int]]:
    before = _count_nodes(roots)
    roots = _canonicalize(roots)
    roots, fused = _fuse(roots)
    # fusion can leave freshly-composed siblings identical: re-cons
    roots = _canonicalize(roots)
    after = _count_nodes(roots)
    return roots, {"nodes_before": before, "nodes_after": after,
                   "fused_elementwise": fused}


# ---------------------------------------------------------------------------
# Detached inputs (so cached compiled plans never pin leaf DATA alive)
# ---------------------------------------------------------------------------


class _Input(Expr):
    """Positional plan input: carries only the leaf's static metadata."""

    __slots__ = ("idx", "is_ds", "grid", "pad")

    def __init__(self, leaf: Expr, idx: int):
        self.idx = idx
        self.is_ds = isinstance(leaf, Leaf)
        if self.is_ds:
            self.grid = leaf.value.grid
            self.pad = leaf.value.pad_state
        else:
            self.grid = self.pad = None
        self.children = ()
        self.meta = leaf.meta        # ShapeDtypeStruct-based: holds no data

    def bind(self, val):
        return DsArray(val, self.grid, self.pad) if self.is_ds else val

    def lower(self):  # pragma: no cover - inputs are bound, not lowered
        raise RuntimeError("plan inputs are bound at execution")

    def rebuild(self, children):
        return self


def _detach(roots: Sequence[Expr], leaves: Sequence[Expr]) -> List[Expr]:
    """Clone the DAG with Leaf/ArrayLeaf replaced by ``_Input`` stubs, so the
    compiled closure references no concrete arrays."""
    memo: Dict[int, Expr] = {
        id(l): _Input(l, i) for i, l in enumerate(leaves)}

    def clone(node: Expr) -> Expr:
        if id(node) in memo:
            return memo[id(node)]
        kids = [clone(c) for c in node.children]
        out = node.rebuild(kids)
        memo[id(node)] = out
        return out

    return [clone(r) for r in roots]


# ---------------------------------------------------------------------------
# Structural plan key + compiled-plan cache
# ---------------------------------------------------------------------------


def _plan_key(roots: Sequence[Expr]) -> Tuple[tuple, List[Expr]]:
    """Linear structural encoding of the DAG + the ordered leaf list.

    Keys capture node kinds, static params and LEAF SIGNATURES (geometry,
    dtype, pad state) — never leaf data — so re-running a structurally
    identical plan on fresh arrays reuses the compiled program.
    """
    entries: List[tuple] = []
    index: Dict[int, int] = {}
    leaves: List[Expr] = []

    def key(node: Expr) -> int:
        if id(node) in index:
            return index[id(node)]
        cids = tuple(key(c) for c in node.children)
        if isinstance(node, (Leaf, ArrayLeaf)):
            leaves.append(node)
            entry = ("input", node.signature())
        else:
            entry = (type(node).__name__, node.local_key(), cids)
        entries.append(entry)
        index[id(node)] = len(entries) - 1
        return index[id(node)]

    rids = tuple(key(r) for r in roots)
    return (tuple(entries), rids), leaves


def _preopt_key(roots: Sequence[Expr]) -> Tuple[tuple, List[Expr]]:
    """Structural key of the RAW (pre-optimization) DAG + its leaf list.

    Same encoding as :func:`_plan_key`, plus an alias-group index per input:
    the optimizer CSEs leaves by value identity, so two recordings that
    differ only in whether two uses share one array must not collide (one
    optimizes to a shared node, the other does not).  The optimized plan is
    a pure function of this key, which is what makes skipping
    re-canonicalization sound.
    """
    entries: List[tuple] = []
    index: Dict[int, int] = {}
    leaves: List[Expr] = []
    alias: Dict[int, int] = {}

    def key(node: Expr) -> int:
        if id(node) in index:
            return index[id(node)]
        cids = tuple(key(c) for c in node.children)
        if isinstance(node, (Leaf, ArrayLeaf)):
            leaves.append(node)
            grp = alias.setdefault(id(node.value), len(alias))
            entry = ("input", node.signature(), grp)
        else:
            entry = (type(node).__name__, node.local_key(), cids)
        entries.append(entry)
        index[id(node)] = len(entries) - 1
        return index[id(node)]

    rids = tuple(key(r) for r in roots)
    return (tuple(entries), rids), leaves


_NAME_MAX = 64


def _op_tag(node: Expr) -> str:
    """A node's op kind for a program name: ``matmul``/``matmul_ta``, a
    reduction's op, a Blockwise's recorded op (a fused chain's outermost),
    else the leading tag of its structural key."""
    if isinstance(node, MatMul):
        return "matmul_ta" if node.transpose_a else "matmul"
    if isinstance(node, Reduce):
        return str(node.op)
    tag = node.key if isinstance(node, Blockwise) else node.local_key()
    while isinstance(tag, tuple) and tag:
        tag = tag[1] if tag[0] in ("fused", "hoistT") else tag[0]
    return tag if isinstance(tag, str) else node.kind.lower()


def program_name(roots: Sequence[Expr]) -> str:
    """``plan_<op>_<op>...`` from the roots' op kinds: a valid identifier,
    the same in every process (no ``hash()``), at most ``_NAME_MAX``
    characters — a longer one keeps its head and a digest of the whole."""
    name = "_".join(["plan"] + [_op_tag(r) for r in roots])
    name = re.sub(r"\W+", "_", name).strip("_")
    if len(name) > _NAME_MAX:
        digest = hashlib.blake2s(name.encode(), digest_size=4).hexdigest()
        name = f"{name[:_NAME_MAX - 9].rstrip('_')}_{digest}"
    return name


# LRU-bounded: structural keys can embed user fn objects (map_blocks), so a
# loop that records a FRESH lambda per iteration would otherwise grow the
# cache — and pin each jitted executable + closure — without bound.
_CACHE: "OrderedDict[tuple, callable]" = OrderedDict()
# preopt structural key -> (optimized plan key, leaf positions, stats):
# repeat recordings of an unchanged DAG skip canonicalize/CSE/fuse entirely
_OPT_CACHE: "OrderedDict[tuple, tuple]" = OrderedDict()
_CACHE_MAX = 256
# cache-discipline counters, registered as "plan.*" in the obs registry
# (obs.snapshot() sees them; cache_stats() below stays the local view)
_STATS = _metrics.CounterGroup(
    "plan", ("hits", "misses", "launches", "opt_runs", "opt_skips",
             "eager_launches", "aot_compiles"))


def cache_stats() -> Dict[str, int]:
    return _STATS.as_dict()


def clear_cache() -> None:
    _CACHE.clear()
    _OPT_CACHE.clear()
    _STATS.reset()


def _fire(site: str, **info) -> None:
    """Fault-injection hook (see ``repro.resilience.inject``).

    Same zero-overhead idiom as ``DsArray._lazy_mode``: only consult the
    injector when its module is already imported (a chaos test armed it);
    clean runs pay one sys.modules lookup, never an import.
    """
    ri = sys.modules.get("repro.resilience.inject")
    if ri is not None:
        ri.maybe_fire(site, **info)


# Plan observers: the analysis CLI records the plans real workloads build
# (estimator fits call compute_multi internally) by registering a callback
# here.  Empty in normal operation — Plan.__init__ pays one truthiness
# check.
_PLAN_OBSERVERS: List = []


@contextlib.contextmanager
def capture_plans():
    """Collect every ``Plan`` constructed inside the block (post-dedup is
    the caller's job — hot loops re-plan the same structure)."""
    captured: List[Plan] = []
    _PLAN_OBSERVERS.append(captured.append)
    try:
        yield captured
    finally:
        _PLAN_OBSERVERS.remove(captured.append)


class Plan:
    """An optimized, compilable plan over one or more roots.

    Optimization is skipped when a structurally-identical DAG was planned
    before (``_OPT_CACHE``): the cached optimized-plan key + input order are
    reused and the optimized roots are only materialized on demand (for
    ``jaxpr()``/``lowered()`` inspection, or a compiled-cache miss).
    """

    def __init__(self, roots: Sequence[Expr]):
        self.stats: Dict[str, int]
        self._raw_roots = list(roots)
        self._roots: Optional[List[Expr]] = None
        with _tracing.span("plan.build"):
            pre_key = None
            raw_leaves: List[Expr] = []
            try:
                pre_key, raw_leaves = _preopt_key(self._raw_roots)
                cached = _OPT_CACHE.get(pre_key)
            except TypeError:        # unhashable static param: no caching
                cached = None
            if cached is not None:
                _OPT_CACHE.move_to_end(pre_key)
                _STATS.inc("opt_skips")
                self.key, positions, stats = cached
                self.stats = dict(stats)
                self.leaves = [raw_leaves[p] for p in positions]
            else:
                self._optimize_now(pre_key, raw_leaves)
        if _PLAN_OBSERVERS:
            for cb in list(_PLAN_OBSERVERS):
                cb(self)

    @property
    def raw_roots(self) -> List[Expr]:
        """The as-recorded (pre-optimization) roots — the plan plane the
        ``recompile-hazard`` rule lints, since canonicalization erases the
        recording artifacts (fresh lambdas, baked scalars) it looks for."""
        return self._raw_roots

    def _optimize_now(self, pre_key=None, raw_leaves=None) -> None:
        _STATS.inc("opt_runs")
        with _tracing.span("plan.optimize",
                           roots=len(self._raw_roots)) as sp:
            opt_roots, self.stats = optimize(self._raw_roots)
            sp.set(nodes_before=self.stats["nodes_before"],
                   nodes_after=self.stats["nodes_after"])
        self.key, self.leaves = _plan_key(opt_roots)
        self._roots = opt_roots
        self.stats["n_inputs"] = len(self.leaves)
        if pre_key is None:
            return
        # optimized leaves are a subset of the raw ones (CSE only merges);
        # record their positions so a later hit can bind fresh leaf values
        pos = {id(l): i for i, l in enumerate(raw_leaves)}
        if all(id(l) in pos for l in self.leaves):
            _OPT_CACHE[pre_key] = (self.key,
                                   tuple(pos[id(l)] for l in self.leaves),
                                   dict(self.stats))
            while len(_OPT_CACHE) > _CACHE_MAX:
                _OPT_CACHE.popitem(last=False)

    @property
    def roots(self) -> List[Expr]:
        if self._roots is None:
            # inspection (or recompilation) after an optimizer-cache hit:
            # re-derive the optimized DAG; same structure => same key/order
            self._optimize_now()
        return self._roots

    def _make_run(self):
        detached = _detach(self.roots, self.leaves)
        n_inputs = len(self.leaves)

        def run(*vals):
            assert len(vals) == n_inputs
            memo: Dict[int, object] = {}

            def ev(node: Expr):
                nid = id(node)
                if nid in memo:
                    return memo[nid]
                if isinstance(node, _Input):
                    out = node.bind(vals[node.idx])
                else:
                    out = node.lower(*[ev(c) for c in node.children])
                memo[nid] = out
                return out

            return tuple(ev(r) for r in detached)

        # jit names its program after the function: the device trace's
        # module line then tells one plan's program from another's
        run.__name__ = run.__qualname__ = program_name(self.roots)
        return run

    def leaf_values(self) -> List:
        return [l.value.blocks if isinstance(l, Leaf) else l.value
                for l in self.leaves]

    def jaxpr(self):
        """make_jaxpr of the compiled body (for tests/inspection)."""
        with _expr.suspend_lazy():
            return jax.make_jaxpr(self._make_run())(*self.leaf_values())

    def lowered(self):
        """jit-lowered (unoptimized-HLO-capable) form for inspection."""
        with _expr.suspend_lazy():
            return jax.jit(self._make_run()).lower(*self.leaf_values())

    def compile_aot(self) -> bool:
        """Ahead-of-time compile this plan into the shared compiled-plan
        cache: ``jit(body).lower().compile()`` on the current leaf values'
        avals, keyed by the same structural :attr:`key` ``execute`` looks
        up.  The serving layer calls this at model-load time so the FIRST
        request for a warmed geometry already hits the cache — no request
        ever pays XLA compilation.  Returns True when a fresh executable
        was compiled, False when the key was already cached (idempotent).

        A ``jax.stages.Compiled`` is positionally callable with exactly the
        avals it was lowered on, which the structural key guarantees: any
        ``execute()`` that maps to this key binds leaf values of identical
        geometry/dtype/format, so the warmed executable replays on every
        later request batch.
        """
        cached = _CACHE.get(self.key)
        if cached is not None:
            _CACHE.move_to_end(self.key)
            return False
        with _tracing.span("plan.aot_compile", inputs=len(self.leaves)):
            with _expr.suspend_lazy():
                compiled = jax.jit(self._make_run()).lower(
                    *self.leaf_values()).compile()
        _STATS.inc("aot_compiles")
        _CACHE[self.key] = compiled
        while len(_CACHE) > _CACHE_MAX:
            _CACHE.popitem(last=False)
        return True

    def execute(self) -> tuple:
        """Launch the compiled plan (compiling it on a cache miss) and
        return its outputs without waiting for the device.  While tracing
        is on, the ``plan.launch`` span around the call times the host's
        dispatch only."""
        _fire("plan_execute", mode="fused")
        compiled = _CACHE.get(self.key)
        cached = compiled is not None
        if not cached:
            _STATS.inc("misses")
            compiled = jax.jit(self._make_run())
            _CACHE[self.key] = compiled
            while len(_CACHE) > _CACHE_MAX:
                _CACHE.popitem(last=False)
        else:
            _STATS.inc("hits")
            _CACHE.move_to_end(self.key)
        _STATS.inc("launches")
        if _tracing.enabled():
            # the span times the host's dispatch, not the device's work
            # (the profiler's device planes have that); the branch keeps
            # the span's kwargs off the disabled path
            with _tracing.span("plan.launch", mode="fused", cached=cached,
                               inputs=len(self.leaves)):
                with _expr.suspend_lazy():
                    return compiled(*self.leaf_values())
        with _expr.suspend_lazy():
            return compiled(*self.leaf_values())

    def execute_eager(self, backend: Optional[str] = None) -> tuple:
        """Per-node un-jitted execution — the degradation rungs.

        The fused jitted plan holds every intermediate of its body live
        inside one XLA launch; when that launch RESOURCE_EXHAUSTs, running
        the same DAG node-by-node (each ``lower`` its own dispatch, memo
        freed per plan) trades launch count for peak footprint.  With
        ``backend`` set (``"einsum"``), local GEMMs additionally bypass the
        Pallas kernel via the ``REPRO_GEMM`` dispatch for the duration of
        this execution.  Results match ``execute()`` modulo float
        reassociation.  Never cached — this is the emergency path.
        """
        _fire("plan_execute", mode=backend or "eager")
        _STATS.inc("eager_launches")
        run = self._make_run()
        if _tracing.enabled():
            with _tracing.span("plan.launch", mode=backend or "eager",
                               inputs=len(self.leaves)):
                return self._run_eager(run, backend)
        return self._run_eager(run, backend)

    def _run_eager(self, run, backend: Optional[str]) -> tuple:
        if backend is None:
            with _expr.suspend_lazy():
                return run(*self.leaf_values())
        prev = os.environ.get("REPRO_GEMM")
        os.environ["REPRO_GEMM"] = backend
        try:
            with _expr.suspend_lazy():
                return run(*self.leaf_values())
        finally:
            if prev is None:
                os.environ.pop("REPRO_GEMM", None)
            else:
                os.environ["REPRO_GEMM"] = prev


def compute_multi(*exprs: Expr) -> tuple:
    """Evaluate several recorded expressions as ONE plan.

    CSE runs across the roots, so sibling reductions over the same operand
    share a single evaluation of it (and of any fused chain feeding it) —
    the plan-level analogue of the paper's shared task graph.
    """
    roots = [e.expr if isinstance(e, (_expr.LazyDsArray, _expr.LazyScalar))
             else e for e in exprs]
    return Plan(roots).execute()


def compute(e) -> object:
    """Evaluate one recorded expression; DsArray out for ds-shaped plans."""
    return compute_multi(e)[0]


def plan_for(*exprs) -> Plan:
    """The optimized Plan for inspection (stats, jaxpr) without executing."""
    roots = [e.expr if isinstance(e, (_expr.LazyDsArray, _expr.LazyScalar))
             else e for e in exprs]
    return Plan(roots)
