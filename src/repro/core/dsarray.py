"""DsArray: the paper's distributed array, adapted to JAX/TPU.

A ds-array is a 2-D array divided into blocks of arbitrary size that live on
different workers and are operated on by per-block parallel tasks behind a
NumPy-like API (paper §4.2).  The TPU-native representation used here is a
single **stacked block tensor** of shape ``(gn, gm, bn, bm)`` — grid dims
first, block dims last — which is the direct SPMD analogue of the paper's
list-of-lists-of-blocks:

* grid cell (i, j)               <->  paper block (i, j)
* sharding grid dims over a mesh <->  PyCOMPSs placing blocks on workers
* vectorized op over grid dims   <->  one PyCOMPSs task per block
* XLA collective                 <->  inter-worker future transfer

Everything is a pure function of the stacked tensor, so a DsArray traces
through ``jax.jit`` and shards with ``NamedSharding(P(axis0, axis1))`` on the
grid dims.  Edge blocks are zero-padded, and each array carries a static
**pad state** — ``ZERO`` (pad exactly 0), ``FILL(v)`` (pad is the known
constant v) or ``DIRTY`` (unknown) — propagated at trace time by probing
each op on the pad constants.  Consumers that need the pad-is-zero invariant
(reductions, matmul, structural ops) enforce it lazily via
``ensure_zero_pad()``, so zero-preserving op chains emit **no** mask pass at
all and a chain ending in a consumer pays at most one.

Hot-path complexity (paper §5 claims, as implemented by ``core.structural``
and ``kernels.matmul``; N = n*m elements, "seed" = the path each row
replaced):

======================  ========================  ==========================
op                      seed path                 block-native path
======================  ========================  ==========================
aligned ``A[r0:r1,...]``  O(N) gather + repack      O(selected blocks) view
unaligned slice/stride  O(N) + gather             O(out) single block gather
row filter ``A[idx]``   O(N) + gather             O(out) single block gather
``rechunk`` (dividing)  O(N) two global layouts   O(N) one regroup reshape
``rechunk`` (general)   O(N) two global layouts   O(N) two block gathers
``concat_rows`` aligned O(sum N_i) x2             O(1) block-grid stack
``A @ B`` local GEMM    O(gk) einsum/kernel       1 fused Pallas launch,
                        launches + partial-C        grid-k x block-k in one
                        HBM round-trips             VMEM fp32 accumulator
elementwise chain (L)   L remask passes           0 remask passes (ZERO-
                                                    preserving) or 1 at the
                                                    consuming reduction
``_reduce`` refill      1 select pass always      0 when pad == identity
``apply_along_axis``    collect + host loop       1 nested-vmap call in
                                                    block layout
``exact_shuffle``       O(N) collect + take       1 per-block row gather
======================  ========================  ==========================

Block formats (paper §4.2: blocks are NumPy arrays OR scipy.sparse CSR):
``block_format`` names the storage — ``"dense"`` is the rank-4 stacked
tensor above; ``"bcoo"`` stores the same grid as one
``jax.experimental.sparse.BCOO`` with batch dims (gn, gm) and element-
sparse (bn, bm) blocks (see ``core.sparse``).  Sparse arrays are
ZERO-padded **by construction** (pad positions own no entry), so
``ensure_zero_pad`` is free.  Per-op storage behaviour:

======================  ======================================================
op on a bcoo array      behaviour
======================  ======================================================
``* / scalar``, ``-x``  sparse-native data map (index-preserving) when
``abs``, ``sqrt``         ``op(0) == 0``; result stays bcoo
``+ scalar``, ``exp``   densify (implicit zeros would change value)
``sp ± sp``, ``sp*sp``  sparse-native index merge (nse grows on ±;
                          ``sparse.canonicalize`` re-packs)
``sp * dense``          sparse-native index-gather of the dense operand
``sp / dense``          sparse-native (sparse side is the numerator)
``dense / sp``          densify (division by implicit zero)
``astype``              sparse-native data cast
``transpose``           sparse-native batch+index swap — O(nnz), no relayout
``sp @ dense``          ONE ``bcoo_dot_general`` over (grid-k, block-k); the
``spᵀ @ dense``           sparse operand is never densified (jaxpr-asserted);
                          dense result
``x @ sp``, ``sp @ sp`` right operand densifies
``sum``                 sparse-native ``bcoo_reduce_sum`` (identity == the
                          implicit zeros); small result is dense
``max``/``min``/mean    max/min densify (implicit zeros compete); mean is
                          sum-based and stays sparse-native
aligned slice           sparse-native batch-dim slice of the stacked BCOO
                          (start on block boundary, unit step; a mid-block
                          stop zero-masks the tail entries' data) — no
                          ``bcoo_todense`` (``sparse.aligned_slice_sparse``)
other slice/rechunk/    densify, then the dense block-native path
concat/shuffle/apply
======================  ======================================================

Estimator layer (``repro.estimators`` + ``repro.algorithms``; the dislib
collection the ds-array exists to power — every class implements the
``BaseEstimator`` contract of fit/predict/score + get_params/set_params,
accepts dense AND bcoo inputs, and records its fit-loop body lazily so
iterations hit the structural plan caches):

======================  ======================================================
estimator               data-matrix path per fit/predict
======================  ======================================================
``CascadeSVM``          chunking = aligned row slices (batch-dim slices of
                          the stacked BCOO — x never densifies, asserted);
                          kernel block ``X @ SVᵀ`` = ONE recorded plan per
                          iteration (sparse-lhs ``bcoo_dot_general``),
                          cache-hit from iteration 2 (``opt_runs == 1``)
``LinearRegression``/   normal equations ``XᵀX``/``Xᵀy`` in one recorded
``Ridge``                 multi-root plan (transpose folded; sparse-lhs for
                          bcoo); TSQR fallback on ill-conditioned inputs
``RandomForest-``       quantize blocks once (dense path; bcoo densifies by
``Classifier``            policy), one histogram einsum per level; predict =
                          one ``apply_along_axis`` vote pass
``KMeans``              ‖x‖² hoisted through one lazy plan; Lloyd
                          contractions sparse-native (``bcoo_dot_general``)
``PCA``                 power iteration records ``xᵀ(x·q)`` (sparse-native
                          with ``center=False``); ``pca()`` is a thin alias
``ALS``                 ``R@V`` / ``Rᵀ@U`` ds-array matmuls (sp @ dense)
======================  ======================================================

Lazy plans record the same classification (``core.expr``): sparse Blockwise
nodes carry BCOO-consuming fns and are **fusion boundaries** — the
optimizer never composes them with dense elementwise chains (``core.plan``)
— but they still CSE and their compiled plans cache by structure + nse.

``check_invariants()`` validates the claims above on concrete arrays (pad
region matches ``pad_state``, grid/shape consistency, BCOO indices
in-bounds-or-zero); exported for tests and run at every construction under
``REPRO_DEBUG=1`` (``pytest --repro-debug`` arms it for a whole test run).
Violations name the offending block: ``block (gi, gj) at offset (bi, bj)``
for dense pads, ``block (gi, gj) slot k`` for BCOO entries.

Numerical guards and resilience (``repro.resilience``) ride the same
block-granular conventions:

======================  ======================================================
entry point             what it does
======================  ======================================================
``finite_report()``     per-block NaN/Inf diagnosis (pad-state aware: FILL/
                          DIRTY pads never false-positive); offending blocks
                          named ``block (gi, gj)`` in ``check_invariants``
                          style — also ``resilience.guards.finite_report``
``guard_finite(...)``   cheap whole-value post-condition: ONE fused
                          reduction per value, raising
                          ``NumericalDivergence`` with the block report
``run_resilient(...)``  guarded plan execution: transient errors retry with
                          backoff, OOM degrades fused → per-node eager →
                          einsum GEMM backend, deterministic errors raise;
                          ``resilience.stats()`` counts recoveries
``inject(FaultSpec)``   deterministic fault injection (chaos harness) at
                          ``plan_execute`` / ``gemm_dispatch`` /
                          ``fit_iteration`` / ``io_load`` /
                          ``serve_dispatch`` sites
======================  ======================================================

Predict serving (``repro.serve``) turns fitted estimators into a
low-latency request loop over the same plan machinery:

======================  ======================================================
entry point             what it does
======================  ======================================================
``ModelRegistry``       named + versioned fitted models — ``register`` an
``.register/.load``       in-process estimator or ``load`` a ``save_model``
                          checkpoint (versions = checkpoint steps); params
                          pinned on device, per-bucket predict plans
                          AOT-compiled at load (``Plan.compile_aot``)
``PredictServer``       micro-batches requests into declared geometry
``.submit/.pump``         buckets (tail rows PAD_ZERO, results sliced back
                          per request; bcoo stays sparse at fixed nse);
                          every plan launch rides ``run_resilient``, and
                          dispatch faults shed batching -> unbatched
                          predict (request-level isolation)
``serve.stats()``       request/latency/queue counters + the plan-cache
                          discipline: steady state serves with ZERO XLA
                          recompiles (``cache_hits == requests``)
======================  ======================================================

Ingestion (``repro.core.io`` + ``core.readers``; paper §4.2.2 — arrays are
built one block-row at a time, so no process ever holds the full matrix):

==========================  ==================================================
entry point                 what it does
==========================  ==================================================
``load_txt_file``           streaming delimited-text loader: line-aligned
                              byte-range chunks (dask ``read_block`` idiom)
                              fill one block-row buffer; peak host memory
                              O(block-row), bitwise-equal to ``from_array``
                              of the full parse
``load_svmlight_file``      streaming svmlight -> ``(x, y)``; per-block-row
                              COO triplets pack into ONE stacked BCOO at
                              shared nse (``sparse.StackedBCOOBuilder``) —
                              larger-than-dense-RAM sparse data never
                              densifies
``load_npy_rows``           memory-mapped ``.npy`` row range streamed block
                              row by block row; untouched pages never fault
                              in (density scan only under ``"auto"``)
``load_npz_sparse``         scipy ``.npz`` -> BCOO ds-array (``from_scipy``)
``save_blocks`` /           one file per block row, dense or sparse
``load_blocks``               (data+indices+nse round-trip) — the spill /
                              checkpoint format
``save_npy``                dense global array; raises on bcoo (explicit
                              ``todense()`` instead of a silent densify)
==========================  ==================================================

Observability (``repro.obs``): one telemetry surface over every layer
above — tracing is OFF by default and allocation-free while off, so the
hot paths are byte-identical to the uninstrumented code:

==========================  ==================================================
entry point                 what it does
==========================  ==================================================
``obs.trace_to(path)``      arm tracing for a ``with`` block and export the
                              captured spans as Chrome trace-event JSON
                              (``chrome://tracing`` / Perfetto-loadable);
                              ``obs.summary()`` renders the same spans as an
                              aggregated terminal tree
``obs.span/@obs.traced``    the span primitives the instrumented sites use;
                              each span is also a profiler
                              ``TraceAnnotation`` under its bare name, so it
                              sits on the device trace's clock; spans never
                              wait for the device (``plan.launch`` times the
                              dispatch).  Span names: ``plan.build`` /
                              ``plan.optimize`` / ``plan.aot_compile`` /
                              ``plan.launch``; ``fit.validate`` /
                              ``fit.stats`` / ``fit.factor`` /
                              ``fit.collect`` / ``fit.solve`` /
                              ``fit.refine`` (linear models),
                              ``fit.row_norms`` / ``fit.init`` /
                              ``fit.loop`` (KMeans), ``fit.iteration``;
                              ``resilience.rung`` (one per attempt, failures
                              tagged ``error=``); ``serve.submit`` (with its
                              request id) / ``serve.batch`` / ``serve.plan``
                              / ``serve.dispatch`` (with its batch id) /
                              ``serve.slice``; ``ingest.load`` /
                              ``ingest.chunk``
``obs.record``              an interval known after the fact, buffered
                              only: ``serve.queue``, a request's wait from
                              submit to its first dispatch
``obs.registry``            the process-wide Counter/Gauge/Histogram
                              registry; ``plan.cache_stats()``,
                              ``resilience.stats()`` and ``serve.stats()``
                              are views over it (metric names ``plan.*``,
                              ``resilience.*``, ``serve.*``, ``gemm.*``,
                              ``solve.*`` (the linear models' device
                              solves, refinement steps, host fallbacks) —
                              all increments locked, safe from server
                              worker threads)
``obs.snapshot()``          flat ``{metric: value}`` across the registry
``obs.reset_all()``           (benchmarks embed a slice of it per record);
                              reset zeroes every metric + the trace buffer
``obs.profile(plan)``       per-node measured wall time + actual output
                              bytes vs the ``costmodel`` byte laws; nodes
                              beyond ``COSTMODEL_DRIFT_FACTOR`` feed the
                              ``costmodel-drift`` analysis rule
==========================  ==================================================

Each claim in the tables above is machine-checked by ``repro.analysis``
(``analysis.check(plan_or_dsarray)``, CLI ``python -m repro.analysis``).
Rule ids per op row:

======================  ======================================================
op family               analyzer rules that police it
======================  ======================================================
sparse op rows          ``no-densify`` — a bcoo operand reaching a dense
(``sp @ dense``, maps,    kernel without a recorded ``Densify``/documented
sums, slices)             sink is an error on both the plan and the jaxpr
elementwise chains      ``remask-budget`` — select/mask passes in the trace
(L ops, ≤1 remask)        vs ``costmodel.chain_remask_passes``;
                          ``no-full-grid-intermediate`` — the fused chain
                          must compile to one body, no full-grid HBM def
pad-state rows          ``pad-soundness`` — a recorded Blockwise may not
(ZERO/FILL/DIRTY)         claim a stronger pad than its fn probe derives
scalar ops / map_blocks ``recompile-hazard`` — baked scalars with weak-type
                          drift, raw lambdas in plan keys, captured arrays
any multi-node plan     ``peak-hbm-liveness`` — naive emission order vs the
                          liveness-minimizing topological order (bytes from
                          ``costmodel.node_live_bytes``); warns at ≥2x
======================  ======================================================

Remask-elision rules: a binary/unary op on known pad states yields the op of
the pad constants (probed on 0-d values at trace time) — nan or a traced
operand demotes to DIRTY; ``_reduce`` refills only when the pad state
differs from the reduction identity; ``__matmul__`` and every structural op
call ``ensure_zero_pad()`` (a no-op on ZERO) before touching raw blocks.

Lazy plans (``core.expr`` / ``core.plan``): inside ``repro.lazy():`` — or
from ``a.lazy()`` — every op above records an ``Expr`` node instead of
dispatching, and ``compute()`` optimizes the whole DAG before lowering it
back onto these eager primitives in one ``jax.jit``.  Fusion rules:

* a run of elementwise/``map_blocks`` nodes whose intermediates have a
  single consumer composes into ONE per-block function — an L-op chain is
  one launch, one HBM read + one write (eager: L dispatches, 2·L passes);
* pad states propagate symbolically across the plan (the composed function
  is re-probed on the leaf pad constants), so a chain pays at most one
  remask at its consumer — zero when it stays ZERO-preserving;
* ``T(T(x)) → x``; elementwise over all-transposed operands hoists the
  transpose; ``(A.T) @ B`` folds into the fused Pallas GEMM with the
  transpose absorbed by block-index maps (``matmul_ta`` — the transposed
  stacked tensor never materializes);
* hash-consing shares identical subexpressions, so sibling reductions over
  the same operand evaluate it once; compiled plans are cached by
  structural hash (node kinds + static params + leaf geometry/dtype/pad,
  never data), so hot-loop bodies compile once and replay.

None of the block-native paths form a rank-2 global ``(n, m)`` tensor, so
they compose with ``jit``/sharding without pulling the array onto one host,
and on ``NamedSharding`` inputs the result is re-placed on the same mesh.
"""

from __future__ import annotations

import dataclasses
import functools
import math
import sys
from typing import Any, Callable, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.sparse import BCOO
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.blocking import BlockGrid, ceil_div, round_up

Number = Union[int, float]


def _debug_validate() -> bool:
    """True when REPRO_DEBUG=1: every DsArray construction self-checks."""
    import os
    return os.environ.get("REPRO_DEBUG") == "1"


def _lazy_mode() -> bool:
    """True when ``repro.lazy()`` recording is armed (see ``core.expr``).

    Checked at the top of every recordable op; resolved through
    ``sys.modules`` so arrays never pay an import until the lazy layer has
    actually been loaded (it cannot be active before it is imported).
    """
    expr = sys.modules.get("repro.core.expr")
    return expr is not None and expr.lazy_active()


def _axis_mask(size: int, g: int, b: int) -> jnp.ndarray:
    """(g, b) bool mask: True where global index g*b_idx + offset < size."""
    gi = jax.lax.broadcasted_iota(jnp.int32, (g, b), 0)
    bi = jax.lax.broadcasted_iota(jnp.int32, (g, b), 1)
    return (gi * b + bi) < size


def _valid_mask(grid: BlockGrid, stacked_grid: Tuple[int, int]) -> jnp.ndarray:
    """Boolean mask over the stacked tensor marking logically-valid elements.

    ``stacked_grid`` may exceed ``grid.grid`` when the grid was padded to a
    mesh multiple; the extra all-pad blocks mask out naturally because their
    global indices exceed the logical shape.  Built from two small per-axis
    masks broadcast together (never four full-size iotas — the broadcast
    keeps the eager cost at ~one pass over the tensor).
    """
    n, m = grid.shape
    bn, bm = grid.block_shape
    gn, gm = stacked_grid
    rows = _axis_mask(n, gn, bn)                 # (gn, bn)
    cols = _axis_mask(m, gm, bm)                 # (gm, bm)
    return rows[:, None, :, None] & cols[None, :, None, :]


# ---------------------------------------------------------------------------
# Pad-state tracking.
#
# The pad region of a stacked tensor is data the logical array does not own;
# instead of forcing it to zero after EVERY op (one full select pass per op,
# the seed behaviour), each DsArray carries a static claim about it.  The
# claim is aux data on the pytree, so it is known at trace time and the
# remask simply does not appear in the jaxpr when it is not needed.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PadState:
    """Static claim about the pad region: "zero" | "fill" (constant
    ``fill``) | "dirty" (unknown).  Hashable aux data, so differing states
    trace/compile separately — which is the point: the mask pass exists only
    in the traces that need it."""

    kind: str
    fill: Optional[Any] = None

    @property
    def value(self):
        """The pad constant (only meaningful for zero/fill)."""
        return 0 if self.kind == "zero" else self.fill


PAD_ZERO = PadState("zero")
PAD_DIRTY = PadState("dirty")


def pad_state_of(val) -> PadState:
    """PadState for a known constant pad value; nan demotes to DIRTY (it
    compares unequal to every reduction identity and would poison pytree
    equality)."""
    try:
        item = np.asarray(val).item()
    except Exception:
        return PAD_DIRTY
    if item != item:  # nan (works for complex too)
        return PAD_DIRTY
    if item == 0:
        return PAD_ZERO
    return PadState("fill", item)


def _probe_scalar(val, dtype):
    """A 0-d concrete array holding ``val`` in ``dtype`` for pad probing."""
    return jnp.asarray(np.asarray(val).item(), dtype=dtype)


def _probe_binary_pad(op, lhs_state: PadState, lhs_dtype, rhs,
                      reverse: bool = False) -> PadState:
    """Pad state of ``op(lhs, rhs)`` from the operands' pad constants.

    ``rhs`` is a PadState+dtype pair (DsArray operand) or a raw scalar.  The
    probe runs on concrete 0-d values, so it stays concrete even while
    tracing — unless an operand IS a tracer, which demotes to DIRTY.
    """
    if lhs_state.kind == "dirty":
        return PAD_DIRTY
    try:
        lv = _probe_scalar(lhs_state.value, lhs_dtype)
        if isinstance(rhs, tuple):
            rstate, rdtype = rhs
            if rstate.kind == "dirty":
                return PAD_DIRTY
            rv = _probe_scalar(rstate.value, rdtype)
        else:
            if isinstance(rhs, jax.core.Tracer):
                return PAD_DIRTY
            rv = rhs
        out = op(rv, lv) if reverse else op(lv, rv)
        if isinstance(out, jax.core.Tracer):
            return PAD_DIRTY
        return pad_state_of(out)
    except Exception:
        return PAD_DIRTY


def _probe_map_pad(fn, state: PadState, dtype) -> PadState:
    """Pad state of ``fn(blocks)`` for an elementwise ``fn``: probe it on a
    (1,1,1,1) constant holding the pad value.  Anything that fails, returns
    a tracer, or changes shape demotes to DIRTY (``map_blocks`` callers with
    non-elementwise fns should pass ``pad=PAD_DIRTY`` explicitly)."""
    if state.kind == "dirty":
        return PAD_DIRTY
    try:
        probe = jnp.full((1, 1, 1, 1), np.asarray(state.value).item(), dtype)
        out = fn(probe)
        if isinstance(out, jax.core.Tracer) or \
                getattr(out, "shape", None) != (1, 1, 1, 1):
            return PAD_DIRTY
        return pad_state_of(out)
    except Exception:
        return PAD_DIRTY


@jax.tree_util.register_pytree_node_class
class DsArray:
    """2-D blocked distributed array with a NumPy-like API (paper §4.2.3).

    Do not call the constructor with unpadded data; use :func:`from_array`,
    :func:`zeros`, :func:`random_array` etc.
    """

    __slots__ = ("blocks", "grid", "pad_state")

    def __init__(self, blocks: jnp.ndarray, grid: BlockGrid,
                 pad_state: PadState = PAD_ZERO):
        if blocks.ndim != 4:
            raise ValueError(f"stacked block tensor must be rank 4, got {blocks.shape}")
        bn, bm = grid.block_shape
        if blocks.shape[2:] != (bn, bm):
            raise ValueError(
                f"block dims {blocks.shape[2:]} != block_shape {grid.block_shape}"
            )
        gn, gm = grid.grid
        if blocks.shape[0] < gn or blocks.shape[1] < gm:
            raise ValueError(
                f"stacked grid {blocks.shape[:2]} smaller than logical grid {grid.grid}"
            )
        if isinstance(blocks, BCOO) and pad_state.kind != "zero":
            # sparse blocks have NO entries in the pad region — the pad is
            # exactly zero by construction, any other claim is a bug
            raise ValueError(
                f"bcoo-blocked ds-arrays are zero-padded by construction, "
                f"got pad_state={pad_state}")
        self.blocks = blocks
        self.grid = grid
        self.pad_state = pad_state
        if _debug_validate():
            self.check_invariants()

    # -- pytree protocol ------------------------------------------------------
    def tree_flatten(self):
        return (self.blocks,), (self.grid, self.pad_state)

    @classmethod
    def tree_unflatten(cls, aux, children):
        (blocks,) = children
        grid, pad_state = aux
        return cls(blocks, grid, pad_state)

    # -- basic properties -----------------------------------------------------
    @property
    def shape(self) -> Tuple[int, int]:
        return self.grid.shape

    @property
    def block_shape(self) -> Tuple[int, int]:
        return self.grid.block_shape

    @property
    def stacked_grid(self) -> Tuple[int, int]:
        return self.blocks.shape[:2]

    @property
    def dtype(self):
        return self.blocks.dtype

    @property
    def block_format(self) -> str:
        """Storage of the stacked blocks: ``"dense"`` | ``"bcoo"``.

        Derived from the blocks' pytree type, so it is static under tracing
        (a BCOO stays a BCOO-of-tracers) and can never disagree with the
        data it describes.
        """
        return "bcoo" if isinstance(self.blocks, BCOO) else "dense"

    @property
    def is_sparse(self) -> bool:
        return self.block_format == "bcoo"

    @property
    def ndim(self) -> int:
        return 2

    @property
    def T(self) -> "DsArray":
        return self.transpose()

    def __repr__(self) -> str:  # pragma: no cover - cosmetic
        return (
            f"DsArray(shape={self.shape}, block_shape={self.block_shape}, "
            f"grid={self.stacked_grid}, dtype={self.dtype})"
        )

    # -- masking --------------------------------------------------------------
    def _mask(self) -> jnp.ndarray:
        return _valid_mask(self.grid, self.stacked_grid)

    def _remask(self, fill: Number = 0) -> jnp.ndarray:
        """Blocks with the pad region forced to ``fill``."""
        if self.is_sparse:
            raise RuntimeError("sparse blocks are zero-padded by construction"
                               " — there is nothing to remask")
        fill_v = jnp.asarray(fill, dtype=self.blocks.dtype)
        return jnp.where(self._mask(), self.blocks, fill_v)

    def ensure_zero_pad(self) -> "DsArray":
        """Self if the pad is known zero, else a re-masked copy.

        The single enforcement point of the pad-is-zero invariant: consumers
        that read raw blocks (matmul, reductions with 0-identity, structural
        ops, kernels) call this, so op chains pay at most one mask pass at
        the consumer instead of one per op."""
        if self.pad_state.kind == "zero":
            return self
        return DsArray(self._remask(), self.grid, PAD_ZERO)

    def _with_blocks(self, blocks: jnp.ndarray, grid: Optional[BlockGrid] = None,
                     pad_state: PadState = PAD_ZERO) -> "DsArray":
        return DsArray(blocks, grid if grid is not None else self.grid, pad_state)

    # -- materialization ------------------------------------------------------
    def collect(self) -> jnp.ndarray:
        """Paper §4.2.3 ``collect``: merge the blocks into one local array."""
        if self.is_sparse:
            return self.todense().collect()
        gn, gm, bn, bm = self.blocks.shape
        n, m = self.shape
        global_form = self.blocks.transpose(0, 2, 1, 3).reshape(gn * bn, gm * bm)
        return global_form[:n, :m]

    def _global_padded(self) -> jnp.ndarray:
        """Global layout including pad (pad forced zero)."""
        me = self.ensure_zero_pad()
        if me.is_sparse:
            me = me.todense()
        gn, gm, bn, bm = me.blocks.shape
        return me.blocks.transpose(0, 2, 1, 3).reshape(gn * bn, gm * bm)

    # -- block-format conversions (paper: NumPy OR scipy.sparse blocks) ------
    def todense(self) -> "DsArray":
        """This array with dense stacked blocks (identity when dense)."""
        from repro.core import sparse as sparse_mod
        return sparse_mod.todense(self)

    def tosparse(self, nse: Optional[int] = None) -> "DsArray":
        """This array with BCOO blocks (identity when sparse).  See
        ``core.sparse`` for the representation and op policy."""
        from repro.core import sparse as sparse_mod
        return sparse_mod.tosparse(self, nse=nse)

    # -- debug validation ------------------------------------------------------
    def check_invariants(self) -> "DsArray":
        """Validate the static claims against the concrete data; raises on
        violation, returns self for chaining.  Checked: grid/shape/block
        geometry, the pad region actually matching ``pad_state``, and (for
        bcoo) indices in-bounds-or-zero-data plus the zero-pad construction
        invariant.  A no-op on traced/abstract blocks.  Runs at every
        construction under ``REPRO_DEBUG=1``; the differential harness calls
        it after every op.
        """
        gn, gm = self.grid.grid
        bn, bm = self.grid.block_shape
        n, m = self.shape
        if n > gn * bn or m > gm * bm:
            raise AssertionError(f"grid {self.grid} does not cover shape")
        leaf = self.blocks.data if self.is_sparse else self.blocks
        if isinstance(leaf, jax.core.Tracer) or not isinstance(leaf, jax.Array):
            return self          # abstract/traced: nothing concrete to check
        if self.is_sparse:
            from repro.core import sparse as sparse_mod
            sparse_mod.check_bcoo_invariants(self)
            return self
        sgn, sgm = self.stacked_grid
        g = np.asarray(self.blocks).transpose(0, 2, 1, 3)
        g = g.reshape(sgn * bn, sgm * bm)
        pad_mask = (np.arange(sgn * bn)[:, None] >= n) | \
                   (np.arange(sgm * bm)[None, :] >= m)
        if self.pad_state.kind == "zero":
            bad = pad_mask & (g != 0)
        elif self.pad_state.kind == "fill":
            want = np.asarray(self.pad_state.fill, self.blocks.dtype)
            bad = pad_mask & (g != want)
        else:
            return self
        if bad.any():
            r, c = (int(v) for v in np.argwhere(bad)[0])
            gi, bi = divmod(r, bn)
            gj, bj = divmod(c, bm)
            raise AssertionError(
                f"pad_state={self.pad_state} but pad region differs: "
                f"{int(bad.sum())} violation(s), first in block "
                f"({gi}, {gj}) at offset ({bi}, {bj}) "
                f"(global ({r}, {c}), value {g[r, c]!r})")
        return self

    def finite_report(self):
        """Block-granular NaN/Inf diagnosis (``resilience.guards``): which
        blocks hold non-finite values, with counts and the first offending
        in-block offset (dense) or entry slot (bcoo).  Pad-state aware — a
        DIRTY or FILL pad region never false-positives.  Returns a
        ``FiniteReport`` (``.ok`` / ``.describe()``); blocks are named
        ``block (gi, gj)`` in the ``check_invariants`` style."""
        from repro.resilience import guards
        return guards.finite_report(self)

    # -- laziness -------------------------------------------------------------
    def lazy(self) -> "LazyDsArray":
        """This array lifted into the lazy expression layer: subsequent ops
        record an ``Expr`` plan that ``compute()`` optimizes (elementwise
        fusion, transpose-folded GEMM, plan-wide pad propagation) before
        running.  See ``core.expr`` / ``core.plan``."""
        from repro.core import expr
        return expr.lift_lazy(self)

    # -- elementwise ----------------------------------------------------------
    def _binary(self, other, op: Callable, reverse: bool = False) -> "DsArray":
        if _lazy_mode():
            from repro.core import expr
            return expr.lift_lazy(self)._binary(other, op, reverse)
        if self.is_sparse or (isinstance(other, DsArray) and other.is_sparse):
            from repro.core import sparse as sparse_mod
            return sparse_mod.binary(self, other, op, reverse)
        me = self
        if isinstance(other, DsArray):
            if other.shape != self.shape or other.block_shape != self.block_shape:
                if other.shape != self.shape:
                    raise ValueError(
                        f"shape mismatch {self.shape} vs {other.shape}")
                other = other.rechunk(self.block_shape)
            if other.stacked_grid != self.stacked_grid:
                # pad whichever operand has the smaller stacked grid (either
                # may have been grown, e.g. by distribute()'s mesh padding)
                common = (max(me.stacked_grid[0], other.stacked_grid[0]),
                          max(me.stacked_grid[1], other.stacked_grid[1]))
                me = me._pad_grid_to(common)
                other = other._pad_grid_to(common)
            rhs = other.blocks
            probe_rhs = (other.pad_state, other.blocks.dtype)
        elif isinstance(other, (int, float, jnp.ndarray, np.ndarray)) and jnp.ndim(other) == 0:
            rhs = other
            probe_rhs = other
        else:
            return NotImplemented
        out = op(rhs, me.blocks) if reverse else op(me.blocks, rhs)
        # both pad regions hold known constants at the SAME positions, so the
        # result pad is the op of the constants — no remask, just bookkeeping
        pad = _probe_binary_pad(op, me.pad_state, me.blocks.dtype, probe_rhs,
                                reverse)
        return DsArray(out, BlockGrid(me.shape, me.block_shape), pad)

    def __add__(self, o):
        return self._binary(o, jnp.add)

    __radd__ = __add__

    def __sub__(self, o):
        return self._binary(o, jnp.subtract)

    def __rsub__(self, o):
        return self._binary(o, jnp.subtract, reverse=True)

    def __mul__(self, o):
        return self._binary(o, jnp.multiply)

    __rmul__ = __mul__

    def __truediv__(self, o):
        return self._binary(o, jnp.divide)

    def __rtruediv__(self, o):
        return self._binary(o, jnp.divide, reverse=True)

    def __pow__(self, o):
        return self._binary(o, jnp.power)

    def __rpow__(self, o):
        return self._binary(o, jnp.power, reverse=True)

    def __neg__(self):
        return self.map_blocks(jnp.negative)

    def map_blocks(self, fn: Callable[[jnp.ndarray], jnp.ndarray],
                   pad: Optional[PadState] = None) -> "DsArray":
        """Apply an elementwise function to every block (one 'task' per block).

        The pad state is propagated by probing ``fn`` on the pad constant —
        zero-preserving fns (neg, sqrt, abs, ...) keep ZERO with no mask pass.
        Non-elementwise fns must pass ``pad=`` explicitly (``PAD_DIRTY`` when
        unknown); the probe cannot see position dependence."""
        if _lazy_mode():
            from repro.core import expr
            return expr.lift_lazy(self).map_blocks(fn, pad=pad)
        if self.is_sparse:
            from repro.core import sparse as sparse_mod
            return sparse_mod.map_blocks_sparse(self, fn, pad)
        out = fn(self.blocks)
        if out.shape != self.blocks.shape:
            raise ValueError("map_blocks must preserve block shapes")
        if pad is None:
            pad = _probe_map_pad(fn, self.pad_state, self.blocks.dtype)
        return DsArray(out, self.grid, pad)

    def sqrt(self) -> "DsArray":
        return self.map_blocks(jnp.sqrt)

    def exp(self) -> "DsArray":
        return self.map_blocks(jnp.exp)

    def abs(self) -> "DsArray":
        return self.map_blocks(jnp.abs)

    def astype(self, dtype) -> "DsArray":
        if _lazy_mode():
            from repro.core import expr
            return expr.lift_lazy(self).astype(dtype)
        if self.is_sparse:
            from repro.core import sparse as sparse_mod
            return sparse_mod.astype_sparse(self, dtype)
        pad = self.pad_state
        if pad.kind == "fill":
            # the physical pad is cast too; re-derive the constant the same
            # way — in NumPy, NOT jnp: under the lazy layer this method runs
            # inside eval_shape, where a jnp op on the constant would be
            # staged into a tracer and wrongly demote the claim to DIRTY
            try:
                pad = pad_state_of(
                    np.asarray(pad.fill, dtype=np.dtype(self.dtype))
                    .astype(np.dtype(dtype)))
            except Exception:
                pad = PAD_DIRTY
        return DsArray(self.blocks.astype(dtype), self.grid, pad)

    # -- structural ops ---------------------------------------------------------
    def transpose(self) -> "DsArray":
        """Paper §5.2: local per-block transpose + block-grid permutation.

        One fused op over the stacked tensor; on a sharded array XLA lowers the
        grid-dim swap to a single all-to-all (vs. the Dataset baseline's
        N^2 + N scatter/gather — see core/dataset_baseline.py).
        """
        if _lazy_mode():
            from repro.core import expr
            return expr.lift_lazy(self).transpose()
        if self.is_sparse:
            from repro.core import sparse as sparse_mod
            return sparse_mod.transpose_sparse(self)
        out = jnp.swapaxes(jnp.swapaxes(self.blocks, 0, 1), 2, 3)
        return DsArray(out, self.grid.transpose(), self.pad_state)

    def _pad_grid_to(self, stacked_grid: Tuple[int, int]) -> "DsArray":
        if self.is_sparse:
            from repro.core import sparse as sparse_mod
            return sparse_mod.pad_grid_sparse(self, stacked_grid)
        gn, gm = self.stacked_grid
        tn, tm = stacked_grid
        if (tn, tm) == (gn, gm):
            return self
        if tn < gn or tm < gm:
            raise ValueError("can only grow the stacked grid")
        # grow with the array's own pad constant so the pad state survives
        cv = 0 if self.pad_state.kind != "fill" else self.pad_state.fill
        out = jnp.pad(self.blocks, ((0, tn - gn), (0, tm - gm), (0, 0), (0, 0)),
                      constant_values=np.asarray(cv, self.blocks.dtype))
        return DsArray(out, self.grid, self.pad_state)

    def rechunk(self, block_shape: Tuple[int, int]) -> "DsArray":
        """Re-block to a new block size (the paper's 'arbitrary block size'
        flexibility; Datasets cannot do this at all).

        Block-native: evenly-dividing shapes regroup the stacked tensor in a
        single reshape; the general case is a windowed per-block gather.  No
        global ``(n, m)`` intermediate is formed either way (see
        ``core.structural.rechunk``).
        """
        if _lazy_mode():
            from repro.core import expr
            return expr.lift_lazy(self).rechunk(block_shape)
        from repro.core import structural
        return structural.rechunk(self, tuple(block_shape))

    def __matmul__(self, other: "DsArray") -> "DsArray":
        """Blocked matmul: C[i,j] = sum_k A[i,k] @ B[k,j].

        The local contraction over (grid-k, block-k) — exactly the paper's
        per-block task graph — lowers through the fused Pallas MXU kernel
        (``kernels.matmul.stacked_matmul``: one launch, fp32 VMEM accumulator,
        one HBM write per C tile) on a TPU for aligned f32/bf16 blocks on one
        device, and through a stacked-block einsum otherwise.  Operands
        placed on a mesh by ``distribute`` always take the einsum, whose grid
        contraction SPMD partitioning schedules: a Mosaic kernel cannot be
        partitioned automatically.  ``core/shmap_ops.py`` holds the
        explicitly scheduled SUMMA and Cannon, which run the kernel on each
        device's local blocks inside ``shard_map``.  Zero pad on both
        operands makes the padded contraction exact; the result pad is
        therefore exactly zero.
        """
        from repro.kernels.matmul.ops import local_matmul
        if _lazy_mode():
            from repro.core import expr
            if isinstance(other, (DsArray, expr.LazyDsArray)):
                return expr.lift_lazy(self) @ other
        if not isinstance(other, DsArray):
            return NotImplemented
        if other.is_sparse:
            # sparse is supported on the LEFT (sp @ dense through
            # bcoo_dot_general); a sparse right operand densifies
            other = other.todense()
        if self.shape[1] != other.shape[0]:
            raise ValueError(f"matmul shape mismatch {self.shape} @ {other.shape}")
        if self.block_shape[1] != other.block_shape[0]:
            other = other.rechunk((self.block_shape[1], other.block_shape[1]))
        if self.stacked_grid[1] != other.stacked_grid[0]:
            k = max(self.stacked_grid[1], other.stacked_grid[0])
            a = self._pad_grid_to((self.stacked_grid[0], k))
            b = other._pad_grid_to((k, other.stacked_grid[1]))
        else:
            a, b = self, other
        a, b = a.ensure_zero_pad(), b.ensure_zero_pad()
        out = local_matmul(a.blocks, b.blocks,
                           out_dtype=jnp.promote_types(a.dtype, b.dtype))
        grid = BlockGrid((self.shape[0], other.shape[1]),
                         (self.block_shape[0], other.block_shape[1]))
        return DsArray(out, grid, PAD_ZERO)

    # -- reductions ---------------------------------------------------------
    def _reduce(self, op: str, axis: Optional[int]) -> Union["DsArray", jnp.ndarray]:
        if _lazy_mode():
            from repro.core import expr
            return expr.lift_lazy(self)._reduce(op, axis)
        if self.is_sparse:
            from repro.core import sparse as sparse_mod
            return sparse_mod.reduce_sparse(self, op, axis)
        fill = {"sum": 0, "max": -jnp.inf, "min": jnp.inf}[op]
        if jnp.issubdtype(self.dtype, jnp.integer):
            fill = {"sum": 0,
                    "max": int(jnp.iinfo(self.dtype).min),
                    "min": int(jnp.iinfo(self.dtype).max)}[op]
        # refill only when the pad is not already the reduction identity —
        # ZERO input + sum (the common case) emits no mask pass at all
        ps = self.pad_state
        if (ps.kind == "zero" and fill == 0) or \
                (ps.kind == "fill" and ps.fill == fill):
            x = self.blocks
        else:
            x = self._remask(fill)
        red = {"sum": jnp.sum, "max": jnp.max, "min": jnp.min}[op]
        if axis is None:
            return red(x)
        if axis == 0:
            # Paper Fig. 5: one task per *column* of blocks, then a psum over
            # the `data` mesh axis — possible only because ds-arrays block
            # both axes (Datasets must gather everything; Fig. 3).
            out = red(x, axis=(0, 2))  # (gm, bm)
            gm, bm = out.shape
            blocks = out.reshape(1, gm, 1, bm)
            grid = BlockGrid((1, self.shape[1]), (1, bm))
        elif axis == 1:
            out = red(x, axis=(1, 3))  # (gn, bn)
            gn, bn = out.shape
            blocks = out.reshape(gn, 1, bn, 1)
            grid = BlockGrid((self.shape[0], 1), (bn, 1))
        else:
            raise ValueError(f"axis must be 0, 1 or None, got {axis}")
        # pad lines of the result reduce over identity-only values, so the
        # result pad IS the identity: bookkeep it instead of re-masking
        return DsArray(blocks, grid, pad_state_of(fill))

    def sum(self, axis: Optional[int] = None):
        return self._reduce("sum", axis)

    def max(self, axis: Optional[int] = None):
        return self._reduce("max", axis)

    def min(self, axis: Optional[int] = None):
        return self._reduce("min", axis)

    def mean(self, axis: Optional[int] = None):
        n, m = self.shape
        denom = {None: n * m, 0: n, 1: m}[axis]
        me = self
        if not jnp.issubdtype(self.dtype, jnp.floating):
            # promote BEFORE summing: an int32/int8 accumulator overflows long
            # before the divide would have promoted the result
            me = self.astype(jnp.promote_types(self.dtype, jnp.float32))
        s = me.sum(axis)
        if isinstance(s, DsArray):
            return s / float(denom)
        return s / denom

    def norm(self, axis: Optional[int] = None):
        """Euclidean norm along an axis (paper's ``w.norm(axis=1)`` example).

        Per-axis norms are expressed through :func:`apply_along_axis` (the
        paper's 1-D-slice API): one vmapped per-slice call in block layout,
        no ``collect()``.  The all-elements norm stays a fused square+sum.
        """
        if _lazy_mode():
            from repro.core import expr
            return expr.lift_lazy(self).norm(axis)
        if axis is None:
            sq = self._binary(self, jnp.multiply)  # x*x keeps pad zero
            return jnp.sqrt(sq.sum())
        return apply_along_axis(lambda v: jnp.sqrt(jnp.sum(v * v)), axis, self)

    # -- indexing ------------------------------------------------------------
    def __getitem__(self, key) -> "DsArray":
        """NumPy-style indexing returning a new ds-array (paper §4.2.3).

        Supports ``A[r]``, ``A[r0:r1]``, ``A[r0:r1, c0:c1]``, integer rows/
        cols, and integer-array row selection (the paper's 'filtering').

        Block-aligned slices are a pure grid slice + edge remask; unaligned
        slices, strides and index arrays lower to one per-block gather per
        axis (``core.structural.getitem``) — the global array is never
        materialized and sharding survives.
        """
        if _lazy_mode():
            from repro.core import expr
            return expr.lift_lazy(self)[key]
        from repro.core import structural
        return structural.getitem(self, key)

    # -- distribution ---------------------------------------------------------
    def distribute(self, mesh: Mesh, axes: Tuple[Optional[str], Optional[str]] = ("data", "model")) -> "DsArray":
        """Place blocks onto a device mesh: grid dims sharded over named axes.

        Pads the grid to mesh-axis multiples first (all-pad blocks mask out),
        the SPMD analogue of PyCOMPSs assigning whole blocks to workers.
        """
        if self.is_sparse:
            from repro.core import sparse as sparse_mod
            return sparse_mod.distribute_sparse(self, mesh, axes)
        dn = mesh.shape[axes[0]] if axes[0] else 1
        dm = mesh.shape[axes[1]] if axes[1] else 1
        gn, gm = self.stacked_grid
        padded = self._pad_grid_to((round_up(gn, dn), round_up(gm, dm)))
        sharding = NamedSharding(mesh, P(axes[0], axes[1], None, None))
        blocks = jax.device_put(padded.blocks, sharding)
        return DsArray(blocks, self.grid, padded.pad_state)

    def sharding_spec(self, axes=("data", "model")) -> P:
        return P(axes[0], axes[1], None, None)


# ---------------------------------------------------------------------------
# Derived block-native routines
# ---------------------------------------------------------------------------


def matmul_ta(a: DsArray, b: DsArray) -> DsArray:
    """``Aᵀ @ B`` with the transpose folded into the GEMM.

    The lazy optimizer rewrites ``MatMul(Transpose(a), b)`` to this: ``a``
    stays in its untransposed stacked layout and ``local_matmul`` absorbs
    the transpose into the contraction (block-index maps on the Pallas path,
    a relabeled einsum otherwise), so the transposed stacked tensor — a full
    HBM relayout under eager ``a.T @ b`` — is never materialized.  Also
    callable eagerly (the PCA Gram-vector products use it every iteration).
    """
    from repro.core import structural
    from repro.kernels.matmul.ops import local_matmul
    if not isinstance(b, DsArray):
        raise TypeError("matmul_ta wants DsArray operands")
    if b.is_sparse:
        b = b.todense()    # sparse is supported on the (transposed) left
    if a.shape[0] != b.shape[0]:
        raise ValueError(f"matmul_ta shape mismatch {a.shape}ᵀ @ {b.shape}")
    if a.block_shape[0] != b.block_shape[0]:
        b = structural.rechunk(b, (a.block_shape[0], b.block_shape[1]))
    if a.stacked_grid[0] != b.stacked_grid[0]:
        k = max(a.stacked_grid[0], b.stacked_grid[0])
        a = a._pad_grid_to((k, a.stacked_grid[1]))
        b = b._pad_grid_to((k, b.stacked_grid[1]))
    a, b = a.ensure_zero_pad(), b.ensure_zero_pad()
    out = local_matmul(a.blocks, b.blocks,
                       out_dtype=jnp.promote_types(a.dtype, b.dtype),
                       transpose_a=True)
    grid = BlockGrid((a.shape[1], b.shape[1]),
                     (a.block_shape[1], b.block_shape[1]))
    return DsArray(out, grid, PAD_ZERO)


def apply_along_axis(fn: Callable[[jnp.ndarray], jnp.ndarray], axis: int,
                     a: DsArray) -> DsArray:
    """Paper §4.2.3 ``apply_along_axis``: ``fn`` over every 1-D slice.

    ``axis=1`` applies ``fn`` to each row, ``axis=0`` to each column; ``fn``
    must map a 1-D vector to a scalar or a fixed-length 1-D vector.  Block-
    native: the stacked tensor is regrouped so each slice is contiguous in
    block layout (rank-3, grid dim leading — never the global ``(n, m)``
    rank-2 form) and ``fn`` runs as ONE nested-vmap call over all slices; no
    ``collect()``, and sharding is re-placed on the result.
    """
    from repro.core import structural
    if axis not in (0, 1):
        raise ValueError(f"axis must be 0 or 1, got {axis}")
    a2 = a.ensure_zero_pad()
    if a2.is_sparse:
        a2 = a2.todense()      # per-slice fns need the dense block layout
    gn, gm, bn, bm = a2.blocks.shape
    n, m = a2.shape
    if axis == 1:
        rows = a2.blocks.transpose(0, 2, 1, 3).reshape(gn, bn, gm * bm)[..., :m]
        out = jax.vmap(jax.vmap(fn))(rows)              # (gn, bn[, k])
        if out.ndim not in (2, 3):
            raise ValueError("fn must return a scalar or 1-D vector")
        if out.ndim == 2:
            out = out[..., None]
        k = out.shape[-1]
        blocks = out[:, None]                           # (gn, 1, bn, k)
        if gn * bn > n:     # fn of an all-pad row is garbage: mask it
            blocks = structural._mask_axes(blocks, n=n)
        res = DsArray(blocks, BlockGrid((n, k), (bn, k)), PAD_ZERO)
    else:
        cols = a2.blocks.transpose(1, 3, 0, 2).reshape(gm, bm, gn * bn)[..., :n]
        out = jax.vmap(jax.vmap(fn))(cols)              # (gm, bm[, k])
        if out.ndim not in (2, 3):
            raise ValueError("fn must return a scalar or 1-D vector")
        if out.ndim == 2:
            out = out[..., None]
        k = out.shape[-1]
        blocks = out.transpose(0, 2, 1)[None]           # (1, gm, k, bm)
        if gm * bm > m:
            blocks = structural._mask_axes(blocks, m=m)
        res = DsArray(blocks, BlockGrid((k, m), (k, bm)), PAD_ZERO)
    return structural.preserve_sharding(res, a.blocks)


# ---------------------------------------------------------------------------
# Creation routines (paper §4.2.2: "one task per block", here one fused op).
# ---------------------------------------------------------------------------


def from_array(arr, block_shape: Tuple[int, int]) -> DsArray:
    """Block a local 2-D array into a ds-array."""
    arr = jnp.asarray(arr)
    if arr.ndim == 1:
        arr = arr.reshape(-1, 1)
    if arr.ndim != 2:
        raise ValueError(f"ds-arrays are 2-D, got shape {arr.shape}")
    grid = BlockGrid(tuple(arr.shape), tuple(block_shape))
    (gn, gm), (bn, bm) = grid.grid, grid.block_shape
    pn, pm = grid.padded_shape
    padded = jnp.pad(arr, ((0, pn - arr.shape[0]), (0, pm - arr.shape[1])))
    blocks = padded.reshape(gn, bn, gm, bm).transpose(0, 2, 1, 3)
    return DsArray(blocks, grid)


def zeros(shape: Tuple[int, int], block_shape: Tuple[int, int], dtype=jnp.float32) -> DsArray:
    grid = BlockGrid(tuple(shape), tuple(block_shape))
    return DsArray(jnp.zeros(grid.stacked_shape, dtype), grid)


def full(shape, block_shape, fill_value, dtype=jnp.float32) -> DsArray:
    # built directly (not zeros+add) so creation stays eager under repro.lazy()
    grid = BlockGrid(tuple(shape), tuple(block_shape))
    blocks = jnp.full(grid.stacked_shape, fill_value, dtype)
    return DsArray(blocks, grid, pad_state_of(fill_value))


def eye(n: int, block_shape: Tuple[int, int], dtype=jnp.float32) -> DsArray:
    grid = BlockGrid((n, n), tuple(block_shape))
    gn, gm, bn, bm = grid.stacked_shape
    shape = (gn, gm, bn, bm)
    gi = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    gj = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    bi = jax.lax.broadcasted_iota(jnp.int32, shape, 2)
    bj = jax.lax.broadcasted_iota(jnp.int32, shape, 3)
    row = gi * bn + bi
    col = gj * bm + bj
    blocks = ((row == col) & (row < n)).astype(dtype)
    return DsArray(blocks, grid)


def random_array(key, shape: Tuple[int, int], block_shape: Tuple[int, int],
                 dtype=jnp.float32, distribution: str = "uniform") -> DsArray:
    """Paper §4.2.2 ``random_array``: one independent RNG stream per block
    ("one task per block"), so the result is identical however the grid is
    later re-distributed."""
    grid = BlockGrid(tuple(shape), tuple(block_shape))
    gn, gm = grid.grid
    bn, bm = grid.block_shape
    keys = jax.random.split(key, gn * gm)
    keys = keys.reshape((gn, gm) + keys.shape[1:])  # raw uint32 keys keep a trailing dim
    sampler = {"uniform": jax.random.uniform, "normal": jax.random.normal}[distribution]
    blocks = jax.vmap(jax.vmap(lambda k: sampler(k, (bn, bm), dtype)))(keys)
    res = DsArray(blocks, grid)
    return res._with_blocks(res._remask())


def identity_like(a: DsArray) -> DsArray:
    if a.shape[0] != a.shape[1]:
        raise ValueError("identity_like needs a square array")
    return eye(a.shape[0], a.block_shape, a.dtype)


def concat_rows(arrays: Sequence[DsArray]) -> DsArray:
    """Vertical concatenation (the paper Dataset ``append`` generalized).

    Block-native: when part row counts align to the block size the grids are
    stacked directly (O(1) data movement); otherwise parts are re-tiled with
    per-block gathers.  See ``core.structural.concat_rows``.
    """
    arrays = list(arrays)
    expr_m = sys.modules.get("repro.core.expr")
    if _lazy_mode() or (expr_m is not None and
                        any(isinstance(a, expr_m.LazyDsArray)
                            for a in arrays)):
        from repro.core import expr
        return expr.record_concat(arrays)
    from repro.core import structural
    return structural.concat_rows(arrays)
