"""repro.core — the ds-array distributed data structure (the paper's contribution).

Public API mirrors the paper's NumPy-like interface (§4.2.3): creation
routines, indexing, elementwise algebra, reductions, transpose, matmul,
shuffles, plus explicit-collective variants for performance work.
"""

from repro.core.blocking import BlockGrid, ceil_div, round_up
from repro.core.dsarray import (
    DsArray,
    PAD_DIRTY,
    PAD_ZERO,
    PadState,
    apply_along_axis,
    concat_rows,
    eye,
    from_array,
    full,
    identity_like,
    matmul_ta,
    pad_state_of,
    random_array,
    zeros,
)
from repro.core.shuffle import exact_shuffle, pseudo_shuffle
from repro.core import costmodel, structural
from repro.core import sparse
from repro.core.sparse import from_scipy, random_sparse
from repro.core import expr, plan
from repro.core.expr import LazyDsArray, lazy
from repro.core.plan import compute, compute_multi
from repro.core.structural import gram, take_cols, take_rows
from repro.core.dataset_baseline import Dataset, Subset, TaskCounter

__all__ = [
    "BlockGrid", "DsArray", "Dataset", "Subset", "TaskCounter",
    "PadState", "PAD_ZERO", "PAD_DIRTY", "pad_state_of",
    "from_array", "zeros", "full", "eye", "identity_like", "random_array",
    "concat_rows", "pseudo_shuffle", "exact_shuffle", "costmodel",
    "structural", "gram", "take_rows", "take_cols",
    "apply_along_axis", "matmul_ta",
    "sparse", "from_scipy", "random_sparse",
    "expr", "plan", "LazyDsArray", "lazy", "compute", "compute_multi",
    "ceil_div", "round_up",
]
