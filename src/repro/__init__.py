"""repro — ds-array reproduction on JAX/TPU.

Top-level conveniences: ``repro.lazy()`` arms lazy recording for ds-array
ops (the paper's task-graph view; see ``repro.core.expr``), and the ds-array
type/constructors re-export from ``repro.core``.  Entry-point scripts call
``enable_compile_cache`` before their first compile.
"""

import os

from repro.core.expr import LazyDsArray, lazy
from repro.core.dsarray import DsArray, from_array
from repro import estimators

__all__ = ["lazy", "LazyDsArray", "DsArray", "from_array", "estimators",
           "enable_compile_cache"]


def enable_compile_cache(directory) -> str:
    """Keep XLA's compiled programs on disk across processes; returns the
    directory in use.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
    already reads its cache from there and no other directory is set.
    Otherwise the cache goes to ``directory``, which must be a fixed path:
    the path is part of every cache key, so a directory that moves never
    hits.  Every program is kept, however short its compile: a fit compiles
    hundreds of small ones, and together they dominate a cold start."""
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(directory))
    return str(directory)
