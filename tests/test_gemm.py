"""Fused stacked-block GEMM: kernel-vs-einsum oracle + lowering assertions.

Three families:

* property sweep: ``stacked_matmul`` (interpret mode) must match the stacked
  ``jnp.einsum`` reference across ragged grid/block shapes and dtypes,
  including the sub-tiling path;
* dispatcher policy: ``local_matmul``/``DsArray.__matmul__`` lower through
  the Pallas kernel when the backend is forced (``REPRO_GEMM=interpret``
  stands in for TPU on this CPU CI) and through einsum otherwise —
  asserted on the jaxpr;
* end-to-end: ds-array ``@`` through the kernel matches NumPy on ragged
  logical shapes (pad blocks contract exactly).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import DsArray, from_array
from repro.kernels.matmul.kernel import stacked_matmul
from repro.kernels.matmul.ops import gemm_backend, local_matmul

settings.register_profile("gemm", max_examples=10, deadline=None)
settings.load_profile("gemm")

RNG = np.random.default_rng(3)


def _einsum_ref(a, b):
    return np.einsum("ikab,kjbc->ijac", np.asarray(a, np.float64),
                     np.asarray(b, np.float64))


@pytest.mark.slow
@given(st.integers(1, 3), st.integers(1, 3), st.integers(1, 3),
       st.integers(1, 9), st.integers(1, 9), st.integers(1, 9),
       st.sampled_from([np.float32, np.float16]))
def test_stacked_matmul_sweep(gi, gk, gj, bn, bk, bm, dtype):
    a = RNG.normal(size=(gi, gk, bn, bk)).astype(dtype)
    b = RNG.normal(size=(gk, gj, bk, bm)).astype(dtype)
    out = stacked_matmul(jnp.asarray(a), jnp.asarray(b), interpret=True)
    assert out.shape == (gi, gj, bn, bm)
    tol = 1e-4 * bk if dtype == np.float32 else 3e-2 * bk
    np.testing.assert_allclose(np.asarray(out, np.float64),
                               _einsum_ref(a, b), atol=tol, rtol=tol)


@pytest.mark.parametrize("tiles", [(8, 128, 128), (16, 128, 256),
                                   (24, 256, 128)])
def test_stacked_matmul_subtiling(tiles):
    """block dims > tile targets split into Pallas grid steps by their
    largest aligned divisor (48 rows -> 8/16/24, 256 lanes -> 128)."""
    tm, tn, tk = tiles
    a = RNG.normal(size=(2, 3, 48, 256)).astype(np.float32)
    b = RNG.normal(size=(3, 2, 256, 256)).astype(np.float32)
    out = stacked_matmul(jnp.asarray(a), jnp.asarray(b), block_m=tm,
                         block_n=tn, block_k=tk, interpret=True)
    np.testing.assert_allclose(np.asarray(out, np.float64),
                               _einsum_ref(a, b), atol=1e-3, rtol=1e-3)


@pytest.mark.parametrize("dim,unit,tile", [
    (512, 8, 512), (300, 8, 300),         # at most the target: whole
    (8192, 8, 512), (10000, 8, 400), (12000, 8, 480), (1000, 8, 200),
    (1152, 128, 384), (640, 128, 128),    # lane dims: multiples of 128
    (1000, 128, 1000),                    # no aligned divisor: whole
])
def test_pick_tile_fits_vmem(dim, unit, tile):
    from repro.kernels.matmul.kernel import _pick_tile
    assert _pick_tile(dim, 512, unit) == tile


def test_local_matmul_backends_agree():
    a = RNG.normal(size=(2, 2, 5, 7)).astype(np.float32)
    b = RNG.normal(size=(2, 3, 7, 4)).astype(np.float32)
    e = local_matmul(jnp.asarray(a), jnp.asarray(b), backend="einsum")
    p = local_matmul(jnp.asarray(a), jnp.asarray(b), backend="interpret")
    np.testing.assert_allclose(np.asarray(e), np.asarray(p), atol=1e-4)


def test_local_matmul_transpose_a_folded():
    """Aᵀ@B with ``a`` in UNtransposed layout (gk, gi, bk, bn): both
    backends must match the explicitly-transposed einsum reference."""
    a = RNG.normal(size=(3, 2, 8, 8)).astype(np.float32)   # A: (24, 16)
    b = RNG.normal(size=(3, 4, 8, 8)).astype(np.float32)   # B: (24, 32)
    at = np.transpose(a, (1, 0, 3, 2))                     # stacked Aᵀ
    want = _einsum_ref(at, b)
    for backend in ("einsum", "interpret"):
        out = local_matmul(jnp.asarray(a), jnp.asarray(b), backend=backend,
                           transpose_a=True)
        assert out.shape == (2, 4, 8, 8)
        np.testing.assert_allclose(np.asarray(out, np.float64), want,
                                   atol=1e-3, rtol=1e-3)


def test_matmul_ta_matches_dense():
    """The eager ``matmul_ta`` helper on ragged shapes + mixed blocks."""
    from repro.core import matmul_ta
    x = RNG.normal(size=(37, 21)).astype(np.float32)
    y = RNG.normal(size=(37, 18)).astype(np.float32)
    a = from_array(x, (8, 8))
    b = from_array(y, (5, 6))          # mismatched row blocks -> rechunk
    c = matmul_ta(a, b)
    assert c.shape == (21, 18)
    np.testing.assert_allclose(np.asarray(c.collect()), x.T @ y,
                               atol=2e-3, rtol=1e-3)
    assert c.pad_state.kind == "zero"


def test_gemm_backend_never_pallas_for_f16(monkeypatch):
    """Mosaic refuses the f16 kernel ("Invalid vector type for load"), so
    even on a TPU with aligned blocks f16 takes einsum; f32/bf16 do not."""
    monkeypatch.delenv("REPRO_GEMM", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert gemm_backend(512, 512, 512, jnp.dtype(jnp.float16)) == "einsum"
    assert gemm_backend(512, 512, 512, jnp.dtype(jnp.float32)) == "pallas"
    assert gemm_backend(512, 512, 512, jnp.dtype(jnp.bfloat16)) == "pallas"


def test_gemm_backend_alignment_follows_orientation(monkeypatch):
    """Under transpose_a the contraction dim sits on A's sublanes, so a
    dislib-style 10,000-row block is aligned there and not otherwise."""
    monkeypatch.delenv("REPRO_GEMM", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    f32 = jnp.dtype(jnp.float32)
    assert gemm_backend(512, 10000, 512, f32, transpose_a=True) == "pallas"
    assert gemm_backend(512, 10000, 512, f32) == "einsum"
    assert gemm_backend(1000, 512, 512, f32, transpose_a=True) == "einsum"
    assert gemm_backend(1000, 512, 512, f32) == "pallas"


def test_gemm_backend_policy(monkeypatch):
    monkeypatch.delenv("REPRO_GEMM", raising=False)
    # off-TPU auto -> einsum, whatever the shapes
    assert gemm_backend(128, 128, 128, jnp.dtype(jnp.float32)) == "einsum"
    # forcing wins over auto
    monkeypatch.setenv("REPRO_GEMM", "interpret")
    assert gemm_backend(3, 5, 7, jnp.dtype(jnp.float32)) == "interpret"
    assert gemm_backend(3, 5, 7, jnp.dtype(jnp.float32),
                        backend="einsum") == "einsum"


# ---------------------------------------------------------------------------
# Lowering assertions: walk the jaxpr for the pallas_call primitive
# (canonical traversal lives in repro.analysis)
# ---------------------------------------------------------------------------

from repro.analysis import jaxpr_primitives as _primitives  # noqa: E402


def test_dsarray_matmul_lowers_through_pallas(monkeypatch):
    """The acceptance assertion: ds-array ``@`` hits the Pallas kernel when
    the MXU path is selected (here forced via interpret), and the einsum
    fallback contains no pallas_call."""
    x = RNG.normal(size=(24, 16)).astype(np.float32)
    a = from_array(x, (8, 8))

    def make_mm():
        # fresh function object per trace: jax caches traces by (fn, avals),
        # which would otherwise hide the env-var backend switch
        return lambda p, q: (DsArray(p, a.grid)
                             @ DsArray(q, a.grid).transpose()).blocks

    monkeypatch.setenv("REPRO_GEMM", "interpret")
    assert "pallas_call" in _primitives(
        jax.make_jaxpr(make_mm())(a.blocks, a.blocks))
    got = np.asarray((a @ from_array(x.T, (8, 8))).collect())
    np.testing.assert_allclose(got, x @ x.T, atol=1e-3)

    monkeypatch.setenv("REPRO_GEMM", "einsum")
    assert "pallas_call" not in _primitives(
        jax.make_jaxpr(make_mm())(a.blocks, a.blocks))


def test_dsarray_matmul_ragged_through_kernel(monkeypatch):
    """Ragged logical shapes: pad blocks contract exactly through the kernel."""
    monkeypatch.setenv("REPRO_GEMM", "interpret")
    x = RNG.normal(size=(37, 29)).astype(np.float32)
    y = RNG.normal(size=(29, 17)).astype(np.float32)
    c = from_array(x, (8, 8)) @ from_array(y, (8, 5))
    np.testing.assert_allclose(np.asarray(c.collect()), x @ y, atol=2e-3)
    # pad region of the product is exactly zero (claimed ZERO)
    assert c.pad_state.kind == "zero"
    gn, gm, bn, bm = c.blocks.shape
    g = np.asarray(c.blocks).transpose(0, 2, 1, 3).reshape(gn * bn, gm * bm)
    assert (g[37:] == 0).all() and (g[:, 17:] == 0).all()


def test_summa_local_gemm_fused(monkeypatch):
    """The shmap local GEMM goes through the same dispatcher (no per-grid-k
    Python loop): one pallas_call for the whole stacked contraction."""
    from repro.core.shmap_ops import _local_gemm
    monkeypatch.setenv("REPRO_GEMM", "interpret")
    a = jnp.asarray(RNG.normal(size=(2, 4, 8, 8)).astype(np.float32))
    b = jnp.asarray(RNG.normal(size=(4, 2, 8, 8)).astype(np.float32))
    jaxpr = jax.make_jaxpr(lambda p, q: _local_gemm(p, q))(a, b)
    prims = _primitives(jaxpr)
    assert "pallas_call" in prims
    np.testing.assert_allclose(np.asarray(_local_gemm(a, b)),
                               _einsum_ref(a, b), atol=1e-3, rtol=1e-3)
