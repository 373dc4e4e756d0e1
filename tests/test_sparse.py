"""Sparse (BCOO) block subsystem: construction, op policy, no-densify
acceptance, lazy integration, and sparse algorithm inputs.

The ISSUE-4 acceptance assertions live here:

* ``sp @ dense`` and the sparse reductions NEVER materialize a dense block
  for the sparse operand — asserted on the jaxpr: no intermediate whose
  shape is the densified stacked form of the BCOO input;
* the lazy layer carries ``block_format`` (sparse Blockwise nodes are
  fusion boundaries but still CSE and cache);
* the paper's workloads (k-means, PCA/Gram, ALS) accept sparse inputs and
  match their dense results.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental.sparse import BCOO

import repro
from repro.core import (DsArray, costmodel, from_array, from_scipy, gram,
                        plan, random_sparse)
from repro.core import sparse as sparse_mod
from repro.core import io as io_mod
from repro.kernels.matmul.ops import local_matmul

pytestmark = pytest.mark.sparse

RNG = np.random.default_rng(41)


def mk_sparse(n=13, m=9, bn=4, bm=3, dtype=np.float32, density=0.3):
    x = (RNG.random((n, m)) < density) * RNG.normal(size=(n, m))
    if np.issubdtype(np.dtype(dtype), np.integer):
        x = np.round(x * 10)
    x = x.astype(dtype)
    a = from_array(x, (bn, bm))
    return x, a, a.tosparse()


# ---------------------------------------------------------------------------
# jaxpr helpers (shared shape-walk with test_lazy-style eqn traversal)
# ---------------------------------------------------------------------------


from repro.analysis import (
    assert_no_densify, walk_eqns)

_walk_eqns = walk_eqns          # canonical traversal lives in conftest


# ---------------------------------------------------------------------------
# Construction + conversions
# ---------------------------------------------------------------------------


def test_roundtrip_and_invariants():
    x, a, s = mk_sparse()
    assert s.block_format == "bcoo" and a.block_format == "dense"
    s.check_invariants()
    np.testing.assert_allclose(np.asarray(s.collect()), x)
    back = s.todense()
    assert back.block_format == "dense"
    back.check_invariants()
    np.testing.assert_allclose(np.asarray(back.collect()), x)
    # tosparse is idempotent, todense of dense is identity
    assert s.tosparse() is s and a.todense() is a


def test_random_sparse_density_and_pad():
    r = random_sparse(jax.random.PRNGKey(0), (21, 13), (6, 4), density=0.15)
    r.check_invariants()     # incl. zero entries in the pad region
    d = sparse_mod.density(r)
    assert 0.05 < d < 0.3, d
    # determinism
    r2 = random_sparse(jax.random.PRNGKey(0), (21, 13), (6, 4), density=0.15)
    np.testing.assert_allclose(np.asarray(r.collect()),
                               np.asarray(r2.collect()))


def test_from_scipy_never_densifies_layout():
    ssp = pytest.importorskip("scipy.sparse")
    m = ssp.random(23, 17, density=0.12, random_state=3, format="csr",
                   dtype=np.float32)
    s = from_scipy(m, (6, 5))
    s.check_invariants()
    assert s.block_format == "bcoo"
    np.testing.assert_allclose(np.asarray(s.collect()), m.toarray())
    # empty matrix edge case
    s0 = from_scipy(ssp.csr_matrix((5, 4), dtype=np.float32), (2, 2))
    s0.check_invariants()
    assert np.asarray(s0.collect()).sum() == 0


def test_io_density_auto_pick():
    dense_arr = RNG.normal(size=(12, 8)).astype(np.float32)
    sparse_arr = ((RNG.random((12, 8)) < 0.05) * dense_arr).astype(np.float32)
    assert io_mod.from_array_auto(dense_arr, (4, 4)).block_format == "dense"
    assert io_mod.from_array_auto(sparse_arr, (4, 4)).block_format == "bcoo"
    # threshold comes from the costmodel storage-crossover law
    thr = costmodel.sparse_storage_crossover_density(4)
    assert thr == pytest.approx(1 / 3)
    assert io_mod.from_array_auto(sparse_arr, (4, 4),
                                  density_threshold=0.0).block_format == "dense"
    assert io_mod.from_array_auto(dense_arr, (4, 4),
                                  block_format="bcoo").block_format == "bcoo"
    assert costmodel.tosparse_pays(0.01) and not costmodel.tosparse_pays(0.9)


def test_bcoo_requires_zero_pad_claim():
    from repro.core.dsarray import PadState
    _, _, s = mk_sparse()
    with pytest.raises(ValueError):
        DsArray(s.blocks, s.grid, PadState("fill", 3.0))


def test_check_invariants_catches_violations():
    x, a, s = mk_sparse(8, 6, 4, 3)
    # smuggle a nonzero value into an out-of-bounds slot
    sp = s.blocks
    bad_data = sp.data.at[0, 0, -1].set(7.0)
    bad_idx = sp.indices.at[0, 0, -1].set(jnp.asarray([4, 3]))
    bad = BCOO((bad_data, bad_idx), shape=sp.shape)
    with pytest.raises(AssertionError):
        DsArray(bad, s.grid).check_invariants()
    # dense: claim ZERO with a dirty pad (13x9 in (4,3) blocks has pad rows)
    from repro.core.dsarray import PAD_ZERO
    _, ragged, _ = mk_sparse(13, 9, 4, 3)
    blocks = ragged.blocks.at[-1, -1, -1, -1].set(9.0)    # global row 15: pad
    with pytest.raises(AssertionError):
        DsArray(blocks, ragged.grid, PAD_ZERO).check_invariants()


def test_repro_debug_validates_at_construction(monkeypatch):
    monkeypatch.setenv("REPRO_DEBUG", "1")
    x, a, s = mk_sparse()
    (s * 2.0).collect()          # constructions self-check without raising
    from repro.core.dsarray import PAD_ZERO
    blocks = a.blocks.at[-1, -1, -1, -1].set(9.0)
    with pytest.raises(AssertionError):
        DsArray(blocks, a.grid, PAD_ZERO)


# ---------------------------------------------------------------------------
# Op policy: sparse-native vs densifying (the docstring table, executable)
# ---------------------------------------------------------------------------


def test_elementwise_policy_and_values():
    x, a, s = mk_sparse()
    y = (RNG.normal(size=x.shape) + 2.5).astype(np.float32)
    b = from_array(y, a.block_shape)
    sb = b.tosparse()
    cases = [
        ("scale", lambda: s * 2.0, x * 2.0, "bcoo"),
        ("div_s", lambda: s / 2.0, x / 2.0, "bcoo"),
        ("neg", lambda: -s, -x, "bcoo"),
        ("abs", lambda: s.abs(), np.abs(x), "bcoo"),
        ("sqrt_abs", lambda: s.abs().sqrt(), np.sqrt(np.abs(x)), "bcoo"),
        ("pow2", lambda: s ** 2, x ** 2, "bcoo"),
        ("add_s", lambda: s + 1.0, x + 1.0, "dense"),
        ("exp", lambda: s.exp(), np.exp(x), "dense"),
        ("rdiv", lambda: 2.0 / (s + 3.0), 2.0 / (x + 3.0), "dense"),
        ("pair_add", lambda: s + sb, x + y, "bcoo"),
        ("pair_sub", lambda: s - sb, x - y, "bcoo"),
        ("pair_mul", lambda: s * sb, x * y, "bcoo"),
        ("gather_mul", lambda: s * b, x * y, "bcoo"),
        ("gather_div", lambda: s / b, x / y, "bcoo"),
        ("rev_gather", lambda: b * s, x * y, "bcoo"),
        ("dense_div_sp", lambda: b / s, None, "dense"),
        ("sp_add_dense", lambda: s + b, x + y, "dense"),
    ]
    for label, build, want, fmt in cases:
        out = build()
        assert out.block_format == fmt, (label, out.block_format)
        out.check_invariants()
        if want is not None:
            np.testing.assert_allclose(np.asarray(out.collect()), want,
                                       rtol=1e-5, atol=1e-5, err_msg=label)


@pytest.mark.parametrize("case", ["square", "overlap", "split_entries",
                                  "lazy_square"])
def test_pair_multiply_keeps_lhs_capacity(case):
    """sparse * sparse lands in the left operand's slots (an all-pairs
    product would hold nse_a * nse_b per block); entries split across
    duplicate slots by an index-merge add are summed before multiplying."""
    x, _, s = mk_sparse(16, 12, 8, 6, density=0.5)
    y, _, t = mk_sparse(16, 12, 8, 6, density=0.5)
    lhs, rhs, want = {
        "square": (s, s, x * x),
        "overlap": (s, t, x * y),
        "split_entries": (s + t, t, (x + y) * y),
        "lazy_square": (s.lazy(), s.lazy(), x * x),
    }[case]
    out = lhs * rhs
    if case.startswith("lazy"):
        out = out.compute()
    assert out.block_format == "bcoo"
    out.check_invariants()
    assert out.blocks.nse == (lhs.compute() if case.startswith("lazy")
                              else lhs).blocks.nse
    np.testing.assert_allclose(np.asarray(out.collect()), want,
                               rtol=1e-5, atol=1e-6)


def test_mixed_format_with_mismatched_blocks():
    """A block-shape mismatch makes alignment rechunk — which densifies a
    sparse operand — and the dispatch must then take the dense path (the
    gather form has no BCOO left to index)."""
    x, a, s = mk_sparse(12, 9, 4, 3)
    y = (RNG.normal(size=(12, 9)) + 2.0).astype(np.float32)
    d = from_array(y, (5, 2))                     # different block shape
    for build, want in [
            (lambda: d * s, y * x), (lambda: s * d, x * y),
            (lambda: d / (s + 2.0), y / (x + 2.0)),
            (lambda: s / d, x / y), (lambda: d + s, y + x),
            (lambda: s - d.tosparse(), x - y)]:
        out = build()
        out.check_invariants()
        np.testing.assert_allclose(np.asarray(out.collect()), want,
                                   rtol=1e-5, atol=1e-5)


def test_nonlinear_data_map_after_index_merge():
    """abs/astype over a sparse ± sparse result (duplicate indices) must
    merge split entries first — |d1 + d2| != |d1| + |d2|."""
    x, a, s = mk_sparse()
    y, b, sb = mk_sparse(13, 9, 4, 3)
    merged = (s * 2.0) - sb
    np.testing.assert_allclose(np.asarray(merged.abs().collect()),
                               np.abs(x * 2.0 - y), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(merged.astype(jnp.int32).collect()),
                               (x * 2.0 - y).astype(np.int32))


def test_transpose_reductions_mean_norm():
    x, a, s = mk_sparse(11, 7, 3, 3)
    t = s.T
    assert t.block_format == "bcoo"
    t.check_invariants()
    np.testing.assert_allclose(np.asarray(t.collect()), x.T)
    assert float(s.sum()) == pytest.approx(float(x.sum()), rel=1e-5)
    np.testing.assert_allclose(
        np.asarray(s.sum(axis=0).collect()).ravel(), x.sum(0), rtol=1e-4)
    np.testing.assert_allclose(
        np.asarray(s.sum(axis=1).collect()).ravel(), x.sum(1), rtol=1e-4)
    assert float(s.max()) == pytest.approx(float(x.max()))
    np.testing.assert_allclose(
        np.asarray(s.min(axis=0).collect()).ravel(), x.min(0))
    np.testing.assert_allclose(
        np.asarray(s.mean(axis=1).collect()).ravel(), x.mean(1), rtol=1e-4)
    assert float(s.norm()) == pytest.approx(float(np.linalg.norm(x)), rel=1e-5)
    # integer mean promotes before summing on the sparse path too
    xi, ai, si = mk_sparse(9, 5, 4, 2, np.int32)
    np.testing.assert_allclose(np.asarray(si.mean(axis=0).collect()).ravel(),
                               xi.mean(0), rtol=1e-6)


def test_structural_ops_densify_but_match():
    x, a, s = mk_sparse(17, 13, 4, 3)
    np.testing.assert_allclose(np.asarray(s[2:9, 1:7].collect()), x[2:9, 1:7])
    np.testing.assert_allclose(np.asarray(s[[0, 5, 12, 3]].collect()),
                               x[[0, 5, 12, 3]])
    np.testing.assert_allclose(np.asarray(s.rechunk((5, 2)).collect()), x)
    from repro.core import concat_rows, exact_shuffle
    y, b, sb = mk_sparse(17, 13, 4, 3)
    np.testing.assert_allclose(np.asarray(concat_rows([s, sb]).collect()),
                               np.concatenate([x, y]))
    out = exact_shuffle(jax.random.PRNGKey(5), s)
    assert sorted(np.asarray(out.collect()).ravel().tolist()) == \
        sorted(x.ravel().tolist())


def test_grid_padding_keeps_invariant():
    x, a, s = mk_sparse(10, 6, 4, 3)
    grown = s._pad_grid_to((5, 4))
    grown.check_invariants()
    assert grown.stacked_grid == (5, 4)
    np.testing.assert_allclose(np.asarray(grown.collect()), x)


# ---------------------------------------------------------------------------
# Acceptance: sp @ dense / spᵀ @ dense / sparse reductions never densify
# ---------------------------------------------------------------------------


def test_spmm_matches_and_never_densifies():
    x, a, s = mk_sparse(24, 18, 6, 6, density=0.2)
    w = RNG.normal(size=(18, 10)).astype(np.float32)
    wd = from_array(w, (6, 5))
    out = s @ wd
    assert out.block_format == "dense"
    np.testing.assert_allclose(np.asarray(out.collect()), x @ w,
                               rtol=1e-4, atol=1e-4)
    # jaxpr of the whole DsArray-level matmul: the sparse operand's dense
    # stacked form (gn, gk, bn, bk) must never appear as an intermediate
    jx = jax.make_jaxpr(lambda sb, wb: local_matmul(sb, wb))(
        s.blocks, wd.ensure_zero_pad().blocks)
    assert_no_densify(jx, s.blocks.shape)


def test_spmm_transpose_a_never_densifies():
    from repro.core.dsarray import matmul_ta
    x, a, s = mk_sparse(20, 12, 5, 4, density=0.25)
    w = RNG.normal(size=(20, 6)).astype(np.float32)
    wd = from_array(w, (5, 3))
    out = matmul_ta(s, wd)
    np.testing.assert_allclose(np.asarray(out.collect()), x.T @ w,
                               rtol=1e-4, atol=1e-4)
    jx = jax.make_jaxpr(
        lambda sb, wb: local_matmul(sb, wb, transpose_a=True))(
        s.blocks, wd.ensure_zero_pad().blocks)
    assert_no_densify(jx, s.blocks.shape)


def test_sparse_matvec():
    x, a, s = mk_sparse(24, 18, 6, 6, density=0.1)
    v = RNG.normal(size=(18, 1)).astype(np.float32)
    vd = from_array(v, (6, 1))
    np.testing.assert_allclose(np.asarray((s @ vd).collect()), x @ v,
                               rtol=1e-4, atol=1e-4)


def test_sparse_reductions_never_densify():
    x, a, s = mk_sparse(24, 18, 6, 6, density=0.2)
    for fn in (lambda sb: DsArray(sb, s.grid).sum(),
               lambda sb: DsArray(sb, s.grid).sum(axis=0).blocks,
               lambda sb: DsArray(sb, s.grid).sum(axis=1).blocks):
        jx = jax.make_jaxpr(fn)(s.blocks)
        assert_no_densify(jx, s.blocks.shape)


def test_sparse_elementwise_never_densifies():
    """Data maps and gather-mul run on (gn, gm, nse)-shaped arrays only."""
    x, a, s = mk_sparse(24, 18, 6, 6, density=0.2)
    y = (RNG.normal(size=x.shape) + 2.0).astype(np.float32)
    b = from_array(y, (6, 6))
    jx = jax.make_jaxpr(
        lambda sb, db: sparse_mod.gather_fn(jnp.multiply, True)(sb, db).data)(
        s.blocks, b.blocks)
    assert_no_densify(jx, s.blocks.shape)
    jx2 = jax.make_jaxpr(
        lambda sb: sparse_mod.data_map_fn(jnp.multiply, 2.0, False)(sb).data)(
        s.blocks)
    assert_no_densify(jx2, s.blocks.shape)


# ---------------------------------------------------------------------------
# Lazy integration: formats in metadata, fusion boundary, CSE + cache
# ---------------------------------------------------------------------------


def test_lazy_sparse_formats_and_values():
    x, a, s = mk_sparse()
    y = (RNG.normal(size=x.shape) + 2.0).astype(np.float32)
    b = from_array(y, a.block_shape)
    with repro.lazy():
        r_sp = ((s * 2.0) - b.tosparse()).abs()       # stays sparse
        r_dn = (s * 3.0) + 1.0                        # densifies mid-chain
        r_ga = s * b                                  # gather stays sparse
    assert r_sp.block_format == "bcoo"
    assert r_dn.block_format == "dense"
    assert r_ga.block_format == "bcoo"
    out = r_sp.compute()
    assert out.block_format == "bcoo"
    out.check_invariants()
    np.testing.assert_allclose(np.asarray(out.collect()),
                               np.abs(x * 2.0 - y), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(r_dn.compute().collect()),
                               x * 3.0 + 1.0, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(r_ga.compute().collect()), x * y,
                               rtol=1e-5, atol=1e-5)


def test_lazy_sparse_is_fusion_boundary_but_cses_and_caches():
    x, a, s = mk_sparse(16, 12, 4, 4)
    with repro.lazy():
        chain = ((s * 2.0) * 3.0).abs()
    p = plan.plan_for(chain)
    assert p.stats["fused_elementwise"] == 0, p.stats    # sparse: no fusion
    with repro.lazy():
        dense_chain = ((a * 2.0) * 3.0).abs()
    assert plan.plan_for(dense_chain).stats["fused_elementwise"] == 2
    # CSE: sibling reductions over one sparse operand share it
    with repro.lazy():
        c = s * 2.0
        s0, s1 = c.sum(axis=0), c.sum(axis=1)
    ps = plan.plan_for(s0, s1)
    assert ps.roots[0].children[0] is ps.roots[1].children[0]
    # cache: same sparse structure AND capacity on fresh data hits (nse is
    # part of the leaf signature, so pin it across the fresh draws)
    plan.clear_cache()
    for i in range(3):
        xi, ai, si = mk_sparse(16, 12, 4, 4)
        with repro.lazy():
            r = (ai.tosparse(nse=8) * 2.0).sum(axis=0)
        r.compute()
    st = plan.cache_stats()
    assert st["misses"] == 1 and st["hits"] == 2, st
    # a different nse is a DIFFERENT plan (stored-entry capacity is shape)
    xi, ai, _ = mk_sparse(16, 12, 4, 4)
    with repro.lazy():
        r = (ai.tosparse(nse=16) * 2.0).sum(axis=0)
    r.compute()
    assert plan.cache_stats()["misses"] == 2


def test_lazy_conversion_nodes():
    x, a, s = mk_sparse()
    with repro.lazy():
        r = (a.lazy().tosparse(nse=16) * 2.0)
        d = s.lazy().todense() + 1.0
    assert r.block_format == "bcoo" and d.block_format == "dense"
    np.testing.assert_allclose(np.asarray(r.compute().collect()), x * 2.0,
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(d.compute().collect()), x + 1.0,
                               rtol=1e-5)
    with pytest.raises(ValueError):
        a.lazy().tosparse()          # lazy conversion needs explicit nse


# ---------------------------------------------------------------------------
# Algorithms accept sparse inputs (the paper's CSVM-style workloads)
# ---------------------------------------------------------------------------


def test_kmeans_sparse_matches_dense():
    from repro.algorithms.kmeans import KMeans
    c0 = np.zeros(12); c0[1] = 5.0
    c1 = np.zeros(12); c1[7] = -5.0
    pts = np.stack([(c0 if i % 2 == 0 else c1)
                    + (RNG.random(12) < 0.2) * RNG.normal(size=12) * 0.1
                    for i in range(40)]).astype(np.float32)
    xd = from_array(pts, (8, 5))
    xs = xd.tosparse()
    km_d = KMeans(n_clusters=2, seed=1).fit(xd)
    km_s = KMeans(n_clusters=2, seed=1).fit(xs)
    np.testing.assert_allclose(np.sort(np.asarray(km_d.centers_), axis=0),
                               np.sort(np.asarray(km_s.centers_), axis=0),
                               atol=1e-4)
    labels = km_s.predict(xs)
    assert labels.shape == (40, 1)
    assert np.isfinite(km_s.score(xs))


def test_kmeans_sparse_assignment_never_densifies():
    """The Lloyd-step contractions on BCOO blocks must not materialize the
    dense stacked x."""
    from repro.algorithms.kmeans import _center_stats
    x, a, s = mk_sparse(24, 12, 6, 4, density=0.2)
    gn, gm, bn, bm = s.blocks.shape
    centers = RNG.normal(size=(3, gm * bm)).astype(np.float32)
    row_valid = np.ones((gn, bn), bool)
    x_sq = RNG.random((gn, bn)).astype(np.float32)
    jx = jax.make_jaxpr(lambda sb: _center_stats(
        sb, jnp.asarray(row_valid), jnp.asarray(centers),
        jnp.asarray(x_sq), 12))(s.blocks)
    assert_no_densify(jx, s.blocks.shape)


def test_pca_gram_als_sparse():
    from repro.algorithms.linalg import frobenius, pca
    from repro.algorithms.als import ALS
    x, a, s = mk_sparse(30, 10, 8, 4, density=0.25)
    cd, vd = pca(a, 2, n_iter=20, center=False)
    cs, vs = pca(s, 2, n_iter=20, center=False)
    np.testing.assert_allclose(np.abs(np.asarray(cd)), np.abs(np.asarray(cs)),
                               atol=1e-3)
    np.testing.assert_allclose(np.asarray(vd), np.asarray(vs), rtol=1e-3)
    assert frobenius(s) == pytest.approx(float(np.linalg.norm(x)), rel=1e-5)
    np.testing.assert_allclose(np.asarray(gram(s)), x.T @ x, atol=1e-3)
    rt = ((RNG.random((24, 18)) < 0.3)
          * (RNG.random((24, 18)) * 4 + 1)).astype(np.float32)
    rd = from_array(rt, (6, 6))
    m_d = ALS(n_factors=4, max_iter=3, seed=0).fit(rd)
    m_s = ALS(n_factors=4, max_iter=3, seed=0).fit(rd.tosparse())
    np.testing.assert_allclose(
        np.asarray((m_d.u_ @ m_d.v_.T).collect()),
        np.asarray((m_s.u_ @ m_s.v_.T).collect()), atol=1e-2)


def test_distribute_sparse_single_device():
    from jax.sharding import Mesh
    x, a, s = mk_sparse(12, 8, 4, 4)
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    placed = s.distribute(mesh)
    assert placed.block_format == "bcoo"
    np.testing.assert_allclose(np.asarray(placed.collect()), x)


# ---------------------------------------------------------------------------
# ISSUE-5 satellites: sparse-native aligned slicing + lazy nse re-compaction
# ---------------------------------------------------------------------------


def test_sparse_aligned_slice_stays_bcoo_and_matches_dense():
    x, a, s = mk_sparse(21, 13, 4, 3)
    cases = [
        (slice(0, 8), slice(None)),          # aligned rows, full cols
        (slice(4, 21), slice(0, 6)),         # aligned both, stop at edge
        (slice(0, 7), slice(3, 11)),         # stop mid-block (data mask)
        (slice(8, 8), slice(None)),          # empty selection
        (slice(None), slice(6, 13)),         # full rows, aligned cols
        (0, slice(None)),                    # aligned single row
        (slice(12, 21), slice(9, 13)),       # tail blocks
    ]
    for key in cases:
        out = s[key]
        ref = a[key]
        assert out.block_format == "bcoo", key
        out.check_invariants()
        assert out.shape == ref.shape and out.block_shape == ref.block_shape
        np.testing.assert_allclose(np.asarray(out.collect()),
                                   np.asarray(ref.collect()), err_msg=str(key))
    # unaligned / gather selections still take the documented densify path
    assert s[1:5].block_format == "dense"
    assert s[[0, 5, 2]].block_format == "dense"


def test_sparse_aligned_slice_no_todense_in_jaxpr():
    """The satellite's acceptance: the sliced plan contains no
    ``bcoo_todense``-style scatter and no dense-stacked intermediate —
    it is a pure batch-dim slice of data/indices."""
    x, a, s = mk_sparse(21, 13, 4, 3)
    lz = s.lazy()[0:8, 0:6]
    assert lz.block_format == "bcoo"
    jx = plan.plan_for(lz).jaxpr()
    prims = {e.primitive.name for e in _walk_eqns(jx)}
    assert "scatter" not in prims and "scatter-add" not in prims, prims
    assert_no_densify(jx, s.blocks.shape)
    out = lz.compute()
    out.check_invariants()
    np.testing.assert_allclose(np.asarray(out.collect()), x[:8, :6])


def test_rows_to_dense_matches_collect():
    x, a, s = mk_sparse(19, 11, 4, 3)
    np.testing.assert_allclose(sparse_mod.rows_to_dense(s), x)
    np.testing.assert_allclose(sparse_mod.rows_to_dense(a), x)
    # duplicate-index storage (sparse+sparse concat) still merges correctly
    two = (s + s)
    np.testing.assert_allclose(sparse_mod.rows_to_dense(two), 2 * x)


def test_lazy_sparse_chain_recompacts_nse():
    """ISSUE-5 satellite: a recorded sparse± chain inserts an nse-shrinking
    canonicalize node once the accumulated capacity passes the block bound
    (``costmodel.bcoo_recompaction_pays``), so long chains stop growing
    capacity unboundedly; values still match the eager chain."""
    from repro.core.expr import Canonicalize
    parts = [mk_sparse(8, 6, 2, 3, density=0.5)[2] for _ in range(6)]
    eager = parts[0]
    for p in parts[1:]:
        eager = eager + p
    lz = parts[0].lazy()
    for p in parts[1:]:
        lz = lz + p
    cap = 2 * 3
    assert eager.blocks.nse > cap          # the eager chain DOES grow
    assert lz.expr.meta.blocks.nse <= cap  # the recorded chain is bounded

    kinds = set()
    seen = set()

    def walk(n):
        if id(n) in seen:
            return
        seen.add(id(n))
        kinds.add(type(n).__name__)
        for c in n.children:
            walk(c)

    walk(plan.plan_for(lz).roots[0])
    assert "Canonicalize" in kinds, kinds
    out = lz.compute()
    out.check_invariants()
    assert out.block_format == "bcoo" and out.blocks.nse <= cap
    np.testing.assert_allclose(np.asarray(out.collect()),
                               np.asarray(eager.collect()), rtol=1e-5)
    # capacity below the bound stays untouched (no gratuitous node): a
    # scalar data map preserves the index structure and nse
    small = parts[0].lazy() * 2.0
    assert small.expr.meta.blocks.nse <= cap
    seen.clear(); kinds.clear()
    walk(plan.plan_for(small).roots[0])
    assert "Canonicalize" not in kinds, kinds


def test_recompaction_costmodel_law():
    assert not costmodel.bcoo_recompaction_pays(5, 6)     # below the bound
    assert costmodel.bcoo_recompaction_pays(7, 6)         # past it
    saved = costmodel.bcoo_recompaction_saved_bytes(12, 6, 4, e=4)
    assert saved == 4 * (costmodel.bcoo_bytes(12, 4)
                         - costmodel.bcoo_bytes(6, 4))
