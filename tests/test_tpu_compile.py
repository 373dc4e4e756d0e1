"""Compile the main-path GEMMs for a described TPU v5e, with no chip.

The TPU compiler is installed with jaxlib: it compiles for a topology that
is described and not attached, and refuses what the chip would refuse (a
Mosaic kernel that overflows VMEM, a dtype Mosaic cannot load, a kernel
under automatic partitioning).  Nothing runs, so these tests say nothing
about results or times.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every xdist worker
imports this file.  ``local_matmul`` asks ``jax.default_backend()``, which
still says "cpu" here, so each test steers it to "tpu" itself.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.kernels.matmul import ops


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off around these
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with pytest.MonkeyPatch.context() as mp:
            # the TPU compiler's logs then follow TMPDIR; by default they go
            # to /tmp/tpu_logs whatever TMPDIR says
            mp.setenv("TPU_LOG_DIR", "disabled")
            yield topologies.get_topology_desc(platform="tpu",
                                               topology_name="v5e:2x2")
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_tpu(monkeypatch):
    monkeypatch.delenv("REPRO_GEMM", raising=False)
    monkeypatch.setattr(ops.jax, "default_backend", lambda: "tpu")


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _stacked(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


# (a stacked shape, b stacked shape, dtype, transpose_a, kernel expected)
CASES = {
    # the Ridge Gram xᵀx on (8192, 512) blocks of a 400,000 x 2,048 array
    "gram_f32_8192x512": ((49, 4, 8192, 512), (49, 4, 8192, 512),
                          jnp.float32, True, True),
    "f32_4096x1024_1024x512": ((1, 1, 4096, 1024), (1, 1, 1024, 512),
                               jnp.float32, False, True),
    "bf16_4096x1024_1024x512": ((1, 1, 4096, 1024), (1, 1, 1024, 512),
                                jnp.bfloat16, False, True),
    "ragged_bn1000": ((2, 1, 1000, 512), (1, 1, 512, 512),
                      jnp.float32, False, True),
    # block heights 512 does not divide: a whole-block tile overflows VMEM
    "bn10000": ((2, 1, 10000, 512), (1, 1, 512, 512),
                jnp.float32, False, True),
    "bn12000": ((2, 1, 12000, 1024), (1, 1, 1024, 512),
                jnp.float32, False, True),
    # the dislib-style Gram: 10,000-row blocks are the contraction dim
    "gram_f32_10000x512": ((4, 2, 10000, 512), (4, 2, 10000, 512),
                           jnp.float32, True, True),
    # Mosaic cannot load f16: the dispatcher must pick einsum
    "f16_takes_einsum": ((1, 1, 4096, 1024), (1, 1, 1024, 512),
                         jnp.float16, False, False),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_local_matmul_compiles_for_v5e(case, one_chip, on_tpu):
    a_shape, b_shape, dtype, ta, kernel = CASES[case]
    a = _stacked(a_shape, dtype, one_chip)
    b = _stacked(b_shape, dtype, one_chip)
    compiled = _compile(lambda p, q: ops.local_matmul(p, q, transpose_a=ta),
                        a, b)
    assert ("tpu_custom_call" in compiled.as_text()) == kernel


def test_distributed_matmul_compiles_on_2x2_mesh(topo, on_tpu):
    """``x.distribute(mesh) @ y`` on four chips: the operands' mesh is in
    their type, so the dispatcher hands the contraction to SPMD
    partitioning (a Mosaic kernel cannot be partitioned automatically)."""
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"))
    spec = NamedSharding(mesh, P("data", "model", None, None))
    a = _stacked((8, 8, 2048, 2048), jnp.float32, spec)
    b = _stacked((8, 8, 2048, 2048), jnp.float32, spec)
    compiled = _compile(lambda p, q: ops.local_matmul(p, q), a, b)
    assert "tpu_custom_call" not in compiled.as_text()
    # each chip holds a quarter of each operand, not a copy of it
    per_chip = compiled.memory_analysis().argument_size_in_bytes
    assert per_chip == 2 * 8 * 8 * 2048 * 2048 * 4 // 4


def test_summa_local_gemm_keeps_kernel_on_2x2_mesh(topo, on_tpu):
    """Inside ``shard_map`` each chip contracts its own blocks, so SUMMA's
    local GEMM stays on the Pallas kernel."""
    from repro.core.shmap_ops import _local_gemm
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"))
    spec = P("data", "model", None, None)

    def body(ab, bb):
        a_full = jax.lax.all_gather(ab, "model", axis=1, tiled=True)
        b_full = jax.lax.all_gather(bb, "data", axis=0, tiled=True)
        return _local_gemm(a_full, b_full)

    summa = jax.shard_map(body, mesh=mesh, in_specs=(spec, spec),
                          out_specs=spec, check_vma=False)
    sharded = NamedSharding(mesh, spec)
    a = _stacked((8, 8, 2048, 2048), jnp.float32, sharded)
    compiled = _compile(summa, a, a)
    assert "tpu_custom_call" in compiled.as_text()


def test_normal_factor_compiles_for_v5e(one_chip):
    """The Ridge solve's device half at the epsilon order (2,000 features
    and the intercept, padded to 2,048) under ``highest``: the assembled
    system's Cholesky factor, the first solution, and one correction."""
    from repro.estimators import linear
    m = 2000
    f32 = jnp.float32
    args = (_stacked((m, m), f32, one_chip), _stacked((m, 1), f32, one_chip),
            _stacked((1, m), f32, one_chip), _stacked((3,), f32, one_chip))
    with jax.default_matmul_precision("highest"):
        factor = linear._normal_factor.lower(*args, intercept=True).compile()
        chol, theta, _, _ = factor.out_info
        assert chol.shape == (2048, 2048) and theta.shape == (m + 1,)
        linear._factor_solve.lower(
            _stacked((2048, 2048), f32, one_chip),
            _stacked((2048,), f32, one_chip)).compile()
