"""Multi-device distribution tests.

These need >1 device, so each runs in a SUBPROCESS with
``--xla_force_host_platform_device_count`` (the main pytest process keeps 1
CPU device per the dry-run isolation rule).
"""

import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
ROOT = os.path.join(os.path.dirname(__file__), "..")


def run_subprocess(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.abspath(SRC), os.path.abspath(ROOT),
         env.get("PYTHONPATH", "")])
    proc = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          capture_output=True, text=True, env=env,
                          timeout=600)
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
    return proc.stdout


def test_shmap_collective_ops():
    out = run_subprocess("""
        import numpy as np, jax
        from repro.core import from_array
        from jax.sharding import Mesh
        from repro.core.shmap_ops import (summa_matmul, cannon_matmul,
                                          transpose_pp, colsum_psum)
        mesh = Mesh(np.array(jax.devices()).reshape(2, 2), ("data", "model"))
        rng = np.random.default_rng(0)
        x = rng.normal(size=(32, 48)).astype(np.float32)
        y = rng.normal(size=(48, 24)).astype(np.float32)
        A, B = from_array(x, (8, 8)), from_array(y, (8, 8))
        with mesh:
            assert np.allclose(summa_matmul(A, B, mesh).collect(), x @ y, atol=1e-3)
            assert np.allclose(cannon_matmul(A, B, mesh).collect(), x @ y, atol=1e-3)
            assert np.allclose(transpose_pp(A, mesh).collect(), x.T)
            assert np.allclose(colsum_psum(A, mesh).collect(),
                               x.sum(0, keepdims=True), atol=1e-3)
            # FILL-pad operands: matmul must re-zero, transpose must carry
            # the pad state (regression: a dropped state let reductions skip
            # the refill and count pad cells)
            Af, Bf = A + 1.0, B - 2.0
            assert np.allclose(summa_matmul(Af, Bf, mesh).collect(),
                               (x + 1) @ (y - 2), atol=1e-3)
            t = transpose_pp(Af, mesh)
            assert t.pad_state == Af.pad_state
            assert abs(float(t.sum()) - (x + 1).sum()) < 1e-2
        print("OK")
    """, devices=4)
    assert "OK" in out


def test_compressed_psum_unbiased():
    out = run_subprocess("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.distributed import compressed_psum
        mesh = Mesh(np.array(jax.devices()), ("pod",))
        x = np.random.default_rng(0).normal(size=(4, 64)).astype(np.float32)

        def body(xs, key):
            return compressed_psum(xs[0], "pod", key[0], 4)

        f = jax.shard_map(body, mesh=mesh, in_specs=(P("pod", None), P("pod")),
                      out_specs=P(None), check_vma=False)
        keys = jax.random.split(jax.random.PRNGKey(0), 4)
        errs = []
        for trial in range(5):
            keys = jax.random.split(jax.random.PRNGKey(trial), 4)
            got = np.asarray(f(jnp.asarray(x), keys))
            errs.append(got - x.sum(0))
        err = np.stack(errs)
        scale = np.abs(x.sum(0)).max()
        assert np.abs(err).max() < 0.1 * scale + 0.2, np.abs(err).max()
        # stochastic rounding -> near-zero mean error across trials
        assert abs(err.mean()) < 0.05 * scale
        print("OK")
    """, devices=4)
    assert "OK" in out


def test_sharded_train_step_runs_and_matches():
    """Distributed train step on a 2x2 mesh == single-device step (loss)."""
    out = run_subprocess("""
        import numpy as np, jax
        from repro.configs import get_smoke_config
        from repro.models.model import build_model
        from repro.models import common as cm
        from repro.optim import make_optimizer
        from repro.train.step import init_state, make_train_step
        from repro.data import SyntheticPipeline, PipelineConfig
        from repro.distributed import sharding as shlib

        cfg = get_smoke_config("yi-9b")
        model = build_model(cfg)
        opt = make_optimizer("adamw", peak_lr=1e-3)
        pipe = SyntheticPipeline(PipelineConfig(global_batch=8, seq_len=16,
                                                vocab_size=cfg.vocab_size))
        batch = pipe.batch_at(0)
        state = init_state(model, opt, jax.random.PRNGKey(0))

        # single-device reference
        _, m_ref = make_train_step(model, opt)(state, batch)

        from jax.sharding import Mesh
        mesh = Mesh(np.array(jax.devices()).reshape(2, 2), ("data", "model"))
        env = cm.ShardEnv(mesh=mesh, dp=("data",), tp="model")
        ps = shlib.param_shardings(state.params, mesh)
        osh = shlib.opt_state_shardings(state.opt_state, state.params, mesh)
        from repro.train.step import TrainState
        ss = TrainState(params=ps, opt_state=osh)
        step = jax.jit(make_train_step(model, opt, env),
                       in_shardings=(ss, shlib.to_shardings(
                           shlib.batch_specs(batch, mesh, ("data",)), mesh)),
                       out_shardings=(ss, None))
        with mesh:
            state2, m = step(state, batch)
        assert abs(float(m["loss"]) - float(m_ref["loss"])) < 1e-2, (
            float(m["loss"]), float(m_ref["loss"]))
        print("OK", float(m["loss"]))
    """, devices=4)
    assert "OK" in out


def test_elastic_checkpoint_reshard():
    """Save on a 4-device mesh, restore onto a 2-device mesh."""
    out = run_subprocess("""
        import tempfile, numpy as np, jax, jax.numpy as jnp
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.checkpoint import save, restore
        mesh4 = Mesh(np.array(jax.devices()), ("data",))
        x = jnp.arange(64.0).reshape(8, 8)
        xs = jax.device_put(x, NamedSharding(mesh4, P("data", None)))
        with tempfile.TemporaryDirectory() as d:
            save(d, 0, {"x": xs})
            mesh2 = Mesh(np.array(jax.devices()[:2]), ("data",))
            sh = {"x": NamedSharding(mesh2, P(None, "data"))}
            out = restore(d, 0, {"x": jnp.zeros((8, 8))}, sh)
            assert np.allclose(np.asarray(out["x"]), np.asarray(x))
            assert out["x"].sharding.spec == P(None, "data")
        print("OK")
    """, devices=4)
    assert "OK" in out


def test_sharding_rules_sanitize():
    import jax
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P
    from repro.distributed.sharding import sanitize_spec
    mesh = Mesh(np.array(jax.devices()[:1]), ("model",))
    # 7 not divisible by any mesh>1 — with size-1 mesh everything divides
    assert sanitize_spec(P("model", None), (7, 3), mesh) == P("model", None)


def test_structural_ops_preserve_sharding():
    """Block-native slice/rechunk/concat keep blocks on the mesh they lived on
    (the seed materialize path silently collapsed to single-device)."""
    out = run_subprocess("""
        import numpy as np, jax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.core import concat_rows, from_array

        mesh = Mesh(np.array(jax.devices()).reshape(2, 2), ("data", "model"))
        x = np.random.default_rng(0).normal(size=(64, 64)).astype(np.float32)
        A = from_array(x, (8, 8)).distribute(mesh)
        want = NamedSharding(mesh, P("data", "model", None, None))
        assert A.blocks.sharding == want

        s = A[16:48, 0:32]                       # block-aligned grid slice
        assert np.allclose(np.asarray(s.collect()), x[16:48, 0:32])
        assert s.blocks.sharding == want, s.blocks.sharding

        r = A.rechunk((4, 4))                    # evenly-dividing regroup
        assert np.allclose(np.asarray(r.collect()), x)
        assert r.blocks.sharding == want, r.blocks.sharding

        c = concat_rows([A, A])                  # grid stack
        assert np.allclose(np.asarray(c.collect()),
                           np.concatenate([x, x], axis=0))
        assert c.blocks.sharding == want, c.blocks.sharding

        f = A[np.arange(1, 64, 2)]               # gather filtering
        assert np.allclose(np.asarray(f.collect()), x[1::2])
        assert f.blocks.sharding == want, f.blocks.sharding
        print("OK")
    """, devices=4)
    assert "OK" in out


def test_distributed_matmul_takes_partition_safe_path():
    """A Mosaic kernel cannot be partitioned automatically: with the TPU
    dispatch branch steered on, ``@`` on distributed operands must still
    take the einsum (SPMD partitions it) and match NumPy."""
    out = run_subprocess("""
        import numpy as np, jax
        from jax.sharding import Mesh
        from repro import obs
        from repro.core import from_array
        from repro.kernels.matmul import ops
        ops.jax.default_backend = lambda: "tpu"   # the chip's branch
        mesh = Mesh(np.array(jax.devices()).reshape(2, 2), ("data", "model"))
        rng = np.random.default_rng(0)
        x = rng.normal(size=(256, 256)).astype(np.float32)
        y = rng.normal(size=(256, 256)).astype(np.float32)
        A = from_array(x, (128, 128)).distribute(mesh)
        B = from_array(y, (128, 128)).distribute(mesh)
        assert ops.gemm_backend(128, 128, 128, np.dtype(np.float32)) == "pallas"
        obs.reset_all()
        C = A @ B
        snap = obs.snapshot("gemm")
        assert snap["gemm.dispatch_einsum"] == 1, snap
        assert snap["gemm.dispatch_pallas"] == 0, snap
        assert np.allclose(np.asarray(C.collect()), x @ y, atol=1e-3)
        print("OK")
    """, devices=4)
    assert "OK" in out
