"""End-to-end behaviour tests: the system reproduces the paper's claims and
the LM framework trains/serves correctly.
"""

import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Dataset, costmodel, from_array
from repro.launch import serve as serve_mod
from repro.launch import train as train_mod


def test_paper_claim_transpose_complexity():
    """Paper §5.2: Dataset transpose N^2+N tasks vs ds-array N tasks; both
    produce the same matrix."""
    x = np.random.default_rng(0).normal(size=(24, 24)).astype(np.float32)
    n = 4
    ds = Dataset.from_array(x, n)
    t0 = ds.counter.tasks
    baseline = ds.transpose()
    baseline_tasks = ds.counter.tasks - t0
    a = from_array(x, (6, 6))
    np.testing.assert_allclose(np.asarray(a.T.collect()), baseline.collect())
    assert baseline_tasks == n * n + n
    # ds-array: grid permutation + local transpose = one fused op,
    # modeled as N tasks (one per block row) on PyCOMPSs
    assert costmodel.dsarray_transpose_tasks(n, n) == n


def test_paper_claim_two_orders_of_magnitude():
    """§5.6 'two orders of magnitude faster in the best case' under the
    calibrated scheduler model at MareNostrum scale (1536 partitions)."""
    n, cores = 1536, 768
    t_dataset = costmodel.pycompss_time(
        costmodel.dataset_transpose_tasks(n), 0.01, cores)
    t_dsarray = costmodel.pycompss_time(
        costmodel.dsarray_transpose_tasks(n, 1), 0.01, cores)
    assert t_dataset / t_dsarray >= 100


def test_train_driver_loss_decreases(tmp_path):
    state = train_mod.main([
        "--arch", "qwen1.5-0.5b", "--smoke", "--steps", "40",
        "--batch", "8", "--seq", "64", "--ckpt-dir", str(tmp_path / "ck"),
        "--ckpt-every", "20", "--lr", "3e-3", "--log-every", "100"])
    assert state is not None


def test_train_driver_restart_resumes(tmp_path):
    # crash at step 12, checkpoint every 10 -> must resume and finish
    train_mod.main([
        "--arch", "mamba2-370m", "--smoke", "--steps", "25",
        "--batch", "4", "--seq", "32", "--ckpt-dir", str(tmp_path / "ck2"),
        "--ckpt-every", "10", "--crash-at", "12", "--log-every", "100"])
    from repro.checkpoint import latest_step
    assert latest_step(str(tmp_path / "ck2")) == 24


def test_serve_driver_families():
    for arch in ["qwen1.5-0.5b", "mamba2-370m", "seamless-m4t-medium",
                 "zamba2-2.7b"]:
        gen = serve_mod.main(["--arch", arch, "--smoke", "--batch", "2",
                              "--prompt-len", "6", "--gen", "6"])
        assert gen.shape == (2, 6)
        assert not np.isnan(np.asarray(gen, dtype=np.float32)).any()


def test_grad_accumulation_equivalence():
    """accum_steps=2 must match accum_steps=1 up to fp tolerance."""
    from repro.configs import get_smoke_config
    from repro.models.model import build_model
    from repro.optim import make_optimizer
    from repro.train.step import init_state, make_train_step
    from repro.data import PipelineConfig, SyntheticPipeline

    cfg = get_smoke_config("yi-9b")
    model = build_model(cfg)
    opt = make_optimizer("adamw", peak_lr=1e-3)
    pipe = SyntheticPipeline(PipelineConfig(global_batch=8, seq_len=16,
                                            vocab_size=cfg.vocab_size))
    batch = pipe.batch_at(0)
    s0 = init_state(model, opt, jax.random.PRNGKey(0))
    _, m1 = make_train_step(model, opt, accum_steps=1)(s0, batch)
    _, m2 = make_train_step(model, opt, accum_steps=2)(s0, batch)
    assert abs(float(m1["loss"]) - float(m2["loss"])) < 5e-3


@pytest.mark.parametrize("env_dir", [False, True])
def test_enable_compile_cache_directory(tmp_path, env_dir):
    """Importing ``repro`` sets no cache; ``enable_compile_cache`` keeps the
    directory ``JAX_COMPILATION_CACHE_DIR`` names where it is set, and
    otherwise sets the fixed directory it is given, which a compile then
    fills."""
    import subprocess
    import sys
    import textwrap

    given, named = tmp_path / "given", tmp_path / "named"
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = str(named)
    code = textwrap.dedent(f"""
        import jax, jax.numpy as jnp
        import repro
        before = jax.config.jax_compilation_cache_dir
        used = repro.enable_compile_cache({str(given)!r})
        jax.jit(lambda v: v * 2 + 1)(jnp.arange(4.0)).block_until_ready()
        print(before, used, jax.config.jax_compilation_cache_dir)
    """)
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = os.path.abspath(src)
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr
    before, used, config = proc.stdout.split()
    want = named if env_dir else given
    assert used == config == str(want)
    assert before == (str(named) if env_dir else "None")
    assert any(want.iterdir())
    if env_dir:
        assert not given.exists()
