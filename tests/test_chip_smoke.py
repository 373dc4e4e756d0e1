"""Rehearse ``chip_smoke.py`` on the CPU.

The script proves on the chip that the fit -> serve path and the
distributed ops run; here its phases run at tiny sizes with the Pallas
kernel in the interpreter, so a broken path or a wrong check shows up
before any chip time is spent.  At full size on the CPU the script must
refuse to run, and without the rest of the repository it must fail.
Each run is a subprocess: the script owns its JAX process, and the
four-device rehearsal needs its own device count.
"""

import json
import os
import shutil
import subprocess
import sys
import textwrap

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

TINY = ("Sizes(dense_rows=2048, dense_cols=300, dense_block=(512, 128), "
        "dislib_rows=2000, dislib_block=(1000, 128), kmeans_k=3, "
        "serve_buckets=(1, 8, 16), serve_requests=6, sparse_rows=600, "
        "sparse_cols=3000, sparse_density=0.01, sparse_block_rows=256, "
        "csvm_iters=2, sparse_buckets=(1, 4), dist_n=512, dist_block=128)")


def _run(args, cwd=ROOT, devices=1, **env):
    env = dict(os.environ, JAX_PLATFORMS="cpu", REPRO_GEMM="interpret",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               **env)
    return subprocess.run([sys.executable] + args, cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=900)


def _rehearse(chips, devices):
    code = textwrap.dedent(f"""
        import json
        from chip_smoke import Sizes, run
        print(json.dumps(run(chips={chips}, sizes={TINY},
                             require_tpu=False)))
    """)
    proc = _run(["-c", code], devices=devices)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    return proc.stdout, json.loads(proc.stdout.strip().splitlines()[-1])


def test_chip_smoke_rehearsal_one_chip():
    out, dev = _rehearse(chips=1, devices=1)
    assert dev == {"platform": "cpu", "kind": "cpu", "count": 1}
    assert "FAILED" not in out
    for phase in ("1 dense fit", "2 serve", "3 sparse"):
        assert f"phase {phase}" in out


def test_chip_smoke_rehearsal_four_devices():
    out, dev = _rehearse(chips=4, devices=4)
    assert dev["platform"] == "cpu" and dev["count"] == 4
    assert "FAILED" not in out
    for op in ("summa_matmul", "cannon_matmul", "transpose_pp",
               "colsum_psum", "eager @ on distributed operands"):
        assert f"check {op}" in out


def test_chip_smoke_refuses_cpu(tmp_path):
    """At full size on the CPU: non-zero exit, no result line."""
    proc = _run(["chip_smoke.py"],
                JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    assert proc.returncode != 0
    assert "no TPU found" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_chip_smoke_needs_the_repository(tmp_path):
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    proc = _run(["chip_smoke.py"], cwd=tmp_path)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
