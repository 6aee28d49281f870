"""``bench/run.py`` prints no result without a TPU, or without the system
beside it, and exits non-zero."""
import os
import shutil
import subprocess
import sys

import bench_cells

ROOT = bench_cells.ROOT
ARGS = ["--workload", "train-paper-n200", "--seed", str(2**31 + 9), "--seconds", "1",
        "--trace", "0"]


def _run(cwd, extra_env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(extra_env or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_refuses_to_run_without_a_tpu():
    proc = _run(ROOT)
    assert proc.returncode == 2, proc.stderr[-2000:]
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_refuses_to_run_without_the_system(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in ("bench", "tests/bench"):
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(tmp_path)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
