"""``bench/run.py`` refuses to report without a TPU, and in a directory
that holds only the benchmark."""

import os
import shutil
import subprocess
import sys

import tiny_cells

ROOT = tiny_cells.ROOT
ARGS = ["--workload", tiny_cells.TRAIN, "--seed", "3000000001",
        "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_refuses_without_tpu():
    p = _run(ROOT)
    assert p.returncode == 2, p.stderr[-2000:]
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_refuses_in_a_directory_of_the_benchmark_alone(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for d in ("bench", "tests/bench"):
        shutil.copytree(os.path.join(ROOT, d), tmp_path / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(str(tmp_path))
    assert p.returncode != 0
    assert p.stdout.strip() == ""
