"""`run.py` exits non-zero and prints no result off a TPU, and in a
directory that holds only BENCHMARK.json and the benchmark's own files."""

import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
ARGS = ["--workload", "inception_v3_chairs.train", "--seed", "3", "--seconds", "1",
        "--trace", "0"]


def run_in(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "benchmark/run.py", *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True, timeout=600)


def test_refuses_off_tpu():
    p = run_in(ROOT)
    assert p.returncode != 0
    assert '"metrics"' not in p.stdout


def test_refuses_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path / "BENCHMARK.json")
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", ".cache"))
    p = run_in(tmp_path)
    assert p.returncode != 0
    assert '"metrics"' not in p.stdout
