"""Without a TPU the benchmark exits non-zero and prints no result."""
import os
import shutil
import subprocess
import sys

from harness.cell import CHECKOUT


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "yi6b-rns-l8.reasoning", "--seed", "3", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_no_tpu_no_result():
    p = _run(CHECKOUT)
    assert p.returncode != 0
    assert "{" not in p.stdout


def test_alone_in_a_directory_no_result(tmp_path):
    shutil.copy(CHECKOUT / "BENCHMARK.json", tmp_path)
    shutil.copytree(CHECKOUT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert "{" not in p.stdout
