"""The entry point refuses to measure where it cannot: without a TPU, and
without the program under test beside it. Either way it exits non-zero
and prints no result."""
import os
import shutil
import subprocess
import sys

from bench import cells

ARGS = ["--workload", "mamba2-780m.chat", "--seed", str(2 ** 33 + 1),
        "--seconds", "1", "--trace", "0"]


def _run(root, env=None):
    return subprocess.run([sys.executable, str(root / "bench" / "run.py"),
                           *ARGS], cwd=root, capture_output=True, text=True,
                          timeout=240, env=env)


def test_no_tpu_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = _run(cells.ROOT, env)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert "{" not in out.stdout


def test_benchmark_files_alone_no_result(tmp_path):
    shutil.copy(cells.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(cells.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _run(tmp_path)
    assert out.returncode != 0
    assert "{" not in out.stdout
