"""The command fails, with a clear message and no result, where there is no
TPU and where the checkout holds only the benchmark's own files."""

import os
import shutil
import subprocess
import sys

from conftest import HERE, ROOT

CMD = ["benchmarks/chip/bench.py", "--workload", "gat-cora-paper", "--seed", "3",
       "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, *CMD], cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=300)


def test_no_tpu_exits_nonzero_without_result():
    r = _run(ROOT)
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert "correct" not in r.stdout


def test_benchmark_files_alone_fail(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    r = _run(tmp_path)
    assert r.returncode != 0
    assert "correct" not in r.stdout
