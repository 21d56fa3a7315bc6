"""Without a TPU the benchmark exits non-zero and prints no result."""
import os
import shutil
import subprocess
import sys

import harness


def _run(cwd, env):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "fleet4096.congested",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = _run(harness.REPO, env)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    shutil.copy(harness.REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = _run(tmp_path, env)
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
