"""The command refuses to measure where it cannot: no TPU, or a directory
that holds only the benchmark and not the program."""
import os
import shutil
import subprocess
import sys

from conftest import REPO


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "internlm2-1.8b.batch_decode", "--seed", str(2**31 + 9),
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_bench_exits_nonzero_without_a_tpu():
    p = _run(REPO)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_bench_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    for d in ("bench", os.path.join("tests", "bench")):
        shutil.copytree(os.path.join(REPO, d), tmp_path / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(str(tmp_path))
    assert p.returncode != 0
    assert p.stdout.strip() == ""
