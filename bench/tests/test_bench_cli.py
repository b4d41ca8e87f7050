"""``bench/run.py`` prints no result and exits non-zero where it cannot
measure: without a TPU, and in a checkout that holds only the benchmark."""
import os
import shutil
import subprocess
import sys

from bench.harness import ROOT


def _run(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "pubmed-backlog", "--seed",
         str(2**33 + 1), "--seconds", "1", *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300,
    )


def test_exits_nonzero_without_a_tpu():
    for trace in ("0", "1"):
        proc = _run(ROOT, "--trace", trace)
        assert proc.returncode != 0
        assert proc.stdout.strip() == ""
        assert "needs a TPU" in proc.stderr


def test_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".store", ".jax_cache", ".out",
                                                  "__pycache__"))
    proc = _run(tmp_path)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
