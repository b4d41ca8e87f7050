"""Process hygiene of the benchmark harness: a failed suite fails the run,
and suites that start JAX child processes refuse to from a TPU host."""
import sys

import pytest

jax = pytest.importorskip("jax")

from benchmarks import common, order_ops  # noqa: E402
from benchmarks import run as bench_run  # noqa: E402
from repro import compile_cache  # noqa: E402


def test_failed_suite_exits_nonzero(monkeypatch, capsys):
    def boom():
        raise RuntimeError("suite broke")

    monkeypatch.setattr(order_ops, "run", boom)
    monkeypatch.setattr(compile_cache, "enable_compile_cache", lambda: "")
    monkeypatch.setattr(sys, "argv", ["run", "--only", "order_ops"])
    with pytest.raises(SystemExit) as exc:
        bench_run.main()
    assert exc.value.code not in (0, None)
    assert "order_ops/FAILED" in capsys.readouterr().out


def test_subprocess_suites_refuse_on_a_tpu_host(monkeypatch):
    common.refuse_jax_children_on_tpu("sharded_spmm")  # CPU: allowed
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="chip_smoke.py --chips 4"):
        common.refuse_jax_children_on_tpu("sharded_spmm")
