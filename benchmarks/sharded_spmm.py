"""Sharded-executor SpMM latency per device count.

The bench process itself runs single-device (jax is already initialized by
the other suites), so the sharded measurements run in a subprocess that
forces an 8-way host-platform mesh — the same harness the distributed test
suite uses — and reports one row per device count:

    sharded_spmm/<graph>/dev<N>  us_per_call  n_devices=..;speedup_vs_1dev=..

Host-platform CPU "devices" share one socket, so these numbers measure the
sharding *machinery* (shard_map dispatch + psum) rather than real scaling;
on a TPU slice the same rows become the per-device-count scaling curve.
"""
from __future__ import annotations

import subprocess
import sys
from pathlib import Path

from benchmarks import common

N_FORCED_DEVICES = 8
DEVICE_COUNTS = (1, 2, 4, 8)
GRAPH = dict(n=3000, density=0.004, alpha=0.9, seed=0)
BENCH_KDIM = 32

_SRC = str(Path(__file__).resolve().parents[1] / "src")

_SCRIPT = r"""
import os, time
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=%(n_dev)d"
import sys
sys.path.insert(0, %(src)r)
import numpy as np, jax, jax.numpy as jnp
from repro.graphs import synth
from repro.tuning import registry, runner

a = synth.power_law_adjacency(%(n)d, %(density)g, %(alpha)g, seed=%(seed)d)
rng = np.random.default_rng(0)
b = jnp.asarray(rng.standard_normal((%(n)d, %(kdim)d)).astype(np.float32))
base_us = None
for d in %(counts)r:
    ex = registry.get_executor(a, n_devices=d)
    us = runner.time_call(lambda: ex.spmm(b), iters=3, warmup=2)
    if base_us is None:
        base_us = us
    print("ROW dev%%d %%f n_devices=%%d;nnz=%%d;speedup_vs_1dev=%%.2fx"
          %% (d, us, d, a.nnz, base_us / us))
"""


def run() -> list:
    common.refuse_jax_children_on_tpu("sharded_spmm")
    rows = []
    name = f"powerlaw{GRAPH['n']}"
    print(f"\n== sharded SpMM ({name}, {N_FORCED_DEVICES} host devices, "
          f"kdim={BENCH_KDIM}) ==")
    script = _SCRIPT % dict(n_dev=N_FORCED_DEVICES, src=_SRC,
                            counts=tuple(DEVICE_COUNTS), kdim=BENCH_KDIM,
                            **GRAPH)
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, timeout=900)
    if r.returncode != 0:
        raise RuntimeError(f"sharded bench subprocess failed: "
                           f"{r.stderr[-500:]}")
    for line in r.stdout.splitlines():
        if not line.startswith("ROW "):
            continue
        _, dev, us, derived = line.split(" ", 3)
        print(f"{dev:6s} {float(us):10.0f} us/spmm  {derived}")
        rows.append((f"sharded_spmm/{name}/{dev}", float(us), derived))
    return rows
