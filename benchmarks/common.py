"""Shared benchmark plumbing: dataset loading (cached), the four-SpMM GCN
cycle model (paper §III.D: PEs allocated ∝ kernel ops, kernels pipelined),
CSV row helpers, the ``--smoke`` size preset (``BENCH_SMOKE=1``), and the
guard that keeps subprocess suites off a TPU host."""
from __future__ import annotations

import functools
import os

import numpy as np

from repro.core import autotuner
from repro.graphs import synth

#: ``benchmarks/run.py --smoke`` sets BENCH_SMOKE=1 before importing the
#: suites: every dataset shrinks to a tiny synthetic preset so the full
#: measurement *pipeline* runs in CI minutes. Smoke numbers gate only
#: size-insensitive ratios (see benchmarks/check_regression.py) — absolute
#: latencies at these scales mean nothing.
SMOKE = os.environ.get("BENCH_SMOKE", "") == "1"

if SMOKE:
    BENCH_SCALE = {"cora": 8, "citeseer": 8, "pubmed": 16, "nell": 64,
                   "reddit": 128}
else:
    # full scale where tractable; reddit scaled (23M-edge build is minutes)
    BENCH_SCALE = {"cora": 1, "citeseer": 1, "pubmed": 1, "nell": 1,
                   "reddit": 4}
X2_DENSITY = {"cora": 0.78, "citeseer": 0.891, "pubmed": 0.776,
              "nell": 0.864, "reddit": 0.60}


def refuse_jax_children_on_tpu(suite: str) -> None:
    """Raise before a suite starts JAX child processes from a TPU host.

    Those children run a forced host-device CPU mesh, and on a TPU host
    they would reach for the chip this process holds — and fail or hang.
    Four-chip runs belong to ``python chip_smoke.py --chips 4``."""
    import jax

    if jax.default_backend() == "tpu":
        raise RuntimeError(
            f"{suite}: refusing to start JAX child processes — this process "
            "holds the TPU, which a child would need; this suite measures a "
            "forced host-device CPU mesh. Run it on a CPU host, and run "
            "four-chip paths with `python chip_smoke.py --chips 4`")


@functools.lru_cache(maxsize=None)
def dataset(name: str):
    return synth.make_dataset(name, scale=BENCH_SCALE[name])


@functools.lru_cache(maxsize=None)
def row_nnz_a(name: str) -> tuple:
    ds = dataset(name)
    rn = np.bincount(np.asarray(ds.adj.row), minlength=ds.num_nodes)
    return tuple(rn.astype(np.int64).tolist())


def gcn_kernels(name: str):
    """The four SpMM kernels of a 2-layer GCN (paper Fig. 15):
    returns list of dicts with row_nnz (workload/row), rounds (output
    columns), ops."""
    ds = dataset(name)
    n = ds.num_nodes
    f = ds.num_features
    h = ds.hidden
    c = ds.num_classes
    a_nnz = np.asarray(row_nnz_a(name), np.float64)
    rng = np.random.default_rng(0)
    _, _, _, _, _, dens_x, _, _ = synth.DATASET_STATS[name]
    x1_nnz = rng.binomial(f, min(1.0, dens_x), size=n).astype(np.float64)
    x2_nnz = np.full(n, h * X2_DENSITY[name])
    return [
        {"kernel": "L1 XW", "row_nnz": x1_nnz, "rounds": h},
        {"kernel": "L1 A(XW)", "row_nnz": a_nnz, "rounds": h},
        {"kernel": "L2 XW", "row_nnz": x2_nnz, "rounds": c},
        {"kernel": "L2 A(XW)", "row_nnz": a_nnz, "rounds": c},
    ]


def pipeline_model(name: str, design, n_pe_total: int, n_rounds: int = 12):
    """Cycles + utilization with PEs ∝ kernel ops and inter-kernel
    pipelining (latency ≈ slowest kernel; §III.D)."""
    kernels = gcn_kernels(name)
    ops = [float(k["row_nnz"].sum()) * k["rounds"] for k in kernels]
    total_ops = sum(ops)
    out = []
    for k, op in zip(kernels, ops):
        n_pe = max(8, int(round(n_pe_total * op / total_ops)))
        cyc = autotuner.total_cycles(k["row_nnz"], n_pe, design,
                                     k["rounds"], n_rounds=n_rounds)
        out.append({"kernel": k["kernel"], "n_pe": n_pe, "ops": op,
                    "cycles": float(cyc),
                    "util": op / max(1e-9, n_pe * cyc)})
    latency = max(k["cycles"] for k in out)          # pipelined
    serial = sum(k["cycles"] for k in out)           # unpipelined bound
    util = total_ops / (n_pe_total * latency)
    return {"kernels": out, "latency_cycles": latency,
            "serial_cycles": serial, "overall_util": min(1.0, util),
            "total_ops": total_ops}


def row(name: str, us_per_call: float, derived: str) -> str:
    return f"{name},{us_per_call:.3f},{derived}"
