"""Serving-engine benchmark: the price of convergence, paid once.

Measures, through ``serving.gcn_engine.GCNServingEngine`` on a throwaway
tuning store:

* **cold start** — first-ever admission of a graph: measured autotune sweep
  (cycle-model pruned), schedule build, device upload, store write;
* **warm start** — the same admission after a simulated restart (fresh
  engine + cleared in-process caches, populated store): deserialize +
  upload only, zero sweeps, zero rebuilds;
* **multi-graph batched throughput** — every resident graph serving a
  batch of perturbed-feature requests through one jitted vmapped forward
  per graph;
* **deadline-aware serving** — ``submit(..., deadline_s=)`` + a ``poll``
  loop instead of manual ``flush``: per-request latency and the
  deadline-miss rate under a tight SLA;
* **mesh throughput** — an 8-way forced host-platform mesh (subprocess,
  same harness as the sharded/distributed suites) serving the same
  multi-graph workload with graphs bin-packed across devices, vs the
  single-device engine above;
* **hot-graph saturation** — ONE graph hammered hard enough that its
  per-request-EWMA × queue-depth backlog trips the engine's replication
  policy: throughput with ``max_replicas=1`` (the pre-replica engine) vs
  the same workload after the engine has grown replicas and splits each
  batch across them, with a bit-identity check between the two engines'
  logits. The subprocess pins XLA's CPU intra-op parallelism to one
  thread: on a real mesh each device is its own silicon, but 8 forced
  host devices share this machine's cores, and without the pin a single
  device's execution already consumes them — hiding exactly the
  device-level concurrency this section measures.
"""
from __future__ import annotations

import shutil
import subprocess
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

from benchmarks import common
from repro.core import gcn
from repro.graphs import synth
from repro.tuning import registry

if common.SMOKE:
    GRAPHS = {"cora": 8, "citeseer": 8, "pubmed": 32}
    BATCH = 4
    N_FLUSHES = 2
else:
    GRAPHS = {"cora": 2, "citeseer": 2, "pubmed": 8}
    BATCH = 8
    N_FLUSHES = 5

# the SLA tracks the workload size: full-scale pubmed batches take a few
# hundred ms on CPU, so a 250 ms deadline would measure misses-by-design
DEADLINE_S = 0.25 if common.SMOKE else 1.5
N_MESH_DEVICES = 8

_SRC = str(Path(__file__).resolve().parents[1] / "src")


def _workloads():
    out = {}
    for name, scale in GRAPHS.items():
        import jax

        ds = synth.make_dataset(name, scale=scale)
        cfg = gcn.GCNConfig(ds.num_features, ds.hidden, ds.num_classes)
        params = gcn.init_params(cfg, jax.random.PRNGKey(0))
        out[name] = (ds, params)
    return out


def _run_deadline(eng, feats) -> list:
    """Deadline-driven serving: every request carries a tight SLA; the
    poll loop auto-flushes queues as their deadlines come due."""
    rows = []
    eng.reset_stats()  # isolate this section's latency/miss numbers
    rng = np.random.default_rng(1)
    n_rounds = 2 * N_FLUSHES
    t0 = time.perf_counter()
    n_req = 0
    for _ in range(n_rounds):
        for name, x in feats.items():
            for _ in range(BATCH):
                mask = (rng.random(x.shape) < 0.9).astype(np.float32)
                eng.submit(name, x * mask, deadline_s=DEADLINE_S)
                n_req += 1
        deadline_at = time.monotonic() + DEADLINE_S
        while eng.stats()["pending_requests"] or eng.stats()["inflight_requests"]:
            eng.poll()
            if time.monotonic() > deadline_at + 1.0:
                eng.flush()  # never hang the bench on a scheduling bug
    dt = time.perf_counter() - t0
    st = eng.stats()
    judged = st["deadline_met"] + st["deadline_misses"]
    miss_rate = st["deadline_misses"] / max(1, judged)
    print(f"deadline serving: {n_req} requests (SLA {DEADLINE_S * 1e3:.0f}ms)"
          f" in {dt:.2f}s = {n_req / dt:.1f} req/s; "
          f"latency mean {st['latency_us_mean'] / 1e3:.1f}ms "
          f"max {st['latency_us_max'] / 1e3:.1f}ms; "
          f"misses {st['deadline_misses']}/{judged} ({miss_rate:.1%})")
    rows.append(("serving/deadline/latency", st["latency_us_mean"],
                 f"sla_ms={DEADLINE_S * 1e3:.0f};"
                 f"max_us={st['latency_us_max']:.0f};"
                 f"req_per_s={n_req / dt:.1f}"))
    rows.append(("serving/deadline/miss_rate", miss_rate * 1e2,
                 f"misses={st['deadline_misses']};served={judged}"))
    return rows


_MESH_SCRIPT = r"""
import os, time
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=%(n_dev)d"
os.environ["BENCH_SMOKE"] = %(smoke)r
import sys
sys.path.insert(0, %(src)r)
sys.path.insert(0, %(root)r)
import numpy as np, jax
from benchmarks import serving as bench_serving
from repro.serving.gcn_engine import GCNServingEngine

loads = bench_serving._workloads()
eng = GCNServingEngine(store_root=%(store)r, devices=%(n_dev)d,
                       autotune_iters=2)
for name, (ds, params) in loads.items():
    rep = eng.add_graph(name, ds.adj, params)
    print("PLACED %%s kind=%%s dev=%%s" %% (
        name, rep.placement.kind, rep.placement.device_index))
feats = {name: np.asarray(ds.features, np.float32)
         for name, (ds, params) in loads.items()}
rng = np.random.default_rng(0)

def one_flush():
    for name, x in feats.items():
        for _ in range(bench_serving.BATCH):
            mask = (rng.random(x.shape) < 0.9).astype(np.float32)
            eng.submit(name, x * mask)
    for v in eng.flush().values():
        jax.block_until_ready(v)

one_flush()  # warmup/compile
t0 = time.perf_counter()
for _ in range(bench_serving.N_FLUSHES):
    one_flush()
dt = time.perf_counter() - t0
n_req = bench_serving.N_FLUSHES * bench_serving.BATCH * len(feats)
n_distinct = len({r.executor.device for r in eng._graphs.values()
                  if r.executor is not None and r.executor.device
                  is not None})
print("ROW mesh_throughput %%f req_per_s=%%.1f;devices=%%d;"
      "distinct_placements=%%d"
      %% (dt / n_req * 1e6, n_req / dt, %(n_dev)d, n_distinct))
"""


#: hot-graph saturation workload: scatter-heavy (high-nnz, narrow
#: features), the regime where one replica's execution is serial enough
#: that splitting a batch across clones buys real concurrency
if common.SMOKE:
    SAT = dict(n=600, density=0.02, feats=32, hidden=32, classes=8,
               batch=8, rounds=2, replicas=2)
else:
    SAT = dict(n=3000, density=0.012, feats=64, hidden=64, classes=8,
               batch=32, rounds=4, replicas=4)

_SAT_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=%(n_dev)d "
    "--xla_cpu_multi_thread_eigen=false intra_op_parallelism_threads=1")
import sys, time
sys.path.insert(0, %(src)r)
import numpy as np, jax
from repro.core import executor as exe, gcn
from repro.graphs import synth
from repro.serving.gcn_engine import GCNServingEngine
from repro.tuning import registry

SAT = %(sat)r
SWEEP = [dict(nnz_per_step=128, rows_per_window=64, cols_per_block=None,
              window_nnz=None, routing=exe.GATHER),
         dict(nnz_per_step=256, rows_per_window=64, cols_per_block=None,
              window_nnz=None, routing=exe.GATHER)]
KW = dict(iters=1, warmup=1, sweep=SWEEP, bf16_report=False)

a = synth.power_law_adjacency(SAT["n"], SAT["density"], 0.9, seed=7)
cfg = gcn.GCNConfig(SAT["feats"], SAT["hidden"], SAT["classes"])
params = gcn.init_params(cfg, jax.random.PRNGKey(7))
x = np.random.default_rng(7).random((SAT["n"], SAT["feats"]),
                                    ).astype(np.float32)
feats = [x * (1.0 - 0.01 * i) for i in range(SAT["batch"])]


def throughput(eng):
    def one_flush():
        for xi in feats:
            eng.submit("hot", xi)
        (out,) = eng.flush().values()
        jax.block_until_ready(out)
        return np.asarray(out)

    ref = one_flush()                       # warmup/compile
    t0 = time.perf_counter()
    for _ in range(SAT["rounds"]):
        out = one_flush()
    dt = time.perf_counter() - t0
    n_req = SAT["rounds"] * SAT["batch"]
    return n_req / dt, dt / n_req * 1e6, out


# --- baseline: replication capped at 1 (the pre-replica engine) ----------
eng1 = GCNServingEngine(store_root=%(store)r, devices=%(n_dev)d,
                        max_batch=2 * SAT["batch"], max_replicas=1,
                        autotune_kwargs=KW)
eng1.add_graph("hot", a, params)
rps1, us1, ref = throughput(eng1)
assert eng1.stats()["replicas"] == {}
print("ROW hot_single %%f req_per_s=%%.2f;replicas=1" %% (us1, rps1))

# --- replicated: saturation grows clones, batches split across them ------
registry.clear_caches()
eng2 = GCNServingEngine(store_root=%(store)r, devices=%(n_dev)d,
                        max_batch=2 * SAT["batch"],
                        max_replicas=SAT["replicas"],
                        replicate_after_s=1e-6, autotune_kwargs=KW)
rep = eng2.add_graph("hot", a, params)
assert rep.warm_start                   # same store entry as the baseline
eng2.serve_batch("hot", feats[:2])      # prime the saturation signal
while (len(eng2.placer.placement_of("hot").device_indices)
       < SAT["replicas"]):
    for xi in feats:
        eng2.submit("hot", xi)
    eng2.poll()                         # backlog > threshold: grow one
eng2.flush()
n_rep = len(eng2.placer.placement_of("hot").device_indices)
rps2, us2, out = throughput(eng2)
identical = bool(np.array_equal(out, ref))
assert identical, "replica logits diverged from the single-replica engine"
print("ROW hot_replicated %%f req_per_s=%%.2f;replicas=%%d;"
      "speedup=%%.2fx;bit_identical=%%d"
      %% (us2, rps2, n_rep, rps2 / rps1, int(identical)))
"""


def _run_saturation(root) -> list:
    """Hot-graph replica scaling on the forced 8-way mesh: one graph,
    ``max_replicas=1`` vs grown replicas, bit-identity asserted."""
    common.refuse_jax_children_on_tpu("serving/saturation")
    rows = []
    script = _SAT_SCRIPT % dict(n_dev=N_MESH_DEVICES, src=_SRC,
                                store=str(root), sat=SAT)
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, timeout=900)
    if r.returncode != 0:
        raise RuntimeError(f"saturation subprocess failed: "
                           f"{r.stderr[-800:]}")
    for line in r.stdout.splitlines():
        if not line.startswith("ROW "):
            continue
        _, name, us, derived = line.split(" ", 3)
        print(f"hot-graph {name.replace('hot_', '')}: {float(us):.0f} "
              f"us/req  {derived}")
        rows.append((f"serving/mesh{N_MESH_DEVICES}/{name}", float(us),
                     derived))
    return rows


def _run_mesh(root) -> list:
    """Multi-device engine throughput on a forced 8-way host mesh. The
    subprocess reuses the store the single-device section populated only
    for its own graphs' *single-device* keys — on an 8-dev mesh the small
    graphs still take the single route, so admissions warm-start."""
    common.refuse_jax_children_on_tpu("serving/mesh")
    rows = []
    script = _MESH_SCRIPT % dict(
        n_dev=N_MESH_DEVICES, src=_SRC,
        root=str(Path(__file__).resolve().parents[1]),
        store=str(root), smoke="1" if common.SMOKE else "")
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, timeout=900)
    if r.returncode != 0:
        raise RuntimeError(f"mesh serving subprocess failed: "
                           f"{r.stderr[-800:]}")
    for line in r.stdout.splitlines():
        if line.startswith("PLACED "):
            print(line)
        if not line.startswith("ROW "):
            continue
        _, name, us, derived = line.split(" ", 3)
        print(f"mesh throughput ({N_MESH_DEVICES} host devices): "
              f"{float(us):.0f} us/req  {derived}")
        rows.append((f"serving/mesh{N_MESH_DEVICES}/{name}", float(us),
                     derived))
    return rows


def run() -> list:
    from repro.serving.gcn_engine import GCNServingEngine

    rows = []
    root = tempfile.mkdtemp(prefix="awb-tuning-store-")
    print("\n== serving engine: cold vs warm start + batched throughput ==")
    try:
        loads = _workloads()

        eng = GCNServingEngine(store_root=root, autotune_iters=2)
        cold_s = {}
        for name, (ds, params) in loads.items():
            rep = eng.add_graph(name, ds.adj, params)
            assert not rep.warm_start
            cold_s[name] = rep.tune_seconds

        registry.clear_caches()  # ≈ process restart (store survives)
        eng2 = GCNServingEngine(store_root=root, autotune_iters=2)
        for name, (ds, params) in loads.items():
            t0 = time.perf_counter()
            rep = eng2.add_graph(name, ds.adj, params)
            warm = time.perf_counter() - t0
            assert rep.warm_start
            speed = cold_s[name] / max(warm, 1e-9)
            print(f"{name:10s} cold {cold_s[name]:6.2f}s  "
                  f"warm {warm * 1e3:7.1f}ms  ({speed:6.0f}x; "
                  f"{rep.device_bytes / 1024:.0f} KiB resident)")
            rows.append((f"serving/{name}/cold_start", cold_s[name] * 1e6,
                         f"sweep+build+upload;K={rep.config.nnz_per_step}"))
            rows.append((f"serving/{name}/warm_start", warm * 1e6,
                         f"store_hit;speedup={speed:.0f}x"))

        # batched multi-graph throughput on the warm engine
        rng = np.random.default_rng(0)
        feats = {name: np.asarray(ds.features, np.float32)
                 for name, (ds, params) in loads.items()}

        def one_flush():
            for name, x in feats.items():
                for _ in range(BATCH):
                    mask = (rng.random(x.shape) < 0.9).astype(np.float32)
                    eng2.submit(name, x * mask)
            outs = eng2.flush()
            for v in outs.values():
                v.block_until_ready()

        one_flush()  # warmup/compile
        t0 = time.perf_counter()
        for _ in range(N_FLUSHES):
            one_flush()
        dt = time.perf_counter() - t0
        n_req = N_FLUSHES * BATCH * len(feats)
        rps = n_req / dt
        print(f"batched throughput: {n_req} requests over {len(feats)} "
              f"graphs in {dt:.2f}s = {rps:.1f} req/s "
              f"(batch {BATCH}/graph, one jitted forward per batch)")
        rows.append(("serving/batched_throughput", dt / n_req * 1e6,
                     f"req_per_s={rps:.1f};batch={BATCH};"
                     f"graphs={len(feats)}"))

        rows.extend(_run_deadline(eng2, feats))
        rows.extend(_run_mesh(root))
        rows.extend(_run_saturation(root))
    finally:
        shutil.rmtree(root, ignore_errors=True)
    return rows
