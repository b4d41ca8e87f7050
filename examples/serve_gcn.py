"""End-to-end driver (the paper's workload is inference): serve batched GCN
inference over multiple resident graphs with the AWB engine.

    PYTHONPATH=src python examples/serve_gcn.py

Trains small 2-layer GCNs on two synthetic graphs, admits them into a
``GCNServingEngine`` backed by an on-disk tuning store — the first
admission runs the measured autotune sweep (pruned by the paper's cycle
model) and persists the converged configuration + schedule — then
**simulates a process restart**: a fresh engine on the same store
warm-starts every graph with zero measured sweeps and zero schedule
rebuilds (the paper's "after converging, reuses the ideal configuration",
made durable). It then serves batched feature-perturbation requests
through one jitted vmapped forward per graph and reports throughput, plus
the AWB-vs-static utilization the balancing buys — first with manual
``flush()``, then deadline-driven: every ``submit(..., deadline_s=)``
carries an SLA and a ``poll()`` loop auto-flushes queues
earliest-deadline-first, reporting per-request latency and the miss rate.

On a multi-device host the same engine takes ``devices=N`` and bin-packs
graphs across the mesh (giant graphs shard across all of it); see
``tests/test_placement.py`` for the 8-way forced-host-mesh drive.
"""
import os

# the replication demo needs a mesh: if the host would expose a single
# CPU device, force 4 host-platform devices (must land before jax loads)
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=4")

import shutil  # noqa: E402
import tempfile  # noqa: E402
import time  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import gcn, schedule  # noqa: E402
from repro.graphs import synth  # noqa: E402
from repro.serving.gcn_engine import GCNServingEngine  # noqa: E402
from repro.tuning import registry  # noqa: E402


def train_workload(name: str, scale: int, seed: int):
    ds = synth.make_dataset(name, scale=scale)
    cfg = gcn.GCNConfig(ds.num_features, ds.hidden, ds.num_classes)
    params = gcn.init_params(cfg, jax.random.PRNGKey(seed))
    x = jnp.asarray(ds.features)
    labels = jnp.asarray(ds.labels)
    val_grad = jax.jit(jax.value_and_grad(
        lambda p: gcn.loss_fn(p, ds.adj, x, labels)))
    for _ in range(60):
        loss, g = val_grad(params)
        params = jax.tree.map(lambda p, gg: p - 0.5 * gg, params, g)
    acc = float(gcn.accuracy(params, ds.adj, x, labels))
    print(f"  {name}: trained (loss {float(loss):.3f}, fit-acc {acc:.2%}, "
          f"chance {1 / ds.num_classes:.2%})")
    return ds, params


def main():
    store_root = tempfile.mkdtemp(prefix="awb-serve-store-")
    try:
        print("training inference weights:")
        loads = {name: train_workload(name, scale, i)
                 for i, (name, scale) in enumerate(
                     [("pubmed", 4), ("cora", 1)])}

        # ---- cold start: converge once, persist ------------------------
        print("\ncold start (measured sweep -> store):")
        engine = GCNServingEngine(store_root=store_root)
        for name, (ds, params) in loads.items():
            rep = engine.add_graph(name, ds.adj, params)
            cfg = rep.config
            naive = schedule.build_naive_schedule(
                ds.adj, cfg.nnz_per_step, cfg.rows_per_window)
            print(f"  {name}: tuned in {rep.tune_seconds:.2f}s -> "
                  f"K={cfg.nnz_per_step} R={cfg.rows_per_window} "
                  f"ktile={cfg.ktile} routing={cfg.routing} "
                  f"({cfg.measured_us:.0f}us/spmm, bf16 max-err "
                  f"{cfg.bf16_max_err:.1e}); AWB util "
                  f"{cfg.utilization:.1%} vs static {naive.utilization:.1%}")

        # ---- restart: warm start from the store ------------------------
        print("\nsimulated restart (fresh engine, same store):")
        registry.clear_caches()  # drop every in-process cache
        engine = GCNServingEngine(store_root=store_root,
                                  devices=len(jax.devices()),
                                  max_replicas=2, replicate_after_s=0.05,
                                  replica_shrink_after=2)
        for name, (ds, params) in loads.items():
            t0 = time.time()
            rep = engine.add_graph(name, ds.adj, params)
            assert rep.warm_start, "store should have been hit"
            print(f"  {name}: warm-started in {time.time() - t0:.3f}s "
                  f"(zero sweeps, zero rebuilds, "
                  f"{rep.device_bytes / 1024:.0f} KiB resident)")

        # ---- serve batched requests over both graphs -------------------
        n_batches, batch = 5, 8
        rng = np.random.default_rng(1)
        t0 = time.time()
        for _ in range(n_batches):
            for name, (ds, params) in loads.items():
                x = np.asarray(ds.features, np.float32)
                for _ in range(batch):
                    mask = (rng.random(x.shape) < 0.9).astype(np.float32)
                    engine.submit(name, x * mask)
            outs = engine.flush()
            for v in outs.values():
                v.block_until_ready()
        dt = time.time() - t0
        n_req = n_batches * batch * len(loads)
        print(f"\nserved {n_req} requests over {len(loads)} graphs in "
              f"{dt:.2f}s ({n_req / dt:.1f} req/s, one jitted forward per "
              f"graph-batch)")

        # ---- deadline-aware serving: SLAs instead of manual flush ------
        engine.reset_stats()
        sla_s = 1.0
        for _ in range(n_batches):
            for name, (ds, params) in loads.items():
                x = np.asarray(ds.features, np.float32)
                for _ in range(batch):
                    mask = (rng.random(x.shape) < 0.9).astype(np.float32)
                    engine.submit(name, x * mask, deadline_s=sla_s)
            # the poll loop is the serving thread: queues auto-flush
            # earliest-deadline-first as their SLAs come due
            while engine.stats()["pending_requests"]:
                engine.poll()
                time.sleep(0.01)
        st = engine.stats()
        judged = st["deadline_met"] + st["deadline_misses"]
        print(f"deadline serving ({sla_s * 1e3:.0f}ms SLA): "
              f"{st['deadline_met']}/{judged} met, latency mean "
              f"{st['latency_us_mean'] / 1e3:.0f}ms "
              f"max {st['latency_us_max'] / 1e3:.0f}ms")

        # ---- one hot graph saturates its device: replicate it ----------
        # hammer a single graph until its backlog (per-request service
        # EWMA x queue depth) trips the replication policy; the clone is
        # warm (same store entry: one upload, zero sweeps) and batches
        # split across replicas behind a least-outstanding-work balancer
        hot = "pubmed"
        ds, params = loads[hot]
        x = np.asarray(ds.features, np.float32)
        for _ in range(3 * batch):
            mask = (rng.random(x.shape) < 0.9).astype(np.float32)
            engine.submit(hot, x * mask, deadline_s=0.0)
        engine.poll()  # due now; the backlog grows a replica first
        st = engine.stats()
        print(f"\nhot-graph replication: {hot!r} now on devices "
              f"{st['replicas'].get(hot, '— (already drained)')} "
              f"(+{st['replicas_added']} replica)")
        for _ in range(3):
            engine.poll()  # idle polls: pressure gone, replicas shed
        st = engine.stats()
        print(f"after idle polls: replicas={st['replicas']} "
              f"(dropped {st['replicas_dropped']})")

        # engine output matches the reference forward
        for name, (ds, params) in loads.items():
            x = jnp.asarray(ds.features)
            ref = gcn.forward(params, ds.adj, x)
            got = engine.infer(name, x)
            err = float(jnp.abs(ref - got).max())
            print(f"  {name}: engine-vs-ref err {err:.1e}")
            assert err < 1e-3
        print("stats:", engine.stats())
        print("OK")
    finally:
        shutil.rmtree(store_root, ignore_errors=True)


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
