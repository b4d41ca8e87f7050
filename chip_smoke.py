"""Smoke run of the served GCN path on the TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the four-chip mesh routes, and only those

One chip: full-size Pubmed (19,717 nodes x 500 features, hidden 16, 3
classes, from ``synth.make_dataset``) with random weights from ``--seed``.
It is admitted into a ``GCNServingEngine`` on a fresh tuning store (a cold
admission: the measured autotune sweep runs on the chip), then into a second
engine on the same store (a warm start: no sweep, no rebuild). The warm
engine serves requests through ``submit`` + ``poll``/``flush`` at three
batch sizes. Every served logit matrix is checked against ``gcn.forward``
at "highest" matmul precision, and the compiled Pallas kernel is checked
once against the same reference SpMM.

Four chips: full Pubmed is forced onto the sharded route by a per-device
budget below its footprint, and full Cora is made hot until it replicates
across the chips. Both are checked against a one-device engine on the same
tuning store and against the reference.

Everything runs in this one process. Without a TPU the script exits
non-zero and prints no result. The last line of standard output is one JSON
object, ``{"ok": true, "device": {"platform", "kind", "count"}}``. The
numbers printed above it come from one smoke run; they are not benchmark
measurements.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
#: tuning stores of this script, wiped at start so the first admission is cold
WORK = ROOT / ".chip_smoke"

#: |served - reference| <= TOL * max|reference|, per request. The reference
#: runs every matmul at "highest" precision. The served X.W runs at the TPU's
#: default precision, which rounds f32 operands to bfloat16 (8 significant
#: bits); simulating that rounding on full Pubmed on the host gives 2.0e-3 to
#: 2.4e-3 of max|reference|, so 1e-2 leaves a 4x margin and still catches a
#: wrong route, a dropped slot or a mis-permuted row.
TOL = 1e-2
#: two routes of one schedule (sharded vs one device, replica vs one device)
#: differ only in how f32 partial sums associate
ROUTE_TOL = 1e-4
#: the Pallas kernel's one-hot contractions run at HIGHEST precision, so its
#: A.B is exact f32 up to summation order: 2.9e-7 of max|reference| for the
#: same schedule through the XLA one-hot body on the host. bfloat16 rounding
#: of B (3.4e-3) fails this limit by far
PALLAS_TOL = 1e-5
BATCH_SIZES = (1, 4, 8)


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileClock:
    """Seconds JAX spent in backend compiles since the last ``take``."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.total = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_) -> None:
        if event == self.EVENT:
            self.total += secs
            self.count += 1

    def take(self) -> tuple[float, int]:
        out = (self.total, self.count)
        self.total, self.count = 0.0, 0
        return out


def model(name: str, seed: int):
    import jax

    from repro.core import gcn
    from repro.graphs import synth

    ds = synth.make_dataset(name, seed=seed, scale=1)
    cfg = gcn.GCNConfig(ds.num_features, ds.hidden, ds.num_classes)
    return ds, gcn.init_params(cfg, jax.random.PRNGKey(seed))


def requests(ds, n: int, seed: int):
    """``n`` feature matrices: the dataset's features with a random tenth of
    the entries dropped, as distinct requests."""
    import numpy as np

    rng = np.random.default_rng(seed)
    x = np.asarray(ds.features, np.float32)
    return [x * (rng.random(x.shape) >= 0.1) for _ in range(n)]


def reference(ds, params):
    """Jitted ``gcn.forward`` with every matmul at "highest" precision."""
    import jax

    from repro.core import gcn

    fwd = jax.jit(lambda x: gcn.forward(params, ds.adj, x))

    def run(x):
        # the precision is read when the call traces, so it wraps the call
        with jax.default_matmul_precision("highest"):
            return jax.block_until_ready(fwd(x))

    return run


def rel_err(got, ref) -> tuple[float, float]:
    import numpy as np

    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    err = float(np.abs(got - ref).max())
    return err, err / max(float(np.abs(ref).max()), 1e-30)


def check(what: str, got, ref, tol: float) -> float:
    err, rel = rel_err(got, ref)
    log(f"  {what}: max abs err {err:.3e}, relative {rel:.3e} (limit {tol:g})")
    if not rel <= tol:
        raise AssertionError(f"{what}: relative error {rel:.3e} above {tol:g}")
    return rel


def describe(cfg) -> str:
    return (
        f"routing={cfg.routing} nnz_per_step={cfg.nnz_per_step} "
        f"rows_per_window={cfg.rows_per_window} "
        f"cols_per_block={cfg.cols_per_block_resolved} ktile={cfg.ktile} "
        f"reorder={cfg.reorder} bf16_accumulate={cfg.bf16_accumulate} "
        f"n_devices={cfg.n_devices}"
    )


def peak_bytes(dev) -> str:
    stats = dev.memory_stats() or {}
    return str(stats.get("peak_bytes_in_use", "not reported"))


def one_chip(seed: int) -> int:
    import jax
    import numpy as np

    from repro.core import schedule, spmm
    from repro.kernels import spmm_pallas
    from repro.serving.gcn_engine import GCNServingEngine
    from repro.tuning import registry, space

    clock = CompileClock()
    dev = jax.devices()[0]
    t0 = time.perf_counter()
    ds, params = model("pubmed", seed)
    nnz = int(np.asarray(ds.adj.row).shape[0])
    log(
        f"pubmed: {ds.num_nodes} nodes x {ds.num_features} features, hidden "
        f"{ds.hidden}, {ds.num_classes} classes, {nnz} nnz "
        f"(generated in {time.perf_counter() - t0:.2f} s)"
    )
    store = WORK / "store1"

    # ---- cold admission: the measured sweep on the chip -------------------
    clock.take()
    eng = GCNServingEngine(store_root=store)
    t0 = time.perf_counter()
    rep = eng.add_graph("pubmed", ds.adj, params)
    cold_s = time.perf_counter() - t0
    comp_s, n_comp = clock.take()
    assert not rep.warm_start, "a fresh store must not warm-start"
    log(f"admit cold: {cold_s:.3f} s (sweep {rep.tune_seconds:.3f} s, "
        f"{n_comp} backend compiles, {comp_s:.3f} s compiling)")
    log(f"  chosen: {describe(rep.config)}; measured "
        f"{rep.config.measured_us:.1f} us/spmm; {rep.device_bytes} device bytes")
    del eng

    # ---- warm start: a second engine on the same store --------------------
    registry.clear_caches()  # as a process restart would
    eng = GCNServingEngine(store_root=store)
    t0 = time.perf_counter()
    rep2 = eng.add_graph("pubmed", ds.adj, params)
    warm_s = time.perf_counter() - t0
    assert rep2.warm_start, "the second engine must warm-start from the store"
    assert rep2.config == rep.config
    log(f"admit warm: {warm_s:.3f} s (warm_start={rep2.warm_start})")

    # ---- serve through submit + poll/flush --------------------------------
    ref = reference(ds, params)
    xs = requests(ds, sum(BATCH_SIZES), seed + 1)
    worst = 0.0
    at = 0
    for b in BATCH_SIZES:
        batch = xs[at : at + b]
        at += b
        clock.take()
        t0 = time.perf_counter()
        for x in batch:
            ticket = eng.submit("pubmed", x, deadline_s=0.0 if b == 1 else None)
            assert ticket.accepted, ticket
        out = eng.poll() if b == 1 else eng.flush()
        got = jax.block_until_ready(out["pubmed"])
        first_s = time.perf_counter() - t0
        comp_s, n_comp = clock.take()
        assert got.shape == (b, ds.num_nodes, ds.num_classes), got.shape
        assert bool(np.isfinite(np.asarray(got)).all()), "non-finite logits"
        log(f"batch {b} via {'poll' if b == 1 else 'flush'}: {first_s:.3f} s "
            f"first call ({n_comp} backend compiles, {comp_s:.3f} s compiling)")
        for i, x in enumerate(batch):
            worst = max(worst, check(f"request {i}", got[i], ref(x), TOL))
    st = eng.stats()
    assert st["queue_served"] == sum(BATCH_SIZES), st["queue_served"]
    log(f"served {st['queue_served']} requests; worst relative error "
        f"{worst:.3e}; peak_bytes_in_use {peak_bytes(dev)}")

    # ---- the compiled Pallas kernel against the reference SpMM ------------
    cb = schedule.auto_cols_per_block(ds.num_nodes)
    k = space.density_matched_k(ds.adj, 32, cb)
    sched = schedule.build_balanced_schedule(ds.adj, k, 32, cols_per_block="auto")
    b = jax.numpy.asarray(
        np.random.default_rng(seed + 2).standard_normal((ds.num_nodes, 16)),
        jax.numpy.float32,
    )
    clock.take()
    t0 = time.perf_counter()
    got = spmm_pallas.spmm_balanced(sched, b, routing="onehot")
    got = jax.block_until_ready(got)
    first_s = time.perf_counter() - t0
    comp_s, n_comp = clock.take()
    with jax.default_matmul_precision("highest"):
        want = spmm.spmm_coo(ds.adj, b)
    log(f"pallas kernel (compiled, routing onehot, {sched.n_steps} steps, "
        f"K={k}, CB={cb}): {first_s:.3f} s first call ({n_comp} backend "
        f"compiles, {comp_s:.3f} s compiling)")
    check("pallas A.B", got, want, PALLAS_TOL)
    return 1


def four_chips(seed: int) -> int:
    import jax
    import numpy as np

    from repro.serving.gcn_engine import GCNServingEngine
    from repro.serving.placement import SHARDED

    devs = jax.devices()
    if len(devs) < 4:
        raise SystemExit(f"--chips 4 needs four devices; JAX sees {len(devs)}")
    big, big_params = model("pubmed", seed)
    hot, hot_params = model("cora", seed + 1)
    # one gather geometry: this phase exercises the mesh routes, not tuning
    kw = dict(
        sweep=[dict(nnz_per_step=256, rows_per_window=64, cols_per_block=None,
                    window_nnz=None, routing="gather")],
        iters=1, warmup=1, bf16_report=False,
    )
    store = WORK / "store4"
    one = GCNServingEngine(store_root=store, devices=[devs[0]], autotune_kwargs=kw)
    # a per-device budget of 3/4 of Pubmed's pre-tune footprint estimate
    # (1.9 MB at full size) forces it onto the sharded route, and leaves
    # room for its shard plus one Cora clone on every device
    budget = one._estimate_bytes(big.adj, big_params) * 3 // 4
    mesh = GCNServingEngine(
        store_root=store, devices=4, device_budget_bytes=budget, max_batch=16,
        max_replicas=4, replicate_after_s=1e-6, replica_shrink_after=2,
        autotune_kwargs=kw,
    )
    t0 = time.perf_counter()
    rep = mesh.add_graph("pubmed", big.adj, big_params)
    log(f"pubmed on 4 chips: {rep.placement.kind} in "
        f"{time.perf_counter() - t0:.3f} s; {describe(rep.config)}")
    assert rep.placement.kind == SHARDED, rep.placement
    rep = mesh.add_graph("cora", hot.adj, hot_params)
    log(f"cora on 4 chips: {rep.placement.kind} on device "
        f"{rep.placement.device_index}")
    for gid, ds, p in (("pubmed", big, big_params), ("cora", hot, hot_params)):
        one.add_graph(gid, ds.adj, p)

    # sharded route vs one device vs the reference
    xs = requests(big, 4, seed + 2)
    got = jax.block_until_ready(mesh.serve_batch("pubmed", xs))
    base = jax.block_until_ready(one.serve_batch("pubmed", xs))
    ref = reference(big, big_params)
    for i, x in enumerate(xs):
        want = ref(x)
        check(f"pubmed sharded vs 1 device, request {i}", got[i], base[i],
              ROUTE_TOL)
        check(f"pubmed sharded vs reference, request {i}", got[i], want, TOL)

    # a hot graph grows replicas; its batches split across them
    xs = requests(hot, 8, seed + 3)
    mesh.serve_batch("cora", xs[:2])  # primes the service-time signal
    polls = 0
    while len(mesh.placer.placement_of("cora").device_indices) < 4 and polls < 8:
        for x in xs:
            mesh.submit("cora", x)
        mesh.poll()
        polls += 1
    mesh.flush()
    devices = mesh.placer.placement_of("cora").device_indices
    log(f"cora replicas after {polls} polls: devices {list(devices)}")
    assert len(devices) >= 2, "the hot graph did not replicate"
    for x in xs:
        mesh.submit("cora", x)
    got = jax.block_until_ready(mesh.flush()["cora"])
    base = jax.block_until_ready(one.serve_batch("cora", xs))
    ref = reference(hot, hot_params)
    same = bool(np.array_equal(np.asarray(got), np.asarray(base)))
    log(f"cora across {len(devices)} replicas: bit-identical to 1 device: {same}")
    for i, x in enumerate(xs):
        check(f"cora replicated vs 1 device, request {i}", got[i], base[i],
              ROUTE_TOL)
        check(f"cora replicated vs reference, request {i}", got[i], ref(x), TOL)
    for d in devs[:4]:
        log(f"  {d}: peak_bytes_in_use {peak_bytes(d)}")
    return 4


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from repro.compile_cache import enable_compile_cache

    log(f"device: {dev.device_kind} x{len(jax.devices())}; jax {jax.__version__}; "
        f"compile cache {enable_compile_cache()}")
    shutil.rmtree(WORK, ignore_errors=True)
    t0 = time.perf_counter()
    count = one_chip(args.seed) if args.chips == 1 else four_chips(args.seed)
    log(f"total {time.perf_counter() - t0:.3f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
