"""One run of one benchmark cell: set-up, the measured window, the drain,
the check against the reference, and the metrics.

Everything particular to a configuration, a traffic mix or a metric is in
a file of its own, found by name from ``BENCHMARK.json``:

* ``bench/configs/<config>.json`` — the architecture (``arch``), sizes,
  graph, features, stated precision, the limit of the check, and the
  deployment (engine settings, and ``add_graph``'s keyword arguments);
* ``bench/models/<arch>.py`` — the architecture: ``init_weights``, the
  plain ``reference_logits``, the work counts ``flops_per_request`` and
  ``batch_bytes``, and ``FORWARD_MODULE``, the program whose executions
  are the forward. The GCN is ``bench/models/gcn.py``;
* ``bench/traffic/<mix>.json`` — a generator kind and its parameters, read
  by ``bench/traffic/kinds/<kind>.py``;
* ``bench/metrics/<metric>.py`` — ``read(run) -> float | None`` for each
  metric, end-to-end and per-layer alike.

The program is reached through its API only: ``GCNServingEngine(...)``,
``add_graph``, ``submit``, ``poll``, ``stats``, ``reset_stats`` and the
``csc.coo_from_arrays`` input type.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import time
from collections import Counter, deque
from pathlib import Path

import numpy as np

from bench import devtrace, graph
from bench.clock import CompileClock
from bench.reference import max_rel_err

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
GID = "g"
#: rows of poll results kept for the check (as a seeded reservoir sample
#: of whole results, ``max(8, KEEP_ROWS // max_batch)`` of them)
KEEP_ROWS = 256
#: how long past the window's close a request may still be answered
DRAIN_S = 60.0


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at the benchmark's own fixed
    directory, whatever the environment says, and cache every program."""
    import jax

    path = str(BENCH / ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def log(msg: str) -> None:
    print(msg, flush=True)


def load_json(path: Path) -> dict:
    return json.loads(Path(path).read_text())


def load_module(path: Path):
    """Import one file of the benchmark (a generator kind, a metric or an
    architecture) by its path; its name may hold dots."""
    if not path.is_file():
        raise FileNotFoundError(f"no such benchmark file: {path}")
    spec = importlib.util.spec_from_file_location(f"bench_file_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """A workload of ``BENCHMARK.json`` with every file it names loaded."""

    name: str
    chips: int
    config: dict
    #: ``bench/models/<arch>.py`` of the configuration's ``arch``
    model: object
    traffic: dict
    kind: object
    end_to_end: list
    per_layer: list


def _reports(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_model(arch: str):
    """The architecture module ``bench/models/<arch>.py``."""
    return load_module(BENCH / "models" / f"{arch}.py")


def resolve(workload: str, bm: dict | None = None, root: Path = ROOT) -> Cell:
    """Find a workload's configuration, architecture, traffic, generator
    kind and metric files by the names ``BENCHMARK.json`` gives."""
    bm = load_json(root / "BENCHMARK.json") if bm is None else bm
    cells = {w["name"]: w for w in bm["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    entry = {c["name"]: c for c in bm["configs"]}[w["config"]]
    config = load_json(root / entry["file"])
    if "arch" not in config:
        raise KeyError(f"{root / entry['file']} names no 'arch': add the name of "
                       f"its bench/models/<arch>.py")
    traffic = load_json(BENCH / "traffic" / f"{w['traffic']}.json")
    return Cell(
        name=workload,
        chips=int(w["chips"]),
        config=config,
        model=load_model(config["arch"]),
        traffic=traffic,
        kind=load_module(BENCH / "traffic" / "kinds" / f"{traffic['kind']}.py"),
        end_to_end=[m for m in bm["end_to_end"] if _reports(m, workload)],
        per_layer=[m for m in bm["per_layer"] if _reports(m, workload)],
    )


def metric_reader(name: str):
    return load_module(BENCH / "metrics" / f"{name}.py")


# ---- the record of one run --------------------------------------------------


@dataclasses.dataclass
class Run:
    """What the metrics read. Times are seconds from the window's start
    (the first scheduled arrival) on the host's ``perf_counter``; NaN where
    a request never got that far."""

    cell: Cell
    seconds: float
    setup_s: float
    arrival: np.ndarray
    submit_start: np.ndarray
    submit_end: np.ndarray
    #: start of the ``submit``/``poll`` call that dispatched its batch
    dispatch_start: np.ndarray
    #: return of the call that handed back its logits (logits ready)
    done: np.ndarray
    #: the ``submit`` call filled the queue to ``max_batch`` and served it
    auto_flush: np.ndarray
    stats: dict
    nnz: int
    peak: dict | None = None
    trace: devtrace.Trace | None = None

    @property
    def in_window(self) -> np.ndarray:
        return self.arrival < self.seconds

    @property
    def latency_s(self) -> np.ndarray:
        sel = self.in_window & np.isfinite(self.done)
        return self.done[sel] - self.arrival[sel]


# ---- set-up -------------------------------------------------------------------


def check_chips(chips: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips; JAX sees {len(devs)}")
    return devs


def make_graph(config: dict):
    """The configuration's fixed graph: host arrays, and the same arrays on
    the default device for the reference."""
    import jax.numpy as jnp

    s, g = config["sizes"], config["graph"]
    rows, cols, vals = graph.power_law_adjacency(
        s["num_nodes"], g["density"], g["alpha"], seed=g["seed"],
        max_degree=g["max_degree"],
    )
    if rows.shape[0] != g["nnz"]:
        raise ValueError(f"graph has {rows.shape[0]} nnz; config says {g['nnz']}")
    dev = {
        "n": s["num_nodes"],
        "rows": jnp.asarray(rows, jnp.int32),
        "cols": jnp.asarray(cols, jnp.int32),
        "vals": jnp.asarray(vals),
    }
    return (rows, cols, vals), dev


def make_requests(config: dict, seed: int) -> list:
    s, f = config["sizes"], config["features"]
    base = graph.sparse_features(
        s["num_nodes"], s["num_features"], f["density"], seed=seed % 2**32
    )
    return graph.request_variants(
        base, int(f["variants"]), float(f["drop"]), np.random.default_rng([seed, 3])
    )


class Spans:
    """The benchmark's host spans: ``jax.profiler.TraceAnnotation`` while a
    trace is recorded, nothing otherwise."""

    def __init__(self, on: bool):
        self.on = on
        if on:
            import jax

            self._ann = jax.profiler.TraceAnnotation

    def __call__(self, name: str):
        return self._ann(name) if self.on else contextlib.nullcontext()


class Reservoir:
    """A seeded uniform sample of ``k`` poll results (device arrays and the
    request ids of their rows); a result that drops out is freed."""

    def __init__(self, k: int, seed: int):
        self.k, self.n, self.items = k, 0, []
        self.rng = np.random.default_rng([seed, 4])

    def offer(self, out, ids) -> None:
        self.n += 1
        if len(self.items) < self.k:
            self.items.append((out, ids))
        else:
            j = int(self.rng.integers(0, self.n))
            if j < self.k:
                self.items[j] = (out, ids)


def build_engine(cell: Cell, params: dict, coo, store_root: Path, **overrides):
    """A ``GCNServingEngine`` with the deployment's settings (``overrides``
    replace some), and the graph admitted with the deployment's
    ``add_graph`` keyword arguments. Returns it and the admission report."""
    from repro.serving.gcn_engine import GCNServingEngine

    kw = dict(cell.config["deployment"]["engine"], **overrides)
    eng = GCNServingEngine(store_root=store_root, **kw)
    t0 = time.perf_counter()
    rep = eng.add_graph(GID, coo, params,
                        **cell.config["deployment"].get("add_graph", {}))
    c = rep.config
    log(
        f"admitted in {time.perf_counter() - t0:.3f} s (warm_start={rep.warm_start}, "
        f"sweep {rep.tune_seconds:.3f} s): routing={c.routing} "
        f"nnz_per_step={c.nnz_per_step} rows_per_window={c.rows_per_window} "
        f"cols_per_block={c.cols_per_block_resolved} ktile={c.ktile} "
        f"reorder={c.reorder} bf16_accumulate={c.bf16_accumulate} "
        f"n_devices={c.n_devices} measured_us={c.measured_us:.1f} "
        f"device_bytes={rep.device_bytes} placement={rep.placement.kind}"
    )
    return eng, rep


@dataclasses.dataclass
class Setup:
    """What one run sets up before its window: the graph (host arrays, the
    program's COO input, the reference's device arrays), the weights and
    request features from the seed, and the warmed engine."""

    rows: np.ndarray
    coo: object
    graph_dev: dict
    weights: dict
    xs: list
    eng: object
    rep: object


def setup(cell: Cell, seed: int, store: Path, **overrides) -> Setup:
    """Make the graph, the weights and the requests, admit the graph to an
    engine of the deployment (``overrides`` replace some of its settings),
    and warm up every shape the cell's traffic uses."""
    from repro.core import csc

    (rows, cols, vals), graph_dev = make_graph(cell.config)
    n = cell.config["sizes"]["num_nodes"]
    coo = csc.coo_from_arrays(rows, cols, vals, (n, n))
    weights = cell.model.init_weights(cell.config["sizes"], seed)
    eng, rep = build_engine(cell, weights, coo, store, **overrides)
    xs = make_requests(cell.config, seed)
    warm_up(eng, cell, xs, cell.traffic.get("deadline_s"))
    return Setup(rows, coo, graph_dev, weights, xs, eng, rep)


def replicas(eng) -> int:
    devs = eng.stats()["replicas"].get(GID)
    return 1 if devs is None else len(devs)


def warm_up(eng, cell: Cell, xs: list, deadline_s) -> None:
    """Drive every shape the window will use through ``submit`` + ``poll``:
    first until the graph holds the deployment's replicas, then the traffic
    kind's own warm-up plan, then one round of each of its sizes with every
    batch awaited and joined (``flush``)."""
    max_batch = int(cell.config["deployment"]["engine"].get("max_batch", 32))
    want = int(cell.config["deployment"].get("replicas", 1))
    plan = cell.kind.make(cell.traffic, 0, 1.0).warmup(max_batch)
    rounds = 0
    while replicas(eng) < want:
        if rounds >= 16:
            raise RuntimeError(
                f"the graph holds {replicas(eng)} replicas after {rounds} warm-up "
                f"rounds; the deployment asks for {want}"
            )
        _warm_round(eng, xs, plan[0], deadline_s)
        rounds += 1
    for n in plan:
        _warm_round(eng, xs, n, deadline_s)
    # a poll that finds every batch in flight settled joins them into one
    # result, a program per shape that the rounds above reach only by
    # chance; flush() always joins, so each compiles here, not in the window
    for n in sorted(set(plan)):
        _warm_round(eng, xs, n, deadline_s, join=True)
    if replicas(eng) != want:
        raise RuntimeError(f"warm-up left {replicas(eng)} replicas; want {want}")


def _warm_round(eng, xs, n: int, deadline_s, join: bool = False) -> None:
    import jax

    got = 0
    for i in range(n):
        eng.submit(GID, xs[i % len(xs)], deadline_s=deadline_s)
    while got < n:
        out = (eng.flush() if join else eng.poll()).get(GID)
        if out is not None:
            got += int(jax.block_until_ready(out).shape[0])


# ---- the window ---------------------------------------------------------------


def drive(eng, cell: Cell, xs: list, seed: int, seconds: float, spans: Spans,
          keep: Reservoir, t0: float):
    """The measured window and its drain. Returns the per-request arrays."""
    import jax

    clock = time.perf_counter
    gen = cell.kind.make(cell.traffic, seed, seconds)
    deadline_s = cell.traffic.get("deadline_s")
    max_batch = int(cell.config["deployment"]["engine"].get("max_batch", 32))
    vrng = np.random.default_rng([seed, 5])
    cap = 1 << 12
    cols = {k: np.full(cap, np.nan) for k in
            ("arrival", "submit_start", "submit_end", "dispatch_start", "done")}
    auto = np.zeros(cap, bool)
    variant = vrng.integers(0, len(xs), cap)
    fifo: deque = deque()  # submitted, not yet handed back
    queued: list = []  # submitted, not yet dispatched
    n = 0
    close = seconds + DRAIN_S
    window = spans("bench.window")
    window.__enter__()
    in_window = True
    while True:
        now = clock() - t0
        if now > close:
            break
        if in_window and now >= seconds:
            window.__exit__(None, None, None)
            in_window = False
        with spans("bench.generate"):
            due = gen.take(now, len(fifo))
        for a in due:
            if n == cap:
                cap *= 2
                for k, v in cols.items():
                    cols[k] = np.concatenate([v, np.full(v.shape, np.nan)])
                auto = np.concatenate([auto, np.zeros(auto.shape, bool)])
                variant = np.concatenate([variant, vrng.integers(0, len(xs), cap // 2)])
            rid, n = n, n + 1
            cols["arrival"][rid] = a
            s0 = clock() - t0
            with spans("bench.submit"):
                ticket = eng.submit(GID, xs[variant[rid]], deadline_s=deadline_s)
            cols["submit_start"][rid] = s0
            cols["submit_end"][rid] = clock() - t0
            if not ticket.accepted:
                continue  # rejected or shed: never answered, counted failed
            fifo.append(rid)
            queued.append(rid)
            if len(queued) >= max_batch:
                auto[rid] = True
                cols["dispatch_start"][queued] = s0
                queued = []
        if fifo:
            p0 = clock() - t0
            with spans("bench.poll"):
                out = eng.poll().get(GID)
                if out is not None:
                    jax.block_until_ready(out)
            p1 = clock() - t0
            if out is None:
                continue
            k = int(out.shape[0])
            if k > len(fifo):
                raise RuntimeError(
                    f"poll handed back {k} rows for {len(fifo)} requests"
                )
            ids = [fifo.popleft() for _ in range(k)]
            cols["done"][ids] = p1
            head = set(ids)
            cols["dispatch_start"][[r for r in queued if r in head]] = p0
            queued = [r for r in queued if r not in head]
            keep.offer(out, ids)
        elif gen.finished(now):
            break
        else:
            nxt = gen.next_time()
            if nxt is not None and nxt > now:
                with spans("bench.idle_wait"):
                    time.sleep(nxt - now)
    if in_window:
        window.__exit__(None, None, None)
    return {k: v[:n] for k, v in cols.items()}, auto[:n], variant[:n]


# ---- the check ----------------------------------------------------------------


def check(keep: Reservoir, variant: np.ndarray, xs: list, weights: dict,
          graph_dev: dict, cell: Cell, platform: str,
          precision: dict | None = None) -> dict:
    """Compare every row of the kept poll results with the reference over
    the same features. Returns the largest relative error, and how many
    rows read above the limit."""
    import jax

    limit = cell.config["correct"]["max_rel_err"]
    precision = cell.config["precision"] if precision is None else precision
    refs, worst, wrong, rows = {}, 0.0, 0, 0
    for out, ids in keep.items:
        got = np.asarray(jax.device_get(out))
        for row, rid in enumerate(ids):
            v = int(variant[rid])
            if v not in refs:
                refs[v] = cell.model.reference_logits(
                    xs[v], weights, graph_dev, precision, platform
                )
            err = max_rel_err(got[row], refs[v])
            worst = max(worst, err)
            wrong += int(not err <= limit)
            rows += 1
    return {"max_rel_err": worst, "wrong": wrong, "rows": rows, "limit": limit}


# ---- trace --------------------------------------------------------------------


def trace_dir() -> Path:
    return BENCH / ".out" / "trace"


def start_trace() -> None:
    import jax

    shutil.rmtree(trace_dir(), ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir()), profiler_options=opts)


def stop_trace(save_to: Path | None = None) -> devtrace.Trace:
    import jax

    jax.profiler.stop_trace()
    files = sorted(trace_dir().glob("plugins/profile/*/*.xplane.pb"))
    if not files:
        raise RuntimeError("the profiler wrote no trace")
    if save_to is not None:
        shutil.copy(files[-1], save_to)
    tr = devtrace.load(files[-1])
    shutil.rmtree(trace_dir(), ignore_errors=True)
    return tr


def read_trace(save_to: Path | None, cell: Cell, require_tpu: bool) -> devtrace.Trace:
    """Stop the profiler and load its trace; on a chip, refuse a trace the
    readers could not read (``devtrace.require``)."""
    tr = stop_trace(save_to)
    if require_tpu:
        devtrace.require(tr, cell.chips, cell.model.FORWARD_MODULE)
    return tr


def breakdown(tr: devtrace.Trace) -> dict:
    lo, hi = tr.window()
    return {
        "device_ops": devtrace.top(devtrace.op_seconds(tr, lo, hi)),
        "idle_gaps": devtrace.top(devtrace.idle_by_span(tr, lo, hi)),
    }


# ---- the run --------------------------------------------------------------------


def peak_table(device_kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in bench/peaks.json")
    return table[device_kind]


def run(workload: str, seed: int, seconds: float, trace: bool, *, t_start: float,
        require_tpu: bool = True, cell: Cell | None = None,
        store_root: Path | None = None, save_trace: Path | None = None) -> dict:
    """One run of one cell; returns the result line (without printing it)."""
    import jax

    cell = resolve(workload) if cell is None else cell
    devs = check_chips(cell.chips) if require_tpu else jax.devices()
    dev = devs[0]
    peak = peak_table(dev.device_kind) if require_tpu else None
    clock = CompileClock()
    s = setup(cell, seed, BENCH / ".store" if store_root is None else store_root)
    eng = s.eng
    eng.reset_stats()
    spans = Spans(trace)
    if trace:
        start_trace()
    comp_s, n_comp, n_hits, _ = clock.take()
    log(f"set-up: {n_comp} backend compiles ({comp_s:.3f} s), {n_hits} cache hits; "
        f"replicas {replicas(eng)}")
    max_batch = int(cell.config["deployment"]["engine"].get("max_batch", 32))
    keep = Reservoir(max(8, KEEP_ROWS // max_batch), seed)
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    times, auto, variant = drive(eng, cell, s.xs, seed, seconds, spans, keep, t0)
    comp_s, n_comp, n_hits, names = clock.take()
    tr = read_trace(save_trace, cell, require_tpu) if trace else None
    stats = eng.stats()
    mem = max(
        int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
        for d in devs[: cell.chips]
    )
    log(f"window: {n_comp} backend compiles ({comp_s:.3f} s), {n_hits} cache hits; "
        f"replicas {replicas(eng)}; compiled: {dict(Counter(names))}")
    del eng
    s.eng = None
    gc.collect()

    r = Run(cell=cell, seconds=seconds, setup_s=setup_s, auto_flush=auto, stats=stats,
            nnz=int(s.rows.shape[0]), peak=peak, trace=tr, **times)
    attempted = int(r.in_window.sum())
    answered = np.isfinite(r.done) & r.in_window
    unanswered = attempted - int(answered.sum())
    res = check(keep, variant, s.xs, s.weights, s.graph_dev, cell, dev.platform)
    failed = unanswered + res["wrong"]
    _log_details(r, stats, mem)
    metrics = {}
    for m in cell.per_layer if trace else cell.end_to_end:
        value = metric_reader(m["name"]).read(r)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devs),
              "memory_peak_bytes": mem}
    correct = failed == 0 and res["rows"] >= 1 and math.isfinite(res["max_rel_err"])
    line = {"correct": correct, "attempted": attempted, "failed": failed,
            "metrics": metrics, "device": device}
    if tr is not None:
        lo, hi = tr.window()
        busy = [devtrace.busy_ns(d, lo, hi) for d in tr.devices[: cell.chips]]
        device["busy_s"] = float(np.mean(busy)) * 1e-9 if busy else 0.0
        device["window_s"] = (hi - lo) * 1e-9
        line["breakdown"] = breakdown(tr)
    line["checks"] = {
        "max_rel_err": {"value": res["max_rel_err"], "limit": res["limit"]},
        "unanswered": {"value": unanswered, "limit": 0},
        "rows_compared": {"value": res["rows"], "limit": 1},
    }
    return line


def _log_details(r: Run, stats: dict, mem: int) -> None:
    sel = r.in_window & np.isfinite(r.submit_start)
    lag = (r.submit_start - r.arrival)[sel] * 1e3
    disp = r.dispatch_start[np.isfinite(r.dispatch_start)]
    sizes = Counter(Counter(disp.tolist()).values())
    log(f"requests: {int(r.in_window.sum())} in the window, "
        f"{int(np.isfinite(r.done).sum())} answered; engine batches "
        f"{stats['batches']}, requests {stats['requests']}")
    log(f"batch sizes (size: batches): {dict(sorted(sizes.items()))}")
    if lag.size:
        log(f"generator lag ms: p50 {np.percentile(lag, 50):.3f} "
            f"p95 {np.percentile(lag, 95):.3f} max {lag.max():.3f}")
    log(f"peak_bytes_in_use {mem}")


def print_checks(line: dict, out=sys.stderr) -> None:
    for name, c in line["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})", file=out)
