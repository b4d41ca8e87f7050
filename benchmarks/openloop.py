"""Open-loop traffic harness: the serving engine under arrivals that don't
wait.

Every other serving number in this repo is closed-loop — the next request
politely waits for the last batch. Real traffic is open-loop: arrivals
follow their own clock, popularity is heavy-tailed, and the engine either
keeps up or melts. This suite drives ``GCNServingEngine`` with
deterministic-seed arrival traces over a Zipf graph-popularity
distribution and reports what an operator would page on:

* **steady** — Poisson arrivals at ~60% of calibrated capacity with a
  generous SLA: p50/p95/p99 latency and goodput-under-SLA (fraction of
  submitted requests served within deadline). The regime the p99-ceiling
  and goodput-floor regression gates watch.
* **steady_learned** — the *same* steady arrival trace replayed against a
  second engine running ``LearnedServiceTimePolicy`` (online ridge
  service-time predictor in place of the EWMAs), warm-started from the
  same store and pinned to the same calibrated EWMAs: a true head-to-head
  of the scheduling policies, not of the tuning. Reports the same
  p50/p99/goodput rows plus the predictor's online accuracy
  (``pred_err``, mean absolute relative error of warm predictions) and
  the goodput delta vs the heuristic — both regression-gated.
* **overload** — on/off bursty arrivals at ~2x capacity with a tight SLA,
  a small ``max_queue_depth``, and deadline-aware shedding enabled: the
  admission controller must reject queue overflow and shed provably
  unmeetable deadlines instead of letting latency diverge. Shed/reject
  rates are reported, and the overload accounting identity
  ``submitted == served + shed + rejected`` is asserted and gated.

Arrival times are passed to ``submit(..., now=t0 + arrival)`` so latency
and deadlines measure from the *scheduled* arrival, not from when the
driver loop got around to the call — the harness stays open-loop even
when the host lags.
"""

from __future__ import annotations

import shutil
import tempfile
import time

import numpy as np

from benchmarks import common
from repro.core import executor as exe
from repro.core import gcn
from repro.graphs import synth

if common.SMOKE:
    GRAPHS = {"cora": 8, "citeseer": 8, "pubmed": 32}
    BATCH = 8
    DURATION_S = 3.0
else:
    GRAPHS = {"cora": 2, "citeseer": 2, "pubmed": 8}
    BATCH = 8
    DURATION_S = 10.0

#: Zipf exponent of the graph-popularity distribution (rank 1 = hottest)
ZIPF_S = 1.1
#: arrival-rate factors relative to calibrated closed-loop capacity;
#: open-loop serving adds submit/poll overhead on top of the calibrated
#: batch compute, so "steady" sits well below 1.0
STEADY_LOAD = 0.4
OVERLOAD_LOAD = 2.0
#: SLA as a multiple of the slowest graph's calibrated batch service time
STEADY_SLA_X = 8.0
OVERLOAD_SLA_X = 4.0
#: per-graph queue bound in the overload section — deliberately below the
#: max_batch threshold so overflow hits the admission controller instead
#: of the auto-flush
OVERLOAD_QUEUE_DEPTH = BATCH // 2
#: pre-generated feature variants cycled per request (keeps rng out of
#: the arrival loop)
N_VARIANTS = 4
SEED = 1234

#: fast deterministic sweep — this suite measures serving under load, not
#: tuning, so admission cost is pinned small
_SWEEP = [
    dict(
        nnz_per_step=128,
        rows_per_window=64,
        cols_per_block=None,
        window_nnz=None,
        routing=exe.GATHER,
    ),
    dict(
        nnz_per_step=256,
        rows_per_window=64,
        cols_per_block=None,
        window_nnz=None,
        routing=exe.GATHER,
    ),
]
_TUNE_KW = dict(iters=1, warmup=1, sweep=_SWEEP, bf16_report=False)


def _poisson_arrivals(rate, duration, rng):
    """Poisson process: exponential gaps at ``rate`` /s over ``duration``."""
    t, out = 0.0, []
    while True:
        t += rng.exponential(1.0 / rate)
        if t >= duration:
            return out
        out.append(t)


def _bursty_arrivals(rate, duration, rng, period=0.4, duty=0.5):
    """On/off modulated Poisson: all arrivals land in the first ``duty``
    fraction of each ``period`` at ``rate/duty`` — same mean rate as the
    steady trace, but in bursts that slam the queues."""
    out, k = [], 0
    while k * period < duration:
        start = k * period
        end = min(start + duty * period, duration)
        t = start
        while True:
            t += rng.exponential(duty / rate)
            if t >= end:
                break
            out.append(t)
        k += 1
    return out


def _workloads():
    out = {}
    for name, scale in GRAPHS.items():
        import jax

        ds = synth.make_dataset(name, scale=scale)
        cfg = gcn.GCNConfig(ds.num_features, ds.hidden, ds.num_classes)
        params = gcn.init_params(cfg, jax.random.PRNGKey(0))
        out[name] = (ds, params)
    return out


def _variants(loads):
    """A few deterministic feature perturbations per graph, cycled by the
    arrival loop so every request is distinct but nothing is computed in
    the hot path."""
    rng = np.random.default_rng(SEED)
    out = {}
    for name, (ds, _params) in loads.items():
        x = np.asarray(ds.features, np.float32)
        out[name] = [
            x * (rng.random(x.shape) < 0.9).astype(np.float32)
            for _ in range(N_VARIANTS)
        ]
    return out


def _compile_all(eng, variants):
    """Serve every batch size in [1, BATCH] once per graph. The jitted
    vmapped forward compiles once per batch *size*; the open-loop drive
    dispatches every size, so compile them all up front — a mid-drive
    compile stall is hundreds of ms of fake service time that poisons
    the EWMAs and the percentiles. As a side effect every served batch
    feeds ``observe_service`` on the engine's policy, so a learned
    policy leaves this loop fitted across the full batch-size range."""
    for name, vs in variants.items():
        for b in range(1, BATCH + 1):
            eng.serve_batch(name, [vs[i % len(vs)] for i in range(b)])


def _calibrate(eng, variants, pops):
    """Closed-loop batch service time per graph (after compile), the
    capacity estimate the arrival rates are scaled from."""
    _compile_all(eng, variants)
    batch_s = {}
    for name, vs in variants.items():
        xs = [vs[i % len(vs)] for i in range(BATCH)]
        t0 = time.perf_counter()
        eng.serve_batch(name, xs)
        batch_s[name] = time.perf_counter() - t0
    _pin_ewmas(eng, batch_s)
    names = list(variants)
    per_req = sum(p * batch_s[n] / BATCH for n, p in zip(names, pops))
    capacity_rps = 1.0 / per_req
    for name in names:
        print(f"  calibrated {name:10s} batch({BATCH}) {batch_s[name] * 1e3:7.1f} ms")
    print(f"  capacity ~{capacity_rps:.0f} req/s (popularity-weighted, batch {BATCH})")
    return batch_s, capacity_rps


def _pin_ewmas(eng, batch_s):
    """Reset the engine's service EWMAs to the calibrated steady-state
    batch times. The warmup batch folds jit-compile seconds into the
    EWMAs, and a collapsed section leaves them inflated by queueing
    contention — either way the next section's shed predicate would
    start pessimistic enough to shed *everything*, and with nothing
    served the EWMA never corrects (an absorbing state). Each section is
    an independent experiment; it starts from the calibrated estimate."""
    for name, b in batch_s.items():
        eng._svc_ewma[name] = b
        eng._svc_req_ewma[name] = b / BATCH


def _drive(eng, variants, pops, arrivals, sla_s):
    """Replay one arrival trace open-loop against the engine; returns the
    wall time of the drive (including drain)."""
    names = list(variants)
    rng = np.random.default_rng(SEED + len(arrivals))
    assign = rng.choice(len(names), size=len(arrivals), p=pops)
    eng.reset_stats()
    t0 = time.monotonic()
    i = 0
    last_poll = 0.0
    while i < len(arrivals):
        now = time.monotonic() - t0
        if arrivals[i] <= now:
            name = names[assign[i]]
            vs = variants[name]
            eng.submit(name, vs[i % len(vs)], deadline_s=sla_s, now=t0 + arrivals[i])
            i += 1
            # bursty traces submit back-to-back; with the overload queue
            # bound below the auto-flush threshold, poll() is the only
            # dispatch path — keep it alive on a time budget so a burst
            # can't starve the engine into shedding everything
            if now - last_poll > 0.005:
                eng.poll()
                last_poll = time.monotonic() - t0
            continue
        eng.poll()
        last_poll = now
        wait = arrivals[i] - (time.monotonic() - t0)
        if wait > 0:
            time.sleep(min(wait, 0.002))
    give_up = time.monotonic() + sla_s + 5.0
    while eng.stats()["pending_requests"] or eng.stats()["inflight_requests"]:
        eng.poll()
        if time.monotonic() > give_up:
            eng.flush()  # never hang the bench on a scheduling bug
            break
        time.sleep(0.001)
    return time.monotonic() - t0


def _section_rows(tag, eng, wall, sla_s, rate):
    st = eng.stats()
    sub = st["submitted"]
    served, shed = st["queue_served"], st["shed"]
    rej, pend = st["rejected"], st["pending_requests"]
    assert sub == served + shed + rej + pend, (
        f"overload accounting identity violated: submitted={sub} != "
        f"served={served} + shed={shed} + rejected={rej} + pending={pend}"
    )
    goodput = st["deadline_met"] / max(1, sub)
    goodput_rps = st["deadline_met"] / wall
    print(
        f"  {tag}: rate {rate:.0f} req/s (sla {sla_s * 1e3:.0f} ms) -> "
        f"p50 {st['latency_us_p50'] / 1e3:.1f} ms  "
        f"p99 {st['latency_us_p99'] / 1e3:.1f} ms  "
        f"goodput {goodput:.1%} ({goodput_rps:.0f} req/s)  "
        f"shed {shed}  rejected {rej}  of {sub}"
    )
    accounting = (
        f"submitted={sub};served={served};shed={shed};rejected={rej};identity=1"
    )
    rows = [
        (
            f"openloop/{tag}/p50",
            st["latency_us_p50"],
            f"p95_us={st['latency_us_p95']:.0f};n={st['latency_n']};"
            f"rate_rps={rate:.1f}",
        ),
        (
            f"openloop/{tag}/p99",
            st["latency_us_p99"],
            f"sla_ms={sla_s * 1e3:.0f};rate_rps={rate:.1f}",
        ),
        (
            f"openloop/{tag}/goodput",
            goodput * 1e2,
            f"goodput_rps={goodput_rps:.1f};{accounting}",
        ),
    ]
    if tag == "overload":
        rows.append(
            (f"openloop/{tag}/shed_rate", (shed + rej) / max(1, sub) * 1e2, accounting)
        )
    return rows


def run() -> list:
    from repro.serving.gcn_engine import GCNServingEngine
    from repro.serving.policy import LearnedServiceTimePolicy

    rows = []
    root = tempfile.mkdtemp(prefix="awb-openloop-store-")
    print("\n== open-loop serving: Poisson/bursty arrivals, Zipf popularity ==")
    try:
        loads = _workloads()
        names = list(loads)
        w = np.array([1.0 / (i + 1) ** ZIPF_S for i in range(len(names))])
        pops = w / w.sum()
        eng = GCNServingEngine(
            store_root=root, max_batch=BATCH, autotune_kwargs=_TUNE_KW
        )
        for name, (ds, params) in loads.items():
            eng.add_graph(name, ds.adj, params)
        variants = _variants(loads)
        batch_s, capacity_rps = _calibrate(eng, variants, pops)
        sla_steady = STEADY_SLA_X * max(batch_s.values())
        sla_over = OVERLOAD_SLA_X * max(batch_s.values())
        rng = np.random.default_rng(SEED)

        # steady: 40% load, generous SLA, shedding on but rarely needed
        eng.shed_unmeetable = True
        eng.max_queue_depth = 8 * BATCH
        rate = STEADY_LOAD * capacity_rps
        arrivals = _poisson_arrivals(rate, DURATION_S, rng)
        wall = _drive(eng, variants, pops, arrivals, sla_steady)
        rows.extend(_section_rows("steady", eng, wall, sla_steady, rate))

        # steady_learned: the *same* arrival trace against a second engine
        # whose scheduling decisions read an online ridge service-time
        # model instead of the EWMAs. Warm-started from the same store
        # (zero autotune sweeps) and pinned to the same calibrated EWMAs,
        # so the only difference is the policy. The first _compile_all
        # pass pays the jit compiles — those serve times are hundreds of
        # ms of compiler, not service, and a ridge fit on them predicts
        # every deadline unmeetable (the EWMA-poisoning problem
        # _pin_ewmas solves, in model form). So: compile under a
        # throwaway policy, then attach a fresh one and feed it a second,
        # warm pass — one clean observation per (graph, batch size),
        # exactly its min_samples. reset_errors() then scopes the
        # accuracy report to predictions made during the drive.
        eng_l = GCNServingEngine(
            store_root=root,
            max_batch=BATCH,
            autotune_kwargs=_TUNE_KW,
            policy=LearnedServiceTimePolicy(),
        )
        for name, (ds, params) in loads.items():
            eng_l.add_graph(name, ds.adj, params)
        _compile_all(eng_l, variants)  # compile pass: timings are poisoned
        pol = LearnedServiceTimePolicy()
        eng_l.policy = pol
        _compile_all(eng_l, variants)  # warm pass: clean observations
        _pin_ewmas(eng_l, batch_s)
        pol.reset_errors()
        eng_l.shed_unmeetable = True
        eng_l.max_queue_depth = 8 * BATCH
        wall_l = _drive(eng_l, variants, pops, arrivals, sla_steady)
        rows.extend(_section_rows("steady_learned", eng_l, wall_l, sla_steady, rate))
        rep = pol.prediction_report()
        rows.append(
            (
                "openloop/steady_learned/pred_err",
                rep["mean_abs_rel_err"] * 1e2,
                f"n_scored={rep['n_scored']};n_samples={rep['n_samples']};"
                f"fallbacks={rep['fallbacks']};fitted={int(rep['fitted'])}",
            )
        )
        g_heur = next(v for k, v, _ in rows if k == "openloop/steady/goodput")
        g_learn = next(v for k, v, _ in rows if k == "openloop/steady_learned/goodput")
        rows.append(
            (
                "openloop/steady_learned/goodput_delta_pp",
                g_learn - g_heur,
                f"heuristic_pct={g_heur:.2f};learned_pct={g_learn:.2f}",
            )
        )
        print(
            f"  head-to-head: learned goodput {g_learn:.1f}% vs heuristic "
            f"{g_heur:.1f}% ({g_learn - g_heur:+.1f} pp); pred err "
            f"{rep['mean_abs_rel_err']:.1%} over {rep['n_scored']} predictions"
        )

        # overload: 2x capacity in bursts, tight SLA, tiny queue bound —
        # the admission controller earns its keep
        _pin_ewmas(eng, batch_s)
        eng.max_queue_depth = OVERLOAD_QUEUE_DEPTH
        rate = OVERLOAD_LOAD * capacity_rps
        arrivals = _bursty_arrivals(rate, DURATION_S, rng)
        wall = _drive(eng, variants, pops, arrivals, sla_over)
        rows.extend(_section_rows("overload", eng, wall, sla_over, rate))
    finally:
        shutil.rmtree(root, ignore_errors=True)
    return rows
