"""Admission control under overload: typed submit tickets, bounded
queues (REJECTED at ``max_queue_depth``), deadline-aware shedding driven
by the EDF load map's predicted wait (clock-injected, so the shed-iff
predicate is asserted exactly), the overload accounting identity
``submitted == queue_served + shed + rejected + pending``, the unified
``UnknownGraphError`` across every serve path, and the backpressure
surface in ``stats()``."""
import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.core import executor as exe, gcn  # noqa: E402
from repro.graphs import synth  # noqa: E402
from repro.serving.gcn_engine import (ACCEPTED, REJECTED,  # noqa: E402
                                      SHED, GCNServingEngine,
                                      SubmitTicket, UnknownGraphError)
from repro.tuning import registry  # noqa: E402

N_NODES = 220
N_FEATS = 20
N_CLASSES = 5

FAST_SWEEP = [
    dict(nnz_per_step=64, rows_per_window=32, cols_per_block=None,
         window_nnz=None, routing=exe.GATHER),
    dict(nnz_per_step=128, rows_per_window=64, cols_per_block=None,
         window_nnz=None, routing=exe.GATHER),
]
FAST_KW = dict(iters=1, warmup=1, sweep=FAST_SWEEP, bf16_report=False)


@pytest.fixture(autouse=True)
def _fresh_caches():
    registry.clear_caches()
    yield
    registry.clear_caches()


def _workload(seed):
    a = synth.power_law_adjacency(N_NODES, 0.03, 0.9, seed=seed)
    cfg = gcn.GCNConfig(N_FEATS, 16, N_CLASSES)
    params = gcn.init_params(cfg, jax.random.PRNGKey(seed))
    x = np.random.default_rng(seed).random((N_NODES, N_FEATS),
                                           ).astype(np.float32)
    return a, params, x


def _engine(root, **kw):
    kw.setdefault("autotune_kwargs", FAST_KW)
    return GCNServingEngine(store_root=root, **kw)


def _identity(eng):
    st = eng.stats()
    assert st["submitted"] == (st["queue_served"] + st["shed"]
                               + st["rejected"] + st["pending_requests"]
                               + st["inflight_requests"]), st
    return st


def test_submit_tickets_and_reject_at_max_queue_depth(tmp_path):
    a, params, x = _workload(0)
    eng = _engine(tmp_path, max_queue_depth=2)
    eng.add_graph("g", a, params)
    t1 = eng.submit("g", x)
    t2 = eng.submit("g", x * 0.5)
    assert isinstance(t1, SubmitTicket)
    assert t1.status == ACCEPTED and t1.accepted and bool(t1)
    assert t1.rid is not None and t2.rid == t1.rid + 1
    t3 = eng.submit("g", x)
    assert t3.status == REJECTED and not t3.accepted and not t3
    assert t3.rid is None and "max_queue_depth" in t3.reason
    st = _identity(eng)
    assert st["submitted"] == 3 and st["rejected"] == 1
    assert st["pending_requests"] == 2
    # the rejected request was never queued: the flush serves exactly two
    out = eng.flush()
    assert out["g"].shape == (2, N_NODES, N_CLASSES)
    st = _identity(eng)
    assert st["queue_served"] == 2 and st["pending_requests"] == 0


def test_ctor_validates_admission_knobs(tmp_path):
    with pytest.raises(ValueError, match="max_queue_depth"):
        _engine(tmp_path, max_queue_depth=0)
    with pytest.raises(ValueError, match="max_dispatch_retries"):
        _engine(tmp_path, max_dispatch_retries=-1)


def test_shed_iff_predicted_wait_exceeds_deadline(tmp_path):
    """Clock-injected shed predicate on an empty engine: with the
    service EWMA pinned to 1.0 s, a deadline below the predicted wait
    sheds and one above it is accepted — exactly at the EWMA boundary."""
    a, params, x = _workload(1)
    eng = _engine(tmp_path, shed_unmeetable=True)
    eng.add_graph("g", a, params)
    eng._svc_ewma["g"] = 1.0
    eng._svc_req_ewma["g"] = 1.0 / 8
    now = 1000.0
    t = eng.submit("g", x, deadline_s=0.5, now=now)
    assert t.status == SHED and not t and t.rid is None
    assert "predicted wait" in t.reason
    t = eng.submit("g", x, deadline_s=1.5, now=now)
    assert t.status == ACCEPTED
    # deadline-free requests are never shed, whatever the EWMA says
    assert eng.submit("g", x, now=now).status == ACCEPTED
    st = _identity(eng)
    assert st["shed"] == 1 and st["pending_requests"] == 2


def test_shed_accumulates_edf_ahead_queues(tmp_path):
    """The shed predicate absorbs co-located queues that dispatch ahead
    of the candidate (EDF order): a deadline one queue's EWMA could meet
    sheds when an earlier-deadline neighbour serializes in front of it —
    and the same deadline is accepted once that neighbour is gone."""
    g1, g2 = _workload(2), _workload(3)
    eng = _engine(tmp_path, shed_unmeetable=True)
    eng.add_graph("g1", g1[0], g1[1])
    eng.add_graph("g2", g2[0], g2[1])
    now = 1000.0
    # queue a g1 request first (EWMAs still unset, so nothing sheds yet),
    # then pin both EWMAs to 1.0 s
    assert eng.submit("g1", g1[2], deadline_s=0.5, now=now).accepted
    for gid in ("g1", "g2"):
        eng._svc_ewma[gid] = 1.0
        eng._svc_req_ewma[gid] = 1.0 / 8
    # g2 deadline 1.5 s: g1's earlier deadline dispatches ahead and the
    # single device serializes, so predicted wait is 2.0 s -> shed
    t = eng.submit("g2", g2[2], deadline_s=1.5, now=now)
    assert t.status == SHED
    # 2.5 s clears the accumulated wait -> accepted
    assert eng.submit("g2", g2[2], deadline_s=2.5, now=now).accepted
    # with g1's queue gone, the same 1.5 s deadline is meetable: only
    # g2's own estimate remains in front of it
    eng._pending.pop("g1")
    assert eng.submit("g2", g2[2], deadline_s=1.5, now=now).accepted
    assert eng.counters["shed"] == 1


def test_reject_takes_precedence_over_shed(tmp_path):
    """A full queue REJECTS before the shed predicate runs — the bounded
    queue is the engine-overloaded signal, shedding is the per-request
    SLA signal."""
    a, params, x = _workload(4)
    eng = _engine(tmp_path, max_queue_depth=1, shed_unmeetable=True)
    eng.add_graph("g", a, params)
    eng._svc_ewma["g"] = 1.0
    now = 1000.0
    assert eng.submit("g", x, deadline_s=10.0, now=now).accepted
    t = eng.submit("g", x, deadline_s=0.1, now=now)
    assert t.status == REJECTED
    assert eng.counters["rejected"] == 1 and eng.counters["shed"] == 0


def test_dispatch_time_shed_on_stale_queue(tmp_path):
    """A request accepted in time can still become unmeetable while
    queued; the dispatcher sheds it at the last gate instead of burning
    device time on a guaranteed miss."""
    a, params, x = _workload(5)
    eng = _engine(tmp_path, shed_unmeetable=True)
    eng.add_graph("g", a, params)
    now = 1000.0
    assert eng.submit("g", x, deadline_s=0.05, now=now).accepted
    out = eng.poll(now=now + 0.2)   # deadline already passed
    assert out == {}
    st = _identity(eng)
    assert st["shed"] == 1 and st["pending_requests"] == 0
    assert st["queue_served"] == 0 and st["batches"] == 0


def test_overload_accounting_identity_mixed_outcomes(tmp_path):
    """One run mixing every admission outcome: accepted+served,
    rejected at the bound, shed at dispatch — the identity holds at
    every step and at the end."""
    g1, g2 = _workload(6), _workload(7)
    eng = _engine(tmp_path, max_queue_depth=2, shed_unmeetable=True)
    eng.add_graph("g1", g1[0], g1[1])
    eng.add_graph("g2", g2[0], g2[1])
    now = 1000.0
    assert eng.submit("g1", g1[2], deadline_s=50.0, now=now).accepted
    assert eng.submit("g1", g1[2] * 0.5, deadline_s=50.0, now=now).accepted
    assert eng.submit("g1", g1[2], deadline_s=50.0, now=now).status \
        == REJECTED
    assert eng.submit("g2", g2[2], deadline_s=0.01, now=now).accepted
    _identity(eng)
    # only g2 is due at now+0.5 — and its deadline has passed: shed
    out = eng.poll(now=now + 0.5)
    assert out == {}
    st = _identity(eng)
    assert st["shed"] == 1 and st["rejected"] == 1
    assert st["pending_requests"] == 2
    # serve the survivors (real clock from here on; their deadlines are
    # pinned-clock absolutes, so disable shedding for the drain)
    eng.shed_unmeetable = False
    out = eng.flush()
    assert out["g1"].shape == (2, N_NODES, N_CLASSES)
    st = _identity(eng)
    assert st["submitted"] == 4 and st["queue_served"] == 2
    assert st["pending_requests"] == 0


def test_threshold_autoflush_counts_queue_served(tmp_path):
    a, params, x = _workload(8)
    eng = _engine(tmp_path, max_batch=2)
    eng.add_graph("g", a, params)
    assert eng.submit("g", x).accepted
    t = eng.submit("g", x * 0.5)     # reaches max_batch: auto-flush
    assert t.accepted
    st = _identity(eng)              # dispatched, left in flight
    assert st["pending_requests"] == 0 and st["inflight_requests"] == 2
    out = eng.poll()                 # awaits the auto-flushed batch
    assert out["g"].shape == (2, N_NODES, N_CLASSES)
    st = _identity(eng)
    assert st["queue_served"] == 2 and st["inflight_requests"] == 0


def test_unknown_graph_error_unified_across_paths(tmp_path):
    eng = _engine(tmp_path)
    x = np.zeros((4, 4), np.float32)
    for op, call in [
        ("submit", lambda: eng.submit("nope", x)),
        ("serve", lambda: eng.serve_batch("nope", [x])),
        ("serve", lambda: eng.infer("nope", x)),
        ("remove_graph", lambda: eng.remove_graph("nope")),
    ]:
        with pytest.raises(UnknownGraphError) as ei:
            call()
        assert isinstance(ei.value, KeyError)   # backward compatible
        assert ei.value.graph_id == "nope" and ei.value.op == op
        assert "nope" in str(ei.value)


def test_stats_backpressure_surface(tmp_path):
    a, params, x = _workload(9)
    eng = _engine(tmp_path)
    eng.add_graph("g", a, params)
    eng.submit("g", x)
    eng._svc_ewma["g"] = 0.5
    st = eng.stats()
    assert st["queue_depth"] == {"g": 1}
    # queued backlog shows up as device saturation seconds
    assert st["saturation_s"][0] == pytest.approx(0.5)
    assert all("saturation_s" in row for row in st["per_device"])
    assert st["latency_us_p50"] == 0.0 and st["latency_n"] == 0
    eng.flush()
    for _ in range(3):
        eng.submit("g", x)
    eng.flush()
    st = eng.stats()
    assert st["queue_depth"] == {} and st["saturation_s"][0] < 0.5
    assert st["latency_n"] == 4
    assert 0.0 < st["latency_us_p50"] <= st["latency_us_p95"] \
        <= st["latency_us_p99"]
    _identity(eng)


def test_reset_stats_clears_latency_reservoir(tmp_path):
    a, params, x = _workload(10)
    eng = _engine(tmp_path)
    eng.add_graph("g", a, params)
    eng.submit("g", x)
    eng.flush()
    assert eng.stats()["latency_us_p50"] > 0.0
    eng.reset_stats()
    st = eng.stats()
    assert st["latency_us_p50"] == 0.0 and st["latency_n"] == 0
    assert st["submitted"] == 0
    _identity(eng)
