"""Failure-injected dispatch recovery: the ``core.executor.FAULTS`` seam
drives device upload, batch dispatch, and per-replica chunk failures
through the serving engine's recovery paths — bounded exponential-backoff
dispatch retries, sibling-replica chunk retries (bit-identical logits),
typed ``RequestFailure``/``FlushError`` outcomes, and the invariant that
a failure never corrupts served-work counters or leaks outstanding-work
charges. Multi-replica recovery runs on an 8-way forced host-platform
mesh in a subprocess under the ``distributed`` marker."""
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.core import executor as exe, gcn  # noqa: E402
from repro.core.executor import FAULTS, InjectedFault  # noqa: E402
from repro.graphs import synth  # noqa: E402
from repro.serving.gcn_engine import (FlushError,  # noqa: E402
                                      GCNServingEngine, RequestFailure)
from repro.tuning import registry  # noqa: E402

SRC = str(Path(__file__).resolve().parents[1] / "src")

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
def _fresh_state():
    registry.clear_caches()
    FAULTS.clear()
    yield
    FAULTS.clear()
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


def _outstanding_settled(eng):
    assert all(v <= 1e-9 for v in eng._dev_outstanding.values()), \
        eng._dev_outstanding


def test_transient_dispatch_fault_retries_and_recovers(tmp_path,
                                                       monkeypatch):
    import repro.serving.gcn_engine as ge

    a, params, x = _workload(0)
    eng = _engine(tmp_path)
    eng.add_graph("g", a, params)
    ref = np.asarray(eng.serve_batch("g", [x]))
    delays = []
    monkeypatch.setattr(ge, "_sleep", delays.append)
    FAULTS.arm("dispatch", times=1, graph="g")
    out = np.asarray(eng.serve_batch("g", [x]))
    np.testing.assert_array_equal(out, ref)   # retry is unobservable
    assert delays == [eng.retry_backoff_s]
    assert eng.counters["dispatch_retries"] == 1
    assert FAULTS.fired == [("dispatch", "g", None)]
    _outstanding_settled(eng)


def test_persistent_dispatch_fault_bounded_backoff_then_raises(
        tmp_path, monkeypatch):
    import repro.serving.gcn_engine as ge

    a, params, x = _workload(1)
    eng = _engine(tmp_path, max_dispatch_retries=2, retry_backoff_s=0.01)
    eng.add_graph("g", a, params)
    eng.serve_batch("g", [x])                 # warm; prime EWMAs
    before = dict(eng.counters)
    delays = []
    monkeypatch.setattr(ge, "_sleep", delays.append)
    FAULTS.arm("dispatch", times=99, graph="g")
    with pytest.raises(InjectedFault):
        eng.serve_batch("g", [x])
    assert delays == [0.01, 0.02]             # exponential, then give up
    assert len(FAULTS.fired) == 3             # initial try + 2 retries
    assert eng.counters["dispatch_retries"] == before["dispatch_retries"] + 2
    assert eng.counters["batches"] == before["batches"]
    assert eng.counters["requests"] == before["requests"]
    _outstanding_settled(eng)
    FAULTS.clear()
    np.testing.assert_array_equal(              # engine fully recovers
        np.asarray(eng.serve_batch("g", [x])),
        np.asarray(eng.serve_batch("g", [x])))


def test_validation_errors_never_burn_retries(tmp_path, monkeypatch):
    import repro.serving.gcn_engine as ge

    a, params, x = _workload(2)
    eng = _engine(tmp_path)
    eng.add_graph("g", a, params)
    monkeypatch.setattr(ge, "_sleep",
                        lambda s: pytest.fail("backoff on a caller bug"))
    with pytest.raises(ValueError, match="nodes"):
        eng.serve_batch("g", [x[:-1]])
    assert eng.counters["dispatch_retries"] == 0


def test_queue_dispatch_fault_flusherror_restores_then_recovers(
        tmp_path, monkeypatch):
    import repro.serving.gcn_engine as ge

    a, params, x = _workload(3)
    eng = _engine(tmp_path)
    eng.add_graph("g", a, params)
    ref = np.asarray(eng.serve_batch("g", [x, x * 0.5]))
    eng.submit("g", x)
    eng.submit("g", x * 0.5)
    monkeypatch.setattr(ge, "_sleep", lambda s: None)
    FAULTS.arm("dispatch", times=99, graph="g")
    with pytest.raises(FlushError) as ei:
        eng.flush()
    assert set(ei.value.failures) == {"g"}
    assert len(eng._pending["g"]) == 2        # both requests survived
    st = eng.stats()
    assert st["submitted"] == st["queue_served"] + st["shed"] \
        + st["rejected"] + st["pending_requests"] + st["inflight_requests"]
    _outstanding_settled(eng)
    FAULTS.clear()
    out = eng.flush()
    np.testing.assert_array_equal(np.asarray(out["g"]), ref)
    st = eng.stats()
    assert st["queue_served"] == 2 and st["pending_requests"] == 0


def test_upload_fault_on_readmission_recovers_via_retry(tmp_path,
                                                        monkeypatch):
    """An evicted graph's re-admission re-uploads its schedule; a
    transient upload failure mid re-admission is absorbed by the dispatch
    retry (nothing was charged or accounted by the failed attempt)."""
    import repro.serving.gcn_engine as ge

    g0, g1 = _workload(4), _workload(5)
    eng = _engine(tmp_path)
    eng.add_graph("g0", g0[0], g0[1])
    eng.add_graph("g1", g1[0], g1[1])
    per = max(r.bytes for r in eng._graphs.values())
    ref0 = np.asarray(eng.infer("g0", g0[2]))

    registry.clear_caches()
    eng2 = _engine(tmp_path, device_budget_bytes=int(per * 1.2))
    eng2.add_graph("g0", g0[0], g0[1])
    eng2.add_graph("g1", g1[0], g1[1])
    assert "g0" not in eng2.resident_graphs   # evicted by g1's admission
    monkeypatch.setattr(ge, "_sleep", lambda s: None)
    FAULTS.arm("upload", times=1)
    out = np.asarray(eng2.infer("g0", g0[2]))
    np.testing.assert_allclose(out, ref0, atol=1e-5)
    assert eng2.counters["dispatch_retries"] == 1
    assert eng2.counters["readmissions"] >= 1
    assert FAULTS.fired and FAULTS.fired[0][0] == "upload"
    _outstanding_settled(eng2)


def test_await_failure_rolls_back_per_chunk_and_surfaces_per_request(
        tmp_path, monkeypatch):
    """Satellite pin: an error in ``_await_batch`` settles the failed
    chunk's outstanding-work charge and restores exactly the failed
    requests — no leaked meter, no inflated counters, queue order kept."""
    import repro.serving.gcn_engine as ge

    a, params, x = _workload(6)
    eng = _engine(tmp_path)
    eng.add_graph("g", a, params)
    eng.serve_batch("g", [x])                 # prime EWMAs: est > 0
    assert eng._svc_req_ewma["g"] > 0
    r1 = eng.submit("g", x)
    r2 = eng.submit("g", x * 0.5)
    before = dict(eng.counters)
    monkeypatch.setattr(ge, "_block_until_ready",
                        lambda out: (_ for _ in ()).throw(
                            RuntimeError("async device fault")))
    with pytest.raises(FlushError):
        eng.flush()
    _outstanding_settled(eng)                 # the rollback under test
    restored = eng._pending["g"]
    assert [r.rid for r in restored] == [r1.rid, r2.rid]
    assert eng.counters["request_failures"] \
        == before["request_failures"] + 2
    assert eng.counters["batches"] == before["batches"]
    assert eng.counters["queue_served"] == before["queue_served"]
    monkeypatch.undo()
    out = eng.flush()
    assert out["g"].shape == (2, N_NODES, N_CLASSES)
    _outstanding_settled(eng)


def test_direct_path_raises_typed_request_failure(tmp_path, monkeypatch):
    import repro.serving.gcn_engine as ge

    a, params, x = _workload(7)
    eng = _engine(tmp_path)
    eng.add_graph("g", a, params)
    eng.serve_batch("g", [x])
    before = dict(eng.counters)
    cause = RuntimeError("async device fault")
    monkeypatch.setattr(ge, "_block_until_ready",
                        lambda out: (_ for _ in ()).throw(cause))
    with pytest.raises(RequestFailure) as ei:
        eng.serve_batch("g", [x, x * 0.5])
    e = ei.value
    assert isinstance(e, RuntimeError)        # backward compatible
    assert e.graph_id == "g" and e.n_failed == 2
    assert e.cause is cause and e.partial is None
    assert eng.counters["request_failures"] \
        == before["request_failures"] + 2
    assert eng.counters["batches"] == before["batches"]
    assert eng.counters["requests"] == before["requests"]
    _outstanding_settled(eng)


# ---------------------------------------------------------------------------
# Multi-replica fault recovery (8 forced host devices, subprocess)
# ---------------------------------------------------------------------------

SCRIPT_REPLICA_FAULTS = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys, tempfile
sys.path.insert(0, %r)
import numpy as np, jax, jax.numpy as jnp
from repro.core import executor as exe, gcn
from repro.core.executor import FAULTS
from repro.graphs import synth
from repro.serving.gcn_engine import (FlushError, GCNServingEngine,
                                      RequestFailure)
from repro.serving.placement import REPLICATED
assert len(jax.devices()) == 8

SWEEP = [dict(nnz_per_step=64, rows_per_window=32, cols_per_block=None,
              window_nnz=None, routing=exe.GATHER)]
KW = dict(iters=1, warmup=1, sweep=SWEEP, bf16_report=False)

def identity(eng):
    st = eng.stats()
    assert st["submitted"] == (st["queue_served"] + st["shed"]
                               + st["rejected"] + st["pending_requests"]
                               + st["inflight_requests"]), st

n = 300
a = synth.power_law_adjacency(n, 0.03, 0.9, seed=5)
cfg = gcn.GCNConfig(16, 16, 4)
params = gcn.init_params(cfg, jax.random.PRNGKey(5))
x = np.random.default_rng(5).random((n, 16)).astype(np.float32)
reqs = [x * (1.0 - 0.02 * i) for i in range(12)]
root = tempfile.mkdtemp(prefix="awb-faults-")

eng = GCNServingEngine(store_root=root, devices=8, max_replicas=3,
                       replicate_after_s=1e-6,
                       replica_shrink_after=10**6, autotune_kwargs=KW)
eng.add_graph("hot", a, params)
ref = np.asarray(eng.serve_batch("hot", reqs))
for _ in range(3):                        # saturation grows the replicas
    for r in reqs:
        eng.submit("hot", r, deadline_s=0.0)
    eng.poll()
pl = eng.placer.placement_of("hot")
assert pl.kind == REPLICATED and len(pl.device_indices) == 3, pl

# --- one replica's chunk fails -> sibling retry, bit-identical logits ----
victim = sorted(eng._graphs["hot"].replicas)[0]
FAULTS.arm("replica_chunk", graph="hot", device=victim, times=1)
out = np.asarray(eng.serve_batch("hot", reqs))
assert np.array_equal(out, ref), "sibling retry changed the logits"
assert not FAULTS._armed                  # the fault fired
assert FAULTS.fired == [("replica_chunk", "hot", victim)]
assert eng.counters["chunk_retries"] >= 1
assert all(v <= 1e-9 for v in eng._dev_outstanding.values()), \
    eng._dev_outstanding
print("SIBLING OK")

# --- every clone poisoned: queue path fails typed, restores, recovers ----
FAULTS.clear()
for r in reqs:
    eng.submit("hot", r, deadline_s=0.0)
FAULTS.arm("replica_chunk", graph="hot", times=999)
try:
    eng.poll()
    raise SystemExit("expected FlushError")
except FlushError as e:
    assert set(e.failures) == {"hot"}
assert len(eng._pending["hot"]) == 12     # every request restored
assert all(v <= 1e-9 for v in eng._dev_outstanding.values())
identity(eng)
FAULTS.clear()
out = np.asarray(eng.poll()["hot"])
assert np.array_equal(out, ref)           # recovery is bit-identical
identity(eng)
print("POISON OK")

# --- direct path: typed RequestFailure, nothing counted served ----------
FAULTS.arm("replica_chunk", graph="hot", times=999)
before = dict(eng.counters)
try:
    eng.serve_batch("hot", reqs)
    raise SystemExit("expected RequestFailure")
except RequestFailure as e:
    assert e.n_failed == 12 and e.partial is None
assert eng.counters["batches"] == before["batches"]
assert eng.counters["requests"] == before["requests"]
FAULTS.clear()
print("TYPED OK")

# --- partial failure surfaces per-request, not per-batch -----------------
SENT = np.float32(12345.0)
bad = reqs[0].copy()
bad[0, 0] = SENT
orig_run = eng._run_unit
def poisoned(unit, gid, chunk):
    if np.any(np.asarray(chunk)[:, 0, 0] == SENT):
        raise RuntimeError("poisoned chunk")
    return orig_run(unit, gid, chunk)
eng._run_unit = poisoned                  # sentinel chunk fails anywhere
for r in [bad] + reqs[1:]:
    eng.submit("hot", r, deadline_s=0.0)
try:
    eng.poll()
    raise SystemExit("expected FlushError")
except FlushError as e:
    part = np.asarray(e.partial["hot"])
restored = eng._pending["hot"]
assert len(restored) == 4                 # exactly the poisoned chunk
assert float(np.asarray(restored[0].x)[0, 0]) == float(SENT)
assert np.array_equal(part, ref[4:])      # the other chunks delivered
assert all(v <= 1e-9 for v in eng._dev_outstanding.values())
identity(eng)
del eng._run_unit
out = np.asarray(eng.flush()["hot"])      # restored requests drain clean
assert out.shape == (4, n, 4)
identity(eng)
print("PARTIAL OK")
""" % (SRC,)


@pytest.mark.distributed
def test_replica_fault_recovery_acceptance():
    r = subprocess.run([sys.executable, "-c", SCRIPT_REPLICA_FAULTS],
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, \
        f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
    for tag in ("SIBLING OK", "POISON OK", "TYPED OK", "PARTIAL OK"):
        assert tag in r.stdout
