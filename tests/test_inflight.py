"""Threshold batches in flight: the request that fills a queue to
``max_batch`` dispatches its batch inside ``submit`` without awaiting it,
and ``poll``/``flush`` await it later — the oldest batch of a graph
always, later ones once settled, everything when the graph's queue is due
or on ``flush``. At most ``_MAX_INFLIGHT`` batches of a graph stay
unawaited, and ``max_queue_depth`` counts every request not yet handed
back. Rows come back in submission order and bit-identical to
``serve_batch``; served-work counters, latencies and service EWMAs move
only at the await; a fault puts the failed batch and every later one
back on the queue in order; faults and ``remove_graph`` settle every
charge."""
import dataclasses
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

jax = pytest.importorskip("jax")

import repro.serving.gcn_engine as ge  # noqa: E402
from repro.core import executor as exe, gcn  # noqa: E402
from repro.graphs import synth  # noqa: E402
from repro.serving.gcn_engine import (REJECTED, SHED,  # noqa: E402
                                      FlushError, GCNServingEngine,
                                      RequestFailure)
from repro.tuning import registry  # noqa: E402

SRC = str(Path(__file__).resolve().parents[1] / "src")
N_NODES = 200
N_FEATS = 12
MAX_BATCH = 4
SWEEP = [dict(nnz_per_step=64, rows_per_window=32, cols_per_block=None,
              window_nnz=None, routing=exe.GATHER)]
FAST_KW = dict(iters=1, warmup=1, sweep=SWEEP, bf16_report=False)


@pytest.fixture(autouse=True)
def _fresh_caches():
    registry.clear_caches()
    yield
    registry.clear_caches()


def _engine(root, **kw):
    a = synth.power_law_adjacency(N_NODES, 0.03, 0.9, seed=1)
    params = gcn.init_params(gcn.GCNConfig(N_FEATS, 8, 3), jax.random.PRNGKey(1))
    eng = GCNServingEngine(store_root=root, autotune_kwargs=FAST_KW,
                           max_batch=MAX_BATCH, **kw)
    eng.add_graph("g", a, params)
    return eng


def _requests(k):
    rng = np.random.default_rng(2)
    return [rng.random((N_NODES, N_FEATS)).astype(np.float32) for _ in range(k)]


def _identity(eng):
    st = eng.stats()
    assert st["submitted"] == (
        st["queue_served"] + st["shed"] + st["rejected"] + st["dropped"]
        + st["pending_requests"] + st["inflight_requests"]), st
    return st


def _refs(eng, xs, sizes):
    """``serve_batch`` over the same batches the queue path forms."""
    out, at = [], 0
    for b in sizes:
        out.append(np.asarray(eng.serve_batch("g", xs[at:at + b])))
        at += b
    return np.concatenate(out)


def _settled_meter(eng):
    assert all(v <= 1e-9 for v in eng._dev_outstanding.values()), \
        eng._dev_outstanding


def _unawaited(eng):
    return len(eng._inflight.get("g", ()))


def _drain(eng):
    got = []
    while True:
        st = _identity(eng)
        if not (st["pending_requests"] or st["inflight_requests"] or eng._done):
            return np.stack(got)
        out = eng.poll().get("g")
        assert out is not None
        got.extend(np.asarray(out))


def _fail_first_await(monkeypatch):
    """Make the first ``_block_until_ready`` raise (an asynchronous
    device fault in the first batch awaited) and every later one pass."""
    calls = []
    real = ge._block_until_ready

    def fake(out):
        calls.append(1)
        if len(calls) == 1:
            raise RuntimeError("async device fault")
        return real(out)

    monkeypatch.setattr(ge, "_block_until_ready", fake)


def test_threshold_submit_dispatches_without_awaiting(tmp_path):
    eng = _engine(tmp_path)
    eng.reset_stats()
    xs = _requests(MAX_BATCH)
    for x in xs[:-1]:
        assert eng.submit("g", x).accepted
        assert _identity(eng)["pending_requests"] > 0
    assert eng.submit("g", xs[-1]).accepted
    st = _identity(eng)
    assert st["stages"]["dispatch"]["n"] == 1 and "await" not in st["stages"]
    assert st["pending_requests"] == 0
    assert st["inflight_requests"] == MAX_BATCH
    assert st["batches"] == st["queue_served"] == st["latency_n"] == 0


def test_inflight_batches_come_back_in_order_and_bit_identical(tmp_path):
    eng = _engine(tmp_path)
    xs = _requests(3 * MAX_BATCH)
    ref = _refs(eng, xs, [MAX_BATCH] * 3)
    eng.reset_stats()
    for x in xs:
        assert eng.submit("g", x).accepted
        _identity(eng)
    st = eng.stats()  # the third dispatch awaited the oldest batch first
    assert st["inflight_requests"] == ge._MAX_INFLIGHT * MAX_BATCH
    assert st["queue_served"] == MAX_BATCH
    got = []
    for _ in range(3):
        out = eng.poll().get("g")
        _identity(eng)
        assert out is not None  # never empty-handed while a batch is in flight
        got.extend(np.asarray(out))
        if len(got) == len(xs):
            break
    assert len(got) == len(xs)
    np.testing.assert_array_equal(np.stack(got), ref)
    st = _identity(eng)
    assert st["queue_served"] == len(xs) and st["batches"] == 3
    assert st["inflight_requests"] == 0 and st["latency_n"] == len(xs)
    assert eng.poll() == {}
    _settled_meter(eng)


def test_overlapped_batches_counts_back_to_back_autoflushes(tmp_path):
    eng = _engine(tmp_path)
    eng.reset_stats()
    for x in _requests(3 * MAX_BATCH):
        eng.submit("g", x)
    assert eng.stats()["overlapped_batches"] == 2
    out = eng.flush()
    assert out["g"].shape[0] == 3 * MAX_BATCH
    st = _identity(eng)
    assert st["overlapped_batches"] == 2 and st["batches"] == 3
    # nothing in flight: the next threshold batch overlaps nothing
    for x in _requests(MAX_BATCH):
        eng.submit("g", x)
    assert eng.stats()["overlapped_batches"] == 2
    eng.flush()


def test_poll_hands_back_the_oldest_then_each_settled_batch(tmp_path, monkeypatch):
    eng = _engine(tmp_path)
    xs = _requests(4 * MAX_BATCH)
    ref = _refs(eng, xs, [MAX_BATCH] * 4)
    for x in xs[:3 * MAX_BATCH]:  # the third dispatch awaits the first
        eng.submit("g", x)
    monkeypatch.setattr(eng, "_settled", lambda b: False)
    first = eng.poll()["g"]  # the awaited one, then the oldest in flight
    assert first.shape[0] == 2 * MAX_BATCH
    assert _identity(eng)["inflight_requests"] == MAX_BATCH
    for x in xs[3 * MAX_BATCH:]:
        eng.submit("g", x)
    assert _identity(eng)["inflight_requests"] == 2 * MAX_BATCH
    monkeypatch.setattr(eng, "_settled", lambda b: True)
    rest = eng.poll()["g"]   # the oldest plus every settled one after it
    assert rest.shape[0] == 2 * MAX_BATCH
    np.testing.assert_array_equal(
        np.concatenate([np.asarray(first), np.asarray(rest)]), ref)
    assert _identity(eng)["inflight_requests"] == 0


def test_due_queue_awaits_older_inflight_batches_first(tmp_path, monkeypatch):
    eng = _engine(tmp_path)
    xs = _requests(MAX_BATCH + 2)
    ref = _refs(eng, xs, [MAX_BATCH, 2])
    monkeypatch.setattr(eng, "_settled", lambda b: False)
    for x in xs:
        eng.submit("g", x, deadline_s=0.0)
    st = _identity(eng)
    assert st["inflight_requests"] == MAX_BATCH and st["pending_requests"] == 2
    out = eng.poll()["g"]  # the queue is due: it and the batch before it
    np.testing.assert_array_equal(np.asarray(out), ref)
    st = _identity(eng)
    assert st["inflight_requests"] == st["pending_requests"] == 0


def test_service_time_of_a_later_batch_starts_at_the_previous_completion(
        tmp_path, monkeypatch):
    eng = _engine(tmp_path)
    seen = []
    orig = eng._note_served

    def record(gid, reqs, t0, t_done):
        seen.append((t0, t_done))
        orig(gid, reqs, t0, t_done)

    monkeypatch.setattr(eng, "_note_served", record)
    disp = []
    orig_dispatch = eng._dispatch_with_retry

    def timed(gid, xs):
        disp.append(ge.time.monotonic())
        return orig_dispatch(gid, xs)

    monkeypatch.setattr(eng, "_dispatch_with_retry", timed)
    for x in _requests(2 * MAX_BATCH):
        eng.submit("g", x)
    eng.flush()
    (t0a, done_a), (t0b, done_b) = seen
    assert t0a <= disp[0] < disp[1]  # the first batch: from its dispatch
    assert disp[1] < done_a          # the second went out before it
    assert t0b == done_a < done_b    # ... so it is timed from done_a
    assert eng._svc_ewma["g"] > 0.0


def test_async_fault_in_inflight_batch_surfaces_at_poll(tmp_path, monkeypatch):
    """An asynchronous device fault in a batch left in flight by
    ``submit`` surfaces at the ``poll`` that awaits it: ``FlushError``,
    exactly those requests restored at the queue front (ahead of a later
    arrival), every charge settled, nothing counted served."""
    eng = _engine(tmp_path)
    xs = _requests(MAX_BATCH + 1)
    eng.serve_batch("g", xs[:1])  # prime the EWMAs: the charges are > 0
    assert eng._svc_req_ewma["g"] > 0
    tickets = [eng.submit("g", x) for x in xs]  # a batch in flight + one queued
    assert any(v > 0 for v in eng._dev_outstanding.values())
    before = dict(eng.counters)
    monkeypatch.setattr(ge, "_block_until_ready",
                        lambda out: (_ for _ in ()).throw(
                            RuntimeError("async device fault")))
    with pytest.raises(FlushError) as ei:
        eng.poll()
    assert set(ei.value.failures) == {"g"}
    _settled_meter(eng)
    assert [r.rid for r in eng._pending["g"]] == [t.rid for t in tickets]
    assert eng.counters["request_failures"] == \
        before["request_failures"] + MAX_BATCH
    assert eng.counters["batches"] == before["batches"]
    assert eng.counters["queue_served"] == before["queue_served"]
    st = _identity(eng)
    assert st["inflight_requests"] == 0
    assert st["pending_requests"] == MAX_BATCH + 1
    monkeypatch.undo()
    out = eng.flush()
    assert out["g"].shape[0] == MAX_BATCH + 1
    _settled_meter(eng)
    assert _identity(eng)["queue_served"] == MAX_BATCH + 1


def test_remove_graph_with_a_batch_in_flight_settles_every_charge(tmp_path):
    eng = _engine(tmp_path)
    xs = _requests(MAX_BATCH + 1)
    eng.serve_batch("g", xs[:1])  # prime the EWMAs: the charges are > 0
    for x in xs:
        eng.submit("g", x)
    assert any(v > 0 for v in eng._dev_outstanding.values())
    with pytest.raises(RequestFailure) as ei:
        eng.remove_graph("g")
    assert ei.value.n_failed == MAX_BATCH + 1
    _settled_meter(eng)
    st = _identity(eng)
    assert st["dropped"] == MAX_BATCH + 1
    assert st["inflight_requests"] == st["pending_requests"] == 0
    assert eng.poll() == {} and eng.flush() == {}


def test_submit_without_polling_caps_batches_in_flight(tmp_path):
    """A caller that submits 10×max_batch without polling never has more
    than ``_MAX_INFLIGHT`` batches unawaited on the device: each further
    dispatch awaits the oldest first, counts it served and keeps only its
    logits. Every row still comes back, in order and bit-identical."""
    eng = _engine(tmp_path)
    xs = _requests(10 * MAX_BATCH)
    ref = _refs(eng, xs, [MAX_BATCH] * 10)
    eng.reset_stats()
    for x in xs:
        assert eng.submit("g", x).accepted
        st = _identity(eng)
        assert _unawaited(eng) <= ge._MAX_INFLIGHT
        assert st["inflight_requests"] <= ge._MAX_INFLIGHT * MAX_BATCH
    assert st["queue_served"] == 8 * MAX_BATCH and st["batches"] == 8
    assert sum(o.shape[0] for o in eng._done["g"]) == 8 * MAX_BATCH
    np.testing.assert_array_equal(_drain(eng), ref)
    assert _identity(eng)["queue_served"] == len(xs)
    _settled_meter(eng)


def test_max_queue_depth_counts_requests_not_yet_handed_back(tmp_path):
    """``max_queue_depth`` bounds a graph's queued, in-flight and
    awaited-but-held requests together: submitting 10×max_batch without
    polling admits the bound and rejects the rest, and a poll that hands
    the rows back makes room again."""
    depth = 3 * MAX_BATCH
    eng = _engine(tmp_path, max_queue_depth=depth)
    xs = _requests(10 * MAX_BATCH)
    ref = _refs(eng, xs[:depth], [MAX_BATCH] * 3)
    eng.reset_stats()
    tickets = []
    for x in xs:
        tickets.append(eng.submit("g", x))
        _identity(eng)
        assert _unawaited(eng) <= ge._MAX_INFLIGHT
    assert [t.accepted for t in tickets] == [True] * depth + [False] * (
        len(xs) - depth)
    assert all(t.status == REJECTED for t in tickets[depth:])
    st = _identity(eng)
    assert st["rejected"] == len(xs) - depth
    assert st["inflight_requests"] == ge._MAX_INFLIGHT * MAX_BATCH
    np.testing.assert_array_equal(_drain(eng), ref)
    assert eng.submit("g", xs[0]).accepted
    eng.flush()
    _settled_meter(eng)


def test_work_in_flight_makes_deadline_queues_due_and_shed_earlier(tmp_path):
    """The EDF walks start from the outstanding work on each device: with
    a threshold batch in flight, a deadline request is shed at submit and
    its queue is due at once, where an idle device would accept it and
    wait."""
    eng = _engine(tmp_path, shed_unmeetable=True)
    xs = _requests(MAX_BATCH + 1)
    eng._svc_req_ewma["g"] = 0.5  # the threshold batch charges 2 s
    for x in xs[:MAX_BATCH]:
        eng.submit("g", x)
    eng._svc_ewma["g"] = 0.1
    now = ge.time.monotonic()
    state = eng._policy_state(now)
    assert state.outstanding_s[0] == pytest.approx(0.5 * MAX_BATCH)
    idle = dataclasses.replace(state, outstanding_s=(0.0,))
    assert eng.policy.predicted_wait(idle, "g") == pytest.approx(0.1)
    assert eng.policy.predicted_wait(state, "g") == pytest.approx(
        0.5 * MAX_BATCH + 0.1)
    assert not eng.policy.shed_on_submit(idle, "g", now + 1.0).shed
    assert eng.submit("g", xs[-1], deadline_s=1.0, now=now).status == SHED
    eng.shed_unmeetable = False
    assert eng.submit("g", xs[-1], deadline_s=1.0, now=now).accepted
    state = eng._policy_state(now)
    assert eng.policy.due_queues(state) == ("g",)
    idle = dataclasses.replace(state, outstanding_s=(0.0,))
    assert eng.policy.due_queues(idle) == ()
    assert eng.poll(now=now)["g"].shape[0] == MAX_BATCH + 1
    _settled_meter(eng)
    assert _identity(eng)["shed"] == 1


def test_fault_with_two_batches_in_flight_requeues_in_submission_order(
        tmp_path, monkeypatch):
    """A fault in the older of two batches in flight fails that batch and
    puts the later one back on the queue with it: no row of the later
    batch is handed back ahead of the failed rows, the queue re-forms in
    submission order, and every charge settles."""
    eng = _engine(tmp_path)
    xs = _requests(2 * MAX_BATCH + 1)
    ref = np.asarray(eng.serve_batch("g", xs))  # primes the EWMAs too
    tickets = [eng.submit("g", x) for x in xs]
    assert _unawaited(eng) == 2
    before = dict(eng.counters)
    _fail_first_await(monkeypatch)
    with pytest.raises(FlushError) as ei:
        eng.poll()
    assert set(ei.value.failures) == {"g"} and ei.value.partial == {}
    _settled_meter(eng)
    assert [r.rid for r in eng._pending["g"]] == [t.rid for t in tickets]
    assert eng.counters["request_failures"] == \
        before["request_failures"] + MAX_BATCH
    assert eng.counters["queue_served"] == before["queue_served"]
    st = _identity(eng)
    assert st["inflight_requests"] == 0 and st["pending_requests"] == len(xs)
    monkeypatch.undo()
    np.testing.assert_array_equal(np.asarray(eng.flush()["g"]), ref)
    _settled_meter(eng)


def test_fault_found_by_the_cap_raises_at_submit(tmp_path, monkeypatch):
    """When a third dispatch awaits the oldest batch and finds it failed,
    ``submit`` raises ``FlushError`` as a synchronous dispatch failure
    does: both batches and the new requests wait on the queue in
    submission order, and nothing stays in flight."""
    eng = _engine(tmp_path)
    xs = _requests(3 * MAX_BATCH)
    ref = np.asarray(eng.serve_batch("g", xs))
    tickets = [eng.submit("g", x) for x in xs[:2 * MAX_BATCH]]
    _fail_first_await(monkeypatch)
    tickets += [eng.submit("g", x) for x in xs[2 * MAX_BATCH:-1]]
    with pytest.raises(FlushError):
        eng.submit("g", xs[-1])
    _settled_meter(eng)
    st = _identity(eng)
    assert st["inflight_requests"] == 0 and st["pending_requests"] == len(xs)
    rids = [r.rid for r in eng._pending["g"]]
    assert rids == sorted(rids) and rids[:-1] == [t.rid for t in tickets]
    monkeypatch.undo()
    np.testing.assert_array_equal(np.asarray(eng.flush()["g"]), ref)


SCRIPT_REPLICATED = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys, tempfile
sys.path.insert(0, %r)
import numpy as np, jax
from repro.core import executor as exe, gcn
from repro.graphs import synth
from repro.serving.gcn_engine import GCNServingEngine
assert len(jax.devices()) == 4

SWEEP = [dict(nnz_per_step=64, rows_per_window=32, cols_per_block=None,
              window_nnz=None, routing=exe.GATHER)]
KW = dict(iters=1, warmup=1, sweep=SWEEP, bf16_report=False)
n = 200
a = synth.power_law_adjacency(n, 0.03, 0.9, seed=5)
params = gcn.init_params(gcn.GCNConfig(12, 8, 3), jax.random.PRNGKey(5))
x = np.random.default_rng(5).random((n, 12)).astype(np.float32)
reqs = [x * (1.0 - 0.02 * i) for i in range(12)]
one = GCNServingEngine(store_root=tempfile.mkdtemp(), max_batch=4,
                       autotune_kwargs=KW)
one.add_graph("hot", a, params)
ref = np.concatenate([np.asarray(one.serve_batch("hot", reqs[i:i + 4]))
                      for i in (0, 4, 8)])
eng = GCNServingEngine(store_root=tempfile.mkdtemp(), devices=4, max_replicas=4,
                       max_batch=4, replicate_after_s=1e-6,
                       replica_shrink_after=10**6, autotune_kwargs=KW)
eng.add_graph("hot", a, params)
eng.serve_batch("hot", reqs[:2])  # prime the service EWMA
futures = []
orig = eng._dispatch_batch
def counted(gid, xs):
    parts = orig(gid, xs)
    futures.append(sum(p.future is not None for p in parts))
    return parts
eng._dispatch_batch = counted
for r in reqs:
    assert eng.submit("hot", r).accepted
st = eng.stats()  # the third dispatch awaited the oldest batch first
assert st["inflight_requests"] == 8 and st["queue_served"] == 4, st
assert st["overlapped_batches"] == 2, st
got = []
for _ in range(3):
    out = eng.poll().get("hot")
    assert out is not None
    got.extend(np.asarray(out))
    if len(got) == 12:
        break
assert np.array_equal(np.stack(got), ref), "replicated in-flight rows differ"
assert sum(futures) >= 2, futures  # some batches split over replica threads
st = eng.stats()
assert st["submitted"] == st["queue_served"] + st["pending_requests"] \
    + st["inflight_requests"] == 12, st
assert all(v <= 1e-9 for v in eng._dev_outstanding.values()), \
    eng._dev_outstanding
print("REPLICATED OK", futures)
""" % (SRC,)


@pytest.mark.distributed
def test_replicated_batches_in_flight_come_back_in_order():
    r = subprocess.run([sys.executable, "-c", SCRIPT_REPLICATED],
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
    assert "REPLICATED OK" in r.stdout
