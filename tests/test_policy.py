"""The learned scheduling policy and the redesigned serving API surface:
``OnlineRidge`` convergence on synthetic linear service times, the
``LearnedServiceTimePolicy`` cold-start fallback to the heuristic EWMAs,
learned estimates flowing into shed/dueness/replication decisions, the
prediction-accuracy report, and the backward-compatible import paths of
the consolidated error/result types."""
import numpy as np
import pytest

from repro.serving.placement import SINGLE
from repro.serving.policy import (
    GraphState,
    HeuristicPolicy,
    LearnedServiceTimePolicy,
    OnlineRidge,
    PolicyState,
)


def G(gid="g", *, depth=0, ed=float("inf"), ewma=0.0, req_ewma=0.0,
      nnz=1_000_000, rows=1000):
    return GraphState(
        graph_id=gid, nnz=nnz, n_rows=rows, bytes=1 << 20, resident=True,
        kind=SINGLE, device_index=0, device_indices=(0,), queue_depth=depth,
        earliest_deadline=ed, svc_ewma=ewma, svc_req_ewma=req_ewma)


def S(graphs, *, now=1000.0, outstanding=0.0):
    return PolicyState(
        now=now, n_devices=1, budget_bytes=64 << 20, used_bytes=(0,),
        outstanding_s=(outstanding,), max_replicas=1, replicate_after_s=0.25,
        replica_shrink_after=3, max_batch=32,
        graphs={g.graph_id: g for g in graphs})


def _true_service(g, b):
    """Synthetic linear ground truth in the policy's feature basis."""
    return (0.003 + 0.001 * b + 0.010 * (g.nnz / 1e6)
            + 0.002 * b * (g.nnz / 1e6))


def _fit(pol, graphs, rng, n=200):
    for _ in range(n):
        g = graphs[int(rng.integers(0, len(graphs)))]
        b = int(rng.integers(1, 9))
        pol.observe_service(g.graph_id, b, _true_service(g, b), g)


# ---------------------------------------------------------------------------
# OnlineRidge
# ---------------------------------------------------------------------------

def test_ridge_recovers_linear_coefficients():
    rng = np.random.default_rng(0)
    theta_true = np.array([0.5, -1.25, 2.0])
    r = OnlineRidge(3, l2=1e-6)
    for _ in range(300):
        x = rng.normal(size=3)
        r.observe(x, float(x @ theta_true))
    np.testing.assert_allclose(r.theta, theta_true, atol=1e-6)
    x = rng.normal(size=3)
    assert r.predict(x) == pytest.approx(float(x @ theta_true), abs=1e-6)


def test_ridge_regularization_shrinks_toward_zero():
    r = OnlineRidge(2, l2=1e6)  # huge lambda: theta ~ 0 despite data
    for _ in range(50):
        r.observe(np.array([1.0, 2.0]), 10.0)
    assert np.all(np.abs(r.theta) < 0.1)
    assert r.n == 50


def test_ridge_theta_cache_invalidates_on_observe():
    r = OnlineRidge(1, l2=1e-8)
    r.observe(np.array([1.0]), 2.0)
    t1 = r.theta[0]
    r.observe(np.array([1.0]), 4.0)
    assert r.theta[0] != t1  # cached theta was refreshed


# ---------------------------------------------------------------------------
# LearnedServiceTimePolicy
# ---------------------------------------------------------------------------

def test_cold_start_falls_back_to_ewma():
    """Below min_samples the learned policy is the heuristic policy:
    every estimate comes from the EWMAs, decision-for-decision."""
    pol = LearnedServiceTimePolicy(min_samples=10)
    heur = HeuristicPolicy()
    g = G(depth=3, ewma=0.7, req_ewma=0.2)
    st = S([g])
    assert not pol.fitted
    assert pol._queue_est(st, g) == 0.7
    assert pol._req_est(st, g) == 0.2
    assert pol.predicted_wait(st, "g", 1001.0) == \
        heur.predicted_wait(st, "g", 1001.0)
    assert pol.shed_on_submit(st, "g", 1000.5).shed == \
        heur.shed_on_submit(st, "g", 1000.5).shed
    # 9 observations: still cold (min_samples=10)
    rng = np.random.default_rng(1)
    _fit(pol, [g], rng, n=9)
    assert not pol.fitted and pol._queue_est(st, g) == 0.7


def test_learned_estimates_converge_to_true_service_times():
    pol = LearnedServiceTimePolicy(min_samples=24)
    rng = np.random.default_rng(2)
    graphs = [G("a", nnz=500_000, rows=500), G("b", nnz=4_000_000, rows=4000)]
    _fit(pol, graphs, rng, n=300)
    assert pol.fitted
    for g0 in graphs:
        for depth in (1, 4, 8):
            g = G(g0.graph_id, depth=depth, nnz=g0.nnz, rows=g0.n_rows,
                  ewma=99.0, req_ewma=99.0)  # EWMAs are wildly wrong
            want = _true_service(g, depth)
            assert pol._queue_est(S([g]), g) == pytest.approx(want, rel=1e-4)
            assert pol._req_est(S([g]), g) == \
                pytest.approx(want / depth, rel=1e-4)
    rep = pol.prediction_report()
    assert rep["fitted"] and rep["n_samples"] == 300
    assert rep["n_scored"] == 300 - 24
    assert rep["mean_abs_rel_err"] < 0.05


def test_learned_model_generalizes_across_graphs():
    """A freshly admitted graph it never observed gets a sensible
    estimate from the shared nnz/rows features."""
    pol = LearnedServiceTimePolicy(min_samples=24)
    rng = np.random.default_rng(3)
    _fit(pol, [G("a", nnz=500_000, rows=500),
               G("b", nnz=4_000_000, rows=4000)], rng, n=300)
    fresh = G("new", depth=2, nnz=2_000_000, rows=2000, ewma=99.0)
    assert pol._queue_est(S([fresh]), fresh) == \
        pytest.approx(_true_service(fresh, 2), rel=1e-3)


def test_learned_estimate_drives_shed_decision():
    """EWMA says the deadline is fine; the fitted model knows better —
    the decision follows the model (and vice versa)."""
    pol = LearnedServiceTimePolicy(min_samples=24)
    rng = np.random.default_rng(4)
    big = G("big", nnz=8_000_000, rows=8000)
    _fit(pol, [big], rng, n=100)
    true_t = _true_service(big, 1)  # ~0.1 s
    g = G("big", depth=1, nnz=big.nnz, rows=big.n_rows, ewma=1e-6)
    st = S([g])
    # heuristic (EWMA ~ 0) would accept this deadline; learned sheds
    dl = st.now + true_t / 2
    assert not HeuristicPolicy().shed_on_submit(st, "big", dl).shed
    assert pol.shed_on_submit(st, "big", dl).shed
    assert not pol.shed_on_submit(st, "big", st.now + 2 * true_t).shed


@pytest.mark.parametrize("pol", [HeuristicPolicy(), LearnedServiceTimePolicy()])
def test_outstanding_work_delays_dueness_and_predicted_wait(pol):
    """Work already in flight on a device is the start of its EDF load
    map: the same queue is due, and a deadline shed, earlier than on an
    idle device."""
    g = G(depth=1, ed=1000.08, ewma=0.02)
    idle, busy = S([g]), S([g], outstanding=0.05)
    assert pol.due_queues(idle) == ()         # due at 1000.08 - 1.5·0.02 - 0.01
    assert pol.due_queues(busy) == ("g",)     # ... at 1000.08 - 1.5·0.07 - 0.01
    assert pol.predicted_wait(idle, "g") == pytest.approx(0.02)
    assert pol.predicted_wait(busy, "g") == pytest.approx(0.07)
    assert not pol.shed_on_submit(idle, "g", 1000.05).shed
    assert pol.shed_on_submit(busy, "g", 1000.05).shed


def test_nonpositive_prediction_falls_back_and_counts():
    pol = LearnedServiceTimePolicy(min_samples=2)
    g = G(ewma=0.3, req_ewma=0.1)
    # two observations of a *negative* target drive predictions negative
    for _ in range(2):
        pol.observe_service("g", 1, -1.0, g)
    assert pol.fitted
    assert pol._queue_est(S([g]), g) == 0.3  # fell back to the EWMA
    assert pol.prediction_report()["fallbacks"] == 1


def test_reset_errors_keeps_model_but_zeroes_accuracy_window():
    pol = LearnedServiceTimePolicy(min_samples=4)
    rng = np.random.default_rng(5)
    g = G()
    _fit(pol, [g], rng, n=50)
    assert pol.prediction_report()["n_scored"] > 0
    pol.reset_errors()
    rep = pol.prediction_report()
    assert rep["n_scored"] == 0 and rep["mean_abs_rel_err"] == 0.0
    assert rep["n_samples"] == 50 and pol.fitted  # the model survived


def test_min_samples_validation():
    with pytest.raises(ValueError, match="min_samples"):
        LearnedServiceTimePolicy(min_samples=0)


# ---------------------------------------------------------------------------
# API surface: consolidated types + backward-compatible import paths
# ---------------------------------------------------------------------------

def test_errors_share_common_base_and_stdlib_parents():
    from repro.serving.errors import (
        FlushError,
        RequestFailure,
        ServingError,
        UnknownGraphError,
    )
    assert issubclass(UnknownGraphError, ServingError)
    assert issubclass(UnknownGraphError, KeyError)
    assert issubclass(RequestFailure, ServingError)
    assert issubclass(RequestFailure, RuntimeError)
    assert issubclass(FlushError, ServingError)
    assert issubclass(FlushError, RuntimeError)
    e = UnknownGraphError("gid", "submit")
    assert e.graph_id == "gid" and e.op == "submit" and "gid" in str(e)


def test_submit_ticket_moved_to_types():
    from repro.serving.types import ACCEPTED, REJECTED, SHED, SubmitTicket
    t = SubmitTicket(3, ACCEPTED)
    assert t.accepted and bool(t) and t.rid == 3
    assert not SubmitTicket(None, REJECTED, "full").accepted
    assert not bool(SubmitTicket(None, SHED, "late"))


def test_old_gcn_engine_import_paths_still_resolve():
    jax = pytest.importorskip("jax")  # noqa: F841 — engine imports jax
    from repro.serving import errors, types
    from repro.serving import gcn_engine as ge

    assert ge.UnknownGraphError is errors.UnknownGraphError
    assert ge.RequestFailure is errors.RequestFailure
    assert ge.FlushError is errors.FlushError
    assert ge.ServingError is errors.ServingError
    assert ge.SubmitTicket is types.SubmitTicket
    assert (ge.ACCEPTED, ge.REJECTED, ge.SHED) == \
        (types.ACCEPTED, types.REJECTED, types.SHED)


def test_serving_package_public_api():
    import repro.serving as serving

    # pure exports resolve without jax
    assert serving.HeuristicPolicy is HeuristicPolicy
    assert serving.LearnedServiceTimePolicy is LearnedServiceTimePolicy
    from repro.serving.errors import ServingError
    from repro.serving.placement import MeshPlacer
    from repro.serving.types import SubmitTicket

    assert serving.ServingError is ServingError
    assert serving.MeshPlacer is MeshPlacer
    assert serving.SubmitTicket is SubmitTicket
    assert "GCNServingEngine" in dir(serving)
    with pytest.raises(AttributeError):
        serving.NoSuchThing


def test_transformer_serve_engine_moved_with_shim():
    pytest.importorskip("jax")
    from repro.models.transformer_serve import ServeEngine as new_path
    from repro.serving.engine import ServeEngine as old_path

    assert old_path is new_path


def test_engine_policy_constructor_seam():
    pytest.importorskip("jax")
    from repro.serving.gcn_engine import GCNServingEngine
    import tempfile

    root = tempfile.mkdtemp(prefix="awb-policy-seam-")
    eng = GCNServingEngine(store_root=root)
    assert isinstance(eng.policy, HeuristicPolicy)
    pol = LearnedServiceTimePolicy()
    eng2 = GCNServingEngine(store_root=root, policy=pol)
    assert eng2.policy is pol
