"""Streaming graph updates (DESIGN.md §11): edge-delta application, the
value-only O(|delta|) schedule patch, incremental schedule repair,
scoped executor re-upload, the engine's versioned zero-gap swap, and the
serving-lifecycle correctness sweep that rode along (remove-with-pending
failure semantics, EWMA resets, store builder versioning, perf-gate
math)."""
import json
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from benchmarks import check_regression as gate  # noqa: E402
from repro.core import csc, executor as exe, gcn, schedule  # noqa: E402
from repro.graphs import synth  # noqa: E402
from repro.serving.gcn_engine import (GCNServingEngine,  # noqa: E402
                                      RequestFailure, UnknownGraphError)
from repro.tuning import registry, runner  # noqa: E402
from repro.tuning import store as store_mod  # noqa: E402
from repro.tuning.store import TuningStore  # noqa: E402

SRC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src")

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

SCHED_KW = dict(nnz_per_step=64, rows_per_window=32)


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


def _pinned_engine(root, cfg):
    """An engine whose sweep has exactly one candidate — the given
    config — so a fresh admission reproduces it deterministically (the
    bit-identity reference for repaired state)."""
    cand = dict(nnz_per_step=cfg.nnz_per_step,
                rows_per_window=cfg.rows_per_window,
                cols_per_block=cfg.cols_per_block,
                window_nnz=cfg.window_nnz,
                routing=cfg.routing,
                ktile=cfg.ktile)
    kw = dict(iters=1, warmup=1, sweep=[cand], bf16_report=False)
    return GCNServingEngine(store_root=root, autotune_kwargs=kw)


def _value_delta(coo, k, rng):
    row = np.asarray(coo.row)
    col = np.asarray(coo.col)
    idx = rng.choice(row.shape[0], size=min(k, row.shape[0]), replace=False)
    vals = (rng.random(idx.shape[0]) + 0.5).astype(np.float32)
    return csc.EdgeDelta(row[idx], col[idx], vals)


def _structural_delta(coo, n, k, rng):
    rows = rng.integers(0, n, k)
    cols = rng.integers(0, n, k)
    vals = (rng.random(k) + 0.1).astype(np.float32)
    return csc.EdgeDelta(rows, cols, vals)


def _dense(coo):
    m, n = coo.shape
    d = np.zeros((m, n), np.float64)
    row = np.asarray(coo.row)
    keep = row != csc.PAD_IDX
    d[row[keep], np.asarray(coo.col)[keep]] = np.asarray(coo.val)[keep]
    return d


def _schedules_equal(a, b):
    for f in schedule._ARRAY_FIELDS:
        if not np.array_equal(getattr(a, f), getattr(b, f)):
            return False
    return a.shape == b.shape


# ---------------------------------------------------------------------------
# apply_edge_delta
# ---------------------------------------------------------------------------

def test_apply_edge_delta_matches_dense_reference():
    a, _, _ = _workload(0)
    rng = np.random.default_rng(0)
    # a mixed delta: inserts, value overwrites, removals, and a no-op
    # removal of an absent edge, with a duplicate coordinate on top
    row = np.asarray(a.row)
    col = np.asarray(a.col)
    hit = rng.choice(row.shape[0], 6, replace=False)
    drow = np.concatenate([row[hit], rng.integers(0, N_NODES, 8), [3, 3]])
    dcol = np.concatenate([col[hit], rng.integers(0, N_NODES, 8), [7, 7]])
    dval = (rng.random(drow.shape[0]) + 0.1).astype(np.float32)
    dval[2] = 0.0          # remove an existing edge
    dval[-2] = 0.25        # duplicate coordinate: last write wins
    dval[-1] = 0.75
    delta = csc.EdgeDelta(drow, dcol, dval)

    ref = _dense(a)
    for r, c, v in zip(drow, dcol, dval):  # one-at-a-time semantics
        if v == 0.0:
            ref[r, c] = 0.0
        else:
            ref[r, c] = v
    out, rep = csc.apply_edge_delta(a, delta, with_report=True)
    np.testing.assert_array_equal(_dense(out), ref)
    # the report's histogram delta must reconcile with the nnz change
    assert rep.n_added - rep.n_removed == out.nnz - a.nnz
    assert rep.row_nnz_delta.sum() == out.nnz - a.nnz
    assert np.array_equal(rep.touched_rows, np.unique(drow))
    # row-major sortedness is the invariant every downstream consumer
    # (CSC conversion, schedule build, repair) relies on
    key = np.asarray(out.row, np.int64) * N_NODES + np.asarray(out.col)
    assert np.all(np.diff(key) > 0)


def test_apply_edge_delta_value_only_fast_branch():
    a, _, _ = _workload(1)
    rng = np.random.default_rng(1)
    delta = _value_delta(a, 12, rng)
    out, rep = csc.apply_edge_delta(a, delta, with_report=True)
    # structure untouched: coordinates identical, only values moved
    assert np.array_equal(np.asarray(out.row), np.asarray(a.row))
    assert np.array_equal(np.asarray(out.col), np.asarray(a.col))
    assert rep.n_added == 0 and rep.n_removed == 0
    assert rep.n_updated == 12
    assert np.all(rep.row_nnz_delta == 0)
    np.testing.assert_array_equal(_dense(out)[delta.row, delta.col],
                                  delta.val.astype(np.float64))


def test_apply_edge_delta_absent_removal_is_noop():
    a, _, _ = _workload(2)
    dense = _dense(a)
    absent = np.argwhere(dense == 0.0)[:5]
    delta = csc.EdgeDelta(absent[:, 0], absent[:, 1],
                          np.zeros(5, np.float32))
    out, rep = csc.apply_edge_delta(a, delta, with_report=True)
    np.testing.assert_array_equal(_dense(out), dense)
    assert rep.n_added == rep.n_removed == rep.n_updated == 0


# ---------------------------------------------------------------------------
# slot index + value-only schedule patch
# ---------------------------------------------------------------------------

def test_slot_entry_keys_indexes_every_nonzero():
    a, _, _ = _workload(3)
    sched = schedule.build_balanced_schedule(a, **SCHED_KW)
    keys, slots = schedule.slot_entry_keys(sched)
    want = (np.asarray(a.row, np.int64) * N_NODES
            + np.asarray(a.col, np.int64))
    pos = np.searchsorted(keys, want)
    assert np.all(keys[pos] == want)  # every edge has a slot
    np.testing.assert_array_equal(sched.val[slots[pos]], np.asarray(a.val))
    # padding slots (val == 0) are all masked to -1, so they can never
    # shadow a real coordinate in the lookup
    n_real = int(np.count_nonzero(sched.val != 0.0))
    assert int(np.count_nonzero(keys != -1)) == n_real


def test_value_patch_schedule_bit_identical_and_miss():
    a, _, _ = _workload(4)
    rng = np.random.default_rng(4)
    sched = schedule.build_balanced_schedule(a, **SCHED_KW)
    index = schedule.slot_entry_keys(sched)
    delta = _value_delta(a, 10, rng)
    new_coo = csc.apply_edge_delta(a, delta)
    patched = schedule.value_patch_schedule(
        sched, index, delta.row, delta.col, delta.val)
    assert patched is not None
    new_sched, slots = patched
    assert slots.shape == (10,)
    cold = schedule.build_balanced_schedule(new_coo, **SCHED_KW)
    assert _schedules_equal(new_sched, cold)
    # an entry absent from the graph misses the index -> None (caller
    # falls back to the generic repair)
    dense = _dense(a)
    r0, c0 = np.argwhere(dense == 0.0)[0]
    miss = schedule.value_patch_schedule(
        sched, index, np.array([r0]), np.array([c0]),
        np.array([1.0], np.float32))
    assert miss is None


def test_repair_schedule_bit_identical_structural():
    a, _, _ = _workload(5)
    rng = np.random.default_rng(5)
    per_row_old = np.bincount(np.asarray(a.row), minlength=N_NODES)
    delta = _structural_delta(a, N_NODES, 24, rng)
    new_coo, rep = csc.apply_edge_delta(a, delta, with_report=True)
    per_row_new = per_row_old.copy()
    per_row_new[rep.touched_rows] += rep.row_nnz_delta
    sched = schedule.build_balanced_schedule(a, **SCHED_KW)
    new_sched, stats = schedule.repair_schedule(
        sched, None, new_coo, rep.touched_rows,
        per_row_old=per_row_old, per_row_new=per_row_new, **SCHED_KW)
    cold = schedule.build_balanced_schedule(new_coo, **SCHED_KW)
    assert _schedules_equal(new_sched, cold)
    assert stats.windows_total == cold.n_windows


# ---------------------------------------------------------------------------
# executor splicing
# ---------------------------------------------------------------------------

#: the two placements of one executor: one device, and a mesh of one
PLACEMENTS = {
    "one_device": lambda sched: exe.ScheduleExecutor(sched, routing=exe.GATHER),
    "mesh": lambda sched: exe.ShardedScheduleExecutor(
        sched, n_devices=1, routing=exe.GATHER),
}


def _assert_same_device_state(got, want):
    assert sorted(got.operands) == sorted(want.operands)
    for key, arr in want.operands.items():
        np.testing.assert_array_equal(np.asarray(got.operands[key]),
                                      np.asarray(arr))
    assert got.geometry == want.geometry
    assert got.device_bytes == want.device_bytes


@pytest.mark.parametrize("placement", sorted(PLACEMENTS))
def test_value_patched_executor_matches_fresh(placement):
    build = PLACEMENTS[placement]
    a, _, _ = _workload(6)
    rng = np.random.default_rng(6)
    sched = schedule.build_balanced_schedule(a, **SCHED_KW)
    ex = build(sched)
    index = schedule.slot_entry_keys(sched)
    delta = _value_delta(a, 9, rng)
    new_sched, slots = schedule.value_patch_schedule(
        sched, index, delta.row, delta.col, delta.val)
    ex2 = exe.value_patched_executor(ex, new_sched, slots,
                                     new_sched.val[slots])
    assert ex2.scoped_upload
    assert ex2.device_bytes == ex.device_bytes
    fresh = build(new_sched)
    _assert_same_device_state(ex2, fresh)
    b = np.random.default_rng(60).random((N_NODES, 16)).astype(np.float32)
    np.testing.assert_array_equal(np.asarray(ex2.spmm(jnp.asarray(b))),
                                  np.asarray(fresh.spmm(jnp.asarray(b))))
    # empty patch: the device stream is shared outright, no upload
    ex3 = exe.value_patched_executor(ex, sched, np.zeros(0, np.int64),
                                     np.zeros(0, np.float32))
    assert ex3.operands["val"] is ex.operands["val"]


@pytest.mark.parametrize("placement", sorted(PLACEMENTS))
def test_repaired_executor_scoped_matches_fresh(monkeypatch, placement):
    monkeypatch.setattr(exe, "SCOPED_UPLOAD_MIN_BYTES", 0)
    build = PLACEMENTS[placement]
    a, _, _ = _workload(7)
    rng = np.random.default_rng(7)
    per_row_old = np.bincount(np.asarray(a.row), minlength=N_NODES)
    sched = schedule.build_balanced_schedule(a, **SCHED_KW)
    ex = build(sched)
    delta = _structural_delta(a, N_NODES, 20, rng)
    new_coo, rep = csc.apply_edge_delta(a, delta, with_report=True)
    per_row_new = per_row_old.copy()
    per_row_new[rep.touched_rows] += rep.row_nnz_delta
    new_sched, stats = schedule.repair_schedule(
        sched, None, new_coo, rep.touched_rows,
        per_row_old=per_row_old, per_row_new=per_row_new, **SCHED_KW)
    ex2 = exe.repaired_executor(ex, new_sched, stats)
    # the step count changed (28 -> 29): the shard's range moved, so it
    # re-uploads in full
    assert new_sched.n_steps != sched.n_steps and not ex2.scoped_upload
    fresh = build(new_sched)
    _assert_same_device_state(ex2, fresh)
    b = np.random.default_rng(70).random((N_NODES, 16)).astype(np.float32)
    np.testing.assert_array_equal(np.asarray(ex2.spmm(jnp.asarray(b))),
                                  np.asarray(fresh.spmm(jnp.asarray(b))))
    # one edge moved within the densest row: the step count holds, only
    # that row's steps move, and they are scatter-patched in place
    dense = _dense(new_coo) != 0
    row = int(np.argmax(dense.sum(1)))
    cols = [np.nonzero(dense[row])[0][0], np.nonzero(~dense[row])[0][0]]
    delta = csc.EdgeDelta(np.array([row, row]), np.array(cols),
                          np.array([0.0, 1.0], np.float32))
    coo3, rep = csc.apply_edge_delta(new_coo, delta, with_report=True)
    per_row_old = np.bincount(np.asarray(new_coo.row), minlength=N_NODES)
    per_row_new = per_row_old.copy()
    per_row_new[rep.touched_rows] += rep.row_nnz_delta
    sched3, stats = schedule.repair_schedule(
        new_sched, None, coo3, rep.touched_rows,
        per_row_old=per_row_old, per_row_new=per_row_new, **SCHED_KW)
    ex3 = exe.repaired_executor(ex2, sched3, stats)
    assert ex3.scoped_upload
    _assert_same_device_state(ex3, build(sched3))


# ---------------------------------------------------------------------------
# engine update_graph
# ---------------------------------------------------------------------------

def test_update_graph_value_lane_report(tmp_path):
    a, params, x = _workload(8)
    rng = np.random.default_rng(8)
    eng = _engine(tmp_path)
    eng.add_graph("g", a, params)
    eng.infer("g", x)
    rep = eng.update_graph("g", _value_delta(eng._graphs["g"].coo, 8, rng))
    assert rep.repaired and not rep.fell_back
    assert rep.scoped_upload
    assert rep.revision == 1
    # the O(nnz) content fingerprint is deferred to the async persist
    # worker: the hot path reports an empty fingerprint but a real,
    # deterministic lineage hash
    assert rep.fingerprint == "" and rep.lineage != ""
    # a value patch reuses the entire step/window layout verbatim
    sched = eng._graphs["g"].sched
    assert rep.steps_reused == sched.n_steps
    assert rep.windows_reused == rep.windows_total == sched.n_windows
    assert eng.counters["graph_updates"] == 1
    assert eng.counters["update_retunes"] == 0


def test_update_graph_chain_bit_identical_to_cold_admission(tmp_path):
    a, params, x = _workload(9)
    rng = np.random.default_rng(9)
    eng = _engine(tmp_path / "hot")
    eng.add_graph("g", a, params)
    eng.infer("g", x)
    for i in range(6):  # alternate value-only and structural deltas
        coo = eng._graphs["g"].coo
        if i % 2 == 0:
            delta = _value_delta(coo, 8, rng)
        else:
            delta = _structural_delta(coo, N_NODES, 8, rng)
        rep = eng.update_graph("g", delta)
        assert rep.repaired and not rep.fell_back
    got = np.asarray(eng.infer("g", x))
    rec = eng._graphs["g"]
    ident = _pinned_engine(tmp_path / "cold", rec.config)
    ident.add_graph("g", rec.coo, params)
    want = np.asarray(ident.infer("g", x))
    assert np.array_equal(got, want)


def test_update_graph_drift_triggers_retune(tmp_path):
    a, params, x = _workload(10)
    rng = np.random.default_rng(10)
    eng = _engine(tmp_path, repair_drift_threshold=1e-9)
    eng.add_graph("g", a, params)
    eng.infer("g", x)
    rep = eng.update_graph("g", _value_delta(a, 8, rng))
    assert not rep.repaired and rep.fingerprint != ""
    assert eng.counters["update_retunes"] == 1
    rec = eng._graphs["g"]
    assert rec.drift_nnz == 0  # the re-tuned schedule is the new baseline
    assert rec.fingerprint == rep.fingerprint
    assert rec.lineage == rep.fingerprint  # lineage re-anchors at re-tune
    got = np.asarray(eng.infer("g", x))
    ident = _pinned_engine(tmp_path / "cold", rec.config)
    ident.add_graph("g", rec.coo, params)
    assert np.array_equal(got, np.asarray(ident.infer("g", x)))


def test_update_graph_errors_leave_state_unchanged(tmp_path):
    a, params, x = _workload(11)
    eng = _engine(tmp_path)
    eng.add_graph("g", a, params)
    ref = np.asarray(eng.infer("g", x))
    with pytest.raises(UnknownGraphError):
        eng.update_graph("nope", csc.EdgeDelta(
            np.array([0]), np.array([0]), np.array([1.0], np.float32)))
    with pytest.raises(ValueError, match="out of bounds"):
        eng.update_graph("g", csc.EdgeDelta(
            np.array([N_NODES]), np.array([0]),
            np.array([1.0], np.float32)))
    assert eng._graphs["g"].revision == 0
    assert np.array_equal(np.asarray(eng.infer("g", x)), ref)


def test_async_persist_backfills_fingerprint_and_warm_restarts(
        tmp_path, monkeypatch):
    a, params, x = _workload(12)
    rng = np.random.default_rng(12)
    eng = _engine(tmp_path)
    eng.add_graph("g", a, params)
    eng.infer("g", x)
    rep = eng.update_graph("g", _value_delta(a, 8, rng))
    assert rep.fingerprint == ""
    eng.drain_persists()
    rec = eng._graphs["g"]
    fp2 = registry.graph_fingerprint(rec.coo)
    assert rec.fingerprint == fp2  # back-filled by the worker
    # a restart admitting the mutated graph warm-starts from the entry
    # the worker persisted: zero measured sweeps, zero rebuilds
    registry.clear_caches()
    monkeypatch.setattr(runner, "measure_candidate",
                        lambda *a_, **k: pytest.fail("sweep on warm start"))
    monkeypatch.setattr(schedule, "build_balanced_schedule",
                        lambda *a_, **k: pytest.fail("rebuild on warm start"))
    eng2 = _engine(tmp_path)
    rep2 = eng2.add_graph("g", rec.coo, params)
    assert rep2.warm_start
    assert np.array_equal(np.asarray(eng2.infer("g", x)),
                          np.asarray(eng.infer("g", x)))


def test_update_graph_zero_gap_under_concurrent_infer(tmp_path):
    a, params, x = _workload(13)
    rng = np.random.default_rng(13)
    eng = _engine(tmp_path)
    eng.add_graph("g", a, params)
    eng.infer("g", x)
    stop = threading.Event()
    served, failures = [0], []

    def _background():
        while not stop.is_set():
            try:
                y = np.asarray(eng.infer("g", x))
                assert np.all(np.isfinite(y))
                served[0] += 1
            except Exception as e:  # pragma: no cover - the bug under test
                failures.append(repr(e))
                return

    th = threading.Thread(target=_background, daemon=True)
    th.start()
    for i in range(4):
        coo = eng._graphs["g"].coo
        delta = (_value_delta(coo, 8, rng) if i % 2 == 0
                 else _structural_delta(coo, N_NODES, 8, rng))
        eng.update_graph("g", delta)
    stop.set()
    th.join(timeout=60.0)
    assert not failures, failures
    assert served[0] > 0


def test_update_graph_on_evicted_graph_is_host_only(tmp_path):
    a, params, x = _workload(14)
    rng = np.random.default_rng(14)
    eng = _engine(tmp_path)
    eng.add_graph("g", a, params)
    eng.infer("g", x)
    eng._evict(eng._graphs["g"])
    assert eng._graphs["g"].executor is None
    rep = eng.update_graph("g", _value_delta(a, 8, rng))
    assert rep.repaired and not rep.scoped_upload
    assert eng._graphs["g"].executor is None  # no re-admission side effect
    got = np.asarray(eng.infer("g", x))  # re-admits the repaired schedule
    rec = eng._graphs["g"]
    ident = _pinned_engine(tmp_path / "cold", rec.config)
    ident.add_graph("g", rec.coo, params)
    assert np.array_equal(got, np.asarray(ident.infer("g", x)))


# ---------------------------------------------------------------------------
# lifecycle sweep: remove-with-pending, EWMA resets, budget-sweep break
# ---------------------------------------------------------------------------

def _accounting(eng):
    c = eng.counters
    st = eng.stats()
    lhs = c["submitted"]
    rhs = (c["queue_served"] + c["shed"] + c["rejected"] + c["dropped"]
           + st["pending_requests"] + st["inflight_requests"])
    return lhs, rhs


def test_remove_graph_with_pending_fails_them_typed(tmp_path):
    a, params, x = _workload(15)
    eng = _engine(tmp_path)
    eng.add_graph("g", a, params)
    for _ in range(3):
        assert eng.submit("g", x, deadline_s=60.0)
    lhs, rhs = _accounting(eng)
    assert lhs == rhs == 3
    with pytest.raises(RequestFailure) as ei:
        eng.remove_graph("g")
    assert ei.value.n_failed == 3
    assert ei.value.graph_id == "g"
    # settled exactly once, into `dropped`; the identity still holds
    assert eng.counters["dropped"] == 3
    lhs, rhs = _accounting(eng)
    assert lhs == rhs == 3
    # removal completed despite the raise: graph + queues + stats gone
    assert "g" not in eng.graphs
    assert "g" not in eng._pending and "g" not in eng._svc_ewma
    assert eng.device_bytes_in_use == 0
    with pytest.raises(UnknownGraphError):
        eng.remove_graph("g")
    assert eng.counters["dropped"] == 3  # no double settle


def test_remove_graph_without_pending_raises_nothing(tmp_path):
    a, params, x = _workload(16)
    eng = _engine(tmp_path)
    eng.add_graph("g", a, params)
    eng.infer("g", x)
    eng.remove_graph("g")
    assert eng.counters["dropped"] == 0
    assert eng.device_bytes_in_use == 0


def test_evict_resets_service_ewmas(tmp_path):
    a, params, x = _workload(17)
    eng = _engine(tmp_path)
    eng.add_graph("g", a, params)
    eng.infer("g", x)
    assert "g" in eng._svc_ewma  # infer measured a service time
    eng._svc_req_ewma["g"] = 0.5
    eng._calm_polls["g"] = 2
    eng._evict(eng._graphs["g"])
    # the EWMAs were measured under the old residency: a re-admitted
    # graph must re-measure, not shed requests off stale predictions
    assert "g" not in eng._svc_ewma
    assert "g" not in eng._svc_req_ewma
    assert "g" not in eng._calm_polls
    eng.infer("g", x)  # re-admission serves and re-measures
    assert "g" in eng._svc_ewma


def test_evict_over_budget_never_evicts_keep(tmp_path):
    a, params, x = _workload(18)
    eng = _engine(tmp_path)
    eng.add_graph("g", a, params)
    eng.infer("g", x)
    d = eng.placer.placement_of("g").device_index
    # inflate the kept graph's accounted footprint past the budget: the
    # sweep finds no replica and no victim besides `keep` and must break
    # out instead of spinning or evicting the graph it protects
    eng.placer.reaccount("g", eng.device_budget_bytes * 2)
    assert eng.placer.used[d] > eng.placer.budget
    eng._evict_over_budget("g")
    assert eng._graphs["g"].executor is not None
    assert eng.placer.used[d] > eng.placer.budget  # still over; no churn
    got = np.asarray(eng.infer("g", x))
    assert np.all(np.isfinite(got))


# ---------------------------------------------------------------------------
# store builder versioning
# ---------------------------------------------------------------------------

def test_store_key_varies_with_builder_version_and_revision(monkeypatch):
    st = TuningStore(root="/tmp/unused-root")
    base = st.key("fp", 16, device="cpu:x", mesh="1dev")
    rev = st.key("fp", 16, device="cpu:x", mesh="1dev", revision=3)
    assert base != rev
    monkeypatch.setattr(store_mod, "SCHEDULE_BUILDER_VERSION",
                        store_mod.SCHEDULE_BUILDER_VERSION + 1)
    bumped = st.key("fp", 16, device="cpu:x", mesh="1dev")
    assert bumped != base  # a builder bump orphans every old entry


def test_store_drops_mixed_builder_version_entries(tmp_path):
    a, _, _ = _workload(19)
    sched = schedule.build_balanced_schedule(a, **SCHED_KW)
    cfg = runner.autotune(a, (N_NODES, 16), store=None, **FAST_KW)
    st = TuningStore(root=tmp_path)
    good = st.key("fp-good", 16)
    stale = st.key("fp-stale", 16)
    st.save(good, cfg, sched)
    st.save(stale, cfg, sched)
    # rewrite one entry as if an older builder lineage produced it
    path = st.path(stale)
    with np.load(path, allow_pickle=False) as z:
        payload = {k: z[k] for k in z.files}
    payload["builder_version"] = np.asarray(
        store_mod.SCHEDULE_BUILDER_VERSION - 1, np.int64)
    np.savez(path, **payload)
    with pytest.warns(UserWarning, match="builder version"):
        assert st.load(stale) is None  # dropped to re-tune, never crash
    assert not path.exists()  # the stale corpse is unlinked
    got = st.load(good)  # the mixed store still serves current entries
    assert got is not None and _schedules_equal(got[1], sched)


def test_engine_retunes_through_stale_builder_entry(tmp_path):
    a, params, x = _workload(20)
    eng = _engine(tmp_path)
    eng.add_graph("g", a, params)
    ref = np.asarray(eng.infer("g", x))
    # corrupt the engine's own entry into a stale-builder one
    (entry,) = eng.store.entries()
    path = eng.store.path(entry)
    with np.load(path, allow_pickle=False) as z:
        payload = {k: z[k] for k in z.files}
    payload["builder_version"] = np.asarray(-7, np.int64)
    np.savez(path, **payload)
    registry.clear_caches()
    eng2 = _engine(tmp_path)
    with pytest.warns(UserWarning, match="builder version"):
        rep = eng2.add_graph("g", a, params)
    assert not rep.warm_start  # dropped to a measured re-tune
    assert eng2.counters["store_misses"] == 1
    np.testing.assert_allclose(np.asarray(eng2.infer("g", x)), ref,
                               atol=1e-3)


# ---------------------------------------------------------------------------
# perf-gate math (benchmarks/check_regression.py)
# ---------------------------------------------------------------------------

def _gate_rows():
    return [
        dict(name="serving/g/warm_start", us_per_call=100.0,
             derived="speedup=50.00x"),
        dict(name="autotune/g", us_per_call=100.0, derived=""),
        dict(name="serving/batched_throughput", us_per_call=50.0,
             derived=""),
        dict(name="serving/mesh8/mesh_throughput", us_per_call=100.0,
             derived=""),
        dict(name="serving/mesh8/hot_replicated", us_per_call=100.0,
             derived="speedup=2.00x;bit_identical=1"),
        dict(name="openloop/steady/p99", us_per_call=1000.0, derived=""),
        dict(name="openloop/steady/goodput", us_per_call=90.0,
             derived="identity=1;submitted=10;served=8;shed=1;rejected=1"),
        dict(name="openloop/steady_learned/goodput", us_per_call=88.0,
             derived="identity=1;submitted=10;served=8;shed=1;rejected=1"),
        dict(name="openloop/steady_learned/pred_err", us_per_call=40.0,
             derived="n_scored=24;n_samples=30;fallbacks=2;fitted=1"),
        dict(name="streaming/small_delta/repair", us_per_call=2000.0,
             derived="speedup=6.00x;bit_identical=1;rebuild_us=12000"),
        dict(name="streaming/zero_gap", us_per_call=500.0,
             derived="gap=0;updates=4;infers=20"),
        dict(name="reorder/g/none", us_per_call=100.0,
             derived="nnz=1000;steps=10;locality=0.400"),
        dict(name="reorder/g/island", us_per_call=95.0,
             derived="speedup_vs_none=1.05x;bit_identical=1;steps=9;"
                     "locality=0.350"),
        dict(name="reorder/g/sweep", us_per_call=95.0,
             derived="winner=island;accepted=1;speedup_vs_none=1.05x"),
        dict(name="reorder/h/sweep", us_per_call=100.0,
             derived="winner=none;accepted=0;speedup_vs_none=1.00x"),
    ]


def _gate_payload(smoke=True, **edits):
    rows = _gate_rows()
    for name, fields in edits.items():
        (row,) = [r for r in rows if r["name"] == name]
        row.update(fields)
    return dict(smoke=smoke, rows=rows)


def test_gate_identity_is_green():
    smoke = _gate_payload()
    ref = _gate_payload(smoke=False)
    assert gate.check(smoke, ref, tolerance=3.0) == []


def test_gate_zero_denominator_is_degenerate_not_crash():
    smoke = _gate_payload(**{
        "serving/batched_throughput": dict(us_per_call=0.0)})
    ref = _gate_payload(smoke=False)
    problems = gate.check(smoke, ref, tolerance=3.0)
    assert any(p.startswith("DEGENERATE") for p in problems)
    assert not any("ZeroDivision" in p for p in problems)
    # degenerate on the reference side too: still a report, not a crash
    problems = gate.check(_gate_payload(), _gate_payload(smoke=False, **{
        "serving/batched_throughput": dict(us_per_call=0.0)}), 3.0)
    assert any(p.startswith("DEGENERATE") for p in problems)


def test_gate_streaming_speedup_floor_and_bit_identity():
    ref = _gate_payload(smoke=False)
    # exactly at the floor (6.00 / 3.0 = 2.00): passes, not a regression
    at_floor = _gate_payload(**{"streaming/small_delta/repair": dict(
        derived="speedup=2.00x;bit_identical=1")})
    assert gate.check(at_floor, ref, tolerance=3.0) == []
    below = _gate_payload(**{"streaming/small_delta/repair": dict(
        derived="speedup=1.99x;bit_identical=1")})
    problems = gate.check(below, ref, tolerance=3.0)
    assert any("REGRESSION" in p and "incremental" in p for p in problems)
    flipped = _gate_payload(**{"streaming/small_delta/repair": dict(
        derived="speedup=6.00x;bit_identical=0")})
    problems = gate.check(flipped, ref, tolerance=3.0)
    assert any(p.startswith("CORRECTNESS") and "bit_identical" in p
               for p in problems)
    missing = dict(smoke=True, rows=[r for r in _gate_rows()
                                     if "streaming" not in r["name"]])
    problems = gate.check(missing, ref, tolerance=3.0)
    assert any("MISSING" in p and "small_delta" in p for p in problems)


def test_gate_zero_gap_hard():
    ref = _gate_payload(smoke=False)
    bad = _gate_payload(**{"streaming/zero_gap": dict(derived="gap=2")})
    problems = gate.check(bad, ref, tolerance=3.0)
    assert any(p.startswith("CORRECTNESS") and "zero_gap" in p
               for p in problems)
    nogap = _gate_payload(**{"streaming/zero_gap": dict(derived="")})
    problems = gate.check(nogap, ref, tolerance=3.0)
    assert any("no gap count" in p for p in problems)


def test_gate_p99_ceiling_edges():
    ref = _gate_payload(smoke=False)
    at = _gate_payload(**{"openloop/steady/p99": dict(us_per_call=3000.0)})
    assert gate.check(at, ref, tolerance=3.0) == []  # exactly at ceiling
    above = _gate_payload(**{
        "openloop/steady/p99": dict(us_per_call=3000.1)})
    problems = gate.check(above, ref, tolerance=3.0)
    assert any("REGRESSION" in p and "p99" in p for p in problems)


def test_gate_learned_head_to_head():
    ref = _gate_payload(smoke=False)
    # goodput below the smoke-internal heuristic floor (90 / 3.0 = 30)
    bad = _gate_payload(**{"openloop/steady_learned/goodput": dict(
        us_per_call=29.9)})
    problems = gate.check(bad, ref, tolerance=3.0)
    assert any("REGRESSION" in p and "learned-policy" in p for p in problems)
    # zero scored predictions: the accuracy report vouches for nothing
    unscored = _gate_payload(**{"openloop/steady_learned/pred_err": dict(
        derived="n_scored=0;n_samples=0;fallbacks=9;fitted=0")})
    problems = gate.check(unscored, ref, tolerance=3.0)
    assert any(p.startswith("DEGENERATE") and "pred_err" in p
               for p in problems)
    # error ceiling is max(absolute, tolerance x reference): with the
    # fixture reference at 40% the 150% absolute ceiling dominates
    wild = _gate_payload(**{"openloop/steady_learned/pred_err": dict(
        us_per_call=150.1)})
    problems = gate.check(wild, ref, tolerance=3.0)
    assert any("REGRESSION" in p and "prediction error" in p
               for p in problems)
    at_ceiling = _gate_payload(**{"openloop/steady_learned/pred_err": dict(
        us_per_call=150.0)})
    assert gate.check(at_ceiling, ref, tolerance=3.0) == []
    # both head-to-head rows absent: the gate reports itself blind
    missing = dict(smoke=True, rows=[r for r in _gate_rows()
                                     if "steady_learned" not in r["name"]])
    problems = gate.check(missing, ref, tolerance=3.0)
    assert any("MISSING" in p and "steady_learned" in p for p in problems)


def test_gate_pred_err_ceiling_scaled_by_reference():
    # when 3x the reference exceeds the 150% absolute floor the scaled
    # ceiling governs: reference at 60% -> ceiling 180%
    ref = _gate_payload(smoke=False, **{
        "openloop/steady_learned/pred_err": dict(us_per_call=60.0)})
    at = _gate_payload(**{"openloop/steady_learned/pred_err": dict(
        us_per_call=180.0)})
    assert gate.check(at, ref, tolerance=3.0) == []
    above = _gate_payload(**{"openloop/steady_learned/pred_err": dict(
        us_per_call=180.1)})
    problems = gate.check(above, ref, tolerance=3.0)
    assert any("REGRESSION" in p and "3x reference 60%" in p
               for p in problems)


def test_gate_pred_err_absolute_ceiling_without_reference_row():
    # a reference trajectory that predates the learned policy carries no
    # pred_err row: the 150% absolute ceiling applies, exactly-at passes
    ref = dict(smoke=False,
               rows=[r for r in _gate_rows()
                     if r["name"] != "openloop/steady_learned/pred_err"])
    at = _gate_payload(**{"openloop/steady_learned/pred_err": dict(
        us_per_call=150.0)})
    assert gate.check(at, ref, tolerance=3.0) == []
    above = _gate_payload(**{"openloop/steady_learned/pred_err": dict(
        us_per_call=150.1)})
    problems = gate.check(above, ref, tolerance=3.0)
    assert any("REGRESSION" in p and "absolute ceiling" in p
               for p in problems)


def test_gate_pred_err_missing_scored_count_is_degenerate():
    # a derived string with no n_scored= at all vouches for nothing,
    # same verdict as n_scored=0 -- and never a parse crash
    blank = _gate_payload(**{"openloop/steady_learned/pred_err": dict(
        derived="fitted=1")})
    problems = gate.check(blank, _gate_payload(smoke=False), tolerance=3.0)
    assert any(p.startswith("DEGENERATE") and "pred_err" in p
               for p in problems)


def test_gate_accounting_identity():
    ref = _gate_payload(smoke=False)
    bad = _gate_payload(**{"openloop/steady/goodput": dict(
        derived="identity=1;submitted=10;served=8;shed=1;rejected=0")})
    problems = gate.check(bad, ref, tolerance=3.0)
    assert any(p.startswith("CORRECTNESS") and "vanished" in p
               for p in problems)
    unasserted = _gate_payload(**{"openloop/steady/goodput": dict(
        derived="submitted=10;served=8;shed=1;rejected=1")})
    problems = gate.check(unasserted, ref, tolerance=3.0)
    assert any("identity=1" in p for p in problems)


def test_gate_reorder_bit_identity_winner_floor_and_diversity():
    ref = _gate_payload(smoke=False)
    flipped = _gate_payload(**{"reorder/g/island": dict(
        derived="speedup_vs_none=1.05x;bit_identical=0;steps=9;"
                "locality=0.350")})
    problems = gate.check(flipped, ref, tolerance=3.0)
    assert any(p.startswith("CORRECTNESS") and "reorder/g/island" in p
               for p in problems)
    # winner floor is 1/tolerance: 0.34x passes at tol 3, 0.33x trips
    at_floor = _gate_payload(**{"reorder/g/sweep": dict(
        derived="winner=island;accepted=1;speedup_vs_none=0.34x")})
    assert gate.check(at_floor, ref, tolerance=3.0) == []
    below = _gate_payload(**{"reorder/g/sweep": dict(
        derived="winner=island;accepted=1;speedup_vs_none=0.33x")})
    problems = gate.check(below, ref, tolerance=3.0)
    assert any("REGRESSION" in p and "measures slower" in p
               for p in problems)
    missing = dict(smoke=True, rows=[r for r in _gate_rows()
                                     if not r["name"].startswith("reorder/")])
    problems = gate.check(missing, ref, tolerance=3.0)
    assert any("MISSING" in p and "reorder" in p for p in problems)
    # a full-scale reference whose sweep always accepts (or always
    # rejects) is a degenerate trajectory: the axis stopped discriminating
    always = _gate_payload(smoke=False, **{"reorder/h/sweep": dict(
        derived="winner=degree;accepted=1;speedup_vs_none=1.01x")})
    problems = gate.check(_gate_payload(), always, tolerance=3.0)
    assert any(p.startswith("DEGENERATE") and "always accepts" in p
               for p in problems)


def test_gate_round_trips_through_json():
    smoke = json.loads(json.dumps(_gate_payload()))
    ref = json.loads(json.dumps(_gate_payload(smoke=False)))
    assert gate.check(smoke, ref, tolerance=3.0) == []


# ---------------------------------------------------------------------------
# sharded + replicated update bit-identity (8 forced host devices)
# ---------------------------------------------------------------------------

SCRIPT_STREAM = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys, tempfile
sys.path.insert(0, %r)
import numpy as np, jax, jax.numpy as jnp
from repro.core import csc, executor as exe, gcn
from repro.graphs import synth
from repro.serving.gcn_engine import GCNServingEngine
from repro.serving.placement import REPLICATED, SHARDED, SINGLE
assert len(jax.devices()) == 8

SWEEP = [dict(nnz_per_step=64, rows_per_window=32, cols_per_block=None,
              window_nnz=None, routing=exe.GATHER)]
KW = dict(iters=1, warmup=1, sweep=SWEEP, bf16_report=False)

def pinned_kw(cfg):
    cand = dict(nnz_per_step=cfg.nnz_per_step,
                rows_per_window=cfg.rows_per_window,
                cols_per_block=cfg.cols_per_block,
                window_nnz=cfg.window_nnz, routing=cfg.routing,
                ktile=cfg.ktile)
    return dict(iters=1, warmup=1, sweep=[cand], bf16_report=False)

def value_delta(coo, k, rng):
    row, col = np.asarray(coo.row), np.asarray(coo.col)
    idx = rng.choice(row.shape[0], size=k, replace=False)
    vals = (rng.random(k) + 0.5).astype(np.float32)
    return csc.EdgeDelta(row[idx], col[idx], vals)

def structural_delta(n, k, rng):
    return csc.EdgeDelta(rng.integers(0, n, k), rng.integers(0, n, k),
                         (rng.random(k) + 0.1).astype(np.float32))

n = 3000
a = synth.power_law_adjacency(n, 0.01, 0.9, seed=99)
gcfg = gcn.GCNConfig(16, 16, 4)
params = gcn.init_params(gcfg, jax.random.PRNGKey(99))
x = np.random.default_rng(99).random((n, 16)).astype(np.float32)
budget = a.nnz * 4  # the graph cannot fit one device: routes SHARDED
rng = np.random.default_rng(17)

root = tempfile.mkdtemp(prefix="awb-stream-mesh-")
eng = GCNServingEngine(store_root=root, devices=8,
                       device_budget_bytes=budget, autotune_kwargs=KW)
rep = eng.add_graph("g", a, params)
assert rep.placement.kind == SHARDED
eng.infer("g", x)
for i in range(4):
    coo = eng._graphs["g"].coo
    delta = (value_delta(coo, 12, rng) if i %% 2 == 0
             else structural_delta(n, 12, rng))
    urep = eng.update_graph("g", delta)
    assert urep.repaired and not urep.fell_back, urep
got = np.asarray(eng.infer("g", x))
rec = eng._graphs["g"]
iroot = tempfile.mkdtemp(prefix="awb-stream-ident-")
ident = GCNServingEngine(store_root=iroot, devices=8,
                         device_budget_bytes=budget,
                         autotune_kwargs=pinned_kw(rec.config))
ident.add_graph("g", rec.coo, params)
want = np.asarray(ident.infer("g", x))
assert np.array_equal(got, want)
print("SHARDED UPDATE OK")

# --- replicated graph: the swap must splice every clone ------------------
n2 = 260
a2 = synth.power_law_adjacency(n2, 0.03, 0.9, seed=5)
p2 = gcn.init_params(gcfg, jax.random.PRNGKey(5))
x2 = np.random.default_rng(5).random((n2, 16)).astype(np.float32)
rroot = tempfile.mkdtemp(prefix="awb-stream-rep-")
eng2 = GCNServingEngine(store_root=rroot, devices=8, autotune_kwargs=KW)
eng2.add_graph("h", a2, p2)
eng2.infer("h", x2)
rec2 = eng2._graphs["h"]
assert eng2._grow_replica(rec2)
assert eng2.placer.placement_of("h").kind == REPLICATED
urep = eng2.update_graph("h", value_delta(rec2.coo, 10, rng))
assert urep.repaired and urep.scoped_upload
# both clones serve the patched values bit-identically
outs = [np.asarray(u.fwd(u.params, jnp.asarray(x2[None]))[0])
        for u in eng2._units(rec2)]
assert len(outs) == 2 and np.array_equal(outs[0], outs[1])
iroot2 = tempfile.mkdtemp(prefix="awb-stream-rident-")
ident2 = GCNServingEngine(store_root=iroot2,
                          autotune_kwargs=pinned_kw(rec2.config))
ident2.add_graph("h", rec2.coo, p2)
assert np.array_equal(outs[0], np.asarray(ident2.infer("h", x2)))
print("REPLICA UPDATE OK")

# --- collapse back to SINGLE resets the split-batch EWMAs ----------------
eng2._svc_ewma["h"] = 0.123
eng2._svc_req_ewma["h"] = 0.456
(shed_dev,) = [d for d in rec2.replicas]
eng2._drop_replica(rec2, shed_dev)
assert eng2.placer.placement_of("h").kind == SINGLE
assert "h" not in eng2._svc_ewma and "h" not in eng2._svc_req_ewma
print("COLLAPSE EWMA OK")
""" % (SRC,)


@pytest.mark.distributed
def test_sharded_and_replicated_updates_bit_identical():
    r = subprocess.run([sys.executable, "-c", SCRIPT_STREAM],
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, \
        f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
    for tag in ("SHARDED UPDATE OK", "REPLICA UPDATE OK",
                "COLLAPSE EWMA OK"):
        assert tag in r.stdout
