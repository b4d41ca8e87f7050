"""Mesh placement + deadline-aware serving acceptance.

Host-side ``MeshPlacer`` policy (bin-packing under per-device budgets,
sharded fallback, eviction-pressure rebalancing) is unit-tested without a
mesh; the engine-level acceptance — distinct-device placement, giant-graph
sharded admission, and restart warm-starts on an 8-way forced
host-platform mesh — runs in a subprocess under the ``distributed``
marker. Deadline scheduling (EDF order, auto-flush, miss accounting,
multi-failure flush restore) runs single-device in-process.
"""
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.core import executor as exe, gcn, schedule  # noqa: E402
from repro.graphs import synth  # noqa: E402
from repro.serving.gcn_engine import (FlushError,  # noqa: E402
                                      GCNServingEngine, _Request)
from repro.serving.placement import (REPLICATED, SHARDED,  # noqa: E402
                                     SINGLE, MeshPlacer, Placement)
from repro.sharding import schedule_shard  # noqa: E402
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


# ---------------------------------------------------------------------------
# MeshPlacer policy (pure host-side — no mesh required)
# ---------------------------------------------------------------------------

def test_worst_fit_spreads_equal_graphs_across_devices():
    p = MeshPlacer(4, 1000)
    for i in range(4):
        pl = p.place(f"g{i}", 300)
        assert pl.kind == SINGLE
        p.account(f"g{i}", 300)
    assert sorted(pl.device_index for pl in p.placements.values()) == [
        0, 1, 2, 3]


def test_bin_packing_with_lru_eviction_never_exceeds_budget():
    """The engine's admission loop in miniature: place + account, evicting
    the least-recently-placed resident on any over-budget device. The
    per-device byte meter must never end a step over budget."""
    rng = np.random.default_rng(0)
    budget = 1000
    p = MeshPlacer(3, budget)
    order = []  # residency in admission order (the LRU stand-in)
    for i in range(40):
        gid = f"g{i}"
        nbytes = int(rng.integers(100, budget + 1))
        pl = p.place(gid, nbytes)
        assert pl.kind == SINGLE  # never over one device's budget here
        p.account(gid, nbytes)
        order.append((gid, nbytes))
        for d in range(p.n_devices):
            while p.used[d] > budget:
                victim = next(
                    (g for g, _ in order
                     if p.is_resident(g) and g != gid
                     and p.placements[g].device_index == d), None)
                assert victim is not None, "nothing left to evict"
                p.note_eviction(victim)
                p.unaccount(victim)
        assert all(p.used[d] <= budget for d in range(p.n_devices))
        assert all(u >= 0 for u in p.used)


def test_giant_graph_routes_sharded_only_on_multi_device_mesh():
    p = MeshPlacer(4, 1000)
    pl = p.place("giant", 5000)
    assert pl.kind == SHARDED and pl.n_devices == 4
    assert pl.device_indices == (0, 1, 2, 3)
    p.account("giant", 5000)
    assert all(u == 1250 for u in p.used)  # even ceil split
    p.unaccount("giant")
    assert all(u == 0 for u in p.used)
    # a 1-device mesh cannot shard: the graph stays single and the
    # engine's keep-active rule degrades to one-at-a-time rotation
    p1 = MeshPlacer(1, 1000)
    assert p1.place("giant", 5000).kind == SINGLE


def test_duplicate_place_or_account_rejected():
    p = MeshPlacer(2, 100)
    p.place("g", 10)
    with pytest.raises(ValueError, match="already placed"):
        p.place("g", 10)
    p.account("g", 10)
    with pytest.raises(ValueError, match="already accounted"):
        p.account("g", 10)
    p.forget("g")
    assert p.placements == {} and p.used == [0, 0]


def test_rebalance_triggers_on_concentrated_pressure_and_resets():
    p = MeshPlacer(2, 100, rebalance_after=3)
    p.place("a", 60)
    p.account("a", 60)       # a -> dev0
    p.place("b", 60)
    p.account("b", 60)       # b -> dev1 (worst fit)
    assert p.rebalance_target() is None
    # thrash graph a on device 0
    for _ in range(3):
        p.note_eviction("a")
        p.unaccount("a")
        p.account("a", 60)
    hot, cool = p.rebalance_target()
    assert (hot, cool) == (0, 1)
    p.move("a", cool)
    assert p.placements["a"].device_index == 1
    assert p.used == [0, 120]           # resident bytes moved with it
    assert p.evictions == [0, 0]        # pressure window reset
    assert p.n_rebalances == 1
    assert p.rebalance_target() is None


def test_sharded_graph_cannot_be_moved():
    p = MeshPlacer(2, 10)
    p.place("giant", 50)
    with pytest.raises(ValueError, match="sharded"):
        p.move("giant", 1)


def test_replica_grow_and_shrink_accounting():
    """add_replica lands on the coolest device, accounts one full clone
    footprint per replica device, and drop_replica frees exactly its
    device's share, collapsing to SINGLE at one remaining replica."""
    p = MeshPlacer(4, 1000)
    p.place("g", 300)
    p.account("g", 300)                  # primary on dev0
    assert p.replica_candidate("g") == 1
    assert p.add_replica("g", 300) == 1
    pl = p.placement_of("g")
    assert pl.kind == REPLICATED and pl.device_indices == (0, 1)
    assert pl.device_index == 0          # primary unchanged
    assert p.used == [300, 300, 0, 0]    # per-replica byte accounting
    p.place("other", 500)
    p.account("other", 500)              # worst-fit -> dev2
    assert p.replica_candidate("g") == 3  # coolest non-hosting device
    p.add_replica("g", 300, device_index=3)
    assert p.placement_of("g").device_indices == (0, 1, 3)
    assert p.used == [300, 300, 500, 300]
    pl = p.drop_replica("g", 1)
    assert pl.device_indices == (0, 3)
    assert p.used == [300, 0, 500, 300]
    pl = p.drop_replica("g", 3)
    assert pl.kind == SINGLE and pl.device_index == 0   # collapsed
    assert p.used == [300, 0, 500, 0]
    p.forget("g")
    assert p.used == [0, 0, 500, 0]


def test_replica_candidate_requires_room_for_the_clone():
    """Growth never evicts resident graphs to make space: with the
    clone's footprint passed, full devices are not candidates, and when
    nothing fits the candidate is None (the unfiltered query still names
    the coolest device)."""
    p = MeshPlacer(3, 1000)
    p.place("g", 400)
    p.account("g", 400)                  # dev0
    p.place("big", 900)
    p.account("big", 900)                # worst-fit -> dev1
    assert p.replica_candidate("g", 400) == 2    # dev1 has no room
    p.place("mid", 700)
    p.account("mid", 700)                # -> dev2
    assert p.replica_candidate("g", 400) is None  # nothing fits now
    assert p.replica_candidate("g") == 2          # unfiltered: coolest


def test_replica_unaccount_clears_every_device():
    p = MeshPlacer(3, 1000)
    p.place("g", 200)
    p.account("g", 200)
    p.add_replica("g", 200)
    p.add_replica("g", 200)
    assert p.used == [200, 200, 200]
    p.unaccount("g")
    assert p.used == [0, 0, 0] and not p.is_resident("g")


def test_replica_invariants_rejected():
    p = MeshPlacer(2, 1000)
    p.place("g", 100)
    with pytest.raises(ValueError, match="not resident"):
        p.add_replica("g", 100)          # must be admitted first
    p.account("g", 100)
    p.add_replica("g", 100)
    with pytest.raises(ValueError, match="already has a replica"):
        p.add_replica("g", 100)          # every device already hosts one
    with pytest.raises(ValueError, match="primary"):
        p.drop_replica("g", 0)
    with pytest.raises(ValueError, match="cannot move"):
        p.move("g", 1)                   # replicated graphs don't migrate
    p2 = MeshPlacer(2, 10)
    p2.place("giant", 50)                # sharded route
    p2.account("giant", 50)
    assert p2.replica_candidate("giant") is None
    with pytest.raises(ValueError, match="sharded"):
        p2.add_replica("giant", 50)


def test_device_report_lists_replicas_per_device():
    p = MeshPlacer(2, 1000)
    p.place("g", 100)
    p.account("g", 100)
    p.add_replica("g", 100)
    rep = p.device_report()
    assert rep[0]["resident"] == ["g"] and rep[1]["resident"] == ["g"]
    p.drop_replica("g", 1)
    rep = p.device_report()
    assert rep[0]["resident"] == ["g"] and rep[1]["resident"] == []


def test_shard_payload_bytes_matches_executor_footprint():
    """The placer's even-split accounting rests on the 12-bytes/slot
    padded-shard model; pin it to the real uploaded footprint so the
    model cannot drift from the executor."""
    a = synth.power_law_adjacency(300, 0.03, 0.9, seed=3)
    s = schedule.build_balanced_schedule(a, 32, 16)
    ex = exe.ShardedScheduleExecutor(s, n_devices=1, routing=exe.GATHER)
    assert int(schedule_shard.shard_payload_bytes(s, 1).sum()) == \
        ex.device_bytes
    # multi-device: the same arithmetic against the stacked shard layout
    # (equal padded shards — the even split IS the per-device slice)
    for d in (2, 3, 8):
        shards = schedule_shard.shard_schedule(s, d)
        per_dev = schedule_shard.shard_payload_bytes(s, d)
        assert per_dev.shape == (d,)
        assert (per_dev
                == shards.steps_per_shard * s.nnz_per_step * 12).all()


# ---------------------------------------------------------------------------
# Deadline-aware serving (single device, in-process)
# ---------------------------------------------------------------------------

def test_poll_serves_due_deadline_bit_identical_to_serve_batch(tmp_path):
    a, params, x = _workload(0)
    eng = _engine(tmp_path)
    eng.add_graph("g", a, params)
    xs = [x, x * 0.5, x + 0.1]
    for xi in xs:
        eng.submit("g", xi, deadline_s=60.0)
    # not due yet: deadline is a minute out and the service estimate is 0
    assert eng.poll() == {}
    assert eng.stats()["pending_requests"] == 3
    # due once the (injected) clock passes the deadline window
    out = eng.poll(now=time.monotonic() + 61.0)
    assert set(out) == {"g"} and out["g"].shape == (3, N_NODES, N_CLASSES)
    # acceptance: the auto-flushed batch is BIT-identical to the manual
    # serve_batch path (same jitted vmapped forward, same stacking)
    ref = eng.serve_batch("g", xs)
    assert np.array_equal(np.asarray(out["g"]), np.asarray(ref))
    # real deadline was a minute out: completion must have beaten it
    st = eng.stats()
    assert st["deadline_met"] == 3 and st["deadline_misses"] == 0
    assert st["latency_us_mean"] > 0 and st["pending_requests"] == 0


def test_service_time_estimate_dispatches_before_deadline(tmp_path):
    a, params, x = _workload(1)
    eng = _engine(tmp_path)
    eng.add_graph("g", a, params)
    eng.submit("g", x, deadline_s=60.0)
    now = time.monotonic()
    assert eng.poll(now=now) == {}  # 60s of slack, no service estimate
    # a measured 61s batch service time means the queue is already due:
    # waiting any longer guarantees a miss
    eng._svc_ewma["g"] = 61.0
    out = eng.poll(now=now)
    assert set(out) == {"g"}


def test_past_deadline_records_miss(tmp_path):
    a, params, x = _workload(2)
    eng = _engine(tmp_path)
    eng.add_graph("g", a, params)
    eng.submit("g", x, deadline_s=-1.0)  # already expired at submit
    out = eng.poll()
    assert set(out) == {"g"}
    assert eng.stats()["deadline_misses"] == 1
    assert eng.stats()["deadline_met"] == 0


def test_max_batch_threshold_auto_flushes(tmp_path):
    a, params, x = _workload(3)
    eng = _engine(tmp_path, max_batch=2)
    eng.add_graph("g", a, params)
    eng.submit("g", x)
    assert eng.stats()["pending_requests"] == 1
    eng.submit("g", x * 0.5)  # hits the threshold: batch dispatches now
    assert eng.stats()["pending_requests"] == 0
    assert eng.stats()["inflight_requests"] == 2
    # the auto-flushed batch is awaited by the next poll/flush
    out = eng.flush()
    assert out["g"].shape == (2, N_NODES, N_CLASSES)
    assert eng.counters["batches"] == 1
    np.testing.assert_allclose(
        np.asarray(out["g"][1]),
        np.asarray(gcn.forward(params, a, jnp.asarray(x * 0.5))), atol=1e-3)


def test_flush_order_is_edf_then_graph_id_not_insertion(tmp_path):
    graphs = {f"g{i}": _workload(10 + i) for i in range(3)}
    eng = _engine(tmp_path)
    for gid, (a, params, x) in graphs.items():
        eng.add_graph(gid, a, params)
    # submission order g2, g0, g1; deadlines order the flush g1 < g0,
    # deadline-free g2 last — regardless of insertion order
    eng.submit("g2", graphs["g2"][2])
    eng.submit("g0", graphs["g0"][2], deadline_s=500.0)
    eng.submit("g1", graphs["g1"][2], deadline_s=100.0)
    order = []
    orig = eng._dispatch_batch

    def recording(graph_id, xs):
        order.append(graph_id)
        return orig(graph_id, xs)

    eng._dispatch_batch = recording
    eng.flush()
    assert order == ["g1", "g0", "g2"]


def test_flush_restores_multiple_failed_queues_in_order(tmp_path):
    """Satellite fix acceptance: several graphs failing in ONE flush all
    get their queues restored, at the front, in original order."""
    graphs = {f"g{i}": _workload(20 + i) for i in range(3)}
    eng = _engine(tmp_path)
    for gid, (a, params, x) in graphs.items():
        eng.add_graph(gid, a, params)
    for gid, (a, params, x) in graphs.items():
        eng.submit(gid, x)
        eng.submit(gid, x * 2.0)
    orig = eng._dispatch_batch

    def failing(graph_id, xs):
        if graph_id in ("g0", "g2"):
            raise RuntimeError(f"{graph_id} device fell over")
        return orig(graph_id, xs)

    eng._dispatch_batch = failing
    with pytest.raises(FlushError) as exc_info:
        eng.flush()
    err = exc_info.value
    assert set(err.failures) == {"g0", "g2"}
    assert set(err.partial) == {"g1"}
    assert err.partial["g1"].shape == (2, N_NODES, N_CLASSES)
    # both failed queues survived, original order intact
    for gid in ("g0", "g2"):
        q = eng._pending[gid]
        assert len(q) == 2
        np.testing.assert_array_equal(np.asarray(q[0].x), graphs[gid][2])
        np.testing.assert_array_equal(np.asarray(q[1].x),
                                      graphs[gid][2] * 2.0)
    assert "g1" not in eng._pending
    eng._dispatch_batch = orig
    out = eng.flush()
    assert set(out) == {"g0", "g2"}
    assert all(v.shape == (2, N_NODES, N_CLASSES) for v in out.values())


def test_restored_queue_front_ordering_with_new_submissions(tmp_path):
    """A failed queue must be restored AT THE FRONT: requests submitted
    after the failed flush retry must serve after the restored ones."""
    a, params, x = _workload(30)
    eng = _engine(tmp_path)
    eng.add_graph("g", a, params)
    eng.submit("g", x)
    orig = eng._dispatch_batch
    eng._dispatch_batch = lambda *a_, **k: (_ for _ in ()).throw(
        RuntimeError("boom"))
    with pytest.raises(FlushError):
        eng.flush()
    eng._dispatch_batch = orig
    eng.submit("g", x * 3.0)
    q = eng._pending["g"]
    np.testing.assert_array_equal(np.asarray(q[0].x), x)       # restored
    np.testing.assert_array_equal(np.asarray(q[1].x), x * 3.0)  # newer
    out = eng.flush()
    assert out["g"].shape == (2, N_NODES, N_CLASSES)


# ---------------------------------------------------------------------------
# poll()'s per-device load map (clock-injected, no real mesh: the map runs
# on placer indices only, so a stubbed placer + hand-built queues pin the
# dispatch decisions deterministically)
# ---------------------------------------------------------------------------

def _load_map_engine(tmp_path, placements):
    """Engine whose scheduler state is hand-built: a stubbed 2-device
    placer, injected service EWMAs, and a _serve_queues that records
    instead of serving."""
    eng = GCNServingEngine(store_root=tmp_path)
    eng.placer = MeshPlacer(2, 1 << 30)
    eng.placer.placements.update(placements)
    eng._serve_queues = lambda gids, now=None: {g: None for g in gids}
    return eng


def _queue(eng, gid, deadline):
    eng._pending.setdefault(gid, []).append(
        _Request(rid=0, x=None, submit_t=0.0, deadline=deadline))


def test_poll_load_map_stacks_colocated_queues(tmp_path):
    """Two queues on ONE device serialize: the tail queue's slack must
    absorb the cumulative service time of everything EDF-ahead of it on
    that device, so it dispatches earlier than its own estimate alone
    would suggest."""
    eng = _load_map_engine(tmp_path, {"a": Placement(SINGLE, 0, 1),
                                      "b": Placement(SINGLE, 0, 1)})
    eng._svc_ewma.update(a=10.0, b=10.0)
    _queue(eng, "a", deadline=1000.0)
    _queue(eng, "b", deadline=1001.0)
    # slack(a) = 1.5*10 + 0.01 -> due at 984.99
    # slack(b) = 1.5*(10 + 10) + 0.01 -> due at 970.99 (stacked behind a)
    assert eng.poll(now=969.0) == {}
    assert set(eng.poll(now=975.0)) == {"a", "b"}  # b due; a rides along


def test_poll_load_map_keeps_devices_independent(tmp_path):
    """The same two queues on DIFFERENT devices do not stack: each
    dispatches on its own estimate. A global (per-engine) accumulator
    would serve both a full stacked-slack early."""
    eng = _load_map_engine(tmp_path, {"a": Placement(SINGLE, 0, 1),
                                      "b": Placement(SINGLE, 1, 1)})
    eng._svc_ewma.update(a=10.0, b=10.0)
    _queue(eng, "a", deadline=1000.0)
    _queue(eng, "b", deadline=1001.0)
    assert eng.poll(now=975.0) == {}              # neither due yet
    assert set(eng.poll(now=985.5)) == {"a"}      # a due; b not (985.99)


def test_poll_load_map_sharded_occupies_every_device(tmp_path):
    """A sharded queue synchronizes the whole mesh at its psum: every
    device advances to its completion time, so a single-device queue
    behind it stacks even though they share no explicit device index."""
    eng = _load_map_engine(
        tmp_path, {"s": Placement(SHARDED, None, 2),
                   "b": Placement(SINGLE, 1, 1)})
    eng._svc_ewma.update(s=10.0, b=10.0)
    _queue(eng, "s", deadline=1000.0)
    _queue(eng, "b", deadline=1001.0)
    # b stacks behind s on device 1: due at 1001 - (1.5*20 + 0.01)
    assert set(eng.poll(now=975.0)) == {"s", "b"}


def test_poll_load_map_replicated_follows_least_loaded_replica(tmp_path):
    """Regression (ISSUE 5): the old load map overwrote every device of a
    multi-device placement with the max-ahead estimate. For a REPLICATED
    queue that is exactly wrong — the batch routes to the least-loaded
    clone, so a busy co-replica device must not drag the dispatch
    forward. Here the hot graph's replica on device 1 is idle: its queue
    is due from its own estimate (due at 1084.99), not from device 0's
    50 s backlog (which the old max-ahead rule would have turned into
    dispatch at 1009.99 — an hour-early batch-splitting waste)."""
    eng = _load_map_engine(
        tmp_path, {"busy": Placement(SINGLE, 0, 1),
                   "hot": Placement(REPLICATED, 0, 1, (0, 1))})
    eng._svc_ewma.update(busy=50.0, hot=10.0)
    _queue(eng, "busy", deadline=1000.0)
    _queue(eng, "hot", deadline=1100.0)
    out = eng.poll(now=1020.0)
    assert set(out) == {"busy"}, (
        "replicated queue dispatched off the busiest replica's backlog")
    assert set(eng.poll(now=1090.0)) == {"busy", "hot"}


def test_placement_survives_restart_warm_start(tmp_path):
    """Restart on the same store: zero sweeps, placements re-derived, and
    the deadline scheduler keeps serving."""
    graphs = {f"g{i}": _workload(40 + i) for i in range(2)}
    eng = _engine(tmp_path)
    refs = {}
    for gid, (a, params, x) in graphs.items():
        rep = eng.add_graph(gid, a, params)
        assert not rep.warm_start
        assert rep.placement.kind == SINGLE
        refs[gid] = np.asarray(eng.infer(gid, x))

    registry.clear_caches()  # ≈ restart (store survives)
    eng2 = _engine(tmp_path)
    for gid, (a, params, x) in graphs.items():
        rep = eng2.add_graph(gid, a, params)
        assert rep.warm_start and rep.tune_seconds == 0.0
        assert rep.placement.kind == SINGLE
    assert eng2.counters["store_hits"] == 2
    assert eng2.counters["store_misses"] == 0
    for gid, (a, params, x) in graphs.items():
        eng2.submit(gid, x, deadline_s=0.0)
    out = eng2.poll()
    assert set(out) == set(graphs)
    for gid in graphs:
        np.testing.assert_allclose(np.asarray(out[gid][0]), refs[gid],
                                   atol=1e-5)


def test_single_device_engine_keeps_default_placement_handle(tmp_path):
    """A graph placed on the process-default device gets a None handle —
    its uploads share the (schedule, None) cache with the registry and
    kernel paths instead of paying a duplicate pinned copy."""
    a, params, x = _workload(50)
    eng = _engine(tmp_path)
    eng.add_graph("g", a, params)
    rec = eng._graphs["g"]
    assert rec.executor.device is None
    out = eng.infer("g", x)
    assert out.devices() == {jax.devices()[0]}


# ---------------------------------------------------------------------------
# Mesh acceptance on 8 forced host devices (subprocess)
# ---------------------------------------------------------------------------

SCRIPT_MESH = r"""
import os, time
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys, tempfile
sys.path.insert(0, %r)
import numpy as np, jax, jax.numpy as jnp
from repro.core import executor as exe, gcn
from repro.core.executor import ShardedScheduleExecutor
from repro.graphs import synth
from repro.serving.gcn_engine import GCNServingEngine
from repro.serving.placement import SHARDED, SINGLE
from repro.tuning import registry
assert len(jax.devices()) == 8

SWEEP = [dict(nnz_per_step=64, rows_per_window=32, cols_per_block=None,
              window_nnz=None, routing=exe.GATHER)]
KW = dict(iters=1, warmup=1, sweep=SWEEP, bf16_report=False)

def workload(n, density, seed):
    a = synth.power_law_adjacency(n, density, 0.9, seed=seed)
    cfg = gcn.GCNConfig(16, 16, 4)
    params = gcn.init_params(cfg, jax.random.PRNGKey(seed))
    x = np.random.default_rng(seed).random((n, 16)).astype(np.float32)
    return a, params, x

small = {f"g{i}": workload(260, 0.03, i) for i in range(4)}
giant = workload(3000, 0.01, 99)
est_small = max(a.nnz * 16 + 3000 for a, _, _ in small.values())
budget = 6 * est_small          # every small graph fits; the giant cannot
assert giant[0].nnz * 16 > budget

root = tempfile.mkdtemp(prefix="awb-placement-")
eng = GCNServingEngine(store_root=root, devices=8,
                       device_budget_bytes=budget, autotune_kwargs=KW)

# --- distinct-device bin-packing, verified via executor shardings --------
devs = {}
for gid, (a, params, x) in small.items():
    rep = eng.add_graph(gid, a, params)
    assert rep.placement.kind == SINGLE
    rec = eng._graphs[gid]
    (dev,) = eng.infer(gid, x).devices()
    assert dev == eng.devices[rep.placement.device_index]
    # default-device placements keep a None handle (shared upload cache);
    # every other mesh device is explicitly pinned
    assert rec.executor.device == (None if dev == jax.devices()[0] else dev)
    devs[gid] = dev
assert len(set(devs.values())) == 4, devs
print("DISTINCT OK", sorted(d.id for d in devs.values()))

# --- giant graph: sharded admission spanning the mesh --------------------
a_g, p_g, x_g = giant
rep = eng.add_graph("giant", a_g, p_g)
assert rep.placement.kind == SHARDED and rep.placement.n_devices == 8
rec = eng._graphs["giant"]
assert isinstance(rec.executor, ShardedScheduleExecutor)
assert rec.executor.n_devices == 8
assert rep.config.n_devices == 8
got = np.asarray(eng.infer("giant", x_g))
ref = np.asarray(gcn.forward(p_g, a_g, jnp.asarray(x_g)))
np.testing.assert_allclose(got, ref, atol=1e-3)
print("SHARDED OK")

# --- deadline auto-flush bit-identical to manual serve_batch -------------
xs = [x_g, x_g * 0.5]
for xi in xs:
    eng.submit("giant", xi, deadline_s=60.0)
for gid, (a, params, x) in small.items():
    eng.submit(gid, x, deadline_s=30.0)
assert eng.poll() == {}
out = eng.poll(now=time.monotonic() + 61.0)
assert set(out) == set(small) | {"giant"}
ref_b = eng.serve_batch("giant", xs)
assert np.array_equal(np.asarray(out["giant"]), np.asarray(ref_b))
for gid, (a, params, x) in small.items():
    ref_b = eng.serve_batch(gid, [x])
    assert np.array_equal(np.asarray(out[gid]), np.asarray(ref_b))
st = eng.stats()
assert st["deadline_met"] == 6 and st["deadline_misses"] == 0
print("DEADLINE OK")

# --- restart: both routes warm-start from the store ----------------------
registry.clear_caches()
eng2 = GCNServingEngine(store_root=root, devices=8,
                        device_budget_bytes=budget, autotune_kwargs=KW)
for gid, (a, params, x) in small.items():
    rep = eng2.add_graph(gid, a, params)
    assert rep.warm_start and rep.tune_seconds == 0.0
rep = eng2.add_graph("giant", a_g, p_g)
assert rep.warm_start and rep.placement.kind == SHARDED
assert eng2.counters["store_hits"] == 5
assert eng2.counters["store_misses"] == 0
got = np.asarray(eng2.infer("giant", x_g))
np.testing.assert_allclose(got, ref, atol=1e-3)
print("WARM OK")

# --- eviction pressure concentrated on one device triggers migration -----
registry.clear_caches()
per_graph = {gid: eng._graphs[gid].bytes for gid in small}
tight = int(max(per_graph.values()) * 1.3)   # one graph per device
assert all(a.nnz * 16 + 3000 <= tight for a, _, _ in small.values())
eng3 = GCNServingEngine(store_root=root, devices=2,
                        device_budget_bytes=tight, rebalance_after=3,
                        autotune_kwargs=KW)
refs = {}
for gid in ("g0", "g1", "g2"):
    a, params, x = small[gid]
    rep = eng3.add_graph(gid, a, params)
    assert rep.warm_start and rep.placement.kind == SINGLE
    refs[gid] = np.asarray(gcn.forward(params, a, jnp.asarray(x)))
# two of the three graphs share a device: alternating them thrashes it
# while the other device idles; the placer must notice the concentrated
# pressure and migrate one of the pair
placed = {gid: eng3.placer.placements[gid].device_index
          for gid in ("g0", "g1", "g2")}
shared = [d for d in set(placed.values())
          if sum(1 for v in placed.values() if v == d) == 2]
assert shared, placed
pair = sorted(g for g, d in placed.items() if d == shared[0])
for _ in range(6):
    for gid in pair:
        np.testing.assert_allclose(
            np.asarray(eng3.infer(gid, small[gid][2])), refs[gid],
            atol=1e-3)
assert eng3.counters["rebalances"] >= 1, eng3.stats()
assert eng3.counters["evictions"] >= 3
for gid in ("g0", "g1", "g2"):   # every graph still serves correctly
    np.testing.assert_allclose(
        np.asarray(eng3.infer(gid, small[gid][2])), refs[gid], atol=1e-3)
print("REBALANCE OK")
""" % (SRC,)


@pytest.mark.distributed
def test_mesh_placement_sharded_giant_and_deadline_acceptance():
    r = subprocess.run([sys.executable, "-c", SCRIPT_MESH],
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, \
        f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
    for tag in ("DISTINCT OK", "SHARDED OK", "DEADLINE OK", "WARM OK",
                "REBALANCE OK"):
        assert tag in r.stdout


# ---------------------------------------------------------------------------
# Multi-replica serving of a hot graph (8 forced host devices, subprocess)
# ---------------------------------------------------------------------------

SCRIPT_REPLICA = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys, tempfile
sys.path.insert(0, %r)
import numpy as np, jax, jax.numpy as jnp
from repro.core import executor as exe, gcn, schedule
from repro.graphs import synth
from repro.serving.gcn_engine import GCNServingEngine
from repro.serving.placement import REPLICATED, SINGLE
from repro.tuning import registry, runner
assert len(jax.devices()) == 8

SWEEP = [dict(nnz_per_step=64, rows_per_window=32, cols_per_block=None,
              window_nnz=None, routing=exe.GATHER)]
KW = dict(iters=1, warmup=1, sweep=SWEEP, bf16_report=False)

n = 300
a = synth.power_law_adjacency(n, 0.03, 0.9, seed=5)
cfg = gcn.GCNConfig(16, 16, 4)
params = gcn.init_params(cfg, jax.random.PRNGKey(5))
x = np.random.default_rng(5).random((n, 16)).astype(np.float32)
reqs = [x * (1.0 - 0.02 * i) for i in range(12)]
root = tempfile.mkdtemp(prefix="awb-replica-")

# --- single-replica reference: max_replicas=1 pins the pre-replica path --
ref_eng = GCNServingEngine(store_root=root, devices=8, max_replicas=1,
                           replicate_after_s=1e-6, autotune_kwargs=KW)
ref_eng.add_graph("hot", a, params)
ref = np.asarray(ref_eng.serve_batch("hot", reqs))
for r in reqs:
    ref_eng.submit("hot", r, deadline_s=0.0)
assert set(ref_eng.poll()) == {"hot"}
assert ref_eng.stats()["replicas"] == {}
assert ref_eng.counters["replicas_added"] == 0    # cap honoured
print("SINGLE OK")

# --- saturation grows replicas; growth is warm (no sweep, no rebuild) ----
registry.clear_caches()
eng = GCNServingEngine(store_root=root, devices=8, max_replicas=3,
                       replicate_after_s=1e-6, replica_shrink_after=2,
                       autotune_kwargs=KW)
rep = eng.add_graph("hot", a, params)
assert rep.warm_start
eng.serve_batch("hot", reqs[:2])          # prime the service EWMA
assert eng._svc_req_ewma["hot"] > 0
orig_measure = runner.measure_candidate
orig_build = schedule.build_balanced_schedule
runner.measure_candidate = lambda *a_, **k: (_ for _ in ()).throw(
    AssertionError("measured sweep during replica growth"))
schedule.build_balanced_schedule = lambda *a_, **k: (_ for _ in ()).throw(
    AssertionError("schedule rebuild during replica growth"))
outs = []
for _ in range(3):
    for r in reqs:
        eng.submit("hot", r, deadline_s=0.0)
    outs.append(np.asarray(eng.poll()["hot"]))
pl = eng.placer.placement_of("hot")
assert pl.kind == REPLICATED and len(set(pl.device_indices)) == 3, pl
assert eng.counters["replicas_added"] == 2
st = eng.stats()
assert st["replicas"] == {"hot": list(pl.device_indices)}
per_dev = {d["device"]: d["resident"] for d in st["per_device"]}
for d in pl.device_indices:
    assert "hot" in per_dev[d]
# secondary replicas are pinned executors on their own mesh devices
for d, unit in eng._graphs["hot"].replicas.items():
    assert unit.executor.device == eng.devices[d]
print("GROW OK", pl.device_indices)

# --- bit-identical logits no matter which replica served -----------------
for out in outs:
    assert out.shape == ref.shape
    assert np.array_equal(out, ref), "replica outputs diverged"
direct = np.asarray(eng.serve_batch("hot", reqs))  # splits across replicas
assert np.array_equal(direct, ref)
# a batch of one serves on the least-loaded clone, but the output still
# lands committed to the PRIMARY's device — which replica served must be
# unobservable, placement included
one = eng.serve_batch("hot", [x])
assert one.devices() == {eng.devices[0]}, one.devices()
print("BITIDENTICAL OK")

# --- budget sweep sheds a secondary replica before evicting a graph ------
runner.measure_candidate = orig_measure
schedule.build_balanced_schedule = orig_build
a2 = synth.power_law_adjacency(260, 0.03, 0.9, seed=6)
p2 = gcn.init_params(cfg, jax.random.PRNGKey(6))
x2 = np.random.default_rng(6).random((260, 16)).astype(np.float32)
eng.add_graph("cold", a2, p2)
eng.infer("cold", x2)               # cold is most-recently-served
sec = sorted(eng._graphs["hot"].replicas)[0]
drops = eng.counters["replicas_dropped"]
eng.placer.used[sec] += eng.placer.budget   # simulated pressure on sec
eng._evict_over_budget(keep="cold")
eng.placer.used[sec] -= eng.placer.budget
assert eng.counters["replicas_dropped"] == drops + 1
assert sec not in eng._graphs["hot"].replicas
assert eng._graphs["hot"].executor is not None   # hot was NOT evicted
assert eng.counters["evictions"] == 0            # nobody paid a full evict
print("SHED OK")

# --- shrink back under idle pressure -------------------------------------
bytes_replicated = eng.device_bytes_in_use
for _ in range(8):
    eng.poll()                            # empty queues: calm accumulates
pl = eng.placer.placement_of("hot")
assert pl.kind == SINGLE, pl
assert eng.counters["replicas_dropped"] == 2
assert eng.device_bytes_in_use < bytes_replicated
assert eng._graphs["hot"].replicas == {}
assert np.array_equal(np.asarray(eng.serve_batch("hot", reqs)), ref)
print("SHRINK OK")
""" % (SRC,)


@pytest.mark.distributed
def test_replicated_hot_graph_acceptance():
    r = subprocess.run([sys.executable, "-c", SCRIPT_REPLICA],
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, \
        f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
    for tag in ("SINGLE OK", "GROW OK", "BITIDENTICAL OK", "SHED OK",
                "SHRINK OK"):
        assert tag in r.stdout
