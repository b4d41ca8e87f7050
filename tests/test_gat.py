"""The GAT (``core.gat``) on the served path: the executor's attention body
over the gather slot stream, and ``GCNServingEngine.add_graph(...,
arch="gat")`` through ``submit``/``poll``, against the plain reference.

The slot stream holds what a softmax must not count: chunk padding and
empty PE slots (both ``val == 0``, their targets real rows), heavy rows
split over several steps and chunks, and permuted target rows under a
reordered schedule. Each case is checked against ``core.gat.forward``
within 1e-5 relative; on four virtual devices, replicas, and the sharded
route's refusal."""
import hashlib
import re
import subprocess
import sys
import tempfile
from pathlib import Path

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.core import csc, gat, gcn, reorder, schedule  # noqa: E402
from repro.core import executor as exe  # noqa: E402
from repro.graphs import synth  # noqa: E402
from repro.serving.gcn_engine import GCNServingEngine  # noqa: E402
from repro.serving.errors import UnsupportedArchitectureError  # noqa: E402
from repro.tuning import registry  # noqa: E402

SRC = str(Path(__file__).resolve().parents[1] / "src")
N_NODES = 200
N_FEATS = 12
MAX_BATCH = 4
SWEEP = [dict(nnz_per_step=64, rows_per_window=32, cols_per_block=None,
              window_nnz=None, routing=exe.GATHER)]
FAST_KW = dict(iters=1, warmup=1, sweep=SWEEP, bf16_report=False)
#: float32 sums in another order than the reference's, nothing more
RTOL = 1e-5


@pytest.fixture(autouse=True)
def _fresh_caches():
    registry.clear_caches()
    yield
    registry.clear_caches()


def _graph(seed=1):
    return synth.power_law_adjacency(N_NODES, 0.03, 0.9, seed=seed)


def _params(seed=1, feats=N_FEATS):
    return gat.init_params(gat.GATConfig(feats), jax.random.PRNGKey(seed))


def _requests(k, seed=2):
    rng = np.random.default_rng(seed)
    return [rng.random((N_NODES, N_FEATS)).astype(np.float32) for _ in range(k)]


def _rel(got, ref):
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    return float(np.abs(got - ref).max() / np.abs(ref).max())


def _engine(root, a, params, **kw):
    eng = GCNServingEngine(store_root=root, autotune_kwargs=kw.pop("tune", FAST_KW),
                           max_batch=MAX_BATCH, **kw)
    eng.add_graph("g", a, params, arch="gat")
    return eng


# ---- the reference ------------------------------------------------------------


def test_reference_is_equations_1_to_6_on_a_dense_graph():
    """``core.gat.forward`` against the paper's equations written densely:
    a masked softmax over each row of A, heads concatenated with ELU, then
    averaged."""
    n = 30
    a = synth.power_law_adjacency(n, 0.2, 0.9, seed=4)
    params = _params(4, 5)
    x = np.random.default_rng(4).random((n, 5)).astype(np.float32)
    mask = np.asarray(csc.coo_to_dense(a)) != 0
    h = x.astype(np.float64)
    for i in range(2):
        w = np.asarray(params[f"w{i}"], np.float64)
        att = np.asarray(params[f"a{i}"], np.float64)
        k, f = att.shape[0], att.shape[1] // 2
        wh = (h @ w).reshape(n, k, f)
        e = (wh @ att[:, :f].T).diagonal(axis1=1, axis2=2)[:, None, :] \
            + (wh @ att[:, f:].T).diagonal(axis1=1, axis2=2)[None, :, :]
        e = np.where(e > 0, e, 0.2 * e)  # [i, j, K]
        e = np.where(mask[:, :, None], e, -np.inf)
        alpha = np.exp(e - e.max(1, keepdims=True))
        alpha /= alpha.sum(1, keepdims=True)
        out = np.einsum("ijk,jkf->ikf", alpha, wh)
        h = (np.where(out > 0, out, np.expm1(out)).reshape(n, k * f) if i == 0
             else out.mean(1))
    assert _rel(gat.forward(params, a, jnp.asarray(x)), h) < RTOL


def test_layer_heads_reads_the_tree_and_refuses_another():
    params = _params()
    assert gat.layer_heads(params) == (8, 8)
    with pytest.raises(ValueError, match="w0..w"):
        gat.layer_heads({"w0": params["w0"]})
    with pytest.raises(ValueError, match="not \\[din, K\\*F\\]"):
        gat.layer_heads(dict(params, a0=params["a0"][:, :3]))
    with pytest.raises(ValueError, match="takes"):
        gat.layer_heads(dict(params, w1=params["w1"][:10]))


# ---- the attention body over the slot stream -----------------------------------


def _split_rows(sched) -> int:
    """Rows whose slots lie in more than one step of the schedule."""
    _, tgt, val = exe._gather_slots(sched)
    steps = np.repeat(np.arange(sched.n_steps), sched.nnz_per_step)
    live = val != 0
    pairs = np.unique(np.stack([tgt[live], steps[live]], 1), axis=0)
    return int((np.bincount(pairs[:, 0]) > 1).sum())


CASES = {
    # (nnz_per_step, rows_per_window, slot_chunk, reorder[, window_nnz])
    "one_chunk": (64, 32, 1 << 18, "none"),
    "split_rows": (8, 8, 1 << 18, "none"),
    "chunks": (16, 8, 96, "none"),
    "reordered": (16, 8, 96, "degree"),
    "reordered_island": (64, 32, 1 << 18, "island"),
    # no row past 128 non-zeros: every step is its own window
    "one_step_windows": (128, 32, 1 << 18, "none"),
    # windows of up to 64 non-zeros over steps of 16: several steps each
    "multi_step_windows": (16, 8, 1 << 18, "none", 64),
    # 100 slots round down to chunks of 6 whole steps; windows straddle them
    "unaligned_chunk": (16, 8, 100, "none", 64),
    "split_rows_reordered": (8, 8, 96, "degree"),
}


def _case_executor(case):
    k, r, chunk, strategy, *window_nnz = CASES[case]
    a = _graph()
    perm, inv = reorder.permutation(a, strategy)
    sched = schedule.build_balanced_schedule(
        a if perm is None else csc.permute_coo(a, perm), k, r,
        window_nnz=(window_nnz or [None])[0])
    return a, exe.ScheduleExecutor(sched, routing=exe.GATHER, slot_chunk=chunk,
                                   row_unperm=inv)


@pytest.mark.parametrize("case", CASES)
def test_attention_body_matches_the_reference(case):
    a, ex = _case_executor(case)
    sched, k, chunk = ex.sched, ex.sched.nnz_per_step, CASES[case][2]
    params = _params()
    xs = jnp.asarray(np.stack(_requests(2)))
    _, _, val = exe._gather_slots(sched)
    assert (val == 0).any()  # empty PE slots are in the stream
    geom = ex.attention_geometry
    if case.startswith("split_rows"):
        assert _split_rows(sched) > 0 and ex.attention_scatter_slots > 0
    if case == "one_step_windows":
        assert geom.one_step_windows and sched.n_evil_chunks == 0
    if case in ("multi_step_windows", "unaligned_chunk"):
        regular = sched.win_id[: sched.n_steps - sched.n_evil_chunks]
        assert np.bincount(regular).max() > 1 and not geom.one_step_windows
    if chunk < val.shape[0]:
        assert geom.n_chunks > 1 and val.shape[0] % chunk  # padded
        assert ex._slot_chunk == chunk // k * k  # whole steps per chunk
    if case == "unaligned_chunk":
        chunk_of_step = np.arange(sched.n_steps) // (ex._slot_chunk // k)
        assert any(np.unique(chunk_of_step[sched.win_id == w]).size > 1
                   for w in np.unique(sched.win_id))  # a window in two chunks
    got = ex.gat_forward_batch(params, xs)
    for i in range(2):
        assert _rel(got[i], gat.forward(params, a, xs[i])) < RTOL


def _scatter_index_dims(hlo: str) -> list:
    """The shape of every scatter's index operand in an HLO module's text."""
    defined = r"^\s*(?:ROOT )?%?([\w.\-]+) = \w+\[([0-9,]*)\]"
    shapes = dict(re.findall(defined, hlo, re.M))
    return [[int(d) for d in shapes[name].split(",") if d]
            for name in re.findall(r" scatter\([^,]+, %?([\w.\-]+)", hlo)]


@pytest.mark.parametrize("case", ["one_step_windows", "multi_step_windows_whole_rows"])
def test_attention_reductions_scatter_no_slot_indices(case):
    """On a schedule that chunks no row over windows the lowered GAT
    forward has no scatter with one index per slot: the row max and the
    weighted sums are reduced onto windows, and a window's steps combine
    by step index."""
    a = _graph()
    k, r, window_nnz = (128, 32, None) if case == "one_step_windows" else (32, 8, 128)
    sched = schedule.build_balanced_schedule(a, k, r, window_nnz=window_nnz)
    # no row is chunked over windows; a window's rows may span its steps
    assert sched.n_evil_chunks == 0
    assert (_split_rows(sched) == 0) == (case == "one_step_windows")
    ex = exe.ScheduleExecutor(sched, routing=exe.GATHER)
    assert ex.attention_scatter_slots == 0
    assert ex.attention_geometry.one_step_windows == (case == "one_step_windows")
    xs = jnp.asarray(np.stack(_requests(2)))
    lowered = exe._batched_gat_jit.lower(
        ex.attention_geometry, ex.attention_operands, _params(), xs)
    hlo = lowered.as_text(dialect="hlo")
    dims = _scatter_index_dims(hlo)
    slots = sched.n_steps * k
    assert all(slots not in d for d in dims), dims
    # the step combine is all that scatters, and only where windows span steps
    assert (dims == []) == (case == "one_step_windows")
    assert all(sched.n_steps in d for d in dims), dims


def test_empty_slots_would_change_the_softmax_if_counted():
    """The mask matters: the same stream with every slot counted as an
    edge (empty PE slots and padding given ``val`` 1) reads far off."""
    a, params = _graph(), _params()
    x = jnp.asarray(_requests(1)[0])
    ex = exe.ScheduleExecutor(schedule.build_balanced_schedule(a, 16, 8),
                              routing=exe.GATHER, slot_chunk=96)
    ops = dict(ex.attention_operands, val=jnp.ones_like(ex.operands["val"]))
    wrong = exe._batched_gat_jit(ex.attention_geometry, ops, params, x[None])[0]
    assert _rel(wrong, gat.forward(params, a, x)) > 1e-2


def test_bf16_accumulation_reads_worse_than_float32():
    a, params = _graph(), _params()
    x = jnp.asarray(_requests(1)[0])
    sched = schedule.build_balanced_schedule(a, 64, 32)
    ref = gat.forward(params, a, x)
    f32, bf16 = [
        _rel(exe.ScheduleExecutor(sched, routing=exe.GATHER, bf16_accumulate=b)
             .gat_forward_batch(params, x[None])[0], ref) for b in (False, True)]
    assert f32 < RTOL and bf16 > 100 * RTOL


def test_one_hot_routing_refuses_the_attention_body():
    sched = schedule.build_balanced_schedule(_graph(), 16, 8, cols_per_block=64)
    ex = exe.ScheduleExecutor(sched, routing=exe.ONEHOT)
    with pytest.raises(exe.UnsupportedRoutingError, match="'onehot' routing"):
        ex.gat_forward_batch(_params(), jnp.ones((1, N_NODES, N_FEATS)))


# ---- the engine ------------------------------------------------------------------


@pytest.mark.parametrize("batch", [1, MAX_BATCH])
def test_engine_serves_a_gat_through_submit_and_poll(tmp_path, batch):
    a, params = _graph(), _params()
    eng = _engine(tmp_path, a, params)
    xs = _requests(2 * batch)
    outs = []
    for x in xs:
        eng.submit("g", x, deadline_s=0 if batch == 1 else None)
        if batch == 1:
            outs.append(np.asarray(eng.poll()["g"]))
    while eng.stats()["inflight_requests"] or eng.stats()["pending_requests"]:
        outs.append(np.asarray(eng.flush()["g"]))
    got = np.concatenate(outs)
    assert got.shape == (2 * batch, N_NODES, 3)
    for g, x in zip(got, xs):
        assert _rel(g, gat.forward(params, a, jnp.asarray(x))) < RTOL
    st = eng.stats()
    nnz = int(np.asarray(a.row).shape[0])
    assert st["attention_edge_heads"] == nnz * 16 * 2 * batch
    assert st["queue_served"] == 2 * batch
    eng.reset_stats()
    assert eng.stats()["attention_edge_heads"] == 0


def test_attention_counter_stays_zero_for_a_gcn(tmp_path):
    a = _graph()
    eng = GCNServingEngine(store_root=tmp_path, autotune_kwargs=FAST_KW,
                           max_batch=MAX_BATCH)
    eng.add_graph("g", a, gcn.init_params(gcn.GCNConfig(N_FEATS, 8, 3),
                                           jax.random.PRNGKey(1)))
    eng.serve_batch("g", _requests(2))
    assert eng.stats()["attention_edge_heads"] == 0


@pytest.mark.parametrize("case", ["whole_rows", "split_rows", "gcn"])
def test_attention_scatter_slots_counts_split_row_chunks(tmp_path, case):
    """``attention_scatter_slots``: the window slots per layer whose
    partials still pass a scatter (a split row's chunks after its first),
    times layers and requests served; 0 where no row is split and for a
    GCN."""
    a = _graph()
    k, r = (128, 32) if case == "whole_rows" else (8, 8)
    sweep = [dict(SWEEP[0], nnz_per_step=k, rows_per_window=r)]
    eng = GCNServingEngine(store_root=tmp_path, max_batch=MAX_BATCH,
                           autotune_kwargs=dict(FAST_KW, sweep=sweep))
    if case == "gcn":
        eng.add_graph("g", a, gcn.init_params(gcn.GCNConfig(N_FEATS, 8, 3),
                                               jax.random.PRNGKey(1)))
    else:
        eng.add_graph("g", a, _params(), arch="gat")
    eng.serve_batch("g", _requests(3))
    got = eng.stats()["attention_scatter_slots"]
    per_layer = eng._graphs["g"].executor.attention_scatter_slots
    if case == "split_rows":
        assert per_layer > 0 and got == per_layer * 2 * 3
    else:
        assert got == 0
    if case == "whole_rows":
        assert per_layer == 0
    eng.reset_stats()
    assert eng.stats()["attention_scatter_slots"] == 0


@pytest.mark.parametrize("seed,scoped", [(2, True), (0, False)])
def test_repaired_executor_carries_the_attention_operands(monkeypatch, seed, scoped):
    """A repair patches the slots' local rows with the slot stream (scoped:
    only the moved steps; else a full upload) and rebuilds the window
    arrays: the attention operands equal a cold build's, and the repaired
    executor serves the new graph."""
    monkeypatch.setattr(exe, "SCOPED_UPLOAD_MIN_BYTES", 0)
    a, params = _graph(), _params()
    sched = schedule.build_balanced_schedule(a, 16, 8)
    ex = exe.ScheduleExecutor(sched, routing=exe.GATHER, slot_chunk=96)
    rng = np.random.default_rng(seed)
    dense = np.asarray(csc.coo_to_dense(a)) != 0
    present = np.argwhere(dense)[rng.choice(int(dense.sum()), 2, replace=False)]
    absent = np.argwhere(~dense)[rng.choice(int((~dense).sum()), 2, replace=False)]
    edges = np.concatenate([present, absent]).astype(np.int32)
    delta = csc.EdgeDelta(edges[:, 0], edges[:, 1],
                          np.asarray([0, 0, 1, 1], np.float32))
    new_coo, rep = csc.apply_edge_delta(a, delta, with_report=True)
    per_row_old = np.bincount(np.asarray(a.row), minlength=N_NODES)
    per_row_new = per_row_old.copy()
    per_row_new[rep.touched_rows] += rep.row_nnz_delta
    new_sched, stats = schedule.repair_schedule(
        sched, None, new_coo, rep.touched_rows, per_row_old=per_row_old,
        per_row_new=per_row_new, nnz_per_step=16, rows_per_window=8)
    ex2 = exe.repaired_executor(ex, new_sched, stats)
    assert ex2.scoped_upload == scoped and ex2.attention_scatter_slots > 0
    cold = exe.ScheduleExecutor(new_sched, routing=exe.GATHER, slot_chunk=96)
    assert ex2.attention_geometry == cold.attention_geometry
    assert ex2.device_bytes == cold.device_bytes
    got, want = ex2.attention_operands, cold.attention_operands
    assert sorted(got) == sorted(want)
    for key in want:
        np.testing.assert_array_equal(np.asarray(got[key]), np.asarray(want[key]))
    x = jnp.asarray(_requests(1)[0])
    assert _rel(ex2.gat_forward_batch(params, x[None])[0],
                gat.forward(params, new_coo, x)) < RTOL


def test_gcn_logits_digest_on_the_tiny_graph_is_unchanged(tmp_path):
    """The GCN's served logits, bit for bit, as before the attention body
    was added beside its forward."""
    a = synth.power_law_adjacency(200, 0.03, 0.9, seed=3)
    params = gcn.init_params(gcn.GCNConfig(12, 16, 3), jax.random.PRNGKey(3))
    xs = np.random.default_rng(3).random((4, 200, 12)).astype(np.float32)
    eng = GCNServingEngine(store_root=tmp_path, max_batch=4, autotune_kwargs=FAST_KW)
    eng.add_graph("g", a, params)
    for x in xs:
        eng.submit("g", x)
    out = np.asarray(eng.flush()["g"])
    assert hashlib.sha256(out.tobytes()).hexdigest() == (
        "b9947507677aca71790b881ec9756f0bc4ac81ebda86c72dcec25eac50d61a08")


def test_engine_bf16_accumulation_reads_worse_than_float32(tmp_path):
    a, params = _graph(), _params()
    x = _requests(1)[0]
    ref = gat.forward(params, a, jnp.asarray(x))
    bf16_kw = dict(FAST_KW, sweep=[dict(SWEEP[0], bf16_accumulate=True)],
                   allow_bf16=True)
    errs = []
    for i, kw in enumerate((FAST_KW, bf16_kw)):
        eng = _engine(tmp_path / str(i), a, params, tune=kw)
        assert eng._graphs["g"].config.bf16_accumulate == bool(i)
        errs.append(_rel(eng.infer("g", x), ref))
    assert errs[0] < RTOL and errs[1] > 100 * RTOL


def test_gat_sweep_holds_gather_candidates_only(tmp_path, monkeypatch):
    """The default sweep a GAT is tuned over has no one-hot point, and a
    given sweep that offers one is refused before any tuning."""
    from repro.tuning import runner

    seen = []
    real = runner.autotune

    def spy(a, b_shape, **kw):
        seen.append(kw["sweep"])
        return real(a, b_shape, **kw)

    monkeypatch.setattr(runner, "autotune", spy)
    a, params = _graph(), _params()
    eng = GCNServingEngine(store_root=tmp_path, max_batch=MAX_BATCH,
                           autotune_kwargs=dict(iters=1, warmup=1, bf16_report=False))
    eng.add_graph("g", a, params, arch="gat")
    assert seen and all(c["routing"] == exe.GATHER for c in seen[0])
    assert eng._graphs["g"].config.routing == exe.GATHER
    onehot = dict(SWEEP[0], routing=exe.ONEHOT, cols_per_block="auto")
    eng2 = GCNServingEngine(store_root=tmp_path, autotune_kwargs=dict(
        FAST_KW, sweep=SWEEP + [onehot]))
    with pytest.raises(UnsupportedArchitectureError, match="gather routing"):
        eng2.add_graph("g", a, params, arch="gat")
    assert eng2.graphs == [] and len(seen) == 1


def test_footprint_estimate_counts_the_attention_working_set(tmp_path):
    """A GAT's pre-tune estimate adds, at its widest layer, at least one
    float32 ``Wh`` row, score and weight per slot and head."""
    a, params = _graph(), _params()
    eng = GCNServingEngine(store_root=tmp_path, autotune_kwargs=FAST_KW)
    nnz = int(np.asarray(a.row).shape[0])
    extra = eng._estimate_bytes(a, params, "gat") - eng._estimate_bytes(a, params)
    assert extra >= nnz * 8 * (8 + 2) * 4


def test_add_graph_validates_the_architecture_and_its_parameters(tmp_path):
    a, params = _graph(), _params()
    eng = GCNServingEngine(store_root=tmp_path, autotune_kwargs=FAST_KW)
    with pytest.raises(UnsupportedArchitectureError, match="'gin'"):
        eng.add_graph("g", a, params, arch="gin")
    with pytest.raises(ValueError, match="w0..w"):
        eng.add_graph("g", a, {"w0": params["w0"]}, arch="gat")
    rect = csc.coo_from_arrays(np.array([0, 1]), np.array([1, 2]),
                               np.ones(2, np.float32), (3, 4))
    with pytest.raises(ValueError, match="square"):
        eng.add_graph("g", rect, params, arch="gat")
    assert eng.graphs == []


@pytest.mark.parametrize("kind", ["structure", "values"])
def test_update_graph_serves_the_repaired_gat(tmp_path, kind):
    a, params = _graph(), _params()
    eng = _engine(tmp_path, a, params)
    rng = np.random.default_rng(7)
    coo = eng._graphs["g"].coo
    if kind == "values":
        idx = rng.choice(np.asarray(coo.row).shape[0], 6, replace=False)
        delta = csc.EdgeDelta(np.asarray(coo.row)[idx], np.asarray(coo.col)[idx],
                              rng.random(6).astype(np.float32) + 0.5)
    else:
        dense = np.asarray(csc.coo_to_dense(coo)) != 0
        absent = np.argwhere(~dense)[rng.choice(int((~dense).sum()), 5, replace=False)]
        present = np.argwhere(dense)[rng.choice(int(dense.sum()), 3, replace=False)]
        delta = csc.EdgeDelta(
            np.concatenate([absent[:, 0], present[:, 0]]).astype(np.int32),
            np.concatenate([absent[:, 1], present[:, 1]]).astype(np.int32),
            np.concatenate([np.ones(5), np.zeros(3)]).astype(np.float32))
    rep = eng.update_graph("g", delta)
    assert rep.repaired
    new = eng._graphs["g"].coo
    x = _requests(1)[0]
    got = eng.infer("g", x)
    assert _rel(got, gat.forward(params, new, jnp.asarray(x))) < RTOL
    if kind == "structure":
        assert _rel(got, gat.forward(params, a, jnp.asarray(x))) > 1e-3


SCRIPT_FOUR_DEVICES = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys, tempfile
sys.path.insert(0, %r)
import numpy as np, jax, jax.numpy as jnp
from repro.core import executor as exe, gat, schedule
from repro.graphs import synth
from repro.serving.errors import UnsupportedArchitectureError
from repro.serving.gcn_engine import GCNServingEngine
assert len(jax.devices()) == 4

SWEEP = [dict(nnz_per_step=64, rows_per_window=32, cols_per_block=None,
              window_nnz=None, routing=exe.GATHER)]
KW = dict(iters=1, warmup=1, sweep=SWEEP, bf16_report=False)
n = 200
a = synth.power_law_adjacency(n, 0.03, 0.9, seed=5)
params = gat.init_params(gat.GATConfig(12), jax.random.PRNGKey(5))
x = np.random.default_rng(5).random((n, 12)).astype(np.float32)
reqs = [x * (1.0 - 0.02 * i) for i in range(8)]

# replicas: a hot GAT grows clones; the split batches match the reference
eng = GCNServingEngine(store_root=tempfile.mkdtemp(), devices=4, max_replicas=4,
                       max_batch=4, replicate_after_s=1e-6,
                       replica_shrink_after=10**6, autotune_kwargs=KW)
eng.add_graph("hot", a, params, arch="gat")
eng.serve_batch("hot", reqs[:2])  # prime the service EWMA
outs = []
for r in reqs:
    eng.submit("hot", r)
    outs.append(eng.poll().get("hot"))
outs.append(eng.flush().get("hot"))
got = np.concatenate([np.asarray(o) for o in outs if o is not None])
assert got.shape[0] == len(reqs), got.shape
assert len(eng.stats()["replicas"]["hot"]) > 1, eng.stats()["replicas"]
for g, r in zip(got, reqs):
    ref = np.asarray(gat.forward(params, a, jnp.asarray(r)))
    err = np.abs(g - ref).max() / np.abs(ref).max()
    assert err < 1e-5, err

# the sharded route refuses a GAT before anything is tuned
big = GCNServingEngine(store_root=tempfile.mkdtemp(), devices=4,
                       device_budget_bytes=1 << 10, autotune_kwargs=KW)
try:
    big.add_graph("big", a, params, arch="gat")
except UnsupportedArchitectureError as e:
    assert "sharded route" in str(e) and e.arch == "gat", e
else:
    raise AssertionError("a GAT took the sharded route")
assert big.graphs == []
sched = schedule.build_balanced_schedule(a, 64, 32)
sh = exe.ShardedScheduleExecutor(sched, n_devices=4, routing=exe.GATHER)
try:
    sh.gat_forward_batch(params, jnp.asarray(np.stack(reqs[:2])))
except exe.UnsupportedRoutingError as e:
    assert "device mesh" in str(e), e
else:
    raise AssertionError("the sharded executor ran the attention body")
print("FOUR DEVICES OK")
""" % SRC


def test_replicas_serve_a_gat_and_the_sharded_route_refuses_it():
    r = subprocess.run([sys.executable, "-c", SCRIPT_FOUR_DEVICES],
                       capture_output=True, text=True, timeout=600,
                       cwd=tempfile.gettempdir())
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
    assert "FOUR DEVICES OK" in r.stdout
