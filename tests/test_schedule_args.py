"""The served forward takes its schedule arrays as jit arguments.

The compiled program then holds no copy of the schedule: its size does not
grow with nnz, every batch size shares the one resident copy, and an
executor rebuilt with unchanged shapes (a streaming repair) reuses the
compiled program. Checked here on the lowered program, for the
single-device, sharded and replicated routes."""
import os
import re
import subprocess
import sys

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.core import csc, gcn, schedule  # noqa: E402
from repro.core import executor as exe  # noqa: E402
from repro.graphs import synth  # noqa: E402
from repro.serving.gcn_engine import GCNServingEngine  # noqa: E402
from repro.tuning import registry  # noqa: E402

SRC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src")

N, F, C = 300, 20, 4
SWEEP = [dict(nnz_per_step=64, rows_per_window=32, cols_per_block=None,
              window_nnz=None, routing=exe.GATHER)]
KW = dict(iters=1, warmup=1, sweep=SWEEP, bf16_report=False)


def _shapes(tree):
    return sorted(tuple(leaf.shape) for leaf in jax.tree.leaves(tree))


def _largest_constant(hlo_text: str) -> int:
    """Element count of the largest constant in a lowered StableHLO module."""
    sizes = [1]
    for shape in re.findall(r"stablehlo\.constant .*: tensor<([0-9x]*)[a-z]", hlo_text):
        dims = [int(d) for d in shape.split("x") if d]
        sizes.append(int(np.prod(dims)) if dims else 1)
    return max(sizes)


def assert_schedule_is_argument(ex, params, xs):
    """The lowered served forward of ``ex`` takes every schedule array as an
    argument and holds no constant anywhere near schedule size."""
    lowered = exe._batched_forward_jit.lower(ex.geometry, ex.operands, params, xs)
    args = _shapes(lowered.args_info)
    for shape in _shapes(ex.operands):
        assert shape in args, (shape, args)
    slots = ex.sched.n_steps * ex.sched.nnz_per_step
    assert _largest_constant(lowered.as_text()) < slots // 4


@pytest.fixture(autouse=True)
def _fresh_caches():
    registry.clear_caches()
    yield
    registry.clear_caches()


def _workload(seed=0):
    a = synth.power_law_adjacency(N, 0.03, 0.9, seed=seed)
    params = gcn.init_params(gcn.GCNConfig(F, 16, C), jax.random.PRNGKey(seed))
    xs = jnp.asarray(np.random.default_rng(seed).random((3, N, F)), jnp.float32)
    return a, params, xs


@pytest.mark.parametrize("routing,cols_per_block", [
    (exe.GATHER, None), (exe.ONEHOT, "auto")])
def test_single_device_forward_takes_schedule_as_arguments(routing,
                                                           cols_per_block):
    a, params, xs = _workload()
    sched = schedule.build_balanced_schedule(a, 64, 32,
                                             cols_per_block=cols_per_block)
    ex = exe.ScheduleExecutor(sched, routing=routing)
    assert_schedule_is_argument(ex, params, xs)
    # the jitted entry points agree with the reference forward
    want = np.asarray(gcn.forward(params, a, xs[0]))
    np.testing.assert_allclose(np.asarray(ex.forward(params, xs[0])), want,
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(ex.forward_batch(params, xs)[0]),
                               want, rtol=1e-5, atol=1e-6)


def test_equal_geometry_shares_one_compiled_program():
    """Two executors of one geometry (here: two uploads of one schedule)
    dispatch to one compiled program per batch size."""
    a, params, xs = _workload()
    sched = schedule.build_balanced_schedule(a, 64, 32)
    ex1 = exe.ScheduleExecutor(sched, routing=exe.GATHER)
    ex2 = exe.ScheduleExecutor(sched, routing=exe.GATHER)
    assert ex1.geometry == ex2.geometry
    out1 = ex1.forward_batch(params, xs)
    n = exe._batched_forward_jit._cache_size()
    out2 = ex2.forward_batch(params, xs)
    assert exe._batched_forward_jit._cache_size() == n
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))


def test_gather_geometry_ignores_the_window_layout():
    """The gather body reads only the slot stream's shape and the output
    rows: schedules of one graph that differ in window height but issue as
    many steps share one compiled program, and each executor computes its
    geometry once."""
    a, params, xs = _workload()
    s16 = schedule.build_balanced_schedule(a, 64, 16)
    s32 = schedule.build_balanced_schedule(a, 64, 32)
    assert s16.n_steps == s32.n_steps and s16.rows_per_window != s32.rows_per_window
    ex16 = exe.ScheduleExecutor(s16, routing=exe.GATHER)
    ex32 = exe.ScheduleExecutor(s32, routing=exe.GATHER)
    assert ex16.geometry is ex16.geometry and ex16.operands is ex16.operands
    g = ex16.geometry
    assert (g.n, g.r, g.cb, g.n_windows) == (0, 0, 0, 0)
    assert ex16.geometry == ex32.geometry
    out16 = ex16.forward_batch(params, xs)
    n = exe._batched_forward_jit._cache_size()
    out32 = ex32.forward_batch(params, xs)
    assert exe._batched_forward_jit._cache_size() == n
    want = np.asarray(gcn.forward(params, a, xs[2]))
    for out in (out16, out32):
        np.testing.assert_allclose(np.asarray(out[2]), want, rtol=1e-5, atol=1e-6)


def _value_delta(coo, k, rng):
    row, col = np.asarray(coo.row), np.asarray(coo.col)
    idx = rng.choice(row.shape[0], size=k, replace=False)
    vals = (rng.random(k) + 0.5).astype(np.float32)
    return csc.EdgeDelta(row[idx], col[idx], vals)


def test_shape_preserving_update_triggers_no_compile(tmp_path):
    a, params, xs = _workload(3)
    rng = np.random.default_rng(3)
    eng = GCNServingEngine(store_root=tmp_path, autotune_kwargs=KW)
    eng.add_graph("g", a, params)
    eng.serve_batch("g", xs)
    # the first update compiles the value-patch scatter for its bucket size
    eng.update_graph("g", _value_delta(eng._graphs["g"].coo, 8, rng))
    eng.serve_batch("g", xs)

    compiles = []

    def listen(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(secs)

    old_ex = eng._graphs["g"].executor
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        rep = eng.update_graph("g", _value_delta(eng._graphs["g"].coo, 8, rng))
        out = jax.block_until_ready(eng.serve_batch("g", xs))
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    new_ex = eng._graphs["g"].executor
    assert rep.repaired and new_ex is not old_ex
    assert new_ex.geometry == old_ex.geometry
    assert compiles == []
    # the new schedule values are what is served
    want = gcn.forward(params, eng._graphs["g"].coo, xs[1])
    np.testing.assert_allclose(np.asarray(out[1]), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


SCRIPT_MESH = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys
sys.path.insert(0, %(src)r)
sys.path.insert(0, %(tests)r)
import numpy as np, jax
from repro.serving.placement import REPLICATED, SHARDED
from repro.serving.gcn_engine import GCNServingEngine
from test_schedule_args import KW, _workload, assert_schedule_is_argument

a, params, xs = _workload(5)
est = GCNServingEngine._estimate_bytes(None, a, params)
# sharded route: a per-device budget below the graph's footprint
eng = GCNServingEngine(store_root=%(root)r + "/s", devices=4,
                       device_budget_bytes=est // 2, autotune_kwargs=KW)
rep = eng.add_graph("g", a, params)
assert rep.placement.kind == SHARDED, rep.placement
ex = eng._graphs["g"].executor
assert_schedule_is_argument(ex, eng._graphs["g"].params, xs)
for arr in ex.operands.values():
    if arr.ndim == 3:  # step-sharded: one shard per device
        assert len({s.device for s in arr.addressable_shards}) == 4
        assert arr.addressable_shards[0].data.shape[0] == 1
ref = np.asarray(eng.serve_batch("g", xs))

# replicated route: a hot graph cloned onto a second device
eng2 = GCNServingEngine(store_root=%(root)r + "/r", devices=4, max_replicas=2,
                        replicate_after_s=1e-6, autotune_kwargs=KW)
eng2.add_graph("g", a, params)
eng2.serve_batch("g", xs[:1])
while eng2.placer.placement_of("g").kind != REPLICATED:
    for x in xs:
        eng2.submit("g", x)
    eng2.poll()
units = eng2._units(eng2._graphs["g"])
assert len(units) == 2
for u in units:
    assert_schedule_is_argument(u.executor, u.params, xs)
    assert {a.device for a in jax.tree.leaves(u.executor.operands)} == {
        eng2.devices[u.device_index]}
np.testing.assert_allclose(np.asarray(eng2.serve_batch("g", xs)), ref,
                           rtol=1e-5, atol=1e-6)
print("OK")
"""


@pytest.mark.distributed
def test_mesh_forwards_take_schedule_as_arguments(tmp_path):
    script = SCRIPT_MESH % dict(src=SRC, tests=os.path.dirname(__file__),
                                root=str(tmp_path))
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout
