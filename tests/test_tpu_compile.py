"""Compile the main path for a described TPU v5e, with no chip attached.

The TPU compiler refuses what interpret mode and the CPU backend accept: a
misaligned kernel block, too much fast memory, a program that does not fit
HBM. These cases compile the Pallas kernel and the served forward at full
published widths for a ``v5e:2x2`` topology described in a fixture. Nothing
runs, so they say nothing about results or times.

The topology is described only inside the module fixture: the TPU library
admits one process at a time, and a description made at import would break
every other test worker.
"""
import dataclasses
import os
import re

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.core import executor as exe  # noqa: E402
from repro.kernels import spmm_pallas  # noqa: E402

HBM_BYTES = 16 * 10**9  # one v5e chip

# Pubmed at full size: 19,717 nodes x 500 features, hidden 16, 3 classes.
# Geometries are those full Pubmed's schedules have: the gather route at
# K=256, R=64 (493 steps), the capped one-hot route at K=8, R=32, CB=256
# (42,437 steps, 617 windows).
PUBMED = dict(n=19717, f=500, hidden=16, classes=3)
GATHER_STEPS, GATHER_K = 493, 256
ONEHOT_STEPS, ONEHOT_K, ONEHOT_R, ONEHOT_CB, ONEHOT_WINDOWS = 42437, 8, 32, 256, 617
# Reddit at full size: 232,965 nodes x 602 features, hidden 128, 41
# classes, ~23M schedule slots in chunks of the executor's default 2**18
REDDIT = dict(n=232965, f=602, hidden=128, classes=41)
REDDIT_SLOTS = 23_000_000
CHUNK = 1 << 18


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # skip only where no TPU compiler is installed; any other failure to
    # describe the topology fails the tests
    pytest.importorskip("libtpu")
    desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _params(m, sharding):
    return {"w0": _sds((m["f"], m["hidden"]), jnp.float32, sharding),
            "w1": _sds((m["hidden"], m["classes"]), jnp.float32, sharding)}


def _gather_ops(slots, sharding, lead=()):
    n_chunks = -(-slots // CHUNK) if slots > CHUNK else 1
    chunk = min(slots, CHUNK)
    shape = (*lead, n_chunks, chunk)
    ops = {"gcol": _sds(shape, jnp.int32, sharding),
           "tgt": _sds(shape, jnp.int32, sharding),
           "val": _sds(shape, jnp.float32, sharding)}
    return ops, n_chunks


def _gather_geometry(m, n_chunks, mesh=None):
    return exe.Geometry(routing=exe.GATHER, m=m["n"], n=0, r=0, cb=0,
                        n_windows=0, n_chunks=n_chunks, bf16=False, mesh=mesh)


def _schedule_bytes(ops) -> int:
    return sum(int(np.prod(o.shape)) * o.dtype.itemsize
               for o in jax.tree.leaves(ops))


def _compile_forward(geom, ops, m, batch, sharding):
    xs = _sds((batch, m["n"], m["f"]), jnp.float32, sharding)
    lowered = exe._batched_forward_jit.lower(geom, ops, _params(m, sharding), xs)
    return lowered.compile()


def _largest_constant(compiled) -> int:
    """Element count of the largest constant in the compiled program."""
    sizes = [1]
    for dims in re.findall(r"= \w+\[([0-9,]*)\]\S* constant\(", compiled.as_text()):
        sizes.append(int(np.prod([int(d) for d in dims.split(",") if d])))
    return max(sizes)


def test_pallas_kernel_compiles_at_pubmed_geometry(one_chip):
    s, k = ONEHOT_STEPS, ONEHOT_K
    args = (_sds((s * k,), jnp.float32, one_chip),
            _sds((s * k,), jnp.int32, one_chip),
            _sds((s * k,), jnp.int32, one_chip),
            _sds((s,), jnp.int32, one_chip),
            _sds((s,), jnp.int32, one_chip),
            _sds((PUBMED["n"], PUBMED["hidden"]), jnp.float32, one_chip))
    compiled = spmm_pallas._spmm_pallas_perm.lower(
        *args, k=k, r=ONEHOT_R, cb=ONEHOT_CB, n_windows=ONEHOT_WINDOWS,
        ktile=128, interpret=False, routing=exe.ONEHOT).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_pallas_gather_routing_refuses_to_compile():
    from repro.core import schedule
    from repro.graphs import synth

    a = synth.power_law_adjacency(64, 0.05, 0.8, seed=0)
    sched = schedule.build_balanced_schedule(a, 16, 8)
    b = jnp.ones((64, 8), jnp.float32)
    with pytest.raises(ValueError, match="interpret=True"):
        spmm_pallas.spmm_balanced(sched, b, interpret=False, routing="gather")


@pytest.mark.parametrize("routing", [exe.GATHER, exe.ONEHOT])
def test_served_forward_compiles_at_pubmed_width(one_chip, routing):
    if routing == exe.GATHER:
        ops, n_chunks = _gather_ops(GATHER_STEPS * GATHER_K, one_chip)
        geom = _gather_geometry(PUBMED, n_chunks)
    else:
        s, k = ONEHOT_STEPS, ONEHOT_K
        ops = {"win": _sds((s,), jnp.int32, one_chip),
               "cblk": _sds((s,), jnp.int32, one_chip),
               "val": _sds((s, k), jnp.float32, one_chip),
               "lrow": _sds((s, k), jnp.int32, one_chip),
               "lcol": _sds((s, k), jnp.int32, one_chip),
               "row_map": _sds((ONEHOT_WINDOWS * ONEHOT_R,), jnp.int32, one_chip)}
        geom = exe.Geometry(routing=exe.ONEHOT, m=PUBMED["n"], n=PUBMED["n"],
                            r=ONEHOT_R, cb=ONEHOT_CB,
                            n_windows=ONEHOT_WINDOWS, n_chunks=0, bf16=False)
    compiled = _compile_forward(geom, ops, PUBMED, 8, one_chip)
    mem = compiled.memory_analysis()
    # the schedule is an argument, not part of the program: no constant
    # comes near its 126,208 (gather) or 339,496 (one-hot) entries. Baked in
    # as constants it made 3.2 MB (gather) and 72.7 MB (one-hot) of code
    assert _largest_constant(compiled) < 1024
    assert mem.argument_size_in_bytes >= _schedule_bytes(ops)
    assert mem.generated_code_size_in_bytes < 3 << 20


def _attention_inputs(steps, k, windows, n_split, sharding):
    """The attention operands and geometry of a one-chunk Pubmed stream of
    ``steps`` steps of ``k`` slots over ``windows`` windows of 64 rows,
    ``n_split`` of whose slots hold a split row's further chunks."""
    ops, n_chunks = _gather_ops(steps * k, sharding)
    one_step = steps == windows
    ops.update(lrow=_sds((n_chunks, steps * k), jnp.int32, sharding),
               row_slot=_sds((PUBMED["n"],), jnp.int32, sharding),
               xslot=_sds((n_split,), jnp.int32, sharding),
               xrow=_sds((n_split,), jnp.int32, sharding))
    if not one_step:
        ops["win"] = _sds((n_chunks, steps), jnp.int32, sharding)
    geom = dataclasses.replace(_gather_geometry(PUBMED, n_chunks), k=k, r=64,
                               n_windows=windows, one_step_windows=one_step)
    return ops, geom


def _compile_gat(geom, ops, sharding):
    n, f = PUBMED["n"], PUBMED["f"]
    params = {"w0": _sds((f, 64), jnp.float32, sharding),
              "a0": _sds((8, 16), jnp.float32, sharding),
              "w1": _sds((64, 24), jnp.float32, sharding),
              "a1": _sds((8, 6), jnp.float32, sharding)}
    xs = _sds((8, n, f), jnp.float32, sharding)
    return exe._batched_gat_jit.lower(geom, ops, params, xs).compile()


def test_served_gat_forward_compiles_at_pubmed_width(one_chip):
    """The GAT of gat-pubmed (8 heads of 8, then 8 of 3) over full Pubmed's
    gather stream, a batch of 8: the attention body compiles for the chip,
    fits its memory, and is its own program beside the GCN's. The chip's
    schedule (K=256, R=64) makes every step its own window."""
    ops, geom = _attention_inputs(GATHER_STEPS, GATHER_K, GATHER_STEPS, 0, one_chip)
    compiled = _compile_gat(geom, ops, one_chip)
    mem = compiled.memory_analysis()
    assert _largest_constant(compiled) < 1024
    assert mem.argument_size_in_bytes >= _schedule_bytes(ops)
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < HBM_BYTES // 4, total
    assert "_batched_gat_body" in compiled.as_text().split("\n", 1)[0]


def test_gat_window_combine_compiles_at_pubmed_width(one_chip):
    """Full Pubmed's schedule at K=128 (1,028 steps over 930 windows, 50
    further chunks of split rows): the attention's step combine and its
    split-row scatter compile for the chip and fit as the K=256 one does;
    the masked max over a step's slots is not materialised."""
    ops, geom = _attention_inputs(1028, 128, 930, 50, one_chip)
    mem = _compile_gat(geom, ops, one_chip).memory_analysis()
    # the masked [batch, steps, k, r, heads] operand of the row max alone
    # would be 8·1028·128·64·8·4 B = 2.2 GB
    assert mem.temp_size_in_bytes < 2 * 10**9, mem.temp_size_in_bytes


def test_served_forward_at_reddit_size_fits_one_chip(one_chip):
    ops, n_chunks = _gather_ops(REDDIT_SLOTS, one_chip)
    geom = _gather_geometry(REDDIT, n_chunks)
    mem = _compile_forward(geom, ops, REDDIT, 1, one_chip).memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert mem.argument_size_in_bytes >= _schedule_bytes(ops)
    assert total < HBM_BYTES, total


def test_sharded_forward_splits_the_schedule_across_four_chips(topo):
    mesh = Mesh(np.asarray(topo.devices[:4]), ("dev",))
    rep = NamedSharding(mesh, P())
    slots = GATHER_STEPS * GATHER_K // 4
    geom = None
    mem = {}
    for spec in (P("dev"), P()):
        ops, n_chunks = _gather_ops(slots, NamedSharding(mesh, spec), lead=(4,))
        geom = _gather_geometry(PUBMED, n_chunks, mesh=mesh)
        compiled = _compile_forward(geom, ops, PUBMED, 8, rep)
        assert "all-reduce" in compiled.as_text()
        assert _largest_constant(compiled) < 1024
        mem[spec] = compiled.memory_analysis().argument_size_in_bytes
    # step-sharded, each chip holds a quarter of the schedule arrays: three
    # quarters fewer argument bytes than with the schedule replicated
    saved = mem[P()] - mem[P("dev")]
    sched = _schedule_bytes(ops)
    assert abs(saved - 0.75 * sched) <= 0.02 * sched, (saved, sched)
