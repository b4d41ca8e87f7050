"""GPipe-style pipeline parallelism over a mesh axis.

The multi-pod mesh's ``pod`` axis defaults to outer data parallelism
(DESIGN.md §4); this module provides the alternative: partition a stack of
identical stages (e.g. transformer segments) across the axis and stream
microbatches through with ``shard_map`` + ``ppermute``.

Schedule: classic GPipe fill-drain. For S stages and M microbatches the
loop runs ``M + S - 1`` ticks; at tick t, stage s computes microbatch
``t - s`` (when in range) and passes its activation to stage ``s+1``.
Bubble fraction = (S-1)/(M+S-1) — reported by ``bubble_fraction`` so the
launcher can size M.

Stage parameters live sharded over the axis (leading dim = stage), so
per-device memory is 1/S of the stack — the PP memory win.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def bubble_fraction(n_stages: int, n_micro: int) -> float:
    return (n_stages - 1) / (n_micro + n_stages - 1)


def pipeline_apply(stage_fn: Callable, stage_params, x, *, mesh,
                   axis: str, n_micro: int):
    """Run ``y = stage_S(...stage_1(x))`` pipelined over ``axis``.

    stage_fn(params_slice, h) -> h, applied per stage; ``stage_params`` is
    a pytree whose leaves have leading dim = n_stages (sharded over
    ``axis``); ``x``: [B, ...] with B divisible by n_micro.
    """
    n_stages = mesh.shape[axis]
    b = x.shape[0]
    assert b % n_micro == 0, "batch must divide into microbatches"
    mb = b // n_micro

    # microbatch stream: [M, mb, ...]
    micro = x.reshape(n_micro, mb, *x.shape[1:])

    def shard_body(params, micro_local):
        # params: this stage's slice (leading dim 1); micro_local: the full
        # microbatch stream (replicated over the pipeline axis)
        idx = jax.lax.axis_index(axis)
        p_local = jax.tree.map(lambda t: t[0], params)
        n_ticks = n_micro + n_stages - 1

        def tick(carry, t):
            buf, outs = carry
            # stage 0 ingests microbatch t; others use the permuted buffer
            feed = jnp.where(t < n_micro, t, 0)
            h_in = jnp.where(idx == 0, micro_local[feed], buf)
            active = jnp.logical_and(t - idx >= 0, t - idx < n_micro)
            h_out = stage_fn(p_local, h_in)
            h_out = jnp.where(active, h_out, jnp.zeros_like(h_out))
            # last stage emits microbatch t - (S-1)
            emit = t - (n_stages - 1)
            outs = jax.lax.cond(
                jnp.logical_and(emit >= 0, emit < n_micro),
                lambda o: o.at[jnp.maximum(emit, 0)].set(h_out),
                lambda o: o, outs)
            # shift activations one stage down the ring
            buf = jax.lax.ppermute(
                h_out, axis,
                [(i, (i + 1) % n_stages) for i in range(n_stages)])
            return (buf, outs), None

        buf0 = jnp.zeros_like(micro_local[0])
        outs0 = jnp.zeros_like(micro_local)
        (buf, outs), _ = jax.lax.scan(
            tick, (buf0, outs0), jnp.arange(n_micro + n_stages - 1))
        # outs is valid on the LAST stage only; broadcast it to all
        outs = jax.lax.psum(
            jnp.where(idx == n_stages - 1, outs, jnp.zeros_like(outs)),
            axis)
        return outs

    other_axes = tuple(a for a in mesh.axis_names if a != axis)
    spec_p = P(axis, *([None] * 0))
    fn = jax.shard_map(
        shard_body, mesh=mesh,
        in_specs=(jax.tree.map(lambda _: P(axis), stage_params), P()),
        out_specs=P(),
        check_vma=False,
    )
    outs = fn(stage_params, micro)
    return outs.reshape(b, *x.shape[1:])
