"""Flash attention Pallas TPU kernel (causal / local-window / GQA).

The LM-side compute hot-spot (prefill_32k cells). Standard IO-aware tiling:
online softmax with running (m, l) statistics in VMEM scratch, one KV block
per inner grid step, output written on the last KV block. GQA is handled in
the BlockSpec index maps (no KV head replication in HBM).

Grid: ``(batch*heads, q_blocks, kv_blocks)``; kv innermost sequential, the
rest parallel. Causal/window-masked KV blocks are skipped via ``pl.when``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                 *, scale: float, causal: bool, window: int | None,
                 sq: int, sk: int, block_q: int, block_k: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    n_kv = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # global positions (q offset by sk - sq: decode-style alignment)
    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0) + (sk - sq)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)

    # block-level visibility (skip fully masked blocks)
    q_max = qi * block_q + block_q - 1 + (sk - sq)
    q_min = qi * block_q + (sk - sq)
    k_min = ki * block_k
    k_max = ki * block_k + block_k - 1
    visible = jnp.asarray(True)
    if causal:
        visible = jnp.logical_and(visible, k_min <= q_max)
    if window is not None:
        visible = jnp.logical_and(visible, k_max > q_min - window)

    @pl.when(visible)
    def _compute():
        q = q_ref[0].astype(jnp.float32)            # [bq, d]
        k = k_ref[0].astype(jnp.float32)            # [bk, d]
        v = v_ref[0].astype(jnp.float32)            # [bk, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [bq, bk]

        mask = k_pos < sk                            # padded kv
        if causal:
            mask = jnp.logical_and(mask, k_pos <= q_pos)
        if window is not None:
            mask = jnp.logical_and(mask, k_pos > q_pos - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                          # [bq, 1]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = corr * l_ref[...] + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = corr * acc_ref[...] + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == n_kv - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "scale", "block_q", "block_k", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int | None = None,
                    scale: float | None = None, block_q: int = 128,
                    block_k: int = 128,
                    interpret: bool | None = None) -> jax.Array:
    """q: [B, Sq, H, D]; k, v: [B, Sk, Hkv, D] (GQA when Hkv < H).
    Returns [B, Sq, H, D]. ``interpret`` defaults to the backend: compiled
    on a TPU, the interpreter everywhere else."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, sq, h, d = q.shape
    _, sk, hkv, _ = k.shape
    assert h % hkv == 0
    group = h // hkv
    if scale is None:
        scale = d ** -0.5

    bq = min(block_q, max(8, sq))
    bk = min(block_k, max(8, sk))
    pad_q = (-sq) % bq
    pad_k = (-sk) % bk

    qt = jnp.pad(q.transpose(0, 2, 1, 3), ((0, 0), (0, 0), (0, pad_q), (0, 0))
                 ).reshape(b * h, sq + pad_q, d)
    kt = jnp.pad(k.transpose(0, 2, 1, 3), ((0, 0), (0, 0), (0, pad_k), (0, 0))
                 ).reshape(b * hkv, sk + pad_k, d)
    vt = jnp.pad(v.transpose(0, 2, 1, 3), ((0, 0), (0, 0), (0, pad_k), (0, 0))
                 ).reshape(b * hkv, sk + pad_k, d)

    grid = (b * h, (sq + pad_q) // bq, (sk + pad_k) // bk)

    def kv_index(bh, qi, ki):
        # bh = bi * h + hi ; kv row = bi * hkv + hi // group
        return ((bh // h) * hkv + (bh % h) // group, ki, 0)

    out = pl.pallas_call(
        functools.partial(_attn_kernel, scale=scale, causal=causal,
                          window=window, sq=sq, sk=sk, block_q=bq,
                          block_k=bk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bk, d), kv_index),
            pl.BlockSpec((1, bk, d), kv_index),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq + pad_q, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(qt, kt, vt)

    out = out.reshape(b, h, sq + pad_q, d)[:, :, :sq].transpose(0, 2, 1, 3)
    return out
