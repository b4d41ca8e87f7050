"""GAT model (Veličković et al., arXiv:1710.10903) on the AWB schedule.

Per layer ``l`` with ``K`` heads of ``F`` features (equations 1–6)::

    Wh      = H · W                       (all heads at once: [n, K·F])
    e_ij    = LeakyReLU_0.2(a_kᵀ [Wh_i,k ‖ Wh_j,k])   for A[i, j] ≠ 0
    α_ij    = softmax over the row i of e_i·
    h'_i,k  = Σ_j α_ij,k · Wh_j,k

Hidden layers concatenate their heads and apply ELU; the last layer
averages its heads and returns logits (no softmax, as ``core.gcn``). The
softmax runs over A's structure, so A carries the self loops that make it
N(i) ∪ {i}; the values of A are not used, and an entry of value 0 is no
edge (it is how A pads).

Parameters: ``w<i>`` of shape ``[din, K·F]`` and ``a<i>`` of shape
``[K, 2·F]`` — the first ``F`` columns of a head score the row's own
``Wh_i``, the last ``F`` its neighbour's ``Wh_j``. There are no biases,
unlike the authors' code: equations 1–6 have none.

This module is the configuration, its initialisation and the plain
reference; the served path is ``core.executor``'s attention body
(``ScheduleExecutor.gat_forward_batch``).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import csc as fmt

#: slope of the LeakyReLU on the edge scores
NEGATIVE_SLOPE = 0.2


@dataclasses.dataclass(frozen=True)
class GATConfig:
    num_features: int
    heads: tuple = (8, 8)
    hidden: int = 8
    num_classes: int = 3

    def layer_dims(self) -> list:
        """``(din, heads, dout)`` of every layer."""
        dims, din = [], self.num_features
        for i, k in enumerate(self.heads):
            dout = self.num_classes if i == len(self.heads) - 1 else self.hidden
            dims.append((din, k, dout))
            din = k * dout
        return dims


def init_params(cfg: GATConfig, key: jax.Array) -> dict:
    """Glorot-uniform ``w<i>`` ``[din, K·F]`` and ``a<i>`` ``[K, 2·F]`` (each
    head's score is a map of fan-in 2·F to one output)."""
    params = {}
    for i, (din, k, dout) in enumerate(cfg.layer_dims()):
        key, kw, ka = jax.random.split(key, 3)
        lim = float(np.sqrt(6.0 / (din + k * dout)))
        shape = (din, k * dout)
        params[f"w{i}"] = jax.random.uniform(kw, shape, jnp.float32, -lim, lim)
        lim = float(np.sqrt(6.0 / (2 * dout + 1)))
        params[f"a{i}"] = jax.random.uniform(ka, (k, 2 * dout), jnp.float32, -lim, lim)
    return params


def layer_heads(params: dict) -> tuple:
    """The heads of each layer of a GAT parameter tree. Raises ValueError
    where the tree is not one: keys other than ``w<i>``/``a<i>`` for
    consecutive layers, ``a<i>`` not ``[K, 2·F]`` with ``w<i>`` ``[din,
    K·F]``, or a hidden layer's ``K·F`` not the next layer's ``din``."""
    n = len(params) // 2
    want = {f"{p}{i}" for i in range(n) for p in "wa"}
    if n == 0 or set(params) != want:
        raise ValueError(
            f"GAT parameters are w0..w{{L-1}} and a0..a{{L-1}}; got {sorted(params)}"
        )
    heads, din = [], None
    for i in range(n):
        w, a = np.shape(params[f"w{i}"]), np.shape(params[f"a{i}"])
        if len(w) != 2 or len(a) != 2 or a[1] % 2 or w[1] != a[0] * (a[1] // 2):
            raise ValueError(
                f"layer {i}: w{i} {w} and a{i} {a} are not [din, K*F] and [K, 2*F]"
            )
        if din is not None and w[0] != din:
            raise ValueError(f"layer {i}: w{i} takes {w[0]} inputs; layer {i - 1} "
                             f"gives {din}")
        heads.append(int(a[0]))
        din = w[1]
    return tuple(heads)


def forward(params: dict, a: fmt.COO, x: jax.Array) -> jax.Array:
    """Logits ``[n, classes]``: the model in straightforward ``jax.numpy``
    and float32, every matmul at "highest" precision, the row softmax as a
    ``segment_max`` and ``segment_sum`` over A's rows."""
    n = a.shape[0]
    valid = (a.row != fmt.PAD_IDX) & (a.val != 0)
    rows = jnp.where(valid, a.row, 0)
    cols = jnp.where(valid, a.col, 0)
    n_layers = len(layer_heads(params))
    h = x.astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        for i in range(n_layers):
            att = params[f"a{i}"]
            k, f = att.shape[0], att.shape[1] // 2
            wh = (h @ params[f"w{i}"]).reshape(n, k, f)
            s_row = (wh * att[:, :f]).sum(-1)  # [n, K]: a_kᵀ Wh_i
            s_col = (wh * att[:, f:]).sum(-1)  # [n, K]: a_kᵀ Wh_j
            e = jax.nn.leaky_relu(s_row[rows] + s_col[cols], NEGATIVE_SLOPE)
            e = jnp.where(valid[:, None], e, -jnp.inf)
            top = jax.ops.segment_max(e, rows, num_segments=n)
            p = jnp.where(valid[:, None], jnp.exp(e - top[rows]), 0.0)
            den = jax.ops.segment_sum(p, rows, num_segments=n)
            alpha = p / jnp.where(den > 0, den, 1.0)[rows]
            h = jax.ops.segment_sum(alpha[:, :, None] * wh[cols], rows, num_segments=n)
            if i < n_layers - 1:
                h = jax.nn.elu(h.reshape(n, k * f))
            else:
                h = h.mean(axis=1)
    return h
