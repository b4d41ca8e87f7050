"""The graph attention network of Veličković et al. (arXiv:1710.10903,
§2.1–2.2, equations 1–6): its weights from the seed, the plain reference
that decides ``correct``, its work counts, and the program that runs its
forward.

The reference imports nothing of the program and takes nothing the
program made. Per layer of K heads of F features::

    Wh     = H · W                                   [n, K·F]
    e_ij   = LeakyReLU_0.2(a_kᵀ [Wh_i ‖ Wh_j])      for every non-zero (i, j) of A
    α_ij   = exp(e_ij − max_j e_ij) / Σ_j exp(...)   over row i of A
    h'_i   = Σ_j α_ij · Wh_j

hidden layers concatenate their heads and apply ELU, the last averages
them (logits, no softmax). A's structure is used and its values are not;
its self loops make the softmax run over N(i) ∪ {i}. No biases (the
authors' code has them; the equations do not). Every matmul runs at
"highest" precision; the configuration's precision is applied to the
operands explicitly (``bench/reference.py``): ``xw`` for X·W, ``score``
for the edge scores, ``softmax`` for the exponentials, ``aggregate`` for
the sums over a row (a precision that states no ``score`` or ``softmax``,
as ``bench/control.py``'s float32 one, gives them ``aggregate``'s type).

The counts are what the model needs, from shapes alone:

* FLOPs per request, per layer of ``din`` inputs, K heads of F: X·W dense
  ``2·n·din·K·F``; the two halves of the scores per node ``4·n·K·F``; per
  non-zero and head 5 element operations (add, LeakyReLU, max, subtract,
  exp) and ``2·F`` for the weighted sum; the head mean ``n·K·C`` once.
* Bytes per batch of B: the batch's features read once and its logits
  written once, A's structure once per layer (a column index per non-zero
  and a row pointer per row, no values), and the weights once.
"""
from __future__ import annotations

import functools

import numpy as np

from bench.reference import operand_types, round_to, weight_key
from bench.shapes import F32

#: the jitted program whose executions are the batched forward
FORWARD_MODULE = "_batched_gat_body"
NEGATIVE_SLOPE = 0.2


def layer_dims(sizes: dict) -> list[tuple[int, int, int]]:
    """``(din, heads, dout)`` of every layer."""
    heads = list(sizes["heads"])
    dims, din = [], sizes["num_features"]
    for i, k in enumerate(heads):
        dout = sizes["num_classes"] if i == len(heads) - 1 else sizes["hidden"]
        dims.append((din, k, dout))
        din = k * dout
    return dims


def init_weights(sizes: dict, seed: int) -> dict:
    """Glorot-uniform float32 ``w<i>`` ``[din, K·F]`` and ``a<i>`` ``[K, 2·F]``,
    made on the device in one jitted call from the seed."""
    import jax
    import jax.numpy as jnp

    dims = tuple(layer_dims(sizes))

    @jax.jit
    def make(key):
        out = {}
        for i, (din, k, dout) in enumerate(dims):
            key, kw, ka = jax.random.split(key, 3)
            lim = float(np.sqrt(6.0 / (din + k * dout)))
            shape = (din, k * dout)
            out[f"w{i}"] = jax.random.uniform(kw, shape, jnp.float32, -lim, lim)
            lim = float(np.sqrt(6.0 / (2 * dout + 1)))
            out[f"a{i}"] = jax.random.uniform(ka, (k, 2 * dout), jnp.float32, -lim, lim)
        return out

    return jax.block_until_ready(make(jax.random.PRNGKey(weight_key(seed))))


@functools.lru_cache(maxsize=8)
def _forward(n: int, n_layers: int, storage: str, xw: str, score: str,
             softmax: str, agg: str):
    import jax
    import jax.numpy as jnp

    st, op = jnp.dtype(storage), jnp.dtype(xw)
    sc, sm, acc = jnp.dtype(score), jnp.dtype(softmax), jnp.dtype(agg)

    @jax.jit
    def fwd(x, weights, rows, cols):
        h = x.astype(st)
        for i in range(n_layers):
            w, att = weights[f"w{i}"].astype(st), weights[f"a{i}"].astype(st)
            k, f = att.shape[0], att.shape[1] // 2
            with jax.default_matmul_precision("highest"):
                wh = jnp.matmul(
                    round_to(h.astype(jnp.float32), op),
                    round_to(w.astype(jnp.float32), op),
                ).astype(st).reshape(n, k, f)
            own = (wh.astype(sc) * att[:, :f].astype(sc)).sum(-1)  # a_kᵀ Wh_i
            nbr = (wh.astype(sc) * att[:, f:].astype(sc)).sum(-1)  # a_kᵀ Wh_j
            e = own[rows] + nbr[cols]  # [nnz, K]
            e = jnp.where(e > 0, e, jnp.asarray(NEGATIVE_SLOPE, sc) * e)
            top = jax.ops.segment_max(e, rows, num_segments=n)
            p = jnp.exp((e - top[rows]).astype(sm))
            den = jax.ops.segment_sum(p.astype(acc), rows, num_segments=n)
            alpha = p.astype(acc) / den[rows]
            out = jax.ops.segment_sum(
                alpha[:, :, None] * wh[cols].astype(acc), rows, num_segments=n
            ).astype(st)
            if i < n_layers - 1:
                h = jax.nn.elu(out).reshape(n, k * f)
            else:
                h = out.mean(axis=1)
        return h.astype(jnp.float32)

    return fwd


def reference_logits(x, weights, graph, precision: dict, platform: str):
    """Logits ``[n, classes]`` (NumPy float32) of one request's features
    ``x`` under the stated ``precision``."""
    import jax

    storage, xw, agg = operand_types(precision, platform)
    fwd = _forward(graph["n"], len(weights) // 2, storage, xw,
                   precision.get("score", agg), precision.get("softmax", agg), agg)
    out = fwd(x, weights, graph["rows"], graph["cols"])
    return np.asarray(jax.device_get(out))


def flops_per_request(sizes: dict, nnz: int) -> int:
    n = sizes["num_nodes"]
    total = 0
    for din, k, f in layer_dims(sizes):
        total += 2 * n * din * k * f + 4 * n * k * f + nnz * k * (5 + 2 * f)
    _, k, c = layer_dims(sizes)[-1]
    return total + n * k * c


def batch_bytes(sizes: dict, nnz: int, batch: int) -> int:
    """Bytes one forward over ``batch`` requests has to move at least."""
    n = sizes["num_nodes"]
    dims = layer_dims(sizes)
    features = batch * n * sizes["num_features"] * F32
    logits = batch * n * sizes["num_classes"] * F32
    structure = len(dims) * (nnz * F32 + (n + 1) * F32)
    weights = sum(din * k * f + k * 2 * f for din, k, f in dims) * F32
    return features + logits + structure + weights
