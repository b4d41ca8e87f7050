"""The 2-layer GCN of Kipf & Welling (arXiv:1609.02907): its weights from
the seed, the plain reference that decides ``correct``, its work counts,
and the program that runs its forward.

The reference imports nothing of the program and takes nothing the
program made. It is the model in straightforward ``jax.numpy``: per layer
``H' = A · (H · W)``, A·(XW) as a ``segment_sum`` over the COO, ReLU
between layers, no ReLU on the logits. Every matmul runs at "highest"
precision; the precision that the configuration states is applied to the
operands explicitly (``bench/reference.py``, ``operand_types``).

The counts are what the model needs, not what any implementation does, so
no kernel can read above its roofline by them:

* FLOPs per request: ``2·n·din·dout + 2·nnz·dout`` per layer — X·W counted
  dense (a request carries X dense), A·(XW) counted once per non-zero.
* Bytes per batch of B: only the unavoidable traffic — the batch's features
  read once (``B·n·f·4``) and its logits written once (``B·n·c·4``), A read
  once per layer as a column index, a value and a row pointer
  (``nnz·8 + (n+1)·4``), and the weights read once.
"""
from __future__ import annotations

import functools

import numpy as np

from bench.reference import operand_types, round_to, weight_key
from bench.shapes import F32

#: the jitted program whose executions are the batched forward
FORWARD_MODULE = "_batched_forward_body"


def layer_dims(sizes: dict) -> list[tuple[int, int]]:
    dims = (
        [sizes["num_features"]]
        + [sizes["hidden"]] * (sizes["n_layers"] - 1)
        + [sizes["num_classes"]]
    )
    return list(zip(dims[:-1], dims[1:]))


def init_weights(sizes: dict, seed: int) -> dict:
    """Glorot-uniform float32 weights ``w0..w{L-1}``, made on the device in
    one jitted call from the seed."""
    import jax
    import jax.numpy as jnp

    dims = tuple(layer_dims(sizes))

    @jax.jit
    def make(key):
        out = {}
        for i, (din, dout) in enumerate(dims):
            key, sub = jax.random.split(key)
            lim = float(np.sqrt(6.0 / (din + dout)))
            out[f"w{i}"] = jax.random.uniform(sub, (din, dout), jnp.float32, -lim, lim)
        return out

    return jax.block_until_ready(make(jax.random.PRNGKey(weight_key(seed))))


@functools.lru_cache(maxsize=8)
def _forward(n: int, n_layers: int, storage: str, xw: str, agg: str):
    import jax
    import jax.numpy as jnp

    st, op, acc = jnp.dtype(storage), jnp.dtype(xw), jnp.dtype(agg)

    @jax.jit
    def fwd(x, weights, rows, cols, vals):
        h = x.astype(st)
        for i in range(n_layers):
            w = weights[f"w{i}"].astype(st)
            with jax.default_matmul_precision("highest"):
                xw_ = jnp.matmul(
                    round_to(h.astype(jnp.float32), op),
                    round_to(w.astype(jnp.float32), op),
                ).astype(st)
            msg = xw_[cols].astype(acc) * vals.astype(st).astype(acc)[:, None]
            h = jax.ops.segment_sum(msg, rows, num_segments=n).astype(st)
            if i < n_layers - 1:
                h = jax.nn.relu(h)
        return h.astype(jnp.float32)

    return fwd


def reference_logits(x, weights, graph, precision: dict, platform: str):
    """Logits ``[n, classes]`` (NumPy float32) of one request's features
    ``x`` under the stated ``precision``."""
    import jax

    storage, xw, agg = operand_types(precision, platform)
    fwd = _forward(graph["n"], len(weights), storage, xw, agg)
    out = fwd(x, weights, graph["rows"], graph["cols"], graph["vals"])
    return np.asarray(jax.device_get(out))


def flops_per_request(sizes: dict, nnz: int) -> int:
    n = sizes["num_nodes"]
    return sum(2 * n * din * dout + 2 * nnz * dout for din, dout in layer_dims(sizes))


def batch_bytes(sizes: dict, nnz: int, batch: int) -> int:
    """Bytes one forward over ``batch`` requests has to move at least."""
    n = sizes["num_nodes"]
    dims = layer_dims(sizes)
    features = batch * n * sizes["num_features"] * F32
    logits = batch * n * sizes["num_classes"] * F32
    adjacency = len(dims) * (nnz * 2 * F32 + (n + 1) * F32)
    weights = sum(din * dout for din, dout in dims) * F32
    return features + logits + adjacency + weights
