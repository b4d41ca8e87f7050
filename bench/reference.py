"""The benchmark's plain GCN: weights from the seed, and the reference
forward that decides ``correct``.

It imports nothing of the program and takes nothing the program made. The
reference is the 2-layer GCN of Kipf & Welling in straightforward
``jax.numpy``: per layer ``H' = A · (H · W)``, A·(XW) as a ``segment_sum``
over the COO, ReLU between layers, no ReLU on the logits. Every matmul runs
at "highest" precision; the precision that the configuration states is
applied to the operands explicitly (``precision`` block of a config):

* ``"xw": "default"`` — X·W at the device's default matmul precision. On a
  TPU that rounds both operands to bfloat16 and accumulates in float32, so
  the reference rounds them to bfloat16 and multiplies exactly; on the CPU
  the default is float32.
* ``"aggregate": "float32"`` — A·(XW) summed in float32.

``lower_precision`` gives the configuration one step down (everything that
was float32 in bfloat16), for the control of ``bench/control.py``.
"""
from __future__ import annotations

import functools

import numpy as np

#: the operand type of a float32 matmul at "default" precision, per platform
DEFAULT_MATMUL_OPERAND = {"tpu": "bfloat16", "cpu": "float32"}


def weight_key(seed: int) -> int:
    """A 31-bit PRNG seed for the weights, drawn from ``--seed``."""
    return int(np.random.default_rng([seed, 1]).integers(0, 2**31 - 1))


def init_weights(sizes: dict, seed: int) -> dict:
    """Glorot-uniform float32 weights ``w0..w{L-1}``, made on the device in
    one jitted call from the seed."""
    import jax
    import jax.numpy as jnp

    from bench.shapes import layer_dims

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


def operand_types(precision: dict, platform: str) -> tuple[str, str, str]:
    """(storage, X·W operand, aggregation) dtype names for a stated
    precision on ``platform``."""
    xw = precision["xw"]
    if xw == "default":
        if platform not in DEFAULT_MATMUL_OPERAND:
            raise ValueError(f"default matmul precision unknown on {platform!r}")
        xw = DEFAULT_MATMUL_OPERAND[platform]
    return precision["storage"], xw, precision["aggregate"]


def lower_precision(precision: dict) -> dict:
    """The stated precision one step down: float32 parts become bfloat16."""
    step = {"float32": "bfloat16", "default": "bfloat16"}
    return {k: step.get(v, v) for k, v in precision.items()}


def round_to(x, dtype):
    """``x`` (float32) rounded to ``dtype``'s precision, kept in float32.
    bfloat16 rounding (to nearest, ties to even) is done on the bits, so
    that no compiler may drop it as a float32 -> bfloat16 -> float32 round
    trip of excess precision."""
    import jax
    import jax.numpy as jnp

    if jnp.dtype(dtype) == jnp.float32:
        return x
    if jnp.dtype(dtype) != jnp.bfloat16:
        raise ValueError(f"no rounding to {dtype}")
    u = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    u = (u + jnp.uint32(0x7FFF) + ((u >> 16) & 1)) & jnp.uint32(0xFFFF0000)
    return jax.lax.bitcast_convert_type(u, jnp.float32)


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


def max_rel_err(got: np.ndarray, ref: np.ndarray) -> float:
    """``max|got - ref| / max|ref|``; infinite where ``got`` is not finite
    or its shape is wrong."""
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    if got.shape != ref.shape or not np.isfinite(got).all():
        return float("inf")
    return float(np.abs(got - ref).max() / max(np.abs(ref).max(), 1e-30))
