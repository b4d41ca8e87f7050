"""The benchmark's precision arithmetic, shared by every architecture's
reference (``bench/models/<arch>.py``): the weight seed drawn from
``--seed``, the operand types of a stated precision, rounding to them, and
the comparison that decides ``correct``.

A reference runs every matmul at "highest" precision and applies the
precision that the configuration states to the operands explicitly
(``precision`` block of a config):

* ``"xw": "default"`` — X·W at the device's default matmul precision. On a
  TPU that rounds both operands to bfloat16 and accumulates in float32, so
  the reference rounds them to bfloat16 and multiplies exactly; on the CPU
  the default is float32.
* ``"aggregate": "float32"`` — A·(XW) summed in float32.

``lower_precision`` gives the configuration one step down (everything that
was float32 in bfloat16), for the control of ``bench/control.py``. The GCN
weights and reference forward that lived here are in
``bench/models/gcn.py``.
"""
from __future__ import annotations

import numpy as np

#: the operand type of a float32 matmul at "default" precision, per platform
DEFAULT_MATMUL_OPERAND = {"tpu": "bfloat16", "cpu": "float32"}


def weight_key(seed: int) -> int:
    """A 31-bit PRNG seed for the weights, drawn from ``--seed``."""
    return int(np.random.default_rng([seed, 1]).integers(0, 2**31 - 1))


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


def max_rel_err(got: np.ndarray, ref: np.ndarray) -> float:
    """``max|got - ref| / max|ref|``; infinite where ``got`` is not finite
    or its shape is wrong."""
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    if got.shape != ref.shape or not np.isfinite(got).all():
        return float("inf")
    return float(np.abs(got - ref).max() / max(np.abs(ref).max(), 1e-30))
