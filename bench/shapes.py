"""Operations and bytes of one served GCN request, from shapes alone.

These count what the model needs, not what any implementation does, so no
kernel can read above its roofline by them:

* FLOPs per request: ``2·n·din·dout + 2·nnz·dout`` per layer — X·W counted
  dense (a request carries X dense), A·(XW) counted once per non-zero.
* Bytes per batch of B: only the unavoidable traffic — the batch's features
  read once (``B·n·f·4``) and its logits written once (``B·n·c·4``), A read
  once per layer as a column index, a value and a row pointer
  (``nnz·8 + (n+1)·4``), and the weights read once.
"""
from __future__ import annotations

F32 = 4


def layer_dims(sizes: dict) -> list[tuple[int, int]]:
    dims = (
        [sizes["num_features"]]
        + [sizes["hidden"]] * (sizes["n_layers"] - 1)
        + [sizes["num_classes"]]
    )
    return list(zip(dims[:-1], dims[1:]))


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


def least_seconds(flops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """The roofline time of work of ``flops`` and ``nbytes`` on a device of
    ``peak``, and which of the two bounds it."""
    t_flops = flops / peak["bf16_flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return (t_bytes, "bytes") if t_bytes >= t_flops else (t_flops, "flops")
