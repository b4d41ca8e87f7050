"""Roofline arithmetic that holds for every architecture: the least time
of a piece of work from its operations and bytes and the device's peaks.

Each architecture counts its own operations and bytes from shapes alone
(``flops_per_request`` and ``batch_bytes`` in ``bench/models/<arch>.py``);
the GCN's counts that lived here are in ``bench/models/gcn.py``.
"""
from __future__ import annotations

F32 = 4


def least_seconds(flops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """The roofline time of work of ``flops`` and ``nbytes`` on a device of
    ``peak``, and which of the two bounds it."""
    t_flops = flops / peak["bf16_flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return (t_bytes, "bytes") if t_bytes >= t_flops else (t_flops, "flops")
