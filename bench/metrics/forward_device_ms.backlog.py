"""Batched forward: median device time of one execution of the
``_batched_forward_body`` program in the trace, over every chip
(device trace)."""
from bench.metrics import _forward


def read(run):
    return _forward.median_ms(run)
