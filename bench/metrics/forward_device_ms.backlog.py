"""Batched forward: median device time of one execution of the cell's
forward program (its model's ``FORWARD_MODULE``; ``_batched_forward_body``
for the GCN) in the trace, over every chip (device trace)."""
from bench.metrics import _forward


def read(run):
    return _forward.median_ms(run)
