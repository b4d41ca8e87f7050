"""Queueing and dispatch: mean host time of the ``engine.dispatch`` span,
which validates and stacks a batch's features (``engine.stack``) and
launches the forward without waiting for it, in ms per batch (host clock,
read inside the program)."""
from bench.metrics import _stages


def read(run):
    return _stages.mean_ms(run, "dispatch")
