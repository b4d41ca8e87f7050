"""Batched forward, host side: mean host time of the ``engine.await``
span, the host blocked until a dispatched batch's logits are ready, in ms
per batch (host clock, read inside the program)."""
from bench.metrics import _stages


def read(run):
    return _stages.mean_ms(run, "await")
