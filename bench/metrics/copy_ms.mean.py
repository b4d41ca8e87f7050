"""Engine submit: mean host time of the ``engine.copy`` span, the
host-to-device copy of one request's features inside ``submit``, in ms per
request (host clock, read inside the program)."""
from bench.metrics import _stages


def read(run):
    return _stages.mean_ms(run, "copy")
