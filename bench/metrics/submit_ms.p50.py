"""Engine submit: median duration of the ``submit`` calls that did not
serve a batch themselves (the benchmark's ``bench.submit`` span; it holds
the host-to-device copy of the request's features) (host clock)."""
import numpy as np


def read(run):
    sel = run.in_window & np.isfinite(run.submit_end) & ~run.auto_flush
    d = (run.submit_end - run.submit_start)[sel]
    return float(np.percentile(d, 50)) * 1e3 if d.size else None
