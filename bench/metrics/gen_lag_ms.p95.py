"""Load generator: 95th percentile of how late ``submit`` was called after
the request's scheduled arrival (host clock)."""
import numpy as np


def read(run):
    sel = run.in_window & np.isfinite(run.submit_start)
    lag = (run.submit_start - run.arrival)[sel]
    return float(np.percentile(lag, 95)) * 1e3 if lag.size else None
