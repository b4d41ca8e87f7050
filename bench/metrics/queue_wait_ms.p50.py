"""Queueing and dispatch: median time from a request's scheduled arrival to
the start of the ``submit``/``poll`` call that dispatched its batch. The
engine's queue is FIFO per graph, so the benchmark knows which requests a
call served (host clock)."""
import numpy as np


def read(run):
    sel = run.in_window & np.isfinite(run.dispatch_start)
    w = (run.dispatch_start - run.arrival)[sel]
    return float(np.percentile(w, 50)) * 1e3 if w.size else None
