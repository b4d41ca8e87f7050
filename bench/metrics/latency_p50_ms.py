"""Median latency of every request whose scheduled arrival falls in the
window: scheduled arrival to the return of the ``submit``/``poll`` call
that handed back its logits, answers in the drain included (host clock)."""
import numpy as np


def read(run):
    lat = run.latency_s
    return float(np.percentile(lat, 50)) * 1e3 if lat.size else None
