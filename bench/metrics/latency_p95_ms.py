"""95th percentile of the latency that ``latency_p50_ms`` takes the median
of (host clock)."""
import numpy as np


def read(run):
    lat = run.latency_s
    return float(np.percentile(lat, 95)) * 1e3 if lat.size else None
