"""Device: the share of the measured window in which no XLA op ran, in %,
averaged over the cell's chips (device trace)."""
from bench import devtrace


def read(run):
    tr = run.trace
    if tr is None or not tr.devices:
        return None
    lo, hi = tr.window()
    devs = tr.devices[: run.cell.chips]
    busy = sum(devtrace.busy_ns(d, lo, hi) for d in devs) / len(devs)
    return 100.0 * (1.0 - busy / (hi - lo))
