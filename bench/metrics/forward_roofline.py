"""Batched forward, as one program until the program names its kernels:
the least time of the traced executions over their device time, in %. The
least time is max(FLOPs / peak FLOP/s, bytes / peak bytes/s), with the
counts of the cell's ``bench/models/<arch>.py`` and ``bench/shapes.py``'s
arithmetic; the log line of the run says which bound it (device trace)."""
from bench.metrics import _forward


def read(run):
    got = _forward.least_and_spent(run)
    if got is None:
        return None
    least, bound, spent = got
    print(f"forward_roofline: bound by {bound}, least {least:.6f} s "
          f"of {spent:.6f} s device time")
    return 100.0 * least / spent if spent > 0 else None
