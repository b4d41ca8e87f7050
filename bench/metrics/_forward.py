"""Executions of the batched forward program in a trace, shared by the
readers of the batched-forward layer."""
from bench import devtrace, shapes
from bench.harness import FORWARD_MODULE


def runs(run):
    """``(device, start_ns, end_ns)`` of every forward execution traced."""
    return devtrace.module_runs(run.trace, FORWARD_MODULE) if run.trace else []


def median_ms(run):
    import numpy as np

    r = runs(run)
    if not r:
        return None
    return float(np.median([(e - s) for _, s, e in r])) * 1e-6


def least_and_spent(run):
    """The roofline time of every traced forward execution together, which
    of the two bounds it, and their device time (seconds). The trace covers
    the window and its drain, and nothing of the warm-up, so the executions
    are exactly those of the run's served requests."""
    r = runs(run)
    if not r or run.peak is None:
        return None
    cfg = run.cell.config
    served = int(run.stats["requests"])
    flops = shapes.flops_per_request(cfg["sizes"], run.nnz) * served
    # bytes are affine in the batch size, so the executions' total is the
    # per-execution constant once per execution plus the per-request part
    per_req = shapes.batch_bytes(cfg["sizes"], run.nnz, 1) - shapes.batch_bytes(
        cfg["sizes"], run.nnz, 0
    )
    nbytes = per_req * served + shapes.batch_bytes(cfg["sizes"], run.nnz, 0) * len(r)
    least, bound = shapes.least_seconds(flops, nbytes, run.peak)
    spent = sum(e - s for _, s, e in r) * 1e-9
    return least, bound, spent
