"""Executions of the batched forward program in a trace, shared by the
readers of the batched-forward layer."""
from bench import devtrace, shapes


def runs(run):
    """``(device, start_ns, end_ns)`` of every execution of the cell's
    forward program (its model's ``FORWARD_MODULE``) traced."""
    if not run.trace:
        return []
    return devtrace.module_runs(run.trace, run.cell.model.FORWARD_MODULE)


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
    sizes, model = run.cell.config["sizes"], run.cell.model
    served = int(run.stats["requests"])
    flops = model.flops_per_request(sizes, run.nnz) * served
    # bytes are affine in the batch size, so the executions' total is the
    # per-execution constant once per execution plus the per-request part
    fixed = model.batch_bytes(sizes, run.nnz, 0)
    per_req = model.batch_bytes(sizes, run.nnz, 1) - fixed
    nbytes = per_req * served + fixed * len(r)
    least, bound = shapes.least_seconds(flops, nbytes, run.peak)
    spent = sum(e - s for _, s, e in r) * 1e-9
    return least, bound, spent
