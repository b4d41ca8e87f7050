"""Whole serving step: model FLOPs (the cell's ``bench/models/<arch>.py``)
of the requests answered inside the traced window over the window's length
times the chips' peak bf16 FLOP/s, in % (host clock for the window and the
answers)."""
import numpy as np


def read(run):
    if run.trace is None or run.peak is None:
        return None
    done = run.done[np.isfinite(run.done)]
    answered = int((done <= run.seconds).sum())
    per_request = run.cell.model.flops_per_request(run.cell.config["sizes"], run.nnz)
    flops = per_request * answered
    return 100.0 * flops / (run.seconds * run.cell.chips * run.peak["bf16_flops_per_s"])
