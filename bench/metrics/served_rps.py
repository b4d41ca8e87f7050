"""Requests answered whose answer came back inside the window, per second
of the window (host clock). A run with a wrong answer is not correct, so
every request counted here was answered correctly or the run fails."""
import numpy as np


def read(run):
    done = run.done[np.isfinite(run.done)]
    return float((done <= run.seconds).sum()) / run.seconds
