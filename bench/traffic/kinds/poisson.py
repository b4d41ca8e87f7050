"""Open loop: Poisson arrivals at a fixed rate.

Parameters: ``rate_rps`` (requests per second). Copied from the arrival
generator of ``benchmarks/openloop.py`` with one change: the number of
arrivals is fixed at ``round(rate_rps · seconds)`` and their times are
uniform over the window, sorted — a Poisson process conditioned on its
count, so every seed offers the same amount of work.
"""
from __future__ import annotations

import numpy as np


class Poisson:
    def __init__(self, params: dict, seed: int, seconds: float):
        n = int(round(float(params["rate_rps"]) * seconds))
        rng = np.random.default_rng([seed, 2])
        self.times = np.sort(rng.uniform(0.0, seconds, n))
        self.i = 0

    def take(self, now: float, outstanding: int) -> list[float]:
        """Scheduled arrivals due by ``now`` (seconds into the window)."""
        j = int(np.searchsorted(self.times, now, side="right"))
        out = self.times[self.i : j].tolist()
        self.i = max(self.i, j)
        return out

    def next_time(self):
        return float(self.times[self.i]) if self.i < len(self.times) else None

    def finished(self, now: float) -> bool:
        return self.i >= len(self.times)

    def warmup(self, max_batch: int) -> list[int]:
        """Requests to submit before each warm-up ``poll``: every batch size
        a poll can serve, alone and behind one or two batches that reached
        ``max_batch`` inside ``submit``."""
        return [
            k * max_batch + b
            for k in range(3)
            for b in range(max_batch + 1)
            if k * max_batch + b > 0
        ]


def make(params: dict, seed: int, seconds: float) -> Poisson:
    return Poisson(params, seed, seconds)
