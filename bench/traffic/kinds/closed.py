"""Closed loop: a fixed number of requests outstanding for the whole window.

Parameters: ``outstanding`` (requests kept in flight). A request is sent as
soon as an earlier one has been handed back, so its scheduled arrival is
the moment its slot frees; nothing is sent once the window has closed.
"""
from __future__ import annotations


class Closed:
    def __init__(self, params: dict, seed: int, seconds: float):
        self.outstanding = int(params["outstanding"])
        self.seconds = float(seconds)

    def take(self, now: float, outstanding: int) -> list[float]:
        """Scheduled arrivals due by ``now`` (seconds into the window)."""
        if now >= self.seconds:
            return []
        return [now] * max(0, self.outstanding - outstanding)

    def next_time(self):
        """The next scheduled arrival, or None when it waits on answers."""
        return None

    def finished(self, now: float) -> bool:
        return now >= self.seconds

    def warmup(self, max_batch: int) -> list[int]:
        """Requests to submit before each warm-up ``poll``: the window's own
        cycle, a few times."""
        return [self.outstanding] * 3


def make(params: dict, seed: int, seconds: float) -> Closed:
    return Closed(params, seed, seconds)
