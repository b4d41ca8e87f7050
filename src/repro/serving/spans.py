"""Named host spans of the serving engine, on the profiler's clock.

``Spans.span(name, **ids)`` wraps one stage of the engine's host path in
a ``jax.profiler.TraceAnnotation`` named ``engine.<name>``, with ``ids``
(request id, graph, batch size) as event stats, and times it on the host
clock into the per-stage aggregate of ``stats()["stages"]``: ``n`` spans
closed, ``s`` their total seconds, and ``self_s`` those seconds less the
time covered by spans opened inside them on the same thread. Replica
chunks run on pool threads, so nesting is tracked per thread and the
aggregate is updated under one lock. There is no switch: with no profiler
listening a span costs an annotation and two clock reads.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, List

import jax


class _Span:
    __slots__ = ("_rec", "_name", "_ann", "_t0", "_child_ns")

    def __init__(self, rec: "Spans", name: str, ids: dict):
        self._rec, self._name = rec, name
        self._ann = jax.profiler.TraceAnnotation(f"engine.{name}", **ids)
        self._child_ns = 0

    def __enter__(self) -> "_Span":
        self._rec._stack().append(self)
        self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        dt = time.perf_counter_ns() - self._t0
        self._ann.__exit__(*exc)
        stack = self._rec._stack()
        stack.pop()
        if stack:
            stack[-1]._child_ns += dt
        self._rec._add(self._name, dt, dt - self._child_ns)


class Spans:
    """Per-stage span recorder; one per engine."""

    def __init__(self):
        self._lock = threading.Lock()
        self._local = threading.local()
        self._agg: Dict[str, List[int]] = {}  # guarded-by: _lock

    def span(self, name: str, **ids) -> _Span:
        """Context manager timing one ``engine.<name>`` span."""
        return _Span(self, name, ids)

    def _stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _add(self, name: str, dt_ns: int, self_ns: int) -> None:
        with self._lock:
            agg = self._agg.setdefault(name, [0, 0, 0])
            agg[0] += 1
            agg[1] += dt_ns
            agg[2] += self_ns

    def snapshot(self) -> Dict[str, dict]:
        """``{stage: {"n", "s", "self_s"}}`` of every stage seen since the
        last ``reset``."""
        with self._lock:
            return {
                name: {"n": n, "s": s * 1e-9, "self_s": self_s * 1e-9}
                for name, (n, s, self_s) in self._agg.items()
            }

    def reset(self) -> None:
        with self._lock:
            self._agg = {}
