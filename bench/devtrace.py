"""Reduction of a JAX profiler trace (``.xplane.pb``) to what the metrics
read: device-op intervals and XLA-module executions per chip, and the
benchmark's own host spans (``bench.*`` ``TraceAnnotation`` events), all
on the profiler's one clock in nanoseconds.

A device is a plane named ``/device:TPU:<k>``; its ops are the events of
its ``XLA Ops`` line, its program executions those of its ``XLA Modules``
line.
"""
from __future__ import annotations

import dataclasses
import gzip
import re
from collections import defaultdict
from pathlib import Path

DEVICE_PLANE = re.compile(r"/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."


@dataclasses.dataclass
class Device:
    index: int
    ops: list  # [(start_ns, end_ns, name)]
    modules: list  # [(start_ns, end_ns, name)]
    lines: tuple = ()  # names of every line of the plane, for error messages


@dataclasses.dataclass
class Trace:
    devices: list  # [Device], by index
    spans: list  # [(start_ns, end_ns, name)] of the benchmark's host spans

    def window(self) -> tuple[float, float]:
        """The ``bench.window`` span: the measured window."""
        for s, e, name in self.spans:
            if name == "bench.window":
                return s, e
        raise ValueError("the trace holds no bench.window span")


def load(path) -> Trace:
    """Read one ``.xplane.pb`` (or ``.xplane.pb.gz``) file."""
    from jax.profiler import ProfileData

    data = Path(path).read_bytes()
    if str(path).endswith(".gz"):
        data = gzip.decompress(data)
    pd = ProfileData.from_serialized_xspace(data)
    devices, spans = [], []
    for plane in pd.planes:
        m = DEVICE_PLANE.search(plane.name)
        if m:
            lines = {line.name: line for line in plane.lines}
            devices.append(
                Device(
                    int(m.group(1)),
                    _events(lines.get(OPS_LINE)),
                    _events(lines.get(MODULES_LINE)),
                    tuple(lines),
                )
            )
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.start_ns, ev.end_ns, ev.name))
    devices.sort(key=lambda d: d.index)
    spans.sort()
    return Trace(devices, spans)


def _events(line) -> list:
    if line is None:
        return []
    return sorted((ev.start_ns, ev.end_ns, ev.name) for ev in line.events)


def union(intervals, lo: float, hi: float) -> list:
    """Merged ``[(start, end)]`` of ``intervals`` clipped to ``[lo, hi]``."""
    out = []
    for s, e, *_ in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_ns(dev: Device, lo: float, hi: float) -> float:
    return sum(e - s for s, e in union(dev.ops, lo, hi))


def gaps(dev: Device, lo: float, hi: float) -> list:
    """Idle ``[(start, end)]`` of one device inside ``[lo, hi]``."""
    out, t = [], lo
    for s, e in union(dev.ops, lo, hi):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def idle_by_span(trace: Trace, lo: float, hi: float) -> dict:
    """Idle device seconds, averaged over devices, by the innermost
    benchmark span open on the host at the time (``bench.window`` itself
    excepted; idle time under no other span is ``(no span)``)."""
    inner = [s for s in trace.spans if s[2] != "bench.window"]
    out = defaultdict(float)
    for dev in trace.devices:
        for g0, g1 in gaps(dev, lo, hi):
            covered = 0.0
            for s, e, name in inner:
                if e <= g0 or s >= g1:
                    continue
                ov = min(e, g1) - max(s, g0)
                out[name] += ov * 1e-9
                covered += ov
            rest = (g1 - g0) - covered
            if rest > 0:
                out["(no span)"] += rest * 1e-9
    n = max(1, len(trace.devices))
    return {k: v / n for k, v in out.items()}


def op_seconds(trace: Trace, lo: float, hi: float) -> dict:
    """Device seconds per XLA op name, summed over devices, for ops that
    start inside ``[lo, hi]``."""
    out = defaultdict(float)
    for dev in trace.devices:
        for s, e, name in dev.ops:
            if lo <= s < hi:
                out[name] += (e - s) * 1e-9
    return dict(out)


def module_runs(trace: Trace, marker: str) -> list:
    """``(device, start_ns, end_ns)`` of every execution of a program whose
    module name contains ``marker``, over all devices."""
    return [
        (dev.index, s, e)
        for dev in trace.devices
        for s, e, name in dev.modules
        if marker in name
    ]


def require(trace: Trace, chips: int, marker: str) -> None:
    """Raise unless the trace can be read: a ``bench.window`` span, a device
    plane for each of the first ``chips`` devices with XLA ops inside the
    window, and an execution of the program ``marker`` names inside it. A
    trace whose lines are named otherwise would read as an idle device and
    no forward, so it stops the run instead."""
    lo, hi = trace.window()
    if len(trace.devices) < chips:
        raise ValueError(
            f"the trace holds {len(trace.devices)} device planes; the cell uses {chips}"
        )
    for dev in trace.devices[:chips]:
        if not union(dev.ops, lo, hi):
            raise ValueError(
                f"device {dev.index} has no {OPS_LINE!r} event in the window "
                f"(its lines: {list(dev.lines)})"
            )
    if not any(lo <= s < hi for _, s, _ in module_runs(trace, marker)):
        names = sorted({n for d in trace.devices[:chips] for _, _, n in d.modules})
        raise ValueError(
            f"no execution of {marker!r} in the window's {MODULES_LINE!r} events "
            f"(modules: {names[:10]})"
        )


def top(d: dict, k: int = 10) -> list:
    return [[name, secs] for name, secs in sorted(d.items(), key=lambda t: -t[1])[:k]]
