"""From a JAX profiler trace to device busy time, idle gaps and op totals.

The benchmark wraps its traced window in ``jax.profiler.TraceAnnotation``
spans (``bench.window`` around it, ``bench.solve`` around each solve,
``bench.draw`` around each input draw).  The profiler writes them on the
host plane of the ``.xplane.pb`` it leaves, on the same clock as the device
planes' ops.  :func:`load` reads both; the rest are pure functions over
``(name, start_ns, end_ns)`` tuples.
"""

from __future__ import annotations

import dataclasses
import re
from pathlib import Path

#: Device planes are named ``/device:TPU:<i>``; their ops are on this line.
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."


@dataclasses.dataclass
class Trace:
    devices: list  # per device: [(op name, start_ns, end_ns)]
    spans: list  # host spans named bench.*: [(name, start_ns, end_ns)]


def load(trace_dir) -> Trace:
    """The newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = sorted(Path(trace_dir).rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(str(files[-1]))
    devices, spans = [], []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = [
                (e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
                for line in plane.lines
                if line.name == OPS_LINE
                for e in line.events
            ]
            devices.append(ops)
        elif plane.name.startswith("/host:"):
            spans += [
                (e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
                for line in plane.lines
                for e in line.events
                if e.name.startswith(SPAN_PREFIX)
            ]
    if not devices:
        names = [p.name for p in data.planes]
        raise ValueError(f"no device plane with {OPS_LINE!r} ops; planes: {names}")
    return Trace(devices=devices, spans=spans)


def merge(intervals, lo, hi) -> list:
    """The union of ``(start, end)`` intervals clipped to ``[lo, hi]``."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_ns(ops, lo, hi) -> int:
    """Nanoseconds in ``[lo, hi]`` in which some op ran."""
    return sum(e - s for s, e in merge([(s, e) for _, s, e in ops], lo, hi))


def gaps(ops, lo, hi) -> list:
    """The idle ``(start, end)`` stretches of ``[lo, hi]``, in time order."""
    out, t = [], lo
    for s, e in merge([(s, e) for _, s, e in ops], lo, hi):
        if s > t:
            out.append((t, s))
        t = e
    if hi > t:
        out.append((t, hi))
    return out


def op_kind(name: str) -> str:
    """HLO op kind of an op name: ``fusion.12`` -> ``fusion``."""
    return re.sub(r"\.\d+$", "", name.split(" ")[0].lstrip("%"))


def op_totals(ops, lo, hi) -> dict:
    """Seconds of ops in ``[lo, hi]`` summed by op kind."""
    totals = {}
    for name, s, e in ops:
        d = min(e, hi) - max(s, lo)
        if d > 0:
            totals[op_kind(name)] = totals.get(op_kind(name), 0) + d
    return {k: v / 1e9 for k, v in totals.items()}


def span_at(spans, t) -> str:
    """Name of the innermost span holding time ``t`` (``"no span"`` if none)."""
    inside = [(e - s, name) for name, s, e in spans if s <= t < e]
    return min(inside)[1] if inside else "no span"


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float  # mean over devices
    solve_busy_s: float  # device busy inside the solve spans, mean over devices
    device_ops: list  # [(op kind, seconds)], most first (device 0)
    idle_gaps: list  # [(span name, seconds)], longest first (device 0)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def reduce(trace: Trace, window="bench.window", solve="bench.solve", top=10) -> Reduction:
    """Busy, idle and op totals of the one ``window`` span of ``trace``."""
    (lo, hi) = [(s, e) for name, s, e in trace.spans if name == window][0]
    solves = [(s, e) for name, s, e in trace.spans if name == solve]
    n = len(trace.devices)
    busy = sum(busy_ns(ops, lo, hi) for ops in trace.devices) / n
    solve_busy = sum(busy_ns(ops, s, e) for ops in trace.devices for s, e in solves) / n
    ops0 = trace.devices[0]
    totals = sorted(op_totals(ops0, lo, hi).items(), key=lambda kv: -kv[1])[:top]
    idle = sorted(
        ((span_at(trace.spans, (s + e) // 2), (e - s) / 1e9) for s, e in gaps(ops0, lo, hi)),
        key=lambda kv: -kv[1],
    )[:top]
    return Reduction(
        window_s=(hi - lo) / 1e9,
        busy_s=busy / 1e9,
        solve_busy_s=solve_busy / 1e9,
        device_ops=totals,
        idle_gaps=idle,
    )
