"""From one profiler trace to device busy time, per program.

The traced sub-window is a ``jax.profiler.TraceAnnotation`` that
``run.py`` opens and closes; its bounds are read from the host plane of
the same trace, so they share the device events' clock.  On each TPU
plane (``/device:TPU:<n>``) only the op line (``XLA Ops``) is read:

- busy is the union of the op events' intervals, clipped to the window,
  never a sum, so it cannot exceed the window;
- a program's device time is the part of that union that falls inside
  the program's own events on the module line (``XLA Modules``);
- idle gaps are the holes in the union, each named after the host event
  that overlaps it most.

A window that holds no device op raises :class:`NoDeviceWork`: the
traffic did not reach the device, and that is not a zero.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, List, Sequence, Tuple

WINDOW_ANNOTATION = "chipbench.window"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = "/host:CPU"
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"

Intervals = List[Tuple[float, float]]


class TraceError(RuntimeError):
    """The trace lacks the window annotation or a device plane."""


class NoDeviceWork(TraceError):
    """The traced window holds no device op."""


def union(spans: Sequence[Tuple[float, float]]) -> Intervals:
    """Sorted, disjoint intervals covering exactly the given ones."""
    out: Intervals = []
    for a, b in sorted(s for s in spans if s[1] > s[0]):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clip(spans: Intervals, lo: float, hi: float) -> Intervals:
    return [(max(a, lo), min(b, hi)) for a, b in spans if b > lo and a < hi]


def intersect(x: Intervals, y: Intervals) -> Intervals:
    """Intersection of two disjoint sorted interval lists."""
    out: Intervals = []
    i = j = 0
    while i < len(x) and j < len(y):
        a, b = max(x[i][0], y[j][0]), min(x[i][1], y[j][1])
        if b > a:
            out.append((a, b))
        if x[i][1] < y[j][1]:
            i += 1
        else:
            j += 1
    return out


def length(spans: Intervals) -> float:
    return sum(b - a for a, b in spans)


def holes(spans: Intervals, lo: float, hi: float) -> Intervals:
    out: Intervals = []
    t = lo
    for a, b in spans:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def _events(line) -> List[Tuple[str, float, float]]:
    return [(e.name, float(e.start_ns), float(e.start_ns) + float(e.duration_ns))
            for e in line.events]


@dataclass
class Reduction:
    window_s: float
    busy_s: float
    devices: int
    program_s: Dict[str, float] = field(default_factory=dict)
    idle_gaps: List[Tuple[str, float]] = field(default_factory=list)

    def top_programs(self, n: int = 10) -> List[list]:
        ranked = sorted(self.program_s.items(), key=lambda kv: -kv[1])
        return [[name, s] for name, s in ranked[:n]]


def find_window(planes, annotation: str = WINDOW_ANNOTATION) -> Tuple[float, float]:
    for plane in planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            for name, a, b in _events(line):
                if name == annotation:
                    return a, b
    raise TraceError(f"no {annotation!r} event on the host plane")


def reduce(planes, annotation: str = WINDOW_ANNOTATION,
           gaps: int = 10) -> Reduction:
    """Busy time, per-program device time and the longest idle gaps of
    the annotated window; see the module docstring."""
    planes = list(planes)
    lo, hi = find_window(planes, annotation)
    devices = [p for p in planes if DEVICE_PLANE.match(p.name)]
    if not devices:
        raise TraceError("no /device:TPU:<n> plane in the trace")
    busy_total = 0.0
    programs: Dict[str, float] = {}
    first_ops: Intervals = []
    for k, plane in enumerate(devices):
        lines = {line.name: line for line in plane.lines}
        ops = clip(union([(a, b) for _, a, b in _events(lines[OP_LINE])]
                         if OP_LINE in lines else []), lo, hi)
        busy_total += length(ops)
        if k == 0:
            first_ops = ops
        if MODULE_LINE in lines:
            by_name: Dict[str, list] = {}
            for name, a, b in _events(lines[MODULE_LINE]):
                by_name.setdefault(name, []).append((a, b))
            for name, spans in by_name.items():
                t = length(intersect(ops, clip(union(spans), lo, hi)))
                if t > 0:
                    programs[name] = programs.get(name, 0.0) + t / 1e9
    if busy_total <= 0:
        raise NoDeviceWork(
            f"no device op in the traced window of {(hi - lo) / 1e9:.3f}s: "
            "the traffic did not reach the device")
    n = len(devices)
    programs = {name: s / n for name, s in programs.items()}
    return Reduction(window_s=(hi - lo) / 1e9, busy_s=busy_total / n / 1e9,
                     devices=n, program_s=programs,
                     idle_gaps=_name_gaps(planes, holes(first_ops, lo, hi),
                                          annotation, gaps))


def _name_gaps(planes, idle: Intervals, annotation: str,
               n: int) -> List[Tuple[str, float]]:
    """The ``n`` longest idle gaps, each named after the host event that
    overlaps it most (the window annotation itself excluded)."""
    longest = sorted(idle, key=lambda g: g[0] - g[1])[:n]
    host = [ev for p in planes if p.name == HOST_PLANE
            for line in p.lines for ev in _events(line)
            if ev[0] != annotation]
    out = []
    for a, b in longest:
        best, best_t = "no host event", 0.0
        for name, ea, eb in host:
            t = min(b, eb) - max(a, ea)
            if t > best_t:
                best, best_t = name, t
        out.append((best, (b - a) / 1e9))
    return out


def load(path: str):
    """The planes of one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    return ProfileData.from_file(path).planes
