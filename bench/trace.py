"""Reduction from a profiler trace to device busy time, idle gaps and
kernel time.

The JAX profiler writes ``<dir>/plugins/profile/<time>/*.xplane.pb``.
Each TPU is a plane ``/device:TPU:<n>``; its ``XLA Ops`` line holds one
event per operation run on the device and its ``XLA Modules`` line one
event per program run. A TPU plane without an ``XLA Ops`` line is an
error: no other line is read in its place. The benchmark's own host spans (``bench.<name>``
``TraceAnnotation``s) land on the host plane, on the same clock. Times
here are in seconds.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
SPAN_PREFIX = "bench."


class Trace:
    """Events of one trace: ``ops`` and ``modules`` as ``(name, start,
    end)`` on the device with the lowest id, ``spans`` the benchmark's
    host spans as ``(name, start, end)`` with the prefix dropped."""

    def __init__(self, ops, modules, spans):
        self.ops: List[tuple] = ops
        self.modules: List[tuple] = modules
        self.spans: List[tuple] = spans

    def window(self, span: str = "sweep") -> Optional[Interval]:
        """From the first ``span`` span's start to the last one's end."""
        hits = [(s, e) for n, s, e in self.spans if n == span]
        if not hits:
            return None
        return min(s for s, _ in hits), max(e for _, e in hits)


def find(directory: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(directory, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    return from_planes(ProfileData.from_file(path).planes, path)


def from_planes(planes, path: str = "") -> Trace:
    """The :class:`Trace` of a profile's planes (each with ``name`` and
    ``lines``; a line with ``name`` and ``events``; an event with
    ``name``, ``start_ns`` and ``duration_ns``)."""
    devices: Dict[int, dict] = {}
    spans = []
    for plane in planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m:
                dest = devices.setdefault(int(m.group(1)), {}).setdefault(
                    line.name, [])
            elif plane.name.startswith("/host:"):
                dest = None
            else:
                continue
            for ev in line.events:
                start = ev.start_ns * 1e-9
                end = start + ev.duration_ns * 1e-9
                if dest is not None:
                    dest.append((ev.name, start, end))
                elif ev.name.startswith(SPAN_PREFIX):
                    spans.append((ev.name[len(SPAN_PREFIX):], start, end))
    if not devices:
        return Trace([], [], spans)
    lines = devices[min(devices)]
    if "XLA Ops" not in lines:
        raise ValueError(f"trace {path}: /device:TPU:{min(devices)} has no "
                         f"'XLA Ops' line, only {sorted(lines)}")
    return Trace(lines["XLA Ops"], lines.get("XLA Modules", []), spans)


def clip(intervals: Sequence[Interval], window: Interval) -> List[Interval]:
    lo, hi = window
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Merge overlapping intervals into disjoint ones, in time order."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy(intervals: Sequence[Interval], window: Interval) -> float:
    """Seconds of ``window`` in which some interval runs."""
    return sum(e - s for s, e in union(clip(intervals, window)))


def gaps(intervals: Sequence[Interval], window: Interval) -> List[Interval]:
    """The idle stretches of ``window``: where no interval runs."""
    out, at = [], window[0]
    for s, e in union(clip(intervals, window)):
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if window[1] > at:
        out.append((at, window[1]))
    return out


def innermost(spans: Sequence[tuple]) -> List[tuple]:
    """Disjoint ``(start, end, name)`` pieces of properly nested spans
    (one thread's), in time order: each instant goes to the innermost
    span open at it; instants outside every span are left out."""
    out: List[tuple] = []
    stack: List[tuple] = []
    at = float("-inf")

    def close_until(t: float) -> None:
        nonlocal at
        while stack and stack[-1][2] <= t:
            name, _, end = stack.pop()
            if end > at:
                out.append((at, end, name))
                at = end

    for name, s, e in sorted(spans, key=lambda sp: (sp[1], -sp[2])):
        close_until(s)
        if stack and s > at:
            out.append((at, s, stack[-1][0]))
        stack.append((name, s, e))
        at = s
    close_until(float("inf"))
    return out


def attribute(idle: Sequence[Interval], spans: Sequence[tuple],
              outside: str = "host_other") -> Dict[str, float]:
    """Idle seconds by what the host was doing: each piece of a gap goes
    to the innermost host span open over it, ``outside`` where none is."""
    pieces = innermost(spans)
    out: Dict[str, float] = {}
    j = 0
    for s, e in sorted(idle):
        rest = e - s
        while j < len(pieces) and pieces[j][1] <= s:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < e:
            ov = min(e, pieces[k][1]) - max(s, pieces[k][0])
            if ov > 0.0:
                name = pieces[k][2]
                out[name] = out.get(name, 0.0) + ov
                rest -= ov
            k += 1
        if rest > 1e-12 * max(1.0, e):
            out[outside] = out.get(outside, 0.0) + rest
    return out


def op_seconds(events: Sequence[tuple], window: Optional[Interval] = None
               ) -> Dict[str, float]:
    """Device seconds per operation name (inside ``window`` if given)."""
    out: Dict[str, float] = {}
    for name, s, e in events:
        if window is not None:
            s, e = max(s, window[0]), min(e, window[1])
            if e <= s:
                continue
        out[name] = out.get(name, 0.0) + (e - s)
    return out


def top(totals: Dict[str, float], n: int = 10) -> List[list]:
    return [[k, v] for k, v in sorted(totals.items(),
                                      key=lambda kv: -kv[1])[:n]]
