"""Reduction of a profiler trace to the device's busy time and its gaps.

``jax.profiler.trace`` writes an XSpace file; ``ProfileData`` reads it.
On a TPU each chip is a plane ``/device:TPU:<n>`` whose line ``XLA Ops``
holds one event per operation run and whose line ``XLA Modules`` holds one
event per program run. The host's own annotations are on ``/host:CPU``.
All timestamps are in nanoseconds on one clock.

Busy time is the union of the operation intervals inside the window; the
idle share is one minus busy over the window. A gap is a stretch of the
window in which no operation runs. The functions below that take events
work in whatever unit the events are in.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, List, Sequence, Tuple

Event = Tuple[str, float, float]          # name, start_ns, end_ns

TPU_PLANE = "/device:TPU:"
HOST_PLANE = "/host:CPU"


def load(trace_dir: str):
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no profiler trace under {trace_dir}")
    return ProfileData.from_file(paths[-1])


def planes(profile, plane_prefixes: Sequence[str],
           lo: float = float("-inf"), hi: float = float("inf"),
           to_time=float) -> Dict[str, Dict[str, List[Event]]]:
    """Every line of the planes whose names start with one of
    ``plane_prefixes``: plane -> line -> events with a duration that
    overlap [lo, hi] (trace ns), clipped to it, their times mapped by
    ``to_time``."""
    out: Dict[str, Dict[str, List[Event]]] = {}
    for plane in profile.planes:
        if not plane.name.startswith(tuple(plane_prefixes)):
            continue
        lines: Dict[str, List[Event]] = {}
        for line in plane.lines:
            evs = []
            for e in line.events:
                s, d = float(e.start_ns), float(e.duration_ns)
                if d > 0 and s < hi and s + d > lo:
                    evs.append((e.name, to_time(max(s, lo)),
                                to_time(min(s + d, hi))))
            if evs:
                lines[line.name] = evs
        if lines:
            out[plane.name] = lines
    return out


def marker_ns(profile, name: str) -> float:
    """Start of the host annotation ``name`` (the clock's anchor)."""
    for plane in profile.planes:
        if not plane.name.startswith(HOST_PLANE):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == name:
                    return float(e.start_ns)
    raise KeyError(f"no host annotation {name!r} in the trace")


def clip(events: Sequence[Event], lo: float, hi: float) -> List[Event]:
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if e > lo and s < hi]


def merged(events: Sequence[Event]) -> List[Tuple[float, float]]:
    spans = sorted((s, e) for _, s, e in events)
    out: List[List[float]] = []
    for s, e in spans:
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy(events: Sequence[Event], lo: float, hi: float) -> float:
    return sum(e - s for s, e in merged(clip(events, lo, hi)))


def gaps(events: Sequence[Event], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    """Idle stretches of [lo, hi], longest first."""
    out, t = [], lo
    for s, e in merged(clip(events, lo, hi)):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return sorted(out, key=lambda g: g[0] - g[1])


def op_name(text: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``%fusion.12``."""
    return text.split(" = ", 1)[0]


def self_times(events: Sequence[Event]) -> List[Tuple[str, float]]:
    """(name, own time) per event: an operation that encloses others on
    its line (a loop around the layers) keeps only the time none of them
    covers, so that no time is counted twice."""
    out: List[Tuple[str, float]] = []
    stack: List[List] = []           # [name, start, end, child time]
    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and s >= stack[-1][2]:
            n0, s0, e0, c0 = stack.pop()
            out.append((n0, (e0 - s0) - c0))
        if stack:
            stack[-1][3] += e - s
        stack.append([name, s, e, 0.0])
    while stack:
        n0, s0, e0, c0 = stack.pop()
        out.append((n0, (e0 - s0) - c0))
    return out


def top_ops(events: Sequence[Event], lo: float, hi: float, n: int = 10
            ) -> List[List]:
    """[[op name, time], ...]: the operations whose own time was largest,
    summed over their runs."""
    tot: Dict[str, float] = {}
    for name, t in self_times(clip(events, lo, hi)):
        key = op_name(name)
        tot[key] = tot.get(key, 0.0) + t
    top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v] for k, v in top]
