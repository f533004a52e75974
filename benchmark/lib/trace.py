"""From a profiler trace to numbers: which planes are devices, the union
of the intervals in which an operation ran, device time of a program by
name, and what the host was doing in each idle gap.

``load`` turns an ``.xplane.pb`` into plain lists (so the reduction can
be tested on a recorded trace without the profiler); everything else
works on those lists. Times are seconds on the trace's own clock.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

#: Planes that are chips, as the TPU runtime names them.
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
#: The line of a device plane that holds whole programs (one event per
#: execution of a jitted function), and the line that holds single ops.
MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"
#: Host spans the benchmark writes (``lib/spans.py``) start with this.
SPAN_PREFIX = "bench."

Event = Tuple[str, float, float]  # name, start_s, duration_s


def newest_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def load(path: str, keep_host: str = SPAN_PREFIX) -> Dict:
    """``{"devices": {plane: {line: [Event]}}, "host": [Event]}``. Of the
    host planes only events whose name starts with ``keep_host`` are kept
    (a host plane holds every Python call the profiler saw)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[str, Dict[str, List[Event]]] = {}
    host: List[Event] = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = devices.setdefault(plane.name, {})
            for line in plane.lines:
                lines[line.name] = [
                    (ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9)
                    for ev in line.events
                ]
        else:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(keep_host):
                        host.append((ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9))
    host.sort(key=lambda e: e[1])
    return {"devices": devices, "host": host}


def program_name(event_name: str) -> str:
    """``jit__als_iteration(1234567)`` -> ``jit__als_iteration``."""
    return re.sub(r"\(\d+\)$", "", event_name)


def union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merged (start, end) intervals, in order."""
    merged: List[List[float]] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(s, e) for s, e in merged]


def busy_intervals(lines: Dict[str, List[Event]]) -> List[Tuple[float, float]]:
    """When an operation ran on one device: the union of its op events
    (of its program events where the trace has no op line)."""
    events = lines.get(OP_LINE) or lines.get(MODULE_LINE) or []
    return union([(s, s + d) for _, s, d in events if d > 0])


def clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def window_of(trace: Dict) -> Tuple[float, float]:
    """The traced window: the ``bench.window`` span where the run wrote
    one, else from the first to the last device event."""
    for name, start, dur in trace["host"]:
        if name == SPAN_PREFIX + "window":
            return start, start + dur
    edges = [
        edge
        for lines in trace["devices"].values()
        for s, e in busy_intervals(lines)
        for edge in (s, e)
    ]
    if not edges:
        raise ValueError("the trace holds no device event")
    return min(edges), max(edges)


def busy_seconds(trace: Dict) -> Tuple[float, float]:
    """(busy seconds averaged over the devices that ran anything, window
    seconds)."""
    lo, hi = window_of(trace)
    per_device = [
        sum(e - s for s, e in clip(busy_intervals(lines), lo, hi))
        for lines in trace["devices"].values()
    ]
    per_device = [b for b in per_device if b > 0]
    if not per_device:
        raise ValueError("no operation ran on a device inside the window")
    return sum(per_device) / len(per_device), hi - lo


def program_events(trace: Dict, pattern: str) -> List[Event]:
    """Executions of the programs whose name matches ``pattern``, on the
    first device, inside the window, in order."""
    lo, hi = window_of(trace)
    rx = re.compile(pattern)
    for _, lines in sorted(trace["devices"].items()):
        found = [
            ev
            for ev in lines.get(MODULE_LINE, [])
            if rx.search(program_name(ev[0])) and ev[1] >= lo and ev[1] + ev[2] <= hi
        ]
        if found:
            return sorted(found, key=lambda e: e[1])
    return []


def enclosing_span(trace: Dict, pattern: str, events: Sequence[Event]) -> List[Optional[str]]:
    """For each device event the host span (name matching ``pattern``)
    inside which it started; where spans overlap, the earliest span not
    yet given away (dispatches reach the device in the order they are
    made)."""
    rx = re.compile(pattern)
    spans = [s for s in trace["host"] if rx.search(s[0])]
    taken = set()
    out: List[Optional[str]] = []
    for _, start, _ in events:
        pick = None
        for n, (name, s, d) in enumerate(spans):
            if s > start:
                break
            if n not in taken and s <= start <= s + d:
                pick = n
                break
        if pick is not None:
            taken.add(pick)
        out.append(spans[pick][0] if pick is not None else None)
    return out


def device_ops(trace: Dict, top: int = 10) -> List[List]:
    """The operations that took most device time inside the window,
    summed by name over the first device."""
    lo, hi = window_of(trace)
    for _, lines in sorted(trace["devices"].items()):
        totals: Dict[str, float] = {}
        for name, s, d in lines.get(OP_LINE) or lines.get(MODULE_LINE) or []:
            if s >= lo and s + d <= hi:
                totals[name] = totals.get(name, 0.0) + d
        if totals:
            ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:top]
            return [[name, seconds] for name, seconds in ranked]
    return []


def idle_gaps(trace: Dict, top: int = 10, short_s: float = 20e-6) -> List[List]:
    """Idle seconds of the first device inside the window, put down to
    what the host was doing: each gap is cut at the edges of the
    benchmark's host spans, and each piece goes to the innermost
    (shortest) span that covers it, or to ``(no span)``: the server
    waiting for a request, or host code the benchmark wraps no span
    around. Gaps under ``short_s`` are summed as ``(between ops)``: the
    device's own turn-around, nobody's wait."""
    lo, hi = window_of(trace)
    spans = [s for s in trace["host"] if s[0] != SPAN_PREFIX + "window"]
    starts = [s[1] for s in spans]
    longest = max([s[2] for s in spans], default=0.0)
    totals: Dict[str, float] = {}

    def add(name: str, seconds: float) -> None:
        totals[name] = totals.get(name, 0.0) + seconds

    for _, lines in sorted(trace["devices"].items()):
        busy = clip(busy_intervals(lines), lo, hi)
        if not busy:
            continue
        edges = [lo] + [t for s, e in busy for t in (s, e)] + [hi]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 - g0 <= 0:
                continue
            if g1 - g0 < short_s:
                add("(between ops)", g1 - g0)
                continue
            over = []
            j = bisect.bisect_left(starts, g1) - 1
            while j >= 0 and starts[j] >= g0 - longest:
                name, s, d = spans[j]
                if s + d > g0:
                    over.append((d, name, max(s, g0), min(s + d, g1)))
                j -= 1
            cuts = sorted({g0, g1, *(t for _, _, a, b in over for t in (a, b))})
            for c0, c1 in zip(cuts, cuts[1:]):
                inside = [(d, name) for d, name, a, b in over if a <= c0 and b >= c1]
                add(min(inside)[1] if inside else "(no span)", c1 - c0)
        break
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:top]
    return [[name, seconds] for name, seconds in ranked]
