"""Reduce a JAX profiler trace (``*.xplane.pb``) to device busy time, op
totals, collective time and idle gaps.

A trace holds planes. Each TPU chip is a plane named ``/device:TPU:<n>``
whose ``XLA Ops`` line has one event per operation that ran on the chip;
host threads are lines of the ``/host:CPU`` plane, where the benchmark's
``jax.profiler.TraceAnnotation`` spans (``bench.window``) and JAX's own
dispatch events sit. All times here are in nanoseconds on the trace's
clock, and every quantity is clipped to a window ``[lo, hi)``.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, NamedTuple, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
# collective operations as XLA names them in the trace (all-reduce.3,
# all-reduce-start.1, all-reduce-done, fusion names keep the prefix)
ALL_REDUCE = re.compile(r"all-reduce")


class Event(NamedTuple):
    name: str
    start: float
    end: float


class Trace(NamedTuple):
    devices: Dict[int, List[Event]]  # chip id -> its ops, by start time
    host: List[Event]  # every host thread's events, by start time


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no *.xplane.pb under {log_dir}")
    return found[-1]


def from_profile(data) -> Trace:
    """``jax.profiler.ProfileData`` -> ``Trace``."""
    devices: Dict[int, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            # an op's event is named by its whole HLO instruction
            # ("%fusion.3 = f32[...] fusion(...)"); keep the instruction name
            ops = [Event(e.name.split(" = ", 1)[0], e.start_ns, e.end_ns)
                   for line in plane.lines if line.name == OPS_LINE
                   for e in line.events]
            devices[int(m.group(1))] = sorted(ops, key=lambda e: e.start)
        elif plane.name == HOST_PLANE:
            host.extend(Event(e.name, e.start_ns, e.end_ns)
                        for line in plane.lines for e in line.events)
    host.sort(key=lambda e: e.start)
    return Trace(devices, host)


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    return from_profile(ProfileData.from_file(path))


def span(trace: Trace, name: str) -> Optional[Tuple[float, float]]:
    """(start, end) of the longest host event called ``name``."""
    hits = [e for e in trace.host if e.name == name]
    if not hits:
        return None
    e = max(hits, key=lambda e: e.end - e.start)
    return e.start, e.end


def _clip(events: Iterable[Event], lo: float, hi: float) -> List[Tuple[float, float]]:
    return [(max(e.start, lo), min(e.end, hi)) for e in events
            if e.end > lo and e.start < hi]


def merged(events: Iterable[Event], lo: float, hi: float) -> List[Tuple[float, float]]:
    """The union of the events' intervals inside [lo, hi), as disjoint
    sorted intervals."""
    out: List[List[float]] = []
    for s, e in sorted(_clip(events, lo, hi)):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(events: Iterable[Event], lo: float, hi: float) -> float:
    return sum(e - s for s, e in merged(events, lo, hi))


def self_times(events: List[Event], lo: float, hi: float) -> Dict[str, float]:
    """Per op name, the time inside [lo, hi) in which it was the innermost
    op running: the one that started last.

    Ops on one line nest (a loop's body inside the loop) and on a TPU also
    overlap without nesting, so a plain sum would count some instants
    twice; these self times add up to ``busy_ns`` exactly.
    """
    clipped = sorted((s, e, ev.name) for ev in events
                     for s, e in _clip([ev], lo, hi))
    points = sorted({t for s, e, _ in clipped for t in (s, e)})
    totals: Dict[str, float] = defaultdict(float)
    active: List[Tuple[float, float, str]] = []
    i = 0
    for a, b in zip(points, points[1:]):
        while i < len(clipped) and clipped[i][0] <= a:
            active.append(clipped[i])
            i += 1
        active = [ev for ev in active if ev[1] > a]
        if active:
            s, e, name = max(active, key=lambda ev: (ev[0], -ev[1]))
            totals[name] += b - a
    return dict(totals)


def matching_ns(events: Iterable[Event], lo: float, hi: float,
                pattern: re.Pattern = ALL_REDUCE) -> float:
    """Union of the time inside [lo, hi) of ops whose name matches."""
    return busy_ns((e for e in events if pattern.search(e.name)), lo, hi)


def gaps(events: Iterable[Event], lo: float, hi: float) -> List[Tuple[float, float]]:
    """The idle intervals of one device inside [lo, hi)."""
    out, t = [], lo
    for s, e in merged(events, lo, hi):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def host_activity(trace: Trace, lo: float, hi: float,
                  skip: Tuple[str, ...] = ()) -> str:
    """What the host was doing in [lo, hi): the innermost host event that
    covers most of the interval."""
    best, best_key = "no host event", (0.0, 0.0)
    for e in trace.host:
        if e.start >= hi:
            break
        if e.end <= lo or e.name in skip:
            continue
        overlap = min(e.end, hi) - max(e.start, lo)
        key = (overlap, -(e.end - e.start))
        if key > best_key:
            best, best_key = e.name, key
    return best


def longest_gaps(trace: Trace, lo: float, hi: float, top: int = 10,
                 skip: Tuple[str, ...] = ()) -> List[Tuple[str, float]]:
    """The ``top`` longest device idle gaps, each named by the host event
    that overlaps it most (prefixed with the chip where there are several)."""
    found = []
    for dev, events in sorted(trace.devices.items()):
        for s, e in gaps(events, lo, hi):
            found.append((e - s, dev, s, e))
    found.sort(reverse=True)
    several = len(trace.devices) > 1
    out = []
    for length, dev, s, e in found[:top]:
        name = host_activity(trace, s, e, skip)
        out.append((f"tpu{dev}: {name}" if several else name, length * 1e-9))
    return out


def top_ops(trace: Trace, lo: float, hi: float, top: int = 10) -> List[Tuple[str, float]]:
    """Device ops by self time inside [lo, hi), in seconds averaged over the
    chips."""
    totals: Dict[str, float] = defaultdict(float)
    for events in trace.devices.values():
        for name, ns in self_times(events, lo, hi).items():
            totals[name] += ns
    n = max(len(trace.devices), 1)
    ranked = sorted(totals.items(), key=lambda kv: kv[1], reverse=True)[:top]
    return [(name, ns * 1e-9 / n) for name, ns in ranked]
