"""Reduction of a ``jax.profiler`` trace to device intervals.

The profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``;
``jax.profiler.ProfileData`` reads it into planes (one per device, one
for the host's threads), lines and events with a start and a duration in
nanoseconds. On a TPU each device plane ``/device:TPU:<i>`` holds an
``XLA Modules`` line (one event per executable run, named after the
jitted function) and an ``XLA Ops`` line (one event per HLO op).
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all", re.IGNORECASE)
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start: float          # ns
    end: float            # ns


def load(logdir: str) -> list[Event]:
    """Every event of the newest trace under ``logdir``."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    out = []
    for plane in ProfileData.from_file(files[-1]).planes:
        for line in plane.lines:
            for e in line.events:
                out.append(Event(plane.name, line.name, e.name,
                                 float(e.start_ns),
                                 float(e.start_ns + e.duration_ns)))
    return out


def select(events, *, plane=None, line=None, name=None) -> list[Event]:
    """Events whose plane starts with ``plane``, whose line is ``line``
    (or matches it, for a compiled pattern) and whose name matches the
    pattern ``name``; ``None`` matches anything."""
    def ok(v, want, prefix=False):
        if want is None:
            return True
        if isinstance(want, re.Pattern):
            return want.search(v) is not None
        return v.startswith(want) if prefix else v == want

    pat = re.compile(name) if isinstance(name, str) else name
    return [e for e in events if ok(e.plane, plane, prefix=True)
            and ok(e.line, line) and ok(e.name, pat)]


def planes(events, prefix: str = DEVICE_PREFIX) -> list[str]:
    return sorted({e.plane for e in events if e.plane.startswith(prefix)})


def merge(intervals) -> list[tuple[float, float]]:
    """Union of (start, end) intervals as sorted disjoint intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def total(intervals) -> float:
    return float(sum(e - s for s, e in merge(intervals)))


def gaps(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Stretches of [lo, hi] that no interval covers."""
    out, t = [], lo
    for s, e in merge(clip(intervals, lo, hi)):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def inside(events, spans) -> list[Event]:
    """Events that overlap any of the (start, end) ``spans``."""
    spans = merge(spans)
    return [e for e in events
            if any(e.end > s and e.start < t for s, t in spans)]


def by_name(events) -> dict[str, float]:
    """Total ns per event name."""
    out: dict[str, float] = {}
    for e in events:
        out[e.name] = out.get(e.name, 0.0) + (e.end - e.start)
    return out


def label(gap: tuple[float, float], host_events) -> str:
    """The host event that overlaps ``gap`` most (the shorter one on a
    tie): what the host was doing while the device idled."""
    best, key = "unlabelled", None
    for e in host_events:
        ov = min(gap[1], e.end) - max(gap[0], e.start)
        if ov <= 0:
            continue
        k = (ov, -(e.end - e.start))
        if key is None or k > key:
            best, key = e.name, k
    return best
