"""Reduction of a JAX profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

A trace holds planes; each plane holds lines of events with a start and a
duration in nanoseconds and a few stats.  A TPU's plane is named
``/device:TPU:<n>``; its ``XLA Ops`` line holds every operation the chip
ran and its ``XLA Modules`` line one event per program run.  The host's
planes hold the benchmark's own annotations (``bench.window`` around the
measured window, ``bench.fit`` around each fit) and the runtime's host
events.  Everything is clipped to the ``bench.window`` annotation.
"""

from __future__ import annotations

import bisect
import dataclasses
import re
from typing import NamedTuple

WINDOW = "bench.window"
FIT = "bench.fit"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_TPU = re.compile(r"^/device:TPU:(\d+)$")


class Event(NamedTuple):
    name: str
    start: float  # ns
    end: float  # ns
    stats: dict

    @property
    def duration(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Plane:
    name: str
    lines: dict  # line name -> list[Event]


def load(path) -> list[Plane]:
    """Read an ``.xplane.pb`` with JAX's own reader.  Stats are kept for
    device events only: a host plane can hold a million events."""
    from jax.profiler import ProfileData

    planes, none = [], {}
    for p in ProfileData.from_file(str(path)).planes:
        lines: dict = {}
        device = p.name.startswith("/device:")
        for line in p.lines:
            lines.setdefault(line.name, []).extend(
                Event(
                    e.name, e.start_ns, e.start_ns + e.duration_ns,
                    dict(e.stats) if device else none,
                )
                for e in line.events
            )
        planes.append(Plane(p.name, lines))
    return planes


def device_planes(planes, count: int | None = None) -> list[Plane]:
    """TPU planes in device order, the first ``count`` of them."""
    tpus = sorted(
        (int(m.group(1)), p) for p in planes if (m := _TPU.match(p.name))
    )
    found = [p for _, p in tpus]
    return found if count is None else found[:count]


def host_events(planes) -> list[Event]:
    """Every event on a host plane, all threads together."""
    return [
        e
        for p in planes
        if p.name.startswith("/host:")
        for events in p.lines.values()
        for e in events
    ]


def window(planes) -> tuple[float, float]:
    """``(start, end)`` of the ``bench.window`` annotation, in ns."""
    marks = [e for e in host_events(planes) if e.name == WINDOW]
    if len(marks) != 1:
        raise ValueError(f"expected one {WINDOW!r} annotation, found {len(marks)}")
    return marks[0].start, marks[0].end


def fits(planes, win) -> int:
    """Whole fits inside the window."""
    lo, hi = win
    return sum(
        1 for e in host_events(planes)
        if e.name == FIT and e.start >= lo and e.end <= hi
    )


def _clip(events, win):
    lo, hi = win
    for e in events:
        s, t = max(e.start, lo), min(e.end, hi)
        if t > s:
            yield s, t, e


def union(intervals) -> list[tuple[float, float]]:
    """Merge ``(start, end)`` pairs into disjoint sorted intervals."""
    merged: list = []
    for s, t in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], t)
        else:
            merged.append([s, t])
    return [(s, t) for s, t in merged]


def busy_intervals(plane: Plane, win) -> list[tuple[float, float]]:
    """Disjoint intervals in which some operation ran on this chip."""
    return union((s, t) for s, t, _ in _clip(plane.lines.get(OPS_LINE, []), win))


def busy_seconds(plane: Plane, win) -> float:
    return sum(t - s for s, t in busy_intervals(plane, win)) * 1e-9


def module_name(event: Event) -> str:
    """A program's name without the run id the trace appends."""
    return event.name.split("(")[0]


def module_seconds(plane: Plane, names, win) -> float:
    """Device seconds of the runs of the named programs."""
    names = set(names)
    return sum(
        t - s
        for s, t, e in _clip(plane.lines.get(MODULES_LINE, []), win)
        if module_name(e) in names
    ) * 1e-9


def is_collective(event: Event) -> bool:
    text = " ".join(
        [event.name, str(event.stats.get("hlo_category", "")),
         str(event.stats.get("long_name", ""))]
    ).lower()
    return "all-reduce" in text or "allreduce" in text


def collective_seconds(plane: Plane, win) -> float:
    """Device seconds of all-reduce operations on this chip."""
    return sum(
        t - s
        for s, t, e in _clip(plane.lines.get(OPS_LINE, []), win)
        if is_collective(e)
    ) * 1e-9


def top_ops(devices, win, k: int = 10) -> list[list]:
    """``[name, seconds]`` of the operations that took most device time,
    averaged over the chips."""
    totals: dict = {}
    for plane in devices:
        for s, t, e in _clip(plane.lines.get(OPS_LINE, []), win):
            totals[e.name] = totals.get(e.name, 0.0) + (t - s) * 1e-9
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:k]
    return [[name, sec / max(len(devices), 1)] for name, sec in ranked]


def idle_gaps(plane: Plane, hosts, win, samples=(), k: int = 10) -> list[list]:
    """``[host activity, seconds]``: idle time of one chip, grouped by the
    innermost host event open at the middle of each gap, longest first.

    Where that event is only the fit itself, or there is none, the gap is
    named by the nearest of ``samples``, ``(ns after the window's start,
    Python function)`` pairs taken from the host's main thread, when one
    lies within 10 ms."""
    lo, hi = win
    times = [lo + t for t, _ in samples]
    busy = busy_intervals(plane, win)
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    spans = [
        (edges[i], edges[i + 1])
        for i in range(0, len(edges), 2)
        if edges[i + 1] > edges[i]
    ]
    pending = sorted(
        (e for e in hosts if e.name != WINDOW), key=lambda e: e.start
    )
    i, active, totals = 0, [], {}
    for s, t in spans:  # in time order: one sweep over the host events
        mid = 0.5 * (s + t)
        while i < len(pending) and pending[i].start <= mid:
            active.append(pending[i])
            i += 1
        active = [e for e in active if e.end > mid]
        label = (
            min(active, key=lambda e: e.duration).name
            if active
            else "(no host event)"
        )
        if label in (FIT, "(no host event)") and times:
            j = bisect.bisect_left(times, mid)
            near = min(
                (c for c in (j - 1, j) if 0 <= c < len(times)),
                key=lambda c: abs(times[c] - mid),
            )
            if abs(times[near] - mid) <= 10e6:
                label = "python: " + samples[near][1]
        totals[label] = totals.get(label, 0.0) + (t - s) * 1e-9
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:k]
    return [[name, sec] for name, sec in ranked]


@dataclasses.dataclass
class Summary:
    """What the metric readers see of one traced window."""

    window_s: float
    fits: int
    chips: int
    busy_s: list  # per chip
    accumulate_s: list  # per chip
    collective_s: list  # per chip
    top_ops: list
    idle_gaps: list


def summarize(
    planes, chips: int, accumulate_modules, samples=()
) -> Summary | None:
    """Reduce a trace; None where it holds no TPU plane.  ``samples`` as
    for :func:`idle_gaps`."""
    devices = device_planes(planes, chips)
    if not devices:
        return None
    win = window(planes)
    hosts = host_events(planes)
    return Summary(
        window_s=(win[1] - win[0]) * 1e-9,
        fits=fits(planes, win),
        chips=len(devices),
        busy_s=[busy_seconds(p, win) for p in devices],
        accumulate_s=[module_seconds(p, accumulate_modules, win) for p in devices],
        collective_s=[collective_seconds(p, win) for p in devices],
        top_ops=top_ops(devices, win),
        idle_gaps=idle_gaps(devices[0], hosts, win, samples),
    )
