"""Reduction of a JAX profiler trace to the numbers the metrics read.

A trace is read into plain ``Event`` records (plane, line, name, start and
duration in nanoseconds), so that the reduction can be checked on a
hand-built list. Device operations are the events on the ``XLA Ops`` line
of each ``/device:`` plane; the harness's own host spans are the
``jax.profiler.TraceAnnotation`` events whose names start with ``bench.``.
Host and device events share one clock in the profiler's output. On a
TPU the name of an ``XLA Ops`` event is the operation's whole HLO text
(``%filter_count.2 = s32[1,1]{...} custom-call(...)``); ``op_name`` cuts
it to the operation's name (``filter_count.2``).
"""
from __future__ import annotations

import bisect
import dataclasses
import pathlib
from typing import Iterable, Optional

HOST_PREFIX = "bench."
DEVICE_OPS_LINE = "XLA Ops"


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def load(trace_dir) -> list[Event]:
    """Every event of the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        return []
    data = ProfileData.from_file(str(files[-1]))
    out = []
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                out.append(Event(plane.name, line.name, ev.name,
                                 float(ev.start_ns), float(ev.duration_ns)))
    return out


def device_ops(events: Iterable[Event]) -> list[Event]:
    return [e for e in events if e.plane.startswith("/device:")
            and e.line == DEVICE_OPS_LINE]


def op_name(name: str) -> str:
    """The HLO operation's name, from a device event's name that is either
    that name or the operation's whole HLO text."""
    return name.split(" = ", 1)[0].strip().lstrip("%")


def host_spans(events: Iterable[Event]) -> list[Event]:
    return [e for e in events if not e.plane.startswith("/device:")
            and e.name.startswith(HOST_PREFIX)]


def union(intervals: Iterable[tuple[float, float]]) -> list[tuple[float, float]]:
    """Merge [start, end) intervals into disjoint sorted ones."""
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def busy_s(events: Iterable[Event]) -> Optional[float]:
    """Seconds in which some operation ran on a device, averaged over the
    devices that ran any; None where no device operation was traced."""
    per_plane: dict[str, list] = {}
    for e in device_ops(events):
        per_plane.setdefault(e.plane, []).append((e.start_ns, e.end_ns))
    if not per_plane:
        return None
    total = sum(sum(b - a for a, b in union(iv)) for iv in per_plane.values())
    return total / len(per_plane) / 1e9


def kernel_s(events: Iterable[Event], kernel: str) -> float:
    """Device seconds of ``kernel``'s own operations, summed over devices.
    A Pallas kernel is one custom call that XLA names after the jitted
    function around it (``merge_join_count.1``); the padding and copies
    that function does besides carry other names."""
    return sum(e.dur_ns for e in device_ops(events)
               if op_name(e.name).split(".")[0] == kernel) / 1e9


def _span_of(events: list[Event]):
    """A function from a device op to the name of the harness span (one
    call of the traffic, ``bench.expression.3``) open on the host when the
    op started, or None. A call ends with its answer on the host, so the
    device work it sends runs inside its span."""
    spans = sorted((s.start_ns, s.end_ns, s.name) for s in host_spans(events))
    starts = [s[0] for s in spans]

    def span_of(op: Event):
        i = bisect.bisect_right(starts, op.start_ns) - 1
        return spans[i][2] if i >= 0 and op.start_ns < spans[i][1] else None
    return span_of


def top_device_ops(events: Iterable[Event], n: int = 10) -> list[list]:
    """[[name, seconds], ...]: the device operations that took most time,
    each named ``<span>/<op>`` by the harness span it ran in, since XLA
    numbers ops (``fusion.1``) per program and every query's program
    has a ``fusion.1``."""
    events = list(events)
    span_of = _span_of(events)
    by_name: dict[str, float] = {}
    for e in device_ops(events):
        span = span_of(e)
        name = f"{span}/{op_name(e.name)}" if span else op_name(e.name)
        by_name[name] = by_name.get(name, 0.0) + e.dur_ns / 1e9
    return [[k, v] for k, v in sorted(by_name.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(events: Iterable[Event], n: int = 10) -> list[list]:
    """[[label, seconds], ...]: the longest stretches with no device
    operation between the first and the last harness span, each labelled
    by the innermost harness span that was open at its middle."""
    events = list(events)
    spans = host_spans(events)
    if not spans:
        return []
    lo = min(s.start_ns for s in spans)
    hi = max(s.end_ns for s in spans)
    busy = union((max(e.start_ns, lo), min(e.end_ns, hi))
                 for e in device_ops(events) if e.end_ns > lo and e.start_ns < hi)
    gaps, cursor = [], lo
    for s, e in busy:
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    if hi > cursor:
        gaps.append((cursor, hi))
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        mid = (s + e) / 2
        open_ = [sp for sp in spans if sp.start_ns <= mid < sp.end_ns]
        label = min(open_, key=lambda sp: sp.dur_ns).name if open_ \
            else "between harness spans"
        out.append([label, (e - s) / 1e9])
    return out
