"""The program's own phases in a profiler trace.

With telemetry on, every span of the program's registry also opens a
profiler annotation ``repro/<span>`` (``session.query``, ``session.bind``,
``session.execute`` and its ``.run``, ``.gather``, ``.dispatch`` and
``.wait``, ``session.fetch``, ...). They land on the host planes, on the
one clock the trace shares with the harness's call spans (``bench.``) and
the device's operations, so device idle time can be put down to what the
program was doing. A program without these annotations yields no program
spans, and every reader here then returns None or keeps the harness's own
labels.
"""
from __future__ import annotations

import bisect
from typing import Iterable, Optional

from bench.harness import trace as tr

PROGRAM_PREFIX = "repro/"
MODULES_LINE = "XLA Modules"
OUTSIDE = "outside program spans"


def program_spans(events: Iterable[tr.Event]) -> list[tr.Event]:
    """The host events named ``repro/...``: the program's spans."""
    return [e for e in events if not e.plane.startswith("/device:")
            and e.name.startswith(PROGRAM_PREFIX)]


def phase(span: tr.Event) -> str:
    """A program span's name without the prefix (``session.fetch``)."""
    return span.name[len(PROGRAM_PREFIX):]


def _innermost(spans: list[tr.Event], t: float) -> Optional[tr.Event]:
    open_ = [s for s in spans if s.start_ns <= t < s.end_ns]
    return min(open_, key=lambda s: s.dur_ns) if open_ else None


def _gaps(events: list[tr.Event]) -> list[tuple[float, float]]:
    """Stretches with no device operation between the first and the last
    harness span, as ``trace.idle_gaps`` finds them."""
    spans = tr.host_spans(events)
    if not spans:
        return []
    lo = min(s.start_ns for s in spans)
    hi = max(s.end_ns for s in spans)
    busy = tr.union((max(e.start_ns, lo), min(e.end_ns, hi))
                    for e in tr.device_ops(events)
                    if e.end_ns > lo and e.start_ns < hi)
    gaps, cursor = [], lo
    for s, e in busy:
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    if hi > cursor:
        gaps.append((cursor, hi))
    return gaps


def idle_gaps(events: Iterable[tr.Event], n: int = 10) -> list[list]:
    """``trace.idle_gaps`` with the program's phase in the label: a gap is
    ``<harness span>/<innermost program span>`` where a program span is
    open at its middle (``bench.expression.9/session.fetch``), else the
    label ``trace.idle_gaps`` gives."""
    events = list(events)
    harness, program = tr.host_spans(events), program_spans(events)
    out = []
    for s, e in sorted(_gaps(events), key=lambda g: g[0] - g[1])[:n]:
        mid = (s + e) / 2
        call = _innermost(harness, mid)
        label = call.name if call else "between harness spans"
        inner = _innermost(program, mid)
        if inner is not None:
            label = f"{label}/{phase(inner)}"
        out.append([label, (e - s) / 1e9])
    return out


def _phase_segments(spans: list[tr.Event]) -> list[tuple[float, float, str]]:
    """Disjoint [start, end) stretches, each with the innermost of the
    (nested) program spans open over it."""
    segs: list[tuple[float, float, str]] = []
    stack: list[tr.Event] = []
    cursor = float("-inf")

    def advance(t: float) -> None:
        nonlocal cursor
        while stack and stack[-1].end_ns <= t:
            top = stack.pop()
            if top.end_ns > cursor:
                segs.append((cursor, top.end_ns, phase(top)))
                cursor = top.end_ns
        if stack and t > cursor:
            segs.append((cursor, t, phase(stack[-1])))
        cursor = max(cursor, t)

    for sp in sorted(spans, key=lambda s: (s.start_ns, -s.dur_ns)):
        advance(sp.start_ns)
        stack.append(sp)
    advance(float("inf"))
    return segs


def idle_by_phase(events: Iterable[tr.Event]) -> dict[str, float]:
    """Seconds of device idle time between the first and the last harness
    span, split by the innermost program span open over each instant;
    idle time under no program span is under ``OUTSIDE``."""
    events = list(events)
    gaps = _gaps(events)
    segs = _phase_segments(program_spans(events))
    out: dict[str, float] = {}
    j = 0
    for gs, ge in gaps:
        covered = 0.0
        while j < len(segs) and segs[j][1] <= gs:
            j += 1
        k = j
        while k < len(segs) and segs[k][0] < ge:
            s, e, name = segs[k]
            d = min(e, ge) - max(s, gs)
            if d > 0:
                out[name] = out.get(name, 0.0) + d / 1e9
                covered += d
            k += 1
        if ge - gs > covered:
            out[OUTSIDE] = out.get(OUTSIDE, 0.0) + (ge - gs - covered) / 1e9
    return out


def _module_of(events: list[tr.Event]):
    """A function from a device op to the name of the XLA module that ran
    it (``jit_aframe_scalar_1a2b3c4d``), from its plane's ``XLA Modules``
    line, or None."""
    per_plane: dict[str, list] = {}
    for e in events:
        if e.plane.startswith("/device:") and e.line == MODULES_LINE:
            per_plane.setdefault(e.plane, []).append(
                (e.start_ns, e.end_ns, e.name.split("(", 1)[0]))
    for mods in per_plane.values():
        mods.sort()
    starts = {p: [m[0] for m in mods] for p, mods in per_plane.items()}

    def module_of(op: tr.Event):
        mods = per_plane.get(op.plane)
        if not mods:
            return None
        i = bisect.bisect_right(starts[op.plane], op.start_ns) - 1
        return mods[i][2] if i >= 0 and op.start_ns < mods[i][1] else None
    return module_of


def top_device_ops(events: Iterable[tr.Event], n: int = 10) -> list[list]:
    """``trace.top_device_ops``, with an op that starts outside every
    harness span named ``<XLA module>/<op>`` instead of by the bare op."""
    events = list(events)
    span_of = tr._span_of(events)
    module_of = _module_of(events)
    by_name: dict[str, float] = {}
    for e in tr.device_ops(events):
        where = span_of(e) or module_of(e)
        op = tr.op_name(e.name)
        name = f"{where}/{op}" if where else op
        by_name[name] = by_name.get(name, 0.0) + e.dur_ns / 1e9
    return [[k, v] for k, v in
            sorted(by_name.items(), key=lambda kv: -kv[1])[:n]]


# -- per-layer readers ---------------------------------------------------------


def frontend_ms_per_query(events: Iterable[tr.Event]) -> Optional[float]:
    """Mean over the traced calls of the harness's call span minus the
    ``session.query`` spans inside it, in ms: the front end's share of a
    call. None where the trace holds no ``session.query`` span."""
    events = list(events)
    queries = sorted((s.start_ns, s.end_ns) for s in program_spans(events)
                     if phase(s) == "session.query")
    calls = tr.host_spans(events)
    if not queries or not calls:
        return None
    starts = [q[0] for q in queries]
    total = 0.0
    for c in calls:
        i = bisect.bisect_left(starts, c.start_ns)
        inside = 0.0
        while i < len(queries) and queries[i][0] < c.end_ns:
            if queries[i][1] <= c.end_ns:
                inside += queries[i][1] - queries[i][0]
            i += 1
        total += c.dur_ns - inside
    return total / len(calls) / 1e6


def ms_per_execute(events: Iterable[tr.Event], phases) -> Optional[float]:
    """Seconds of the program spans named in ``phases`` per
    ``session.execute`` span, in ms; None where there is no
    ``session.execute`` span."""
    n, total = 0, 0.0
    for s in program_spans(events):
        name = phase(s)
        if name == "session.execute":
            n += 1
        if name in phases:
            total += s.dur_ns
    return total / n / 1e6 if n else None
