"""Process-wide telemetry: a thread-safe metrics registry + trace spans.

One registry serves the whole engine (every Session, Feed, compactor thread,
and kernel dispatch in the process writes to it), mirroring what a metrics
sidecar would scrape from a serving AsterixDB node:

  * **counters** — monotone event counts (plan-cache hits per level,
    compaction attempts / CAS conflicts / retries, kernel launches, ...);
  * **gauges**   — last-known values (retired-component device bytes,
    stall pressure, resident run counts, last-execute wall time);
  * **histograms** — latency/size distributions with fixed exponential
    buckets (flush build time, write-stall duration, query phases);
  * **spans**    — lightweight structured traces (name, labels, start,
    duration, parent, query) kept in a bounded ring; every finished span
    also feeds the ``<name>_seconds`` histogram, so phase timers and traces
    are one call site. Each span also opens a ``jax.profiler``
    annotation ``repro/<name>``, so a profiler trace shows the program's
    phases on the same clock as the device's operations (an annotation
    with no profiler running costs well under a microsecond). A
    ``query_span`` starts a query: it draws a sequence number, which every
    span opened inside it records as ``query``.

Series are labeled: ``inc("kernel.launches_total", kernel="filter_count")``
creates the series ``kernel.launches_total{kernel=filter_count}``. Label
sets are expected to be low-cardinality (dataset names, levels, modes).

Overhead contract: ``enabled`` gates everything that costs real time —
span capture (``perf_counter`` pairs, ring appends, profiler annotations)
and histogram observation are no-ops when disabled. Counters and gauges
always record: they ARE the engine's operational state (``Session.stats``,
``Catalog.gc_stats`` and the ingest/compactor mirrors are thin views over
them), and an increment is one locked dict add. Disable with
``set_enabled(False)`` or the ``REPRO_TELEMETRY=0`` environment variable.

``snapshot()`` exports everything as one JSON-serializable dict; benchmarks
attach it to their result files and CI asserts on the series.
``snapshot(normalize=True)`` zeroes every time-valued field (histogram
sum/min/max/buckets, span start/duration, ``*seconds*`` gauges) so two runs
of the same deterministic workload produce identical snapshots — the form
golden tests compare.
"""
from __future__ import annotations

import bisect
import itertools
import json
import os
import threading
import time
from collections import deque
from typing import Optional

# Exponential latency buckets (seconds): 100µs .. 10s, the range between a
# cached plan bind and a stalled flush. Sizes (rows/bytes) reuse the same
# histogram type; their buckets are irrelevant and dropped on normalize.
DEFAULT_BUCKETS = (1e-4, 2.5e-4, 5e-4, 1e-3, 2.5e-3, 5e-3, 1e-2, 2.5e-2,
                   5e-2, 1e-1, 2.5e-1, 5e-1, 1.0, 2.5, 5.0, 10.0)


def series_key(name: str, labels: dict) -> str:
    """Canonical series id: ``name{k1=v1,k2=v2}`` with sorted label keys —
    snapshot keys are deterministic strings, not tuples."""
    if not labels:
        return name
    inner = ",".join(f"{k}={labels[k]}" for k in sorted(labels))
    return f"{name}{{{inner}}}"


class _Histogram:
    __slots__ = ("count", "total", "min", "max", "buckets")

    def __init__(self):
        self.count = 0
        self.total = 0.0
        self.min = float("inf")
        self.max = float("-inf")
        self.buckets = [0] * (len(DEFAULT_BUCKETS) + 1)  # last = +inf

    def observe(self, value: float) -> None:
        self.count += 1
        self.total += value
        if value < self.min:
            self.min = value
        if value > self.max:
            self.max = value
        # the first bucket whose bound is >= value; past the last, +inf
        self.buckets[bisect.bisect_left(DEFAULT_BUCKETS, value)] += 1

    def snapshot(self, normalize: bool = False) -> dict:
        if normalize:  # timing-dependent fields zeroed, event count kept
            return {"count": self.count, "sum": 0.0, "min": 0.0, "max": 0.0}
        out = {"count": self.count, "sum": self.total,
               "min": self.min if self.count else 0.0,
               "max": self.max if self.count else 0.0,
               "buckets": {}}
        for le, n in zip(DEFAULT_BUCKETS, self.buckets):
            if n:
                out["buckets"][str(le)] = n
        if self.buckets[-1]:
            out["buckets"]["+inf"] = self.buckets[-1]
        return out


class _NoopSpan:
    """Shared do-nothing span: what ``span()`` hands out when telemetry is
    disabled — enter/exit touch no clock and allocate nothing."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NOOP_SPAN = _NoopSpan()

# A span's profiler annotation is named ``repro/<span name>``.
ANNOTATION_PREFIX = "repro/"
_TRACE_ANNOTATION = None  # jax.profiler.TraceAnnotation, imported on first use


def _trace_annotation():
    global _TRACE_ANNOTATION
    if _TRACE_ANNOTATION is None:
        from jax.profiler import TraceAnnotation
        _TRACE_ANNOTATION = TraceAnnotation
    return _TRACE_ANNOTATION


class Span:
    # Few calls per span on purpose: a traced window's Python tracer
    # records every function call, and spans sit on every query's path.
    __slots__ = ("_registry", "name", "labels", "start", "duration", "parent",
                 "query", "_ann", "_stack")

    def __init__(self, registry: "MetricsRegistry", name: str, labels: dict,
                 query: Optional[int] = None):
        self._registry = registry
        self.name = name
        self.labels = labels
        self.start = 0.0
        self.duration = 0.0
        self.parent: Optional[str] = None
        self.query = query  # set here only on a query's own span
        self._ann = self._stack = None

    def __enter__(self) -> "Span":
        tls = self._registry._tls
        stack = self._stack = tls.__dict__.setdefault("stack", [])
        annotation = _TRACE_ANNOTATION or _trace_annotation()
        if self.query is not None:  # a query's span: its id goes in the trace
            ann = annotation(ANNOTATION_PREFIX + self.name, query=self.query)
        else:
            if stack:
                self.query = stack[-1].query
            ann = annotation(ANNOTATION_PREFIX + self.name)
        ann.__enter__()
        self._ann = ann
        self.parent = stack[-1].name if stack else None
        stack.append(self)
        self.start = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        self.duration = time.perf_counter() - self.start
        self._ann.__exit__(None, None, None)
        stack = self._stack
        if stack and stack[-1] is self:
            stack.pop()
        self._registry._finish_span(self)
        return False


class MetricsRegistry:
    def __init__(self, enabled: bool = True, max_spans: int = 1024):
        self.enabled = enabled
        self._lock = threading.Lock()
        self._counters: dict[str, float] = {}
        self._gauges: dict[str, float] = {}
        self._hists: dict[str, _Histogram] = {}
        self._spans: deque = deque(maxlen=max_spans)
        self._tls = threading.local()
        self._query_ids = itertools.count(1)
        # (span name, label items) -> its ``<name>_seconds`` histogram
        self._span_hists: dict = {}

    # -- recording ----------------------------------------------------------

    def inc(self, name: str, value=1, **labels) -> None:
        """Counter add. Unconditional (see module docstring): the engine's
        back-compat stats surfaces read these even with telemetry off."""
        key = series_key(name, labels)
        with self._lock:  # int() keeps numpy scalars out of JSON snapshots
            self._counters[key] = self._counters.get(key, 0) + int(value)

    def inc_series(self, counts: dict) -> None:
        """Counter adds keyed by ready-made series ids (``series_key``),
        under one lock: how a compiled query counts the kernel launches
        of each execution."""
        with self._lock:
            for key, value in counts.items():
                self._counters[key] = self._counters.get(key, 0) + value

    def set_gauge(self, name: str, value, **labels) -> None:
        key = series_key(name, labels)
        with self._lock:
            self._gauges[key] = float(value)

    def observe(self, name: str, value: float, **labels) -> None:
        """Histogram observation — gated: observations carry timings/sizes
        whose capture is exactly the overhead ``enabled`` exists to avoid."""
        if not self.enabled:
            return
        key = series_key(name, labels)
        with self._lock:
            h = self._hists.get(key)
            if h is None:
                h = self._hists[key] = _Histogram()
            h.observe(value)

    def span(self, name: str, **labels):
        """Context manager timing one phase. On exit the span lands in the
        trace ring AND observes the ``<name>_seconds`` histogram (same
        labels). Returns the shared no-op when disabled."""
        if not self.enabled:
            return NOOP_SPAN
        return Span(self, name, labels)

    def query_span(self, name: str, **labels):
        """``span`` for the whole of one query: it draws the query's
        sequence number, which every span opened inside it records and
        which its profiler annotation carries."""
        if not self.enabled:
            return NOOP_SPAN
        return Span(self, name, labels, query=next(self._query_ids))

    # -- reading ------------------------------------------------------------

    def counter_value(self, name: str, **labels):
        with self._lock:
            return self._counters.get(series_key(name, labels), 0)

    def gauge_value(self, name: str, default=None, **labels):
        with self._lock:
            return self._gauges.get(series_key(name, labels), default)

    def counters(self, prefix: str = "") -> dict:
        with self._lock:
            return {k: v for k, v in self._counters.items()
                    if k.startswith(prefix)}

    def gauges(self, prefix: str = "") -> dict:
        with self._lock:
            return {k: v for k, v in self._gauges.items()
                    if k.startswith(prefix)}

    def spans(self, name: Optional[str] = None) -> list[dict]:
        with self._lock:
            out = list(self._spans)
        return out if name is None else [s for s in out if s["name"] == name]

    def snapshot(self, normalize: bool = False, include_spans: bool = True) -> dict:
        """One JSON-serializable dict of every series. ``normalize=True``
        zeroes time-valued fields (histogram sum/min/max/buckets, span
        start/duration, gauges whose name contains "seconds") so
        deterministic workloads snapshot identically."""
        with self._lock:
            counters = dict(sorted(self._counters.items()))
            gauges = dict(sorted(self._gauges.items()))
            hists = {k: h.snapshot(normalize)
                     for k, h in sorted(self._hists.items())}
            spans = list(self._spans) if include_spans else []
        if normalize:
            gauges = {k: (0.0 if "seconds" in k else v)
                      for k, v in gauges.items()}
            spans = [dict(s, start=0.0, duration=0.0) for s in spans]
        return {"counters": counters, "gauges": gauges,
                "histograms": hists, "spans": spans}

    def to_json(self, normalize: bool = False, **kw) -> str:
        return json.dumps(self.snapshot(normalize), **kw)

    def reset(self) -> None:
        with self._lock:
            self._counters.clear()
            self._gauges.clear()
            self._hists.clear()
            self._span_hists.clear()
            self._spans.clear()

    # -- span plumbing ------------------------------------------------------

    def _finish_span(self, span: Span) -> None:
        record = {"name": span.name, "labels": span.labels,
                  "start": span.start, "duration": span.duration,
                  "parent": span.parent, "query": span.query}
        which = (span.name, *span.labels.items())
        with self._lock:
            self._spans.append(record)
            h = self._span_hists.get(which)
            if h is None:
                key = series_key(span.name + "_seconds", span.labels)
                h = self._hists.get(key)
                if h is None:
                    h = self._hists[key] = _Histogram()
                self._span_hists[which] = h
            h.observe(span.duration)


# -- the process-wide registry -----------------------------------------------

REGISTRY = MetricsRegistry(
    enabled=os.environ.get("REPRO_TELEMETRY", "1").lower()
    not in ("0", "false", "off"))


def registry() -> MetricsRegistry:
    return REGISTRY


def set_enabled(on: bool) -> None:
    REGISTRY.enabled = bool(on)


def enabled() -> bool:
    return REGISTRY.enabled


# Module-level conveniences: call sites write `tel.inc(...)` without holding
# the registry object.

def inc(name: str, value=1, **labels) -> None:
    REGISTRY.inc(name, value, **labels)


def set_gauge(name: str, value, **labels) -> None:
    REGISTRY.set_gauge(name, value, **labels)


def observe(name: str, value: float, **labels) -> None:
    REGISTRY.observe(name, value, **labels)


def span(name: str, **labels):
    return REGISTRY.span(name, **labels)


def query_span(name: str, **labels):
    return REGISTRY.query_span(name, **labels)


def counter_value(name: str, **labels):
    return REGISTRY.counter_value(name, **labels)


def gauge_value(name: str, default=None, **labels):
    return REGISTRY.gauge_value(name, default, **labels)


def snapshot(normalize: bool = False, include_spans: bool = True) -> dict:
    return REGISTRY.snapshot(normalize, include_spans)
