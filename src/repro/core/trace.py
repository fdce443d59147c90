"""Spans and counters of the program, on the clock of the device trace.

One recorder, always on.  :class:`span` marks a phase of a call::

    with trace.span("stream.pack", row=r) as s:
        s.attrs["bytes"] = write(...)

Each span records its name, ``start_ns``/``end_ns`` from
``time.perf_counter_ns()``, the id of the span that encloses it on the same
thread (the span that caused it; ``None`` for a root) and its attrs, a
request id among them where one exists (the LM ``rid``, a streamed
item's row in its batch).  Records go into a ring of the last :data:`RING_SPANS` spans in
memory; nothing is written anywhere.  Each span also enters a
``jax.profiler.TraceAnnotation`` named ``repro.<name>``, so that in a
profiler trace it lands on the host plane, on the device trace's clock.
With no profiler session open that costs about a microsecond.

Two kinds of span are recorded after the fact, from hooks installed when
this module is imported: ``compile``, one per XLA backend compile (or load
from the persistent compilation cache) anywhere in the process, from a
``jax.monitoring`` duration listener, and ``gc``, one per collection of
the cyclic garbage collector, from ``gc.callbacks``.

:func:`calls` reads the ring back: the root spans of one name, each with
its duration, the self time of every span name beneath it (a span's
duration minus what its children cover, summed by name) and the deltas of
the process counters over it.

The counters live in :data:`METRICS`, a :class:`Metrics` registry whose
:meth:`Metrics.render` is Prometheus text.  An operator who fronts the
program with :class:`repro.serve.FrontDoor` passes
``FrontDoor(metrics=trace.METRICS)`` to get the front door's metrics and
the program's in one ``/metrics`` payload.
"""
from __future__ import annotations

import collections
import dataclasses
import gc
import itertools
import re
import threading
import time
from typing import Any, Dict, Iterable, List, Mapping, NamedTuple, Optional, Tuple

import jax

__all__ = [
    "Call", "Counter", "Gauge", "Histogram", "METRICS", "Metrics",
    "RING_SPANS", "SpanRecord", "calls", "records", "span",
]


# ---------------------------------------------------------------------------
# Metrics: counters / gauges / histograms + Prometheus exposition
# ---------------------------------------------------------------------------

_NAME_RE = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$")
_LABEL_RE = re.compile(r"^[a-zA-Z_][a-zA-Z0-9_]*$")


def _label_key(labels: Mapping[str, str]) -> Tuple[Tuple[str, str], ...]:
    for k in labels:
        if not _LABEL_RE.match(k):
            raise ValueError(f"invalid metric label name {k!r}")
    return tuple(sorted((k, str(v)) for k, v in labels.items()))


def _fmt_labels(key: Tuple[Tuple[str, str], ...],
                extra: Tuple[Tuple[str, str], ...] = ()) -> str:
    items = key + extra
    if not items:
        return ""
    body = ",".join(f'{k}="{v}"' for k, v in items)
    return "{" + body + "}"


class _Metric:
    """Common label-set bookkeeping for one named metric.  The lock is
    re-entrant: the garbage collector's hook counts its pauses, and a
    collection can start while the same thread holds the lock."""

    kind = "untyped"

    def __init__(self, name: str, help: str = ""):
        if not _NAME_RE.match(name):
            raise ValueError(f"invalid metric name {name!r}")
        self.name = name
        self.help = help
        self._lock = threading.RLock()
        self._series: Dict[Tuple[Tuple[str, str], ...], Any] = {}

    def _header(self) -> List[str]:
        lines = []
        if self.help:
            lines.append(f"# HELP {self.name} {self.help}")
        lines.append(f"# TYPE {self.name} {self.kind}")
        return lines


class Counter(_Metric):
    """Monotonically increasing count, optionally per label set."""

    kind = "counter"

    def inc(self, value: float = 1.0, **labels: str) -> None:
        if value < 0:
            raise ValueError("counters only go up")
        key = _label_key(labels)
        with self._lock:
            self._series[key] = self._series.get(key, 0.0) + value

    def value(self, **labels: str) -> float:
        with self._lock:
            return float(self._series.get(_label_key(labels), 0.0))

    def total(self) -> float:
        """Sum over every label set."""
        with self._lock:
            return float(sum(self._series.values()))

    def render(self) -> List[str]:
        with self._lock:
            series = sorted(self._series.items())
        lines = self._header()
        for key, v in series:
            lines.append(f"{self.name}{_fmt_labels(key)} {_num(v)}")
        return lines


class Gauge(_Metric):
    """A value that goes up and down (queue depth, in-flight, liveness)."""

    kind = "gauge"

    def set(self, value: float, **labels: str) -> None:
        with self._lock:
            self._series[_label_key(labels)] = float(value)

    def value(self, **labels: str) -> float:
        with self._lock:
            return float(self._series.get(_label_key(labels), float("nan")))

    def render(self) -> List[str]:
        with self._lock:
            series = sorted(self._series.items())
        lines = self._header()
        for key, v in series:
            lines.append(f"{self.name}{_fmt_labels(key)} {_num(v)}")
        return lines


class Histogram(_Metric):
    """Sampled observations (latencies), rendered as a Prometheus summary
    with p50/p99/p999 quantiles computed by
    :meth:`repro.core.process.ProfileParameters.percentile`."""

    kind = "summary"
    quantiles = (50.0, 99.0, 99.9)

    def observe(self, value: float, **labels: str) -> None:
        key = _label_key(labels)
        with self._lock:
            prof = self._series.get(key)
            if prof is None:
                from repro.core.process import ProfileParameters
                prof = ProfileParameters(enable=True)
                self._series[key] = prof
            prof.record(float(value))

    def percentile(self, p: float, **labels: str) -> float:
        """p-th percentile of the observations; nan when empty."""
        with self._lock:
            prof = self._series.get(_label_key(labels))
        if prof is None:
            return float("nan")
        return prof.percentile(p)

    def count(self, **labels: str) -> int:
        with self._lock:
            prof = self._series.get(_label_key(labels))
        return 0 if prof is None else len(prof.samples)

    def render(self) -> List[str]:
        with self._lock:
            series = sorted(self._series.items())
        lines = self._header()
        for key, prof in series:
            for q in self.quantiles:
                ql = (("quantile", f"{q / 100.0:.10g}"),)
                lines.append(
                    f"{self.name}{_fmt_labels(key, ql)} "
                    f"{_num(prof.percentile(q))}")
            lines.append(f"{self.name}_count{_fmt_labels(key)} "
                         f"{len(prof.samples)}")
            lines.append(f"{self.name}_sum{_fmt_labels(key)} "
                         f"{_num(sum(prof.samples))}")
        return lines


def _num(v: float) -> str:
    """Prometheus number formatting: integers without a trailing .0."""
    f = float(v)
    if f != f:
        return "NaN"
    if f == int(f) and abs(f) < 1e15:
        return str(int(f))
    return repr(f)


class Metrics:
    """Registry of named metrics.  ``counter``/``gauge``/``histogram``
    get-or-create (re-registering with a different kind raises), and
    :meth:`render` produces the whole registry in Prometheus text
    exposition format — the ``/metrics`` payload of a deployment."""

    def __init__(self):
        self._lock = threading.Lock()
        self._metrics: Dict[str, _Metric] = {}

    def _get(self, cls, name: str, help: str):
        with self._lock:
            m = self._metrics.get(name)
            if m is None:
                m = cls(name, help)
                self._metrics[name] = m
            elif not isinstance(m, cls):
                raise ValueError(
                    f"metric {name!r} already registered as {m.kind}")
            return m

    def counter(self, name: str, help: str = "") -> Counter:
        return self._get(Counter, name, help)

    def gauge(self, name: str, help: str = "") -> Gauge:
        return self._get(Gauge, name, help)

    def histogram(self, name: str, help: str = "") -> Histogram:
        return self._get(Histogram, name, help)

    def render(self) -> str:
        """The registry as Prometheus text exposition (one block per
        metric, label sets sorted — deterministic for tests)."""
        with self._lock:
            metrics = [self._metrics[k] for k in sorted(self._metrics)]
        lines: List[str] = []
        for m in metrics:
            lines.extend(m.render())
        return "\n".join(lines) + ("\n" if lines else "")


#: the program's process-wide counters, each counted where the work happens
METRICS = Metrics()
H2D_BYTES = METRICS.counter(
    "repro_h2d_bytes_total", "bytes copied from host arrays to a device")
D2H_BYTES = METRICS.counter(
    "repro_d2h_bytes_total", "bytes copied from a device to host arrays")
COMPILES = METRICS.counter(
    "repro_compiles_total",
    "XLA backend compiles, loads from the persistent cache included")
COMPILE_SECONDS = METRICS.counter(
    "repro_compile_seconds_total", "seconds spent in XLA backend compiles")
GC_PAUSE_SECONDS = METRICS.counter(
    "repro_gc_pause_seconds_total",
    "seconds the cyclic garbage collector held the interpreter")
CACHE_HITS = METRICS.counter(
    "repro_compile_cache_hits_total",
    "aot_compile calls answered from the in-process executable cache")
CACHE_MISSES = METRICS.counter(
    "repro_compile_cache_misses_total",
    "aot_compile calls that traced, lowered and compiled")
SUBWORD_BYTES = METRICS.counter(
    "repro_arena_subword_bytes_total",
    "bytes of 8- and 16-bit arena entries packed or unpacked on the host")
MOE_ASSIGNMENTS = METRICS.counter(
    "repro_moe_assignments_total",
    "(token, expert) pairs an expert layer's router chose, over all experts")
MOE_HELD_ASSIGNMENTS = METRICS.counter(
    "repro_moe_held_assignments_total",
    "(token, expert) pairs computed here: the chosen expert is held here")
MOE_ROWS = METRICS.counter(
    "repro_moe_rows_total", "token rows through an expert layer")
STAGING_REUSES = METRICS.counter(
    "repro_staging_reuses_total",
    "host staging buffers of stacked batches handed out again")
STAGING_ALLOCS = METRICS.counter(
    "repro_staging_allocs_total",
    "host staging buffers of stacked batches allocated")
AUX_NATIVE_BYTES = METRICS.counter(
    "repro_aux_native_bytes_total",
    "bytes of aux handed to a launch as their own arrays (static Data)")
AUX_PACKED_BYTES = METRICS.counter(
    "repro_aux_packed_bytes_total",
    "bytes of aux handed to a launch as arena words the program decodes")
_PROCESS_COUNTERS = (H2D_BYTES, D2H_BYTES, COMPILES, COMPILE_SECONDS,
                     GC_PAUSE_SECONDS, CACHE_HITS, CACHE_MISSES, SUBWORD_BYTES,
                     MOE_ASSIGNMENTS, MOE_HELD_ASSIGNMENTS, MOE_ROWS,
                     STAGING_REUSES, STAGING_ALLOCS, AUX_NATIVE_BYTES,
                     AUX_PACKED_BYTES)
for _c in _PROCESS_COUNTERS:
    _c.inc(0.0)         # a series from the start: rendered at 0, and read
                        # unlocked by _counter_values


def _counter_values() -> Tuple[float, ...]:
    # the process counters carry no labels; one dict read each is atomic
    return tuple(c._series[()] for c in _PROCESS_COUNTERS)


# ---------------------------------------------------------------------------
# The span recorder
# ---------------------------------------------------------------------------

#: spans the ring holds; the oldest are dropped first
RING_SPANS = 65536


class SpanRecord(NamedTuple):
    """One finished span."""

    id: int
    parent: Optional[int]           # enclosing span on the same thread
    name: str
    start_ns: int                   # time.perf_counter_ns()
    end_ns: int
    attrs: Dict[str, Any]
    #: counter name -> growth over the span (root spans only, else None)
    deltas: Optional[Dict[str, float]]

    @property
    def duration_s(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9


_RING: "collections.deque[SpanRecord]" = collections.deque(maxlen=RING_SPANS)
_IDS = itertools.count(1)
_LOCAL = threading.local()


def _stack() -> List[int]:
    """The open span ids of the calling thread, innermost last."""
    try:
        return _LOCAL.stack
    except AttributeError:
        _LOCAL.stack = []
        return _LOCAL.stack


class span:
    """Context manager recording one phase of a call (see the module
    docstring).  ``attrs`` may be added to inside the block."""

    __slots__ = ("name", "attrs", "_id", "_parent", "_before", "_ann",
                 "_start")

    def __init__(self, name: str, **attrs: Any):
        self.name = name
        self.attrs = attrs

    def __enter__(self) -> "span":
        stack = _stack()
        self._parent = stack[-1] if stack else None
        self._id = next(_IDS)
        stack.append(self._id)
        self._before = _counter_values() if self._parent is None else None
        self._ann = jax.profiler.TraceAnnotation("repro." + self.name)
        self._ann.__enter__()
        self._start = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        end = time.perf_counter_ns()
        self._ann.__exit__(*exc)
        stack = _stack()
        while stack and stack.pop() != self._id:
            pass            # a span left open by an abandoned generator
        deltas = None
        if self._before is not None:
            deltas = {c.name: after - before for c, before, after in zip(
                _PROCESS_COUNTERS, self._before, _counter_values())}
        _RING.append(SpanRecord(self._id, self._parent, self.name,
                                self._start, end, self.attrs, deltas))


def _record_past(name: str, start_ns: int, end_ns: int, **attrs: Any
                 ) -> None:
    """Record a span that has already ended, under the calling thread's
    innermost open span."""
    stack = _stack()
    _RING.append(SpanRecord(next(_IDS), stack[-1] if stack else None, name,
                            start_ns, end_ns, attrs, None))


#: the event JAX records around every backend compile (and persistent-cache
#: load), with the compiled function's name
_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _on_duration(event: str, duration: float, **kwargs: Any) -> None:
    if event != _BACKEND_COMPILE_EVENT:
        return
    end = time.perf_counter_ns()
    COMPILES.inc()
    COMPILE_SECONDS.inc(duration)
    _record_past("compile", end - int(duration * 1e9), end,
                 fun=kwargs.get("fun_name"))


def _on_gc(phase: str, info: Dict[str, int]) -> None:
    if phase == "start":
        _LOCAL.gc = (time.perf_counter_ns(),
                     jax.profiler.TraceAnnotation("repro.gc"))
        _LOCAL.gc[1].__enter__()
        return
    started = getattr(_LOCAL, "gc", None)
    if started is None:
        return
    _LOCAL.gc = None
    end = time.perf_counter_ns()
    started[1].__exit__(None, None, None)
    GC_PAUSE_SECONDS.inc((end - started[0]) * 1e-9)
    _record_past("gc", started[0], end, generation=info.get("generation"))


jax.monitoring.register_event_duration_secs_listener(_on_duration)
gc.callbacks.append(_on_gc)


# ---------------------------------------------------------------------------
# Reading the ring back
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Call:
    """One root span with what happened beneath it."""

    span: SpanRecord
    #: span name -> seconds of self time, over the root and its descendants
    self_s: Dict[str, float]
    #: span name -> number of spans, over the root and its descendants
    counts: Dict[str, int]

    @property
    def duration_s(self) -> float:
        return self.span.duration_s

    @property
    def deltas(self) -> Dict[str, float]:
        """Counter name -> growth over the call."""
        return self.span.deltas or {}


def records() -> List[SpanRecord]:
    """Every span the ring holds, in the order they ended."""
    return list(_RING)


def _covered_ns(parent: SpanRecord, children: Iterable[SpanRecord]) -> int:
    """Nanoseconds of ``parent`` that the union of ``children`` covers."""
    total, reach = 0, parent.start_ns
    for c in sorted(children, key=lambda c: c.start_ns):
        s, e = max(c.start_ns, reach), min(c.end_ns, parent.end_ns)
        if e > s:
            total += e - s
            reach = e
    return total


def calls(name: str) -> List[Call]:
    """The recorded root spans named ``name``, oldest first."""
    recs = list(_RING)
    children: Dict[int, List[SpanRecord]] = collections.defaultdict(list)
    for r in recs:
        if r.parent is not None:
            children[r.parent].append(r)
    out = []
    for root in sorted((r for r in recs if r.parent is None
                        and r.name == name), key=lambda r: r.start_ns):
        self_ns: Dict[str, int] = collections.defaultdict(int)
        counts: Dict[str, int] = collections.defaultdict(int)
        todo = [root]
        while todo:
            r = todo.pop()
            kids = children.get(r.id, ())
            self_ns[r.name] += r.end_ns - r.start_ns - _covered_ns(r, kids)
            counts[r.name] += 1
            todo.extend(kids)
        out.append(Call(root, {k: v * 1e-9 for k, v in self_ns.items()},
                        dict(counts)))
    return out
