"""From a profiler trace to the numbers the per-layer metrics read.

The profiler writes an ``.xplane.pb``: one plane per TPU chip, whose
``XLA Ops`` line holds every operation that ran on it and whose
``XLA Modules`` line holds every program execution, and host planes whose
lines hold the benchmark's own ``jax.profiler.TraceAnnotation`` spans on
the same clock.  The reduction is:

- the traced window is the host span named ``chipbench.window``;
- a chip is busy while any operation runs on it: busy time is the union
  of its op intervals inside the window, never their sum;
- device time per op name and per program (the module whose execution
  interval holds the op), summed over chips;
- each idle gap of a chip is given to the benchmark span (``chipbench.``
  prefix) that overlaps it most: what the host was doing while the chip
  waited.
"""
from __future__ import annotations

import bisect
import dataclasses
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW = "chipbench.window"
SPAN_PREFIX = "chipbench."
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
NO_SPAN = "(no benchmark span)"


@dataclasses.dataclass(frozen=True)
class Interval:
    name: str
    start_ns: float
    end_ns: float

    @property
    def dur_ns(self) -> float:
        return self.end_ns - self.start_ns


@dataclasses.dataclass
class DeviceTrace:
    ops: List[Interval]
    modules: List[Interval]


@dataclasses.dataclass
class Trace:
    devices: Dict[str, DeviceTrace]
    spans: List[Interval]             # benchmark host spans, any thread


def _intervals(events) -> List[Interval]:
    return sorted((Interval(e.name, float(e.start_ns),
                            float(e.start_ns) + float(e.duration_ns))
                   for e in events), key=lambda i: i.start_ns)


def read_xplane(path: str) -> Trace:
    """Load the device ops and modules and the benchmark's host spans."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices: Dict[str, DeviceTrace] = {}
    spans: List[Interval] = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {line.name: line for line in plane.lines}
            devices[plane.name] = DeviceTrace(
                ops=_intervals(lines[OPS_LINE].events)
                if OPS_LINE in lines else [],
                modules=_intervals(lines[MODULES_LINE].events)
                if MODULES_LINE in lines else [])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(i for i in _intervals(line.events)
                             if i.name.startswith(SPAN_PREFIX))
    return Trace(devices, sorted(spans, key=lambda i: i.start_ns))


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    out = []
    for i in intervals:
        s, e = max(i.start_ns, lo), min(i.end_ns, hi)
        if e > s:
            out.append(Interval(i.name, s, e))
    return out


def union(intervals: Iterable[Interval]) -> List[Tuple[float, float]]:
    """Merged ``(start, end)`` pairs covering the intervals."""
    merged: List[List[float]] = []
    for i in sorted(intervals, key=lambda i: i.start_ns):
        if merged and i.start_ns <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], i.end_ns)
        else:
            merged.append([i.start_ns, i.end_ns])
    return [(s, e) for s, e in merged]


def busy_ns(ops: Sequence[Interval], lo: float, hi: float) -> float:
    return sum(e - s for s, e in union(clip(ops, lo, hi)))


def gaps(ops: Sequence[Interval], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    """The stretches of ``[lo, hi]`` in which no op runs."""
    out, t = [], lo
    for s, e in union(clip(ops, lo, hi)):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def program_of(ops: Sequence[Interval], modules: Sequence[Interval]
               ) -> List[Optional[str]]:
    """For each op, the module whose execution holds its midpoint."""
    starts = [m.start_ns for m in modules]
    out: List[Optional[str]] = []
    for op in ops:
        mid = 0.5 * (op.start_ns + op.end_ns)
        k = bisect.bisect_right(starts, mid) - 1
        out.append(modules[k].name
                   if k >= 0 and modules[k].end_ns >= mid else None)
    return out


def attribute(gap_list: Sequence[Tuple[float, float]],
              spans: Sequence[Interval]) -> Dict[str, float]:
    """Nanoseconds of idle time per benchmark span: each gap goes whole
    to the span that overlaps it most."""
    out: Dict[str, float] = defaultdict(float)
    for s, e in gap_list:
        best, best_ov = NO_SPAN, 0.0
        for sp in spans:
            if sp.start_ns >= e:
                break
            ov = min(e, sp.end_ns) - max(s, sp.start_ns)
            if ov > best_ov:
                best, best_ov = sp.name, ov
        out[best] += e - s
    return dict(out)


def window_of(trace: Trace, name: str = WINDOW) -> Interval:
    found = [s for s in trace.spans if s.name == name]
    if len(found) != 1:
        raise ValueError(f"expected one {name!r} span in the trace, found "
                         f"{len(found)}")
    return found[0]


def summarize(trace: Trace, window: str = WINDOW) -> Dict[str, object]:
    """The reduced trace every per-layer reader takes.

    Times are seconds.  ``busy_s`` and ``idle_by_span_s`` are means over
    the chips; ``op_s``, ``program_s`` and ``program_calls`` are sums over
    the chips."""
    win = window_of(trace, window)
    lo, hi = win.start_ns, win.end_ns
    spans = [s for s in trace.spans if s.name != window]
    n = len(trace.devices)
    if n == 0:
        raise ValueError("the trace holds no TPU device plane")
    busy: Dict[str, float] = {}
    op_s: Dict[str, float] = defaultdict(float)
    program_s: Dict[str, float] = defaultdict(float)
    program_calls: Dict[str, int] = defaultdict(int)
    idle: Dict[str, float] = defaultdict(float)
    for dev, dt in sorted(trace.devices.items()):
        ops = clip(dt.ops, lo, hi)
        busy[dev] = busy_ns(ops, lo, hi) * 1e-9
        for op, prog in zip(ops, program_of(ops, dt.modules)):
            op_s[op.name] += op.dur_ns * 1e-9
            program_s[prog or "(no module)"] += op.dur_ns * 1e-9
        for m in dt.modules:
            if lo <= m.start_ns < hi:
                program_calls[m.name] += 1
        for name, ns in attribute(gaps(ops, lo, hi), spans).items():
            idle[name] += ns * 1e-9 / n
    return {
        "window_s": (hi - lo) * 1e-9,
        "n_devices": n,
        "busy_s": sum(busy.values()) / n,
        "busy_s_by_device": busy,
        "op_s": dict(op_s),
        "program_s": dict(program_s),
        "program_calls": dict(program_calls),
        "idle_by_span_s": dict(idle),
    }


def breakdown(summary: Dict[str, object], top: int = 10
              ) -> Dict[str, List[List[object]]]:
    """The ``breakdown`` of a traced result line: the device ops that
    took most time (seconds per chip) and the idle time by host span."""
    n = summary["n_devices"]
    ops = sorted(summary["op_s"].items(), key=lambda kv: -kv[1])[:top]
    idle = sorted(summary["idle_by_span_s"].items(),
                  key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v / n] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in idle]}
