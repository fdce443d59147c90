"""The benchmark harness: everything that is not one system's driver.

``run.py`` calls :func:`main`.  A cell is found by its name in
``BENCHMARK.json``; its configuration file names the system's driver
(``chipbench/drivers/<system>.py``) and its traffic mix is a data file
(``chipbench/traffic/<mix>.json``).  Per-layer metrics are readers under
``chipbench/metrics/<metric>.py``; a metric named ``<quantity>.<qualifier>``
without a file of its own is read by ``metrics/<quantity>.py``.  Adding a
cell, a mix or a metric adds files and entries; it edits none.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import glob
import importlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, Tuple

from chipbench import generator
from chipbench.peaks import Peaks, peaks_for

#: the checkout: BENCHMARK.json sits here, the program under src/
CHECKOUT = Path(__file__).resolve().parents[1]


class NoChipError(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Cell:
    """One run of one cell, as its driver sees it."""

    config: Dict[str, Any]
    mix: Dict[str, Any]
    chips: int
    seed: int
    seconds: float
    devices: List[Any]
    started: float                   # perf_counter at process start
    tracer: "Tracer"


@dataclasses.dataclass
class Outcome:
    """What a driver hands back after its window and its check."""

    metrics: Dict[str, float]            # end-to-end values by name
    counters: Dict[str, Any]             # what per-layer readers read
    checks: Dict[str, Tuple[float, float]]   # name -> (number, limit)
    attempted: int
    failed: int
    memory_peak_bytes: int


@dataclasses.dataclass
class Reading:
    """What a per-layer reader reads."""

    counters: Dict[str, Any]
    trace: Optional[Dict[str, Any]]      # trace_reduce.summarize output
    config: Dict[str, Any]
    chips: int
    peaks: Peaks


def span(name: str):
    """A host span in the profiler's trace, around a call into the
    program (costs next to nothing while no trace is recorded)."""
    import jax
    return jax.profiler.TraceAnnotation(f"chipbench.{name}")


class Tracer:
    """Records a profiler trace over a few steady seconds of the window.

    The driver calls :meth:`tick` at each boundary between calls into the
    program, with the seconds since its window opened.  The trace starts
    at the first boundary after ``start_s`` and stops at the first one
    after ``start_s + length_s``, so it always holds whole calls."""

    def __init__(self, enabled: bool, start_s: float, length_s: float):
        self.enabled = enabled
        self.start_s, self.length_s = start_s, length_s
        self.dir: Optional[str] = None
        self.state = "idle"              # idle -> on -> done
        self.on_at: Optional[float] = None
        self.off_at: Optional[float] = None
        self._window = None
        self._on_elapsed = 0.0

    def tick(self, elapsed: float) -> None:
        if not self.enabled:
            return
        if self.state == "idle" and elapsed >= self.start_s:
            self._start(elapsed)
        elif self.state == "on" and elapsed >= self._on_elapsed \
                + self.length_s:
            self.stop()

    def _start(self, elapsed: float) -> None:
        import jax
        self._on_elapsed = elapsed
        self.dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._window = span("window")
        self._window.__enter__()
        self.on_at = time.perf_counter()
        self.state = "on"

    def stop(self) -> None:
        if self.state != "on":
            return
        import jax
        self.off_at = time.perf_counter()
        self._window.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.state = "done"

    def covers(self, t0: float, t1: float) -> bool:
        """Whether the host interval ``[t0, t1]`` lay inside the trace
        (asked once the trace has stopped)."""
        return (self.on_at is not None and self.off_at is not None
                and self.on_at <= t0 and t1 <= self.off_at)

    def summary(self) -> Optional[Dict[str, Any]]:
        """The reduced trace, or None if no trace was recorded."""
        from chipbench import trace_reduce
        if self.state != "done":
            return None
        try:
            paths = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                              recursive=True)
            if len(paths) != 1:
                raise RuntimeError(f"expected one trace file, found {paths}")
            return trace_reduce.summarize(trace_reduce.read_xplane(paths[0]))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


# -- lookups by name -----------------------------------------------------

def load_benchmark(root: Path) -> Dict[str, Any]:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def find(entries: List[Dict[str, Any]], name: str, what: str
         ) -> Dict[str, Any]:
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"chipbench: no {what} named {name!r} in "
                     "BENCHMARK.json")


def for_cell(entries: List[Dict[str, Any]], cell: str, e2e: List[str]
             ) -> List[Dict[str, Any]]:
    """The metrics a cell reports: those that list it, or, without a
    ``workloads`` key, those whose moved metric the cell reports."""
    out = []
    for m in entries:
        if "workloads" in m:
            if cell in m["workloads"]:
                out.append(m)
        elif m.get("moves", m["name"]) in e2e:
            out.append(m)
    return out


def load_reader(root: Path, name: str) -> Callable[[Reading], Any]:
    """The reader of metric ``name``: ``metrics/<name>.py``, or, where
    there is none, the reader of its quantity, ``metrics/<quantity>.py``
    for a name ``<quantity>.<qualifier>``."""
    metrics = root / "chipbench" / "metrics"
    path = metrics / f"{name}.py"
    if not path.is_file():
        path = metrics / f"{name.split('.')[0]}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def require_chips(chips: int) -> List[Any]:
    """The first ``chips`` TPU chips; anything else is refused."""
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise NoChipError(
            f"needs a TPU; JAX found {len(devs)} {d.platform} device(s) "
            f"({d.device_kind!r}). The benchmark never runs elsewhere.")
    if len(devs) < chips:
        raise NoChipError(f"the cell needs {chips} TPU chip(s), JAX found "
                          f"{len(devs)}")
    return devs[:chips]


def enable_compile_cache(root: Path) -> str:
    """JAX's persistent compilation cache: ``$JAX_COMPILATION_CACHE_DIR``
    when set, else the fixed ``<checkout>/.jax_cache``.  Every program is
    kept, however fast it compiled, so that only a cell's first run in a
    checkout compiles."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest chip."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def free_program_state() -> None:
    """Drop what the driver no longer references, before a reference
    runs on the same chips."""
    gc.collect()


# -- the run ----------------------------------------------------------------

def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(
        prog="chipbench/run.py",
        description="Run one benchmark cell on TPU chips and print one "
                    "JSON result line.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def trace_plan(seconds: float) -> Tuple[float, float]:
    """(start, length) of the traced stretch: a few seconds after the
    first quarter of the window."""
    return 0.25 * seconds, min(4.0, 0.5 * seconds)


def run_cell(args: argparse.Namespace, *, root: Path, started: float,
             chips_fn: Callable[[int], List[Any]] = require_chips
             ) -> Dict[str, Any]:
    """One run of one cell; returns the result object."""
    bench = load_benchmark(root)
    entry = find(bench["workloads"], args.workload, "workload")
    cfg_entry = find(bench["configs"], entry["config"], "config")
    with open(root / cfg_entry["file"]) as f:
        config = json.load(f)
    mix = generator.load_mix(root / "chipbench", entry["traffic"])
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or args.workload in m["workloads"]]
    e2e_names = [m["name"] for m in e2e]
    per_layer = for_cell(bench["per_layer"], args.workload, e2e_names)

    devices = chips_fn(int(entry["chips"]))
    enable_compile_cache(root)
    peaks = peaks_for(devices[0].device_kind)
    driver = importlib.import_module(f"chipbench.drivers.{config['system']}")
    tracer = Tracer(bool(args.trace), *trace_plan(args.seconds))
    cell = Cell(config=config, mix=mix, chips=int(entry["chips"]),
                seed=args.seed, seconds=args.seconds, devices=devices,
                started=started, tracer=tracer)
    out: Outcome = driver.run(cell)
    summary = tracer.summary()

    metrics: Dict[str, Dict[str, Any]] = {}
    if args.trace:
        reading = Reading(out.counters, summary, config, cell.chips, peaks)
        for m in per_layer:
            value = load_reader(root, m["name"])(reading)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        # "<quantity>.<qualifier>" reports the driver's <quantity> for the
        # cells it lists, under a bound of its own
        for m in e2e:
            metrics[m["name"]] = {
                "value": out.metrics[m["name"].split(".")[0]],
                "unit": m["unit"]}
    d = devices[0]
    device: Dict[str, Any] = {"platform": d.platform, "kind": d.device_kind,
                              "count": len(devices),
                              "memory_peak_bytes": out.memory_peak_bytes}
    result: Dict[str, Any] = {
        "correct": bool(all(v <= lim for v, lim in out.checks.values())
                        and out.failed == 0),
        "attempted": out.attempted, "failed": out.failed,
        "metrics": metrics, "device": device}
    if args.trace:
        if summary is None:
            raise RuntimeError("the traced run recorded no trace: the "
                               "window ended before the traced stretch")
        from chipbench import trace_reduce
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        result["breakdown"] = trace_reduce.breakdown(summary)
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in out.checks.items()}
    return result


def main(argv=None, *, started: float, root: Path = CHECKOUT) -> int:
    args = parse(argv)
    try:
        result = run_cell(args, root=root, started=started)
    except NoChipError as e:
        print(f"chipbench: {e}", file=sys.stderr, flush=True)
        return 2
    for k, c in result["checks"].items():
        verdict = "ok" if c["value"] <= c["limit"] else "FAILED"
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r}) {verdict}",
              file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0
