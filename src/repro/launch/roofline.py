"""Roofline terms from a compiled dry-run artifact (EXPERIMENTS.md §Roofline).

    compute    = HLO_FLOPs_per_chip / peak_FLOPs
    memory     = HLO_bytes_per_chip / HBM_bw
    collective = collective_bytes_per_chip / ICI_bw

Peaks come from :data:`PEAKS`, one table keyed by ``device_kind``.  The
dry-run estimates a v5e production mesh, so :class:`Roofline` reads the
v5e row (:data:`V5E`) by name; the :class:`KernelChooser` looks up the
device it runs on and breaks no tie by a bound it cannot compute.
``cost_analysis()`` reports the SPMD-partitioned per-device module
(verified in tests/test_roofline.py), so no device division is applied.
collective_bytes is parsed from the compiled HLO text: max(input, output)
bytes of every all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute (including their -start forms).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any, Dict, List, Optional, Tuple


def cost_dict(compiled) -> Dict[str, float]:
    """The numeric metrics of ``compiled.cost_analysis()`` as floats
    (``{}`` when the backend reports no analysis)."""
    cost = compiled.cost_analysis() or {}
    return {k: float(v) for k, v in cost.items() if isinstance(v, (int, float))}


@dataclasses.dataclass(frozen=True)
class DevicePeaks:
    flops: float        # bf16 FLOP/s per chip
    hbm_bw: float       # HBM bytes/s per chip
    ici_bw: float       # interconnect bytes/s per link


#: Published per-chip peaks keyed by ``jax.Device.device_kind``.
#: TPU v5e: Google Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16,
#: 819 GB/s HBM, 1,600 Gbit/s chip-to-chip (4 links of 50 GB/s).
PEAKS: Dict[str, DevicePeaks] = {
    "TPU v5 lite": DevicePeaks(flops=197e12, hbm_bw=819e9, ici_bw=50e9),
}

#: The production-mesh estimate's chip (the dry-run's target, by name).
V5E = PEAKS["TPU v5 lite"]


_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_COLL_RE = re.compile(
    r"\b(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\b")
_SHAPE_RE = re.compile(r"\b([a-z]\d*[a-z0-9]*)\[([\d,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Per-collective byte totals from HLO text (per-device program)."""
    out: Dict[str, int] = {}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if m is None or "-done" in m.group(0) or "=" not in line:
            continue
        kind = m.group(1)
        # "%x = <output shapes> all-reduce(<operand shapes>), ..."
        head = line[: m.start()]
        head = head.partition("=")[2]          # output shapes live after '='
        tail = line[m.end():]
        out_bytes = sum(_shape_bytes(d, s) for d, s in _SHAPE_RE.findall(head))
        in_bytes = sum(_shape_bytes(d, s) for d, s in _SHAPE_RE.findall(tail))
        out[kind] = out.get(kind, 0) + max(out_bytes, in_bytes)
    return out


_OPNAME_RE = re.compile(r'op_name="([^"]+)"')


def collective_sources(hlo_text: str, top: int = 15) -> List[Tuple[str, str, int]]:
    """Attribute collective bytes to model ops via HLO op_name metadata.
    Returns the top (kind, op_name-suffix, bytes) triples — the §Perf
    profiling view (we have no wall-clock trace; this is the dry-run
    equivalent of 'which op is hogging the interconnect')."""
    agg: Dict[Tuple[str, str], int] = {}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if m is None or "-done" in m.group(0) or "=" not in line:
            continue
        kind = m.group(1)
        head = line[: m.start()].partition("=")[2]
        tail = line[m.end():]
        out_b = sum(_shape_bytes(d, s) for d, s in _SHAPE_RE.findall(head))
        in_b = sum(_shape_bytes(d, s) for d, s in _SHAPE_RE.findall(tail))
        nm = _OPNAME_RE.search(line)
        name = nm.group(1) if nm else "?"
        # keep the trailing, human-meaningful path components
        name = "/".join(name.split("/")[-3:])
        key = (kind, name)
        agg[key] = agg.get(key, 0) + max(out_b, in_b)
    ranked = sorted(agg.items(), key=lambda kv: -kv[1])[:top]
    return [(k, n, b) for (k, n), b in ranked]


#: ring-algorithm wire multipliers: an all-reduce moves ~2x the tensor
#: (reduce-scatter + all-gather phases); the others move ~1x
WIRE_WEIGHT = {"all-reduce": 2.0}


def wire_bytes(breakdown: Dict[str, int]) -> float:
    return float(sum(WIRE_WEIGHT.get(k, 1.0) * v for k, v in breakdown.items()))


@dataclasses.dataclass
class Roofline:
    flops: float                   # per-chip HLO flops
    hbm_bytes: float               # per-chip HLO bytes accessed
    coll_bytes: float              # per-chip collective WIRE bytes
    coll_breakdown: Dict[str, int]
    model_flops: float             # 6*N*D (train) or 2*N*D (inference), global

    @property
    def t_compute(self) -> float:
        return self.flops / V5E.flops

    @property
    def t_memory(self) -> float:
        return self.hbm_bytes / V5E.hbm_bw

    @property
    def t_collective(self) -> float:
        return self.coll_bytes / V5E.ici_bw

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def t_bound(self) -> float:
        return max(self.t_compute, self.t_memory, self.t_collective)

    def useful_flops_ratio(self, n_chips: int) -> float:
        """MODEL_FLOPS / (per-chip HLO flops * chips)."""
        total = self.flops * n_chips
        return self.model_flops / total if total else float("nan")

    def mfu_bound(self, n_chips: int) -> float:
        """Model-FLOPs utilization ceiling implied by the dominant term."""
        if self.t_bound <= 0:
            return float("nan")
        return self.model_flops / (self.t_bound * n_chips * V5E.flops)

    def to_dict(self, n_chips: int) -> Dict[str, Any]:
        return {
            "flops_per_chip": self.flops,
            "hbm_bytes_per_chip": self.hbm_bytes,
            "coll_bytes_per_chip": self.coll_bytes,
            "coll_breakdown": self.coll_breakdown,
            "t_compute_s": self.t_compute,
            "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "bottleneck": self.bottleneck,
            "model_flops": self.model_flops,
            "useful_flops_ratio": self.useful_flops_ratio(n_chips),
            "mfu_bound": self.mfu_bound(n_chips),
        }


# ---------------------------------------------------------------------------
# MODEL_FLOPS = 6 N D (train) / 2 N D (inference), N = active params
# ---------------------------------------------------------------------------

def count_params(params_tree, cfg) -> Tuple[float, float]:
    """(total, active) parameter counts from a (spec) tree."""
    import jax
    import numpy as np

    total = active = 0.0
    flat, _ = jax.tree_util.tree_flatten_with_path(params_tree)
    for path, leaf in flat:
        name = jax.tree_util.keystr(path)
        n = float(np.prod(np.shape(leaf))) if np.ndim(leaf) else 1.0
        total += n
        if cfg.n_experts and re.search(r"moe.*(w_gate|w_up|w_down)", name) \
                and "shared" not in name:
            active += n * cfg.top_k / cfg.n_experts
        else:
            active += n
    return total, active


def model_flops(cfg, params_tree, kind: str, batch: int, seq: int) -> float:
    _, active = count_params(params_tree, cfg)
    if kind == "train":
        return 6.0 * active * batch * seq
    if kind == "prefill":
        return 2.0 * active * batch * seq
    return 2.0 * active * batch  # decode: one token per row


# ---------------------------------------------------------------------------
# KernelChooser: roofline + one-shot timed calibration -> pallas-vs-XLA
# ---------------------------------------------------------------------------

#: relative gap below which the measured times are considered a tie and the
#: roofline bound breaks it (memory-bound -> the fused Pallas pass, which
#: exists to cut HBM traffic; compute-bound -> XLA, whose op fusion and
#: layout assignment win on arithmetic-heavy bodies).  On a device with no
#: row in :data:`PEAKS` the bound is ``"unknown"`` and the faster measured
#: backend is kept.
CALIBRATION_TIE_BAND = 0.10

_CALIB_TAG = "__kernel_calibration__"


@dataclasses.dataclass(frozen=True)
class KernelCalibration:
    """One (kernel, layout, device) calibration verdict.

    ``t_pallas_s`` / ``t_xla_s`` are min-of-reps wall-clock of the
    AOT-compiled backends (``inf`` for a backend that was not timed).
    ``interpreted`` marks Pallas interpret-mode timings, which are NOT
    comparable to compiled XLA — when set, the verdict is forced to
    ``"xla"`` unless timing was explicitly forced for reporting.
    """
    kernel: str
    layout: Any
    device: str
    backend: str                   # "pallas" | "xla"
    t_pallas_s: float
    t_xla_s: float
    t_compute_est_s: float         # roofline terms from the XLA compile
    t_memory_est_s: float          # (nan on a device without peaks)
    bound: str                     # "compute" | "memory" | "unknown"
    interpreted: bool
    reason: str

    @property
    def use_pallas(self) -> bool:
        return self.backend == "pallas"

    def to_dict(self) -> Dict[str, Any]:
        d = dataclasses.asdict(self)
        d["layout"] = repr(self.layout)
        return d


def _calibration_cache() -> Dict[Any, Any]:
    # the per-process compile cache doubles as the calibration store:
    # verdicts live next to the executables they describe and are dropped
    # together on cache clears (deferred import: core.process imports are
    # heavy and must not cycle through launch at module import time)
    from repro.core.process import _COMPILE_CACHE
    return _COMPILE_CACHE


def _device_key(device=None) -> str:
    import jax
    d = device or jax.devices()[0]
    return f"{d.platform}:{getattr(d, 'device_kind', '')}:{d.id}"


def _layout_key(args, kwargs) -> Any:
    def enc(a):
        if hasattr(a, "shape") and hasattr(a, "dtype"):
            return ("arr", tuple(a.shape), str(a.dtype))
        return ("lit", repr(a))
    return (tuple(enc(a) for a in args),
            tuple(sorted((k, enc(v)) for k, v in kwargs.items())))


class KernelChooser:
    """Measured pallas-vs-XLA backend selection per (kernel, layout, device).

    For a registered kernel (``repro.core.registry``) and a concrete input
    layout, :meth:`calibrate` AOT-compiles BOTH backends (the Pallas entry
    point and its pure-jnp oracle), reads the roofline estimate off the XLA
    compile's ``cost_analysis``, runs a one-shot min-of-``reps`` timing of
    each, and caches the verdict in the compile cache.  :meth:`use_pallas`
    is the query that ``use_pallas="auto"`` processes call at trace time —
    it only needs shapes/dtypes, so tracers are fine: a first query for a
    layout calibrates on concrete zero-filled examples, outside the trace.

    Off-TPU the Pallas backend runs in interpret mode (Python-loop
    semantics, orders of magnitude slower than its compiled self), so its
    timing says nothing about TPU performance: ``use_pallas`` short-circuits
    to XLA without timing anything, and benchmark harnesses that still want
    both numbers pass ``force_timing=True`` (the record is then marked
    ``interpreted`` and excluded from any speedup claim).
    """

    def __init__(self, reps: int = 3):
        self.reps = reps

    # -- cached query -------------------------------------------------------

    def use_pallas(self, name: str, *args, **kwargs) -> bool:
        from repro.kernels.common import interpret_mode
        cached = self.lookup(name, *args, **kwargs)
        if cached is not None:
            return cached.use_pallas
        if interpret_mode():
            # don't run the timed calibration at all: interpret-mode Pallas
            # always loses, and timing it inside a trace would be pure waste
            rec = self._record_untimed(name, args, kwargs,
                                       reason="pallas would run in interpret "
                                              "mode on this backend")
            return rec.use_pallas
        return self.calibrate(name, *args, **kwargs).use_pallas

    def lookup(self, name: str, *args, **kwargs) -> Optional[KernelCalibration]:
        key = (_CALIB_TAG, name, _layout_key(args, kwargs), _device_key())
        return _calibration_cache().get(key)

    def records(self) -> List[KernelCalibration]:
        return [v for k, v in _calibration_cache().items()
                if isinstance(k, tuple) and k and k[0] == _CALIB_TAG]

    # -- calibration --------------------------------------------------------

    def calibrate(self, name: str, *args, force_timing: bool = False,
                  **kwargs) -> KernelCalibration:
        """AOT-compile both backends for this layout, time them, and cache
        the verdict.  ``args`` may be tracers of an enclosing trace or
        abstract values: only shapes/dtypes are read, and the timing runs
        on zero-filled concrete examples built outside that trace."""
        import time

        import jax
        import jax.numpy as jnp

        from repro.core.registry import KernelRegistry
        from repro.kernels.common import interpret_mode

        cached = self.lookup(name, *args, **kwargs)
        if cached is not None and not (force_timing and cached.t_pallas_s == float("inf")):
            return cached

        entry = KernelRegistry().entry(name)
        if entry.ref is None:
            raise KeyError(f"kernel {name!r} has no XLA oracle to choose from")
        # arrays become zero-filled runtime inputs; everything else (flags,
        # block sizes) stays a static Python literal inside the closure
        is_arr = [hasattr(a, "shape") and hasattr(a, "dtype") for a in args]
        specs = [jax.ShapeDtypeStruct(a.shape, a.dtype)
                 for a, arr in zip(args, is_arr) if arr]

        def staged(fn):
            def g(*xs):
                it = iter(xs)
                full = [next(it) if arr else a
                        for a, arr in zip(args, is_arr)]
                return fn(*full, **kwargs)
            return g

        fn_c = jax.jit(staged(entry.fn)).lower(*specs).compile()
        ref_c = jax.jit(staged(entry.ref)).lower(*specs).compile()

        # no row for this kind (the CPU among others): no bound from a guess
        peaks = PEAKS.get(jax.devices()[0].device_kind)
        if peaks is None:
            t_compute = t_memory = float("nan")
            bound = "unknown"
        else:
            cd = cost_dict(ref_c)
            t_compute = cd.get("flops", 0.0) / peaks.flops
            t_memory = cd.get("bytes accessed", 0.0) / peaks.hbm_bw
            bound = "memory" if t_memory >= t_compute else "compute"

        # concrete even when called while an enclosing jit is tracing
        with jax.ensure_compile_time_eval():
            ex = [jnp.zeros(sp.shape, sp.dtype) for sp in specs]

        def timed(compiled) -> float:
            jax.block_until_ready(compiled(*ex))      # warmup
            best = float("inf")
            for _ in range(self.reps):
                t0 = time.perf_counter()
                jax.block_until_ready(compiled(*ex))
                best = min(best, time.perf_counter() - t0)
            return best

        interpreted = interpret_mode()
        t_xla = timed(ref_c)
        if interpreted and not force_timing:
            return self._store(name, args, kwargs, KernelCalibration(
                kernel=name, layout=_layout_key(args, kwargs),
                device=_device_key(), backend="xla",
                t_pallas_s=float("inf"), t_xla_s=t_xla,
                t_compute_est_s=t_compute, t_memory_est_s=t_memory,
                bound=bound, interpreted=True,
                reason="pallas interpret-mode timing not comparable"))
        t_pallas = timed(fn_c)

        if interpreted:
            backend, reason = "xla", ("interpret-mode pallas timing recorded "
                                      "for reporting only")
        elif (bound != "unknown" and abs(t_pallas - t_xla)
              <= CALIBRATION_TIE_BAND * max(t_pallas, t_xla)):
            backend = "pallas" if bound == "memory" else "xla"
            reason = f"measured tie (<{CALIBRATION_TIE_BAND:.0%}); roofline {bound}-bound"
        elif t_pallas < t_xla:
            backend, reason = "pallas", f"measured {t_xla / t_pallas:.2f}x faster"
        else:
            backend, reason = "xla", f"measured {t_pallas / t_xla:.2f}x faster"

        return self._store(name, args, kwargs, KernelCalibration(
            kernel=name, layout=_layout_key(args, kwargs),
            device=_device_key(), backend=backend,
            t_pallas_s=t_pallas, t_xla_s=t_xla,
            t_compute_est_s=t_compute, t_memory_est_s=t_memory,
            bound=bound, interpreted=interpreted, reason=reason))

    def _record_untimed(self, name, args, kwargs, reason) -> KernelCalibration:
        return self._store(name, args, kwargs, KernelCalibration(
            kernel=name, layout=_layout_key(args, kwargs),
            device=_device_key(), backend="xla",
            t_pallas_s=float("inf"), t_xla_s=float("inf"),
            t_compute_est_s=float("nan"), t_memory_est_s=float("nan"),
            bound="unknown", interpreted=True, reason=reason))

    def _store(self, name, args, kwargs, rec: KernelCalibration) -> KernelCalibration:
        key = (_CALIB_TAG, name, _layout_key(args, kwargs), _device_key())
        _calibration_cache()[key] = rec
        return rec


_DEFAULT_CHOOSER: Optional[KernelChooser] = None


def default_chooser() -> KernelChooser:
    global _DEFAULT_CHOOSER
    if _DEFAULT_CHOOSER is None:
        _DEFAULT_CHOOSER = KernelChooser()
    return _DEFAULT_CHOOSER


def resolve_backend(use_pallas, name: str, *args, **kwargs) -> bool:
    """The ``use_pallas="auto"`` contract: ``True``/``False`` are honored
    verbatim; ``"auto"`` asks the default :class:`KernelChooser` (cached
    per kernel/layout/device, safe to call at trace time)."""
    if use_pallas == "auto":
        return default_chooser().use_pallas(name, *args, **kwargs)
    return bool(use_pallas)
