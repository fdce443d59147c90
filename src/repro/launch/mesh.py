"""Device meshes and per-device throughput profiles.

Two concerns live here, both device-count housekeeping the framework hides
from user code (paper §III-A.1a — selecting devices is the ONLY
device-dependent call the user makes):

* **Mesh construction** — explicit-device ``("data", "model")`` meshes.
  These are FUNCTIONS (not module-level constants) so importing this
  module never touches jax device state: device count is locked at first
  jax init, and the dry-run must set ``XLA_FLAGS`` before that happens.
  :class:`repro.core.app.CLapp` builds :func:`make_data_mesh` over its
  *selected* devices at ``init()``; every transfer and launch then goes
  through the mesh (``app.data_sharding``) instead of naming devices.

* **Device throughput profiles** — :class:`DeviceProfile` /
  :class:`DeviceProfileRegistry`, the measured items/sec record behind
  the streaming executor's ``split="proportional"`` policy (the EngineCL
  direction from the ROADMAP: per-device batch splits proportional to
  measured throughput instead of the equal ``NamedSharding`` split).
  Every proportionally-split launch feeds its per-device wall times back
  into the registry, so the split self-calibrates: the first batch runs
  balanced (the cold fallback), and every batch after that is carved by
  the rates the previous batches actually achieved.  See
  :mod:`repro.core.stream` for the execution side and
  ``docs/architecture.md`` for the full story.
"""
from __future__ import annotations

import dataclasses
import threading
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_data_mesh(devices: Sequence[jax.Device],
                   axis_names: Tuple[str, str] = ("data", "model"),
                   model: int = 1,
                   ) -> jax.sharding.Mesh:
    """An explicit-device ``(data, model)`` mesh over the given devices.

    ``model=1`` (the default) puts every device on the ``data`` axis — the
    pure data-parallel mesh :class:`repro.core.app.CLapp` builds over its
    *selected* devices (which may be a subset or reordering of
    ``jax.devices()``, so ``jax.make_mesh`` — which always takes the first
    N global devices — is not usable here).  ``model=m`` folds the devices
    into a 2D ``(len(devices)//m, m)`` grid: consecutive devices form one
    model group, so a batch row sharded over ``data`` lands on a group
    whose ``m`` members co-execute one ``shard_map``-partitioned program
    (see :data:`LOGICAL_AXES` / :func:`shard_by_logical`)."""
    if not devices:
        raise ValueError("cannot build a mesh over zero devices")
    if model < 1:
        raise ValueError(f"model-axis size must be >= 1, got {model}")
    if len(devices) % model:
        raise ValueError(
            f"{len(devices)} device(s) do not fold into a (data, model={model}) "
            "mesh; the model-axis size must divide the device count")
    grid = np.array(devices, dtype=object).reshape(len(devices) // model, model)
    return jax.sharding.Mesh(grid, axis_names)


def make_host_mesh() -> jax.sharding.Mesh:
    """Whatever devices exist locally, as a (data, model) mesh — used by the
    examples and tests on the single CPU device."""
    return make_data_mesh(jax.devices())


def make_device_mesh(device: jax.Device,
                     axis_names: Tuple[str, str] = ("data", "model"),
                     ) -> jax.sharding.Mesh:
    """A trivial single-device ``(data, model)`` mesh — the compile and
    placement target of per-device pinned executables and upload lanes
    (:mod:`repro.core.stream`).  Mirrors ``CLapp.default_sharding``'s mesh
    shape so compile-cache fingerprints stay uniform across the default,
    mesh-sharded and pinned variants."""
    return jax.sharding.Mesh(
        np.array([[device]], dtype=object), axis_names)


def make_group_mesh(devices: Sequence[jax.Device],
                    axis_names: Tuple[str, str] = ("data", "model"),
                    ) -> jax.sharding.Mesh:
    """A ``(1, m)`` mesh over one model group — the compile/placement
    target of per-group pinned executables when the app mesh is 2D (the
    generalization of :func:`make_device_mesh` the streaming executor's
    proportional-split/lanes machinery carves batches over).  A singleton
    group reduces exactly to :func:`make_device_mesh` (same shape, axes and
    device ids, so compile-cache fingerprints coincide)."""
    if not devices:
        raise ValueError("cannot build a group mesh over zero devices")
    return jax.sharding.Mesh(
        np.array(list(devices), dtype=object).reshape(1, len(devices)),
        axis_names)


def pinned_sharding(device: jax.Device) -> jax.sharding.NamedSharding:
    """Fully-replicated ``NamedSharding`` over :func:`make_device_mesh` —
    where a per-device sub-batch (upload lane) or per-device aux replica
    lands."""
    return jax.sharding.NamedSharding(
        make_device_mesh(device), jax.sharding.PartitionSpec())


def group_sharding(devices: Sequence[jax.Device]
                   ) -> jax.sharding.NamedSharding:
    """Fully-replicated ``NamedSharding`` over :func:`make_group_mesh` —
    where a per-group sub-batch or aux replica lands on a 2D mesh.  The
    ``shard_map``-partitioned program inside the group's executable then
    splits the replicated rows over the group's ``model`` axis."""
    return jax.sharding.NamedSharding(
        make_group_mesh(devices), jax.sharding.PartitionSpec())


# ---------------------------------------------------------------------------
# Logical axes: name every weight/activation axis ONCE, bind names to mesh
# axes in one table
# ---------------------------------------------------------------------------

#: THE logical-axis table — the single place a logical array-axis name is
#: bound to a mesh axis (or to ``None`` = never partitioned).  Processes
#: annotate their arrays with these names (``shard_by_logical``) instead of
#: naming mesh axes, so re-binding an axis (e.g. moving ``frame`` off the
#: ``model`` axis) is a one-line change here, not a hunt through kernels.
LOGICAL_AXES: Dict[str, Optional[str]] = {
    # streamed items / decode batch rows ride the data axis (the streaming
    # executor's batch placement; see repro.core.stream)
    "batch": "data",
    # large per-item grids split over the model axis: independent MRI
    # frames, and decode slots (each slot's row + cache strip is
    # self-contained up to the shared scalar position, a pmax)
    "frame": "model",
    "slot": "model",
    # per-item working axes — never partitioned
    "coil": None, "height": None, "width": None,
    "layer": None, "head": None, "seq": None, "embed": None, "vocab": None,
}


def mesh_axis(logical: Optional[str]) -> Optional[str]:
    """Mesh axis a logical axis name is bound to (``None`` = replicated).
    Unknown names are an error — the table is the contract."""
    if logical is None:
        return None
    if logical not in LOGICAL_AXES:
        raise KeyError(
            f"unknown logical axis {logical!r}; add it to "
            f"repro.launch.mesh.LOGICAL_AXES (known: {sorted(LOGICAL_AXES)})")
    return LOGICAL_AXES[logical]


def logical_pspec(axes: Optional[Sequence[Optional[str]]]
                  ) -> jax.sharding.PartitionSpec:
    """``PartitionSpec`` for one array whose dims carry the given logical
    names (``None`` entries — and ``axes=None`` entirely — replicate)."""
    if axes is None:
        return jax.sharding.PartitionSpec()
    return jax.sharding.PartitionSpec(*(mesh_axis(a) for a in axes))


def logical_sharding(mesh: jax.sharding.Mesh,
                     axes: Optional[Sequence[Optional[str]]]
                     ) -> jax.sharding.NamedSharding:
    """``NamedSharding`` over ``mesh`` from logical axis names."""
    return jax.sharding.NamedSharding(mesh, logical_pspec(axes))


def model_axis_size(mesh: Optional[jax.sharding.Mesh]) -> int:
    """Size of the mesh's ``model`` axis (1 when there is no mesh)."""
    if mesh is None:
        return 1
    return int(dict(mesh.shape).get("model", 1))


def shard_by_logical(fn: Callable,
                     in_axes: Sequence[Optional[Sequence[Optional[str]]]],
                     out_axes,
                     *, mesh: Optional[jax.sharding.Mesh] = None) -> Callable:
    """Partition ``fn`` over the mesh with :func:`jax.shard_map`, with
    per-dim *logical* axis names instead of mesh axes.

    ``in_axes`` holds one annotation per positional argument: a tuple of
    logical names (one per dim, ``None`` = replicated dim) or ``None`` to
    replicate the whole argument (pytree arguments allowed there).
    ``out_axes`` annotates a single output the same way; a **list** of
    such annotations annotates a tuple-returning ``fn`` per output.

    The wrapper is a no-op — it calls ``fn`` directly — only where there
    is nothing to place: no mesh (``mesh=None`` and no compile in
    progress) or a mesh of one device.  On any larger mesh ``fn`` runs
    under ``shard_map``, so a Pallas kernel inside it is never left to
    the automatic partitioner (which refuses Mosaic kernels).  A
    partitioned dim its mesh axis does not divide makes every argument
    replicated instead.  ``mesh=None`` resolves the mesh the enclosing
    AOT compilation is lowering under (:func:`repro.core.process.
    current_compile_mesh`), which is how one annotated ``apply`` body runs
    whole in a pinned per-device executable and ``model``-sharded in the
    same pipeline's 2D mesh executable.  Under ``vmap(...,
    spmd_axis_name="data")`` (the sharded stream executor) the batch dim
    is split over ``data`` as well."""
    in_axes = tuple(in_axes)

    def wrapped(*args):
        from repro.core.process import current_compile_mesh  # lazy: no cycle
        m = mesh if mesh is not None else current_compile_mesh()
        if m is None or m.size == 1:
            return fn(*args)
        if len(args) != len(in_axes):
            raise ValueError(
                f"shard_by_logical: {len(args)} argument(s) but "
                f"{len(in_axes)} in_axes annotation(s)")
        shape = dict(m.shape)
        in_specs = [logical_pspec(a) for a in in_axes]
        if isinstance(out_axes, list):             # list = one entry per output
            out_specs: Any = tuple(logical_pspec(a) for a in out_axes)
        else:
            out_specs = logical_pspec(out_axes)
        whole = any(
            axes_ann is not None and name is not None
            and arg.shape[d] % shape.get(mesh_axis(name), 1)
            for arg, axes_ann in zip(args, in_axes)
            for d, name in enumerate(axes_ann or ()))
        if whole:                                  # indivisible: stay whole
            P = jax.sharding.PartitionSpec
            in_specs = [P()] * len(in_specs)
            out_specs = (tuple(P() for _ in out_specs)
                         if isinstance(out_axes, list) else P())
        return jax.shard_map(fn, mesh=m, in_specs=tuple(in_specs),
                             out_specs=out_specs, check_vma=False)(*args)

    return wrapped


# ---------------------------------------------------------------------------
# Per-device throughput profiles (EngineCL-style measured load balancing)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class DeviceProfile:
    """Measured throughput of one device: items/sec, refined per launch.

    ``record(items, seconds)`` folds one observation into an exponential
    moving average (``ema`` weight on the newest sample), so the estimate
    tracks drifting device speed (thermal throttling, contention) without
    a warmup restart.  The raw per-launch wall times are kept in a
    :class:`~repro.core.process.ProfileParameters` (``seconds``) so the
    usual mean/p50/p99 statistics are available for introspection.
    """

    device_id: int
    ema: float = 0.3
    items: int = 0                  # total items this device has processed
    _rate: float = float("nan")     # EMA items/sec

    def __post_init__(self):
        # lazy import: mesh must stay importable before core is set up
        from repro.core.process import ProfileParameters
        self.seconds = ProfileParameters(enable=True)

    def record(self, items: int, seconds: float) -> None:
        """Fold one measured launch (``items`` rows in ``seconds``) in."""
        if items <= 0 or seconds <= 0:
            return
        self.seconds.record(seconds)
        self.items += int(items)
        sample = items / seconds
        if self.cold:
            self._rate = sample
        else:
            self._rate = self.ema * sample + (1.0 - self.ema) * self._rate

    @property
    def rate(self) -> float:
        """Current items/sec estimate; ``nan`` when nothing was recorded."""
        return self._rate

    @property
    def cold(self) -> bool:
        return self._rate != self._rate      # nan check

    def set_rate(self, rate: float) -> None:
        """Seed the estimate directly (benchmarks, tests, emulated pools)."""
        if rate < 0:
            raise ValueError(f"rate must be >= 0 items/sec, got {rate}")
        self._rate = float(rate)


class DeviceProfileRegistry:
    """Per-device :class:`DeviceProfile` store owned by a ``CLapp``.

    The streaming executor records into it from every proportionally-split
    launch (one sample per device per batch) and reads it back through
    :meth:`split` to carve the next stacked batch.  Thread-safe: the
    executor's per-device completion timers record from worker threads
    while the dispatch loop reads the current rates.
    """

    def __init__(self, ema: float = 0.3):
        self.ema = ema
        self._profiles: Dict[int, DeviceProfile] = {}
        self._lock = threading.Lock()

    def profile(self, device: jax.Device) -> DeviceProfile:
        with self._lock:
            p = self._profiles.get(device.id)
            if p is None:
                p = DeviceProfile(device_id=device.id, ema=self.ema)
                self._profiles[device.id] = p
            return p

    def record(self, device: jax.Device, items: int, seconds: float) -> None:
        p = self.profile(device)
        with self._lock:
            p.record(items, seconds)

    def set_rate(self, device: jax.Device, rate: float) -> None:
        p = self.profile(device)
        with self._lock:
            p.set_rate(rate)

    def rates(self, devices: Sequence[jax.Device]) -> List[float]:
        """Current items/sec estimate per device (``nan`` where cold)."""
        return [self.profile(d).rate for d in devices]

    def warm(self, devices: Sequence[jax.Device]) -> bool:
        """True when EVERY given device has a measured rate."""
        return all(not self.profile(d).cold for d in devices)

    def total_rate(self, devices: Sequence[jax.Device]) -> float:
        """Aggregate measured capacity of ``devices`` in items/sec — the
        sum of their rates, or ``nan`` until every one is warm (a partial
        sum would understate the pool and mislead whoever balances load
        on it, e.g. the serving control plane's ``"profile"`` router)."""
        rates = self.rates(devices)
        if any(r != r for r in rates):
            return float("nan")
        return float(sum(rates))

    def reset(self) -> None:
        with self._lock:
            self._profiles.clear()

    def split(self, rows: int, devices: Sequence[jax.Device],
              ) -> Optional[Tuple[int, ...]]:
        """Per-device row counts for ``rows`` items, proportional to the
        measured rates — or ``None`` when the proportional carve is not
        justified and the caller should fall back to an equal split:

        * any device's profile is **cold** (no measurement yet),
        * the batch is **too small to matter** (``rows < 2 *
          len(devices)`` — a proportional carve can differ from balanced
          by at most one row per device there),
        * every measured rate is zero (degenerate).

        A zero-rate device gets **zero rows** (it is skipped entirely —
        the "broken accelerator stays in the pool" case; the streaming
        plan's balanced fallback also excludes zero-rate devices, so the
        exclusion survives the ``None`` cases above — see
        :meth:`repro.core.stream._BatchPlan.split_vector`).  Rounding is
        largest-remainder with ties broken by device order, so the vector
        is deterministic for given rates and always sums to ``rows``.
        """
        n = len(devices)
        if n == 0:
            raise ValueError("cannot split over zero devices")
        if rows < 2 * n:
            return None
        rates = self.rates(devices)
        if any(r != r for r in rates):       # any cold -> fall back
            return None
        total = sum(rates)
        if total <= 0:
            return None
        quotas = [rows * r / total for r in rates]
        counts = [int(q) for q in quotas]
        # largest-remainder rounding: hand out the missing rows to the
        # largest fractional parts (stable: ties go to the earlier device)
        remainder = rows - sum(counts)
        order = sorted(range(n), key=lambda i: (-(quotas[i] - counts[i]), i))
        for i in order[:remainder]:
            counts[i] += 1
        return tuple(counts)

    @staticmethod
    def balanced(rows: int, n: int) -> Tuple[int, ...]:
        """The equal-split fallback vector: rows spread as evenly as they
        divide (the first ``rows % n`` devices carry one extra row)."""
        if n <= 0:
            raise ValueError("cannot split over zero devices")
        base, extra = divmod(rows, n)
        return tuple(base + (1 if i < extra else 0) for i in range(n))
