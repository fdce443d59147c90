"""Device meshes and logical axes — device-count housekeeping the
framework hides from user code (paper §III-A.1a — selecting devices is
the ONLY device-dependent call the user makes).

Meshes are explicit-device ``("data", "model")`` meshes built by
FUNCTIONS (not module-level constants) so importing this module never
touches jax device state: device count is locked at first jax init, and
the dry-run must set ``XLA_FLAGS`` before that happens.
:class:`repro.core.app.CLapp` builds :func:`make_data_mesh` over its
*selected* devices at ``init()``; every transfer and launch then goes
through the mesh (``app.data_sharding``) instead of naming devices.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Sequence, Tuple

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


def make_group_mesh(devices: Sequence[jax.Device],
                    axis_names: Tuple[str, str] = ("data", "model"),
                    ) -> jax.sharding.Mesh:
    """A ``(1, m)`` mesh over one model group: the devices of one
    ``data``-axis row of a 2D app mesh, which together hold one item of a
    sharded batch."""
    if not devices:
        raise ValueError("cannot build a group mesh over zero devices")
    return jax.sharding.Mesh(
        np.array(list(devices), dtype=object).reshape(1, len(devices)),
        axis_names)


def group_sharding(devices: Sequence[jax.Device]
                   ) -> jax.sharding.NamedSharding:
    """Fully-replicated ``NamedSharding`` over :func:`make_group_mesh` —
    the placement of one item's output blob that a model group computed
    on a 2D mesh (:func:`repro.core.arena.split_batched_blob`)."""
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
    whole on one device and ``model``-sharded in the same pipeline's 2D
    mesh executable.  Under ``vmap(...,
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
