"""CLapp — the application/device-management object (paper §III-B).

Owns: device discovery & selection by traits, the data registry
(handle -> Data, device-resident arena blobs), the kernel registry, the
``("data", "model")`` device mesh built over the selected devices, and the
host staging buffers streamed batches are written into
(:attr:`CLapp.staging`).  This is the single place where "housekeeping"
lives, exactly as in the paper: ``init()`` selects devices in one call,
and everything downstream — transfers (``host2device`` places via
``NamedSharding``), launches, sharded streaming — is
device-count-agnostic.

Operators are wired to Data declaratively: ``Process.bind(...)`` maps
typed ports to named edges and :class:`~repro.core.graph.Pipeline`
composes, validates, and runs the graph in all three execution modes (see
:mod:`repro.core.graph` and ``docs/pipeline.md``).  Handles registered
with :meth:`CLapp.addData` remain the currency between operators and the
arena — the Pipeline plumbs them for you.
"""
from __future__ import annotations

import dataclasses
import enum
import os
import threading
import weakref
from pathlib import Path
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from . import trace
from .arena import WORD, blob_spec
from .data import Data
from .registry import KernelRegistry
from .sync import Coherence, SyncSource

DataHandle = int
INVALID_HANDLE: DataHandle = -1

#: The checkout root (``src/repro/core/app.py`` -> three levels up).
CHECKOUT = Path(__file__).resolve().parents[3]


def compile_cache_dir() -> str:
    """Where JAX's persistent compilation cache lives:
    ``$JAX_COMPILATION_CACHE_DIR`` when it is set, otherwise the fixed
    ``<checkout>/.jax_cache`` (a fixed path, since the path is part of the
    cache key: a directory that moves never hits)."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or str(CHECKOUT / ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache before a program's first
    compile and return its directory.  Entry points (``chip_smoke.py``, the
    examples, ``benchmarks/run.py``) call this; importing the library never
    does.  Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself
    and nothing is changed here."""
    path = compile_cache_dir()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", path)
    return path


class DeviceType(enum.Enum):
    ANY = "any"
    CPU = "cpu"
    GPU = "gpu"
    TPU = "tpu"


# Paper-style aliases (CLapp::DEVICE_TYPE_CPU etc.)
DEVICE_TYPE_ANY = DeviceType.ANY
DEVICE_TYPE_CPU = DeviceType.CPU
DEVICE_TYPE_GPU = DeviceType.GPU
DEVICE_TYPE_TPU = DeviceType.TPU


@dataclasses.dataclass
class PlatformTraits:
    """Selection criteria for the OpenCL *platform* — in JAX terms, the
    backend ('cpu', 'gpu', 'tpu')."""

    name: Optional[str] = None          # backend name; None = default backend
    version: Optional[str] = None       # accepted for API parity; unused


@dataclasses.dataclass
class DeviceTraits:
    """Selection criteria for the computing device(s)."""

    type: DeviceType = DeviceType.ANY
    index: Optional[int] = None          # pick the i-th matching device
    min_count: int = 1                   # need at least this many devices
    count: Optional[int] = None          # use exactly this many (None = all)


class NoMatchingDeviceError(RuntimeError):
    pass


class _Staged:
    """One pooled staging buffer: the rows still to be placed since it was
    last handed out, the view it was handed out as (weakly: a view dropped
    before it was placed frees the buffer), and the device arrays whose
    readiness proves that everything placed from it has been read."""

    __slots__ = ("buf", "view", "unplaced", "fences")

    def __init__(self, buf: np.ndarray):
        self.buf = buf
        self.view: Optional[weakref.ref] = None
        self.unplaced = 0
        self.fences: List[jax.Array] = []

    def hand_out(self) -> np.ndarray:
        view = self.buf[:]
        self.view = weakref.ref(view)
        self.unplaced = self.buf.shape[0]
        self.fences = []
        return view

    def busy(self) -> bool:
        """Handed out and still being written: not every row is placed."""
        return self.unplaced > 0 and self.view() is not None

    def landed(self, block: bool) -> bool:
        """Whether every transfer placed from the buffer has landed (with
        ``block``, after waiting for them).  Fences that have landed are
        dropped, so the pool holds no device memory past them."""
        self.fences = [f for f in self.fences if not _ready(f, block)]
        return not self.fences


def _ready(x: jax.Array, block: bool) -> bool:
    """``x`` is ready (with ``block``, once waited for).  An array deleted
    by a donation that no launch has reported yet is not."""
    try:
        if x.is_deleted():
            return False
        if block:
            x.block_until_ready()
            return True
        return x.is_ready()
    except jax.errors.JaxRuntimeError:     # donated while being asked
        return False


class StagingPool:
    """Host staging buffers of stacked batches, allocated once and reused
    across calls (the paper's pinned buffers, §III-A.2, allocated once for
    every transfer).  Buffers are ``(rows, total_words)`` words, pooled by
    shape.  A buffer is handed out again only once every transfer that
    read it has landed: the placed array, or, once a launch consumed that
    array, the launch's output.  A shape's pool grows only while every
    buffer of it is in flight, to at most ``cap``; past that the oldest is
    waited for, and a buffer made while every one is still being written
    is not kept."""

    def __init__(self):
        self._lock = threading.Lock()
        self._pool: Dict[Tuple[int, int], List[_Staged]] = {}

    def acquire(self, shape: Tuple[int, int], cap: int) -> np.ndarray:
        """A ``shape`` buffer of words to write a batch into."""
        with self._lock:
            pool = self._pool.setdefault(shape, [])
            idle = [s for s in pool if not s.busy()]      # oldest first
            got = next((s for s in idle if s.landed(block=False)), None)
            if got is None and idle and len(pool) >= cap \
                    and idle[0].landed(block=True):
                got = idle[0]
            if got is not None:
                pool.remove(got)
                pool.append(got)
                trace.STAGING_REUSES.inc()
                return got.hand_out()
            trace.STAGING_ALLOCS.inc()
            buf = np.empty(shape, WORD)
            if len(pool) >= cap:
                return buf
            got = _Staged(buf)
            pool.append(got)
            return got.hand_out()

    def placed(self, blob: np.ndarray, out: jax.Array) -> None:
        """Rows of a pooled buffer (``blob``, the buffer or a slice of it)
        were placed as ``out``."""
        owner = blob if blob.base is None else blob.base
        with self._lock:
            for s in self._pool.get(owner.shape, ()):
                if s.buf is owner:
                    s.fences.append(out)
                    s.unplaced -= blob.shape[0]
                    return

    def consumed(self, inputs: Sequence[jax.Array],
                 outputs: Sequence[jax.Array]) -> None:
        """A launch read ``inputs`` and made ``outputs``: buffers placed as
        one of the inputs are fenced by the outputs from now on, since a
        donated input can no longer be waited for."""
        with self._lock:
            for pool in self._pool.values():
                for s in pool:
                    kept = [f for f in s.fences
                            if not any(f is x for x in inputs)]
                    if len(kept) < len(s.fences):
                        s.fences = kept + list(outputs)


class CLapp:
    """Main framework object.  ``init`` selects devices in a single call
    (paper §III-A.1a); ``addData`` registers + transfers a Data set in a
    single call (§III-A.2a); ``loadKernels`` builds kernels (§III-A.3a)."""

    def __init__(self):
        self._devices: List[jax.Device] = []
        self._mesh: Optional[jax.sharding.Mesh] = None
        self._mesh_explicit = False  # set_mesh() called; init() must not rebuild
        self._data: Dict[DataHandle, Data] = {}
        self._next_handle: DataHandle = 0
        self.kernels = KernelRegistry()
        self._initialized = False
        # handle -> coherence state to settle into once the dispatched
        # host->device transfer lands (see host2device(wait=False))
        self._in_flight: Dict[DataHandle, Coherence] = {}
        #: host buffers the streaming executor stacks batches into, kept
        #: across calls (a call of one batch must still find last call's)
        self.staging = StagingPool()

    # ------------------------------------------------------------------ init
    def init(self, platform_traits: PlatformTraits | None = None,
             device_traits: DeviceTraits | None = None,
             model_axis: int = 1) -> "CLapp":
        """Select devices and build the app mesh.

        ``model_axis=m`` folds the selected devices into a 2D
        ``(n//m, m)`` mesh so annotated programs partition over the
        ``model`` axis (:data:`repro.launch.mesh.LOGICAL_AXES`) while
        streaming keeps sharding batches over ``data`` — the device count
        must be a multiple of ``m``.  The default keeps the model axis
        trivial (pure data parallelism).  Ignored when a mesh was provided
        explicitly via :meth:`set_mesh`."""
        platform_traits = platform_traits or PlatformTraits()
        device_traits = device_traits or DeviceTraits()

        backend = platform_traits.name
        if backend is None and device_traits.type not in (DeviceType.ANY,):
            backend = device_traits.type.value
        try:
            devices = jax.devices(backend) if backend else jax.devices()
        except RuntimeError as e:
            raise NoMatchingDeviceError(
                f"no devices for platform traits {platform_traits}: {e}"
            ) from e

        if device_traits.type not in (DeviceType.ANY,):
            devices = [d for d in devices if d.platform == device_traits.type.value]
        if device_traits.index is not None:
            if device_traits.index >= len(devices):
                raise NoMatchingDeviceError(
                    f"device index {device_traits.index} out of range ({len(devices)} found)"
                )
            devices = [devices[device_traits.index]]
        if len(devices) < device_traits.min_count:
            raise NoMatchingDeviceError(
                f"need >= {device_traits.min_count} devices, found {len(devices)}"
            )
        if device_traits.count is not None:
            devices = devices[: device_traits.count]

        self._devices = devices
        self._initialized = True
        if not self._mesh_explicit:
            # housekeeping promise of the paper: selecting N devices is ALL
            # the caller does; transfers and launches become device-count-
            # agnostic through the (data, model) mesh built here.  Rebuilt on
            # every init() so re-selecting devices never leaves a stale mesh
            # spanning deselected ones; a mesh provided via set_mesh() is
            # respected and never overwritten.
            from repro.launch.mesh import make_data_mesh  # lazy: keep core light
            self._mesh = make_data_mesh(devices, model=model_axis)
        return self

    @property
    def devices(self) -> List[jax.Device]:
        if not self._initialized:
            raise RuntimeError("CLapp.init() has not been called")
        return self._devices

    @property
    def device(self) -> jax.Device:
        return self.devices[0]

    def split(self, n: int) -> List["CLapp"]:
        """Partition the selected devices into ``n`` independent replica
        apps — the backend pool of the serving control plane
        (:class:`repro.serve.control.FrontDoor`): each returned app owns a
        contiguous, disjoint device subset with its own mesh and data
        registry, so replicas fail in isolation.  Requires at least one
        device per replica; extra devices go to the earlier replicas.
        """
        devices = self.devices            # raises if init() never ran
        if n < 1:
            raise ValueError(f"need n >= 1 replicas, got {n}")
        if n > len(devices):
            raise ValueError(
                f"cannot split {len(devices)} device(s) into {n} replicas "
                "(each replica needs at least one device)")
        from repro.launch.mesh import make_data_mesh
        base, extra = divmod(len(devices), n)
        apps, start = [], 0
        for i in range(n):
            stop = start + base + (1 if i < extra else 0)
            app = CLapp()
            app._devices = list(devices[start:stop])
            app._mesh = make_data_mesh(app._devices)
            app._initialized = True
            apps.append(app)
            start = stop
        return apps

    # ------------------------------------------------------------------ mesh
    def set_mesh(self, mesh: jax.sharding.Mesh) -> None:
        self._mesh = mesh
        self._mesh_explicit = mesh is not None  # set_mesh(None) re-enables auto

    @property
    def mesh(self) -> Optional[jax.sharding.Mesh]:
        return self._mesh

    def data_sharding(self, layout: Optional[Sequence[Optional[str]]] = None,
                      ) -> jax.sharding.NamedSharding:
        """A :class:`~jax.sharding.NamedSharding` over the app mesh.

        ``layout`` is the partition spec, one mesh-axis name (or ``None``)
        per array dimension: ``("data",)`` shards a stacked ``(batch,
        nbytes)`` arena blob row-wise across the selected devices (the
        streaming executor's batch placement); the default ``None`` (or
        ``()``) replicates — the placement for aux/broadcast blobs.
        """
        if self._mesh is None:
            raise RuntimeError("CLapp has no mesh (init() not called?)")
        spec = jax.sharding.PartitionSpec(*(layout or ()))
        return jax.sharding.NamedSharding(self._mesh, spec)

    @property
    def default_sharding(self) -> jax.sharding.Sharding:
        """Placement of single (unbatched) Data blobs: replicated over a
        trivial mesh holding only the primary device.  Equivalent to the old
        ``device_put(blob, self.device)`` — single-device behaviour is
        byte-identical — but expressed as a NamedSharding so every transfer
        goes through one placement path."""
        mesh = jax.sharding.Mesh(
            np.array([[self.device]], dtype=object), ("data", "model"))
        return jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())

    # ----------------------------------------------------------------- kernels
    def loadKernels(self, modules: str | Sequence[str]) -> List[str]:
        return self.kernels.load(modules)

    def getKernel(self, name: str):
        return self.kernels.get(name)

    # ------------------------------------------------------------------- data
    def addData(self, data: Data, to_device: bool = True) -> DataHandle:
        """Register a Data set; packs it into one arena blob and transfers it
        to the device in a single call.  Spec-only Data (no host values) gets
        a zero-initialised device blob of the right layout."""
        handle = self._next_handle
        self._next_handle += 1
        self._data[handle] = data
        if to_device:
            self.host2device(handle)
        return handle

    def getData(self, handle: DataHandle) -> Data:
        try:
            return self._data[handle]
        except KeyError:
            raise KeyError(f"invalid data handle {handle}") from None

    def delData(self, handle: DataHandle) -> None:
        data = self._data.pop(handle, None)
        self._in_flight.pop(handle, None)
        if data is not None:
            data.device_blob = None  # drop device references
            data.device_entries = None

    def host2device(self, handle: DataHandle, *, wait: bool = True,
                    sharding: Optional[jax.sharding.Sharding] = None) -> None:
        """Pack + transfer a Data set in one call (the paper's single-call
        transfer).  ``jax.device_put`` is asynchronous either way; with the
        default ``wait=True`` the Data's coherence is stamped with its final
        state immediately (readers block transparently, the pre-streaming
        behaviour).  ``wait=False`` is the streaming path: the handle is
        marked ``Coherence.TRANSFERRING`` and tracked in flight, so a later
        ``wait_transfers()`` is the ONLY blocking sync point — this lets
        batch *i+1*'s upload overlap batch *i*'s compute.

        ``sharding`` overrides the placement (e.g. ``app.data_sharding()``
        to replicate an aux blob over every selected device for sharded
        streaming); the default is :attr:`default_sharding` — the primary
        device, matching pre-mesh behaviour exactly.

        A static Data (:attr:`~repro.core.data.Data.static`) is placed as
        its own arrays, one ``device_put`` of its entries in layout order,
        and holds no blob: the one copy on the device is the one programs
        read."""
        data = self.getData(handle)
        if data.layout is None:
            data.plan()
        target = sharding if sharding is not None else self.default_sharding
        hosted = all(a.host is not None for a in data)
        coherence = Coherence.IN_SYNC if hosted else Coherence.DEVICE_FRESH
        if data.static:
            host = {a.name: a.host for a in data}
            arrays = tuple(
                np.zeros(e.shape, e.np_dtype) if host[e.name] is None
                else host[e.name] for e in data.layout.entries)
            data.device_entries = tuple(jax.device_put(arrays, target))
            nbytes = sum(a.nbytes for a in arrays)
        else:
            if hosted:
                blob = data.pack_host()
            else:
                spec = blob_spec(data.layout)
                blob = np.zeros(spec.shape, spec.dtype)
            data.device_blob = jax.device_put(blob, target)
            nbytes = blob.nbytes
        trace.H2D_BYTES.inc(nbytes)
        data.donated_by = None  # explicit re-upload resurrects a donated Data
        if wait:
            self._in_flight.pop(handle, None)
            data.coherence = coherence
        else:
            data.coherence = Coherence.TRANSFERRING
            self._in_flight[handle] = coherence

    def wait_transfers(self, handles: Optional[Sequence[DataHandle]] = None) -> None:
        """Explicit sync point: block until the dispatched host->device
        transfers of ``handles`` (default: all in-flight) have landed, then
        settle their coherence states."""
        todo = list(self._in_flight) if handles is None else \
            [h for h in handles if h in self._in_flight]
        for h in todo:
            data = self.getData(h)
            if data.on_device:
                jax.block_until_ready(data.device_args())
            data.coherence = self._in_flight.pop(h)

    @property
    def in_flight_handles(self) -> List[DataHandle]:
        return sorted(self._in_flight)

    def device2Host(self, handle: DataHandle,
                    sync: SyncSource = SyncSource.BUFFER_ONLY) -> None:
        data = self.getData(handle)
        if sync is SyncSource.HOST_ONLY:
            return  # host already authoritative
        self.wait_transfers([handle])
        data.sync_to_host()

    # internal: processes replace a Data's device blob after computing
    def _set_device_blob(self, handle: DataHandle, blob: jax.Array) -> None:
        data = self.getData(handle)
        if data.static:
            raise ValueError(
                f"data handle {handle} is static (read-only): it cannot "
                "be a program's output")
        data.device_blob = blob
        data.donated_by = None  # fresh result resurrects a donated edge
        # internal pipeline edges are planned to live on the device only;
        # everything else is an ordinary "device copy newer" write
        data.coherence = (Coherence.DEVICE_RESIDENT
                          if data.residency == "device"
                          else Coherence.DEVICE_FRESH)
        self._in_flight.pop(handle, None)  # old upload superseded

    @property
    def data_handles(self) -> List[DataHandle]:
        return sorted(self._data)


# Alias used throughout the repo docs
CLIPERApp = CLapp
