"""Streaming executor: double-buffered transfers + batched launches.

The paper's overhead story (§III-A.2) is that OpenCLIPER hides transfer
housekeeping with pinned-memory buffer mapping so host↔device traffic can
overlap compute.  The single-shot ``init()/launch()`` path reproduced in
:mod:`repro.core.process` is still fully synchronous per Data set: pack,
``device_put``, launch, repeat.  This module makes process chains
production-shaped for many independent Data sets (MRI slice stacks,
inference requests):

* :class:`StreamQueue` — a bounded prefetching host→device feed.  While
  batch *i* executes, batch *i+1*'s arena blob is already in flight via an
  asynchronously dispatched ``jax.device_put``; ``block_until_ready`` only
  happens at explicit sync points (never per item).

* :class:`BatchedProcess` — AOT-compiles a process's
  :class:`~repro.core.process.PureLaunchable` ONCE for a leading batch
  axis: ``vmap`` over the arena-blob unpack/compute/pack, EVERY streaming
  input batched, aux blobs broadcast.  k independent Data sets become one
  launch instead of a Python loop of k launches.  Reuses the global
  compile cache (the batch size is part of the spec key) and the donation
  rule (in-place programs donate the stacked blob of the donated input —
  always a transfer temporary, so donation is safe by construction).

* :func:`stream_launch` — the engine behind ``Process.stream(datasets,
  batch=k)`` and the Pipeline's ``mode="stream"``: group into batches,
  pack each item once, straight into its row of a host staging buffer
  the app keeps across calls (:class:`~repro.core.app.StagingPool`),
  feed through a StreamQueue, launch batched, and scatter the per-item
  output blobs into fresh output Data objects.

* :class:`_JoinFeed` — multi-input (fan-in) streaming.  A launchable with
  N streaming inputs gets N per-edge StreamQueues whose batches are
  **zipped row-aligned** before each launch: one shared group plan decides
  which items (and how many padded rows) every batch carries, each edge's
  queue stacks ITS blobs for exactly those rows, and one joined launch
  consumes one batch from every edge.  The ragged-tail policy below spans
  all edges — a tail executable is compiled for the whole joined program,
  never per edge.  Items for a multi-input launchable are tuples (or
  ``{input name -> Data}`` mappings), one Data per input edge.

* :class:`_BatchPlan` — the ragged-tail policy.  A final batch with fewer
  than ``batch`` items is either padded by repeating the last item (cheap
  when the waste is small — no second compile) or, when the padding waste
  fraction exceeds ``tail_waste_threshold``, executed through a SECOND,
  smaller executable compiled just for the tail size.  Tail executables go
  through the same global compile cache, so a recurring tail size (e.g. a
  serving loop that often flushes half-full batches) compiles once.  Under
  ``sharded=True`` a tail that does not divide the ``data``-axis size
  falls back to padding (every device must get whole items).

Results are bit-identical to sequential ``launch()`` — the vmapped program
runs the same per-item computation, only batched (verified in
tests/test_stream.py, tests/test_pipeline.py and
benchmarks/stream_throughput.py).  The serving loop
(:mod:`repro.serve.pipeline`) builds on the same pieces: StreamQueue as the
admission buffer, _BatchPlan for dynamic batch sizes.

Sharded streaming contract (``Process.stream(..., sharded=True)``)
------------------------------------------------------------------

With ``sharded=True`` the executor is *mesh-aware*: it uses the
``("data", "model")`` mesh the owning :class:`~repro.core.app.CLapp`
built over its selected devices (paper §III-A.1a: device selection is the
ONLY device-count-dependent call the user makes).  The contract:

* **Placement** — each stacked ``(batch, total_words)`` arena blob is
  ``device_put`` with ``NamedSharding(mesh, P("data"))``: rows (items)
  are scattered round-robin across every device on the ``data`` axis in
  ONE call.  Aux blobs are replicated (``P()``) over the same mesh.
* **Compilation** — the vmapped program is AOT-compiled once with
  ``in_shardings``/``out_shardings`` matching that placement, so ONE
  launch computes ``batch`` items split over all devices.  The compile
  cache keys on the full mesh fingerprint (every device id + axis names)
  and the shardings, so sharded/unsharded variants and different device
  sets never collide on one executable.
* **Constraints** — ``batch`` must be divisible by the ``data``-axis size
  (the ragged tail is already padded up to ``batch`` by repetition, so
  every dispatched batch is full).
* **Results** — per-item outputs are sliced out of the sharded result's
  ``addressable_shards``: each item's blob stays resident on the device
  that computed it (no gather, no bounce through device 0).  Outputs are
  bit-identical to sequential ``launch()`` — items never interact.
* **Fallback** — ``sharded=False`` (default) and single-device apps keep
  the exact pre-mesh behaviour: everything on ``app.device``.

Throughput-proportional splits (``split="proportional"``)
---------------------------------------------------------

The equal ``NamedSharding`` split above gives every device the same number
of rows — which wastes the fast devices whenever the pool is asymmetric
(CPU+GPU co-execution, thermally throttled chips, shared hosts).  With
``split="proportional"`` (requires ``sharded=True``) the executor carves
each stacked batch into **per-device sub-batches sized by measured
throughput** instead:

* The owning app's :class:`~repro.launch.mesh.DeviceProfileRegistry`
  (``app.device_profiles``) holds an items/sec estimate per device.
  :meth:`_BatchPlan.stack_group` asks it for a split vector ONCE per item
  group — so in a fan-in join **every edge shares one split vector** and
  row alignment across edges is preserved by construction.
* While profiles are **cold** (or the batch is too small to matter, or
  every rate is zero) the plan falls back to the balanced vector — the
  first batch is the warmup launch that populates the registry.
* Each sub-batch is ``device_put`` to its device and launched through a
  per-device executable (compiled once per ``(device, rows)`` via the
  global compile cache); dispatch is asynchronous, so all devices compute
  concurrently, each on exactly the rows the registry assigned it.  A
  zero-rate device receives zero rows and is skipped entirely.
* A per-device completion timer records every launch's items/sec back
  into the registry (the live ``ProfileParameters`` samples), so the
  split **self-calibrates** batch over batch.
* Because the vmapped program computes items independently, outputs are
  **bit-identical** to the equal split (and to sequential ``launch()``)
  in all three modes for batch-size-invariant programs — every
  elementwise kernel; only the placement of work changes.  Programs
  whose XLA lowering picks batch-size-dependent algorithms (the FFT)
  match at rtol 1e-6 instead — the same caveat the ragged-tail
  executable already carries.  Uneven row counts are legal here: the
  per-device executables carry an explicit split vector, so neither the
  batch size nor a ragged tail needs to divide the device count.

Per-device upload lanes (``lanes=True``) and phase profiling
------------------------------------------------------------

``lanes=True`` (requires ``sharded=True``) keeps the equal carve but
uploads it on per-device double-buffered lanes — one pinned
:class:`StreamQueue` per mesh device per input edge
(:class:`_UploadLanes`) — so each device's host2device transfer is
dispatched independently and overlaps every other device's upload and
compute, instead of funnelling through one global mesh scatter.  Because
the per-device executables carry explicit row counts, the mesh-sharded
batch-divisibility constraint is lifted.  Outputs stay bit-identical.

Passing a :class:`~repro.core.process.ProfileParameters` with
``enable=True`` additionally records a per-launch phase breakdown into
``profile.phases``: ``"transfer"`` (host→device upload, dispatch→landed),
``"transfer_d2d"`` (a device-resident group moved device-to-device — the
proof that pipeline-internal edges incur zero host2device traffic),
``"compile"`` (AOT compiles on cache miss) and ``"compute"`` (launch
dispatch→ready).  Phases are measured by daemon timer threads and overlap
by design — they break down where wall time went, they do not partition
it.
"""
from __future__ import annotations

import functools
import threading
import time
import weakref
from collections import deque
from typing import (Any, Iterable, Iterator, List, Mapping, Optional,
                    Sequence, Tuple)

import jax
import numpy as np

from . import trace
from .arena import batched_spec, blob_spec, split_batched_blob
from .data import Data
from .process import (PureLaunchable, ProfileParameters, aot_compile,
                      _layout_fingerprint)
from .sync import Coherence


class StreamQueue:
    """Bounded, double-buffered host→device transfer queue.

    Wraps an iterator of host blobs (numpy arrays).  Up to ``depth`` items
    are dispatched ahead with ``jax.device_put`` (asynchronous — JAX only
    blocks a *reader* of the array); consuming item *i* immediately starts
    the transfer of item *i+depth*.  ``depth=2`` is classic double
    buffering; larger depths trade memory for more dispatch-ahead slack.

    ``device`` may be a :class:`jax.Device`, a :class:`jax.sharding.
    Sharding`, or a **callable placement** ``item -> device batch``: the
    streaming executor passes :meth:`_BatchPlan.place`, which puts a
    stacked batch on the plan's device or, sharded, scatters it across the
    mesh's ``data`` axis (``NamedSharding(mesh, P("data"))``) in one
    ``device_put``, carves a proportional split's batch into per-device
    sub-batches as a :class:`SplitBatch`, and reports every staging
    buffer it placed to the app's pool.

    ``profile`` (a :class:`~repro.core.process.ProfileParameters`) records
    each dispatched placement's dispatch-to-landed wall time — measured
    from a daemon timer thread, so the queue never blocks — into the
    ``"transfer"`` phase bucket for host→device uploads, or
    ``"transfer_d2d"`` for device-resident items that never touch the
    host (the residency benchmark's proof that internal edges incur zero
    host2device time).  Phases overlap compute by design.
    """

    def __init__(self, items: Iterable[np.ndarray], device=None, depth: int = 2,
                 profile: ProfileParameters | None = None):
        if depth < 1:
            raise ValueError(f"depth must be >= 1, got {depth}")
        self._it = iter(items)
        self._device = device
        self._place = device if callable(device) else \
            (lambda item: _put(item, device))
        self._depth = depth
        self._profile = profile
        self._fifo: deque = deque()
        self._exhausted = False
        self.transfers = 0  # number of device_puts issued (introspection)
        # every issued-but-not-yet-synced transfer, INCLUDING blobs already
        # popped by the consumer (sync() must block on those too — popping
        # hands over the array, it does not mean the transfer landed).
        # Weakrefs: a blob the consumer dropped (or donated to a launch) has
        # no buffer left to wait on and must not be kept alive by the queue.
        self._issued: List[weakref.ref] = []

    def _fill(self) -> None:
        # retire refs whose arrays are gone (dropped by the consumer or
        # donated to a launch) so _issued stays bounded by the number of
        # LIVE blobs, not the stream length
        self._issued = [
            ref for ref in self._issued
            if (b := ref()) is not None and not _is_deleted(b)
        ]
        while not self._exhausted and len(self._fifo) < self._depth:
            try:
                item = next(self._it)
            except StopIteration:
                self._exhausted = True
                return
            t0 = time.perf_counter()
            blob = self._place(item)
            self._fifo.append(blob)
            self._issued.append(weakref.ref(blob))
            self.transfers += 1
            if self._profile is not None and self._profile.enable:
                self._record_transfer(item, blob, t0)

    def _record_transfer(self, item: Any, blob: Any, t0: float) -> None:
        """Time one placement dispatch→landed from a daemon thread (phase
        ``"transfer"`` for host blobs, ``"transfer_d2d"`` for device-
        resident ones)."""
        src = item.blob if isinstance(item, _SplitStack) else item
        phase = "transfer" if isinstance(src, np.ndarray) else "transfer_d2d"
        prof = self._profile

        def timer():
            try:
                jax.block_until_ready(blob)
            except Exception:
                return      # blob donated/deleted before it landed
            prof.record_phase(phase, time.perf_counter() - t0)

        threading.Thread(target=timer, name="transfer-timer",
                         daemon=True).start()

    def __iter__(self) -> Iterator[jax.Array]:
        return self

    def __next__(self) -> jax.Array:
        self._fill()
        if not self._fifo:
            raise StopIteration
        out = self._fifo.popleft()
        self._fill()  # start the next transfer before the caller computes
        return out

    @property
    def in_flight(self) -> int:
        """Issued transfers not yet retired by ``sync()`` whose arrays are
        still live (queued OR already handed to the consumer)."""
        return sum(
            1 for ref in self._issued
            if (b := ref()) is not None and not _is_deleted(b)
        )

    def sync(self) -> None:
        """Explicit sync point: block until every in-flight blob has landed
        — both blobs still queued in the FIFO and blobs already popped by
        the consumer.  Donated/garbage-collected blobs are skipped (their
        buffers are gone; there is nothing left to land)."""
        for ref in self._issued:
            blob = ref()
            if blob is not None and not _is_deleted(blob):
                jax.block_until_ready(blob)
        self._issued.clear()


def _put(blob: Any, target: Any, copy: bool = False) -> jax.Array:
    """One placement dispatch (``jax.device_put``, of a copy of ``blob``
    with ``copy``), recorded as a ``stream.place`` span; a host array's
    bytes count as host-to-device traffic."""
    nbytes = blob.nbytes if isinstance(blob, np.ndarray) else 0
    with trace.span("stream.place", bytes=nbytes):
        out = jax.device_put(blob.copy() if copy else blob, target)
    trace.H2D_BYTES.inc(nbytes)
    return out


def _is_deleted(blob: jax.Array) -> bool:
    """True if the array's buffer is gone (donated to a launch / deleted)."""
    try:
        return bool(blob.is_deleted())
    except AttributeError:  # non-jax arrays in tests
        return False


def _single_device_mesh(device: jax.Device) -> jax.sharding.Mesh:
    """The compile target of per-device pinned executables — see
    :func:`repro.launch.mesh.make_device_mesh` (shared so the lanes, the
    aux replicas and the pinned executables all agree on one mesh shape)."""
    from repro.launch.mesh import make_device_mesh  # lazy: keep core light
    return make_device_mesh(device)


class _SplitStack:
    """One edge's stacked HOST blob plus the per-device split vector its
    group was assigned.  Produced by :meth:`_BatchPlan.stack_group` in
    proportional mode — the vector is decided once per item group, so
    every edge of a join carries the SAME vector (row alignment across
    edges survives the uneven carve by construction)."""

    __slots__ = ("blob", "split")

    def __init__(self, blob: np.ndarray, split: Tuple[int, ...]):
        self.blob = blob
        self.split = split


class SplitBatch:
    """Per-device parts of one proportionally-split stacked batch.

    ``parts[j]`` is a ``(counts[j], total_words)`` blob resident on
    ``devices[j]`` (zero-count devices are omitted); concatenating the
    parts in order restores the items in stream order.  Quacks enough
    like a stacked ``jax.Array`` for the queue bookkeeping: ``shape``,
    ``is_deleted`` and ``block_until_ready`` (the latter is what
    ``jax.block_until_ready`` calls on non-array leaves).
    """

    # __weakref__: StreamQueue tracks issued batches by weak reference
    __slots__ = ("parts", "counts", "devices", "__weakref__")

    def __init__(self, parts: Sequence[jax.Array], counts: Sequence[int],
                 devices: Sequence[jax.Device]):
        self.parts = tuple(parts)
        self.counts = tuple(int(c) for c in counts)
        self.devices = tuple(devices)

    @property
    def shape(self) -> Tuple[int, int]:
        return (sum(self.counts), int(self.parts[0].shape[1]))

    def is_deleted(self) -> bool:
        return all(_is_deleted(p) for p in self.parts)

    def block_until_ready(self) -> "SplitBatch":
        for p in self.parts:
            jax.block_until_ready(p)
        return self


class BatchedProcess:
    """A process AOT-compiled once for a leading batch axis.

    ``fn(*in_blobs, *aux) -> blob`` becomes ``vmap(fn)`` over ``(k,
    nbytes)`` stacked blobs — EVERY streaming input carries the batch
    axis, aux blobs broadcast; compilation goes through
    :func:`~repro.core.process.aot_compile`, so repeated construction for
    the same process/batch size hits the global compile cache (the paper's
    "init once" at batch scale).

    ``sharded=True`` compiles the batched program with ``in_shardings`` /
    ``out_shardings`` that split every stacked blob's leading axis over
    the app mesh's ``data`` axis (aux blobs replicated): one launch runs
    ``batch`` items spread across every selected device, with each input
    edge's rows co-located item-wise (row i of every edge lands on the
    same device — a join never shuffles items across devices).  The batch
    size must be divisible by the ``data``-axis size.

    ``device=...`` instead pins the whole batched program to ONE device
    (a trivial single-device mesh): the proportional-split plan compiles
    one of these per ``(device, rows)`` so each device can carry a
    different share of a batch.  Mutually exclusive with ``sharded``.

    ``group=...`` pins to one model GROUP — the devices of one data-axis
    row of a 2D app mesh, compiled under a ``(1, m)``
    :func:`~repro.launch.mesh.make_group_mesh` with the sub-batch
    replicated across the group; the program's ``shard_by_logical``
    annotations then partition its per-item grids over the group's
    ``model`` axis.  A singleton group is byte-identical to ``device=``
    (same mesh fingerprint, same cached executable).
    """

    def __init__(self, process, batch: int, *, sharded: bool = False,
                 device: Optional[jax.Device] = None,
                 group: Optional[Tuple[jax.Device, ...]] = None,
                 profile: ProfileParameters | None = None):
        if batch < 1:
            raise ValueError(f"batch must be >= 1, got {batch}")
        if group is not None and len(group) == 1:
            device, group = group[0], None       # singleton group: pin plain
        if sharded and (device is not None or group is not None):
            raise ValueError("sharded=True and device=/group= are mutually "
                             "exclusive (a pinned program spans one device "
                             "group)")
        if device is not None and group is not None:
            raise ValueError("device= and group= are mutually exclusive")
        self.process = process
        self.batch = batch
        self.sharded = sharded
        self.device = device
        self.group = group
        self.profile = profile      # records "compile" phase on cache miss
        #: placement of stacked input batches (None = primary device); set
        #: by init() and reused by stream_launch as the StreamQueue target
        #: for every input edge
        self.batch_sharding: Optional[jax.sharding.Sharding] = None
        self.launchable: Optional[PureLaunchable] = None
        self._compiled = None

    def init(self) -> "BatchedProcess":
        p = self.process
        app = p.getApp()
        for name in p.kernel_names:
            app.kernels.load(name)
        la = p.launchable()
        n_in = la.n_inputs
        # sharded: the batch dim of any shard_map inside the program
        # (``shard_by_logical``) is split over ``data`` too, so each device
        # computes only its own rows
        batched = jax.vmap(
            la.fn, in_axes=(0,) * n_in + (None,) * len(la.aux_handles),
            spmd_axis_name="data" if self.sharded else None)
        specs = [batched_spec(lay, self.batch) for lay in la.in_layouts]
        specs += p._aux_specs(la)
        in_shardings = out_shardings = None
        mesh = app.mesh
        if self.device is not None or self.group is not None:
            # pinned program: compile under a trivial mesh holding only
            # that device (or the group's (1, m) mesh), everything
            # replicated on it.  The mesh/sharding fingerprints in the
            # compile cache key keep one executable per (device|group,
            # rows) — they never collide with the mesh-sharded or
            # default-placement variants.
            if self.group is not None:
                from repro.launch.mesh import make_group_mesh
                mesh = make_group_mesh(self.group)
            else:
                mesh = _single_device_mesh(self.device)
            pinned = jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec())
            self.batch_sharding = pinned
            in_shardings = (pinned,) * (n_in + len(la.aux_handles))
            out_shardings = pinned
        elif self.sharded:
            mesh = app.mesh
            if mesh is None:
                raise RuntimeError(
                    "sharded streaming needs the app mesh (CLapp.init builds "
                    "one over the selected devices)")
            n_data = int(mesh.shape.get("data", 1))
            if self.batch % n_data != 0:
                raise ValueError(
                    f"batch={self.batch} not divisible by the mesh data-axis "
                    f"size {n_data}; pick batch as a multiple of the device "
                    "count so every device gets whole items")
            self.batch_sharding = app.data_sharding(("data",))
            replicated = app.data_sharding()
            in_shardings = (self.batch_sharding,) * n_in + \
                (replicated,) * len(la.aux_handles)
            out_shardings = self.batch_sharding
        self._compiled = aot_compile(
            batched, specs,
            tag=f"{la.tag}@vmap",
            donate_argnums=(la.donate_idx,) if la.donate_idx is not None
            else (),
            static_key=(la.static_key, _layout_fingerprint(app, la)),
            mesh=mesh,
            in_shardings=in_shardings,
            out_shardings=out_shardings,
            profile=self.profile,
        )
        self.launchable = la
        return self

    def __call__(self, stacked_blobs,
                 aux_blobs: Sequence[jax.Array] = ()) -> jax.Array:
        """One launch for ``batch`` independent Data sets.  Asynchronous —
        the caller decides when (whether) to block on the result.

        ``stacked_blobs`` is one ``(k, nbytes)`` blob per streaming input
        (a lone array is accepted for single-input processes)."""
        if self._compiled is None:
            self.init()
        if isinstance(stacked_blobs, jax.Array) or hasattr(
                stacked_blobs, "shape"):
            stacked_blobs = (stacked_blobs,)
        return self._compiled(*stacked_blobs, *aux_blobs)


class _BatchPlan:
    """Batch executables + ragged-tail policy + split policy (see module
    docstring).

    ``launch_rows(rows)`` decides how many rows the final stacked blob
    should carry: the full ``batch`` (pad by repetition) or exactly
    ``rows`` (compile a second, smaller executable).  ``executable(rows)``
    returns the matching :class:`BatchedProcess`; tail executables are
    built lazily and cached per size (backed by the global compile cache).

    ``split="proportional"`` (requires ``sharded=True``) replaces the
    single mesh-sharded executable with per-device pinned executables:
    :meth:`stack_group` asks the app's
    :class:`~repro.launch.mesh.DeviceProfileRegistry` for a split vector
    once per item group (balanced while profiles are cold), :meth:`place`
    carves each edge's stacked host blob accordingly, and
    :meth:`launch` dispatches one pinned launch per device — recording
    every device's completion time back into the registry so the split
    self-calibrates.  Outputs are bit-identical to the equal split.

    ``lanes=True`` (requires ``sharded=True``) keeps the EQUAL carve but
    routes it through the same per-device pinned machinery: each stacked
    batch is split into balanced per-device sub-batches uploaded on
    per-device double-buffered lanes (one :class:`StreamQueue` per mesh
    device in :func:`stream_launch` — see :class:`_UploadLanes`) instead
    of one global mesh scatter, so every device's host2device upload
    overlaps every other device's compute.  As a side effect the
    batch-divisibility constraint of the mesh-sharded executable is
    lifted (per-device executables carry explicit row counts).  Outputs
    stay bit-identical; ``split="proportional"`` implies the same
    per-device dispatch, so ``lanes`` only changes the ``"equal"`` path.
    """

    def __init__(self, process, batch: int, *, sharded: bool = False,
                 tail_waste_threshold: float = 0.5, split: str = "equal",
                 lanes: bool = False, depth: int = 2,
                 profile: ProfileParameters | None = None):
        if split not in ("equal", "proportional"):
            raise ValueError(
                f"unknown split policy {split!r}: expected 'equal' | "
                "'proportional'")
        if split == "proportional" and not sharded:
            raise ValueError(
                "split='proportional' needs sharded=True — proportional "
                "batch carving distributes work over the app mesh's data-"
                "axis devices")
        if lanes and not sharded:
            raise ValueError(
                "lanes=True needs sharded=True — per-device upload lanes "
                "carve each batch over the app mesh's data-axis devices")
        self.process = process
        self.batch = batch
        self.sharded = sharded
        self.split = split
        self.lanes = lanes
        self.profile = profile
        # staging buffers a batch shape may hold: the queue's depth, the
        # batch being launched and the one being written
        self.staging_cap = depth + 2
        self.tail_waste_threshold = float(tail_waste_threshold)
        self.main = BatchedProcess(process, batch, sharded=sharded,
                                   profile=profile)
        self._tails: dict = {}
        # proportional state: the data-axis devices, the per-(device, rows)
        # pinned executables, per-device aux replicas, and the live
        # completion-timer threads feeding the registry
        self._devices: Tuple[jax.Device, ...] = ()
        self._groups: Tuple[Tuple[jax.Device, ...], ...] = ()
        self._group_by_leader: dict = {}
        self._la: Optional[PureLaunchable] = None
        self._pinned: dict = {}
        self._device_aux_cache: dict = {}
        self._base_aux: Optional[List[jax.Array]] = None
        self._timers: List[Any] = []

    @property
    def proportional(self) -> bool:
        return self.split == "proportional"

    @property
    def per_device(self) -> bool:
        """True when batches are carved into per-device pinned sub-batches
        (proportional split OR equal-split upload lanes) instead of one
        mesh-sharded launch."""
        return self.proportional or self.lanes

    def init(self) -> "_BatchPlan":
        if not self.per_device:
            self.main.init()
            return self
        # per-device mode never compiles the mesh-wide executable; it
        # resolves the launchable + data-axis devices and precompiles the
        # balanced full-batch executables (the cold-start warmup set)
        p = self.process
        app = p.getApp()
        mesh = app.mesh
        if mesh is None:
            raise RuntimeError(
                "per-device batch carving (split='proportional' / "
                "lanes=True) needs the app mesh (CLapp.init builds one "
                "over the selected devices)")
        other = {a: int(s) for a, s in mesh.shape.items()
                 if a not in ("data", "model") and int(s) != 1}
        if other:
            raise ValueError(
                "per-group batch carving (split='proportional' / "
                "lanes=True) needs a (data, model) mesh; "
                f"axes {sorted(other)} are non-trivial")
        for name in p.kernel_names:
            app.kernels.load(name)
        # carve units are data-axis GROUPS: each row of the (data, model)
        # device grid is one model group that co-executes its sub-batch
        # (shard_by_logical partitions per-item grids over the group's
        # model axis).  On a 1D mesh every group is a single device, which
        # reduces exactly to the historical per-device carving.
        n_data = int(dict(mesh.shape).get("data", 1))
        grid = np.asarray(mesh.devices, dtype=object).reshape(n_data, -1)
        self._groups = tuple(tuple(row) for row in grid)
        # group leaders key the profile registry and the executable cache:
        # a group's measured rate is the rate of its co-executing whole
        self._devices = tuple(g[0] for g in self._groups)
        self._group_by_leader = {g[0].id: g for g in self._groups}
        self._la = p.launchable()
        self.precompile(self.batch)
        return self

    @property
    def launchable(self) -> PureLaunchable:
        return self._la if self.per_device else self.main.launchable

    @property
    def batch_sharding(self):
        return None if self.per_device else self.main.batch_sharding

    @property
    def staging(self):
        return self.process.getApp().staging

    @property
    def registry(self):
        return self.process.getApp().device_profiles

    def _data_axis(self) -> int:
        mesh = self.process.getApp().mesh
        return int(mesh.shape.get("data", 1)) if mesh is not None else 1

    def launch_rows(self, rows: int) -> int:
        """Rows the stacked blob for a ``rows``-item group should carry."""
        if rows >= self.batch or rows < 1:
            return self.batch
        waste = (self.batch - rows) / self.batch
        if waste <= self.tail_waste_threshold:
            return self.batch                      # cheap enough: pad
        if self.per_device:
            return rows                 # uneven carve: any row count works
        if self.sharded and rows % self._data_axis() != 0:
            return self.batch                      # devices need whole items
        return rows                                # compile a tail executable

    def executable(self, rows: int) -> BatchedProcess:
        if self.per_device:
            raise RuntimeError(
                "per-device plans have no single batch executable; use "
                "launch()/precompile() (per-device pinned executables)")
        if rows == self.batch:
            return self.main
        bp = self._tails.get(rows)
        if bp is None:
            bp = BatchedProcess(self.process, rows, sharded=self.sharded,
                                profile=self.profile).init()
            self._tails[rows] = bp
        return bp

    def precompile(self, rows: int) -> None:
        """Build whatever executable(s) a ``rows``-item group will need
        BEFORE the launch loop: the (tail) batch executable, or —
        proportional — the pinned per-device executables of the CURRENT
        split vector (balanced fallback + today's measured vector).  For
        the equal split this makes compilation never stall the launch
        loop; under proportional splits the registry keeps refining, so a
        batch whose vector shifted since the last precompile can still
        compile lazily inside the loop — the EMA converges quickly and
        each (device, rows) pair compiles at most once (global cache), so
        the cost amortizes away but is not strictly zero."""
        rows = self.launch_rows(rows)
        if not self.per_device:
            self.executable(rows)
            return
        from repro.launch.mesh import DeviceProfileRegistry
        vectors = {DeviceProfileRegistry.balanced(rows, len(self._devices)),
                   self.split_vector(rows)}
        for vec in vectors:
            for dev, c in zip(self._devices, vec):
                if c:
                    self.device_executable(dev, c)

    def device_executable(self, device: jax.Device, rows: int
                          ) -> BatchedProcess:
        """The pinned executable running ``rows`` items on ``device``'s
        model group (``device`` is the group leader; on a 1D mesh the
        group is just the device).  Lazy; backed by the global compile
        cache."""
        key = (device.id, rows)
        bp = self._pinned.get(key)
        if bp is None:
            group = self._group_by_leader.get(device.id, (device,))
            bp = BatchedProcess(self.process, rows, group=group,
                                profile=self.profile).init()
            self._pinned[key] = bp
        return bp

    def lane_sharding(self, device: jax.Device) -> jax.sharding.Sharding:
        """Placement of one upload lane / aux replica: the leader's model
        group replicated (plain pinned sharding on a 1D mesh)."""
        group = self._group_by_leader.get(device.id, (device,))
        if len(group) == 1:
            from repro.launch.mesh import pinned_sharding
            return pinned_sharding(device)
        from repro.launch.mesh import group_sharding
        return group_sharding(group)

    def split_vector(self, rows: int) -> Tuple[int, ...]:
        """The per-device row counts for one ``rows``-item group: measured-
        proportional when the registry is warm, balanced otherwise (the
        cold/small-batch fallback).  A device explicitly measured/seeded at
        rate 0 (the "broken accelerator stays in the pool" case) is
        excluded from the balanced fallback too — only if EVERY device is
        zero-rated (degenerate) does the balance span the full pool.

        ``lanes=True`` with the equal split ALWAYS returns the plain
        balanced vector over every device — the lanes change the upload
        topology, not the carve policy."""
        devices = self._devices
        if not self.proportional:       # lanes + equal split: balanced
            from repro.launch.mesh import DeviceProfileRegistry
            return DeviceProfileRegistry.balanced(rows, len(devices))
        vec = self.registry.split(rows, devices)
        if vec is not None:
            return vec
        from repro.launch.mesh import DeviceProfileRegistry
        rates = self.registry.rates(devices)
        usable = [i for i, r in enumerate(rates) if r != 0]   # nan: usable
        if not usable:
            usable = list(range(len(devices)))
        balanced = DeviceProfileRegistry.balanced(rows, len(usable))
        out = [0] * len(devices)
        for i, c in zip(usable, balanced):
            out[i] = c
        return tuple(out)

    def stack_group(self, items: Sequence[Tuple[Any, ...]]) -> List[Any]:
        """Stacked per-edge batches for one row-aligned group of items
        (each a per-edge tuple of Data, or of blobs packed at admission):
        ``launch_rows`` decides the row count, padding repeats the last
        item.  The one place the group -> stacked-batch policy lives:
        :class:`_JoinFeed` (stream + manual serve drain) and the
        background serve flush both call it.  An edge whose items all
        live whole on one device stacks there; every other edge is
        written row by row into a staging buffer from the app's pool, a
        Data's host arrays packed straight into their row (one
        ``stream.pack`` span an item), a packed blob copied.  In
        proportional mode the split vector is ALSO decided here — once
        per group — and attached to every edge's stack, so a join's edges
        can never disagree on the carve."""
        rows = self.launch_rows(len(items))
        layouts = self.launchable.in_layouts
        with trace.span("stream.stack", rows=rows):
            cols = [[_source(it[e]) for it in items]
                    for e in range(len(layouts))]
            stacks: List[Any] = []
            host = []
            for e, (col, lay) in enumerate(zip(cols, layouts)):
                if _on_one_device(col):
                    stacks.append(_stack_device(_pad_rows(col, rows), lay))
                else:
                    stacks.append(self.staging.acquire(
                        (rows, lay.total_words), self.staging_cap))
                    host.append(e)
            for r in range(len(items)):
                writes = [(stacks[e][r], cols[e][r]) for e in host]
                if any(isinstance(src, Data) for _, src in writes):
                    with trace.span("stream.pack", row=r) as sp:
                        sp.attrs["bytes"] = sum(_write_row(*w)
                                                for w in writes)
                else:
                    for w in writes:
                        _write_row(*w)
            for e in host:
                stacks[e][len(items):] = stacks[e][len(items) - 1]
        if not self.per_device:
            return stacks
        split = self.split_vector(rows)
        return [_SplitStack(s, split) for s in stacks]

    # ---------------------------------------------------- placement + launch
    def put(self, blob: Any, target: Any) -> jax.Array:
        """One placement (:func:`_put`); rows of a staging buffer are
        reported to the app's pool, which waits for them before handing
        the buffer out again."""
        if not isinstance(blob, np.ndarray):
            return _put(blob, target)
        # a CPU device may adopt an aligned host buffer as its own memory
        # (JAX 0.9 does so even under may_alias=False), and the staging
        # buffer is written again: that placement copies first
        devices = (target.device_set
                   if isinstance(target, jax.sharding.Sharding) else {target})
        out = _put(blob, target,
                   copy=any(d.platform == "cpu" for d in devices))
        self.staging.placed(blob, out)
        return out

    def place(self, item: Any) -> Any:
        """Place one edge's stacked blob: a plain array goes to the
        plan's sharding/device in one ``device_put``; a
        :class:`_SplitStack` is carved into per-device sub-batches (one
        async ``device_put`` per device with a non-zero share)."""
        if not isinstance(item, _SplitStack):
            target = self.batch_sharding or self.process.getApp().device
            return self.put(item, target)
        parts, counts, devices = [], [], []
        off = 0
        for dev, c in zip(self._devices, item.split):
            if c:
                sharding = self.device_executable(dev, c).batch_sharding
                parts.append(self.put(item.blob[off:off + c], sharding))
                counts.append(c)
                devices.append(dev)
            off += c
        return SplitBatch(parts, counts, devices)

    def launch(self, dev_blobs: Sequence[Any],
               aux_blobs: Sequence[jax.Array]) -> Any:
        """One batched launch for one group: the single (sharded)
        executable for plain stacked blobs, or one pinned launch per
        device for a :class:`SplitBatch` — dispatched asynchronously so
        the devices compute concurrently, with a completion timer per
        device feeding measured items/sec back into the registry (and the
        ``"compute"`` phase bucket when the plan carries a profile)."""
        with trace.span("stream.launch"):
            out = self._launch(dev_blobs, aux_blobs)
            # a donated input can no longer be waited for: the staging
            # buffers placed as the inputs are fenced by the outputs
            self.staging.consumed(_parts(dev_blobs), _parts((out,)))
        return out

    def _launch(self, dev_blobs: Sequence[Any],
                aux_blobs: Sequence[jax.Array]) -> Any:
        if not isinstance(dev_blobs[0], SplitBatch):
            t0 = time.perf_counter()
            out = self.executable(int(dev_blobs[0].shape[0]))(
                tuple(dev_blobs), aux_blobs)
            if self.profile is not None and self.profile.enable:
                self._time_completion(None, 0, t0, out)
            return out
        sb0 = dev_blobs[0]
        out_parts = []
        for j, (dev, c) in enumerate(zip(sb0.devices, sb0.counts)):
            bp = self.device_executable(dev, c)   # may compile (cached)
            aux = self._device_aux(dev, aux_blobs)
            t0 = time.perf_counter()
            out = bp(tuple(sb.parts[j] for sb in dev_blobs), aux)
            out_parts.append(out)
            self._time_completion(dev, c, t0, out)
        return SplitBatch(out_parts, sb0.counts, sb0.devices)

    def split_output(self, out: Any) -> List[jax.Array]:
        """Per-item output blobs of one launched group, in item order."""
        if not isinstance(out, SplitBatch):
            return split_batched_blob(out)
        items: List[jax.Array] = []
        for part in out.parts:
            items.extend(split_batched_blob(part))
        return items

    def prepare_aux(self) -> List[jax.Array]:
        """Device aux blobs for this plan's launches (see
        :func:`_prepare_aux`).  Per-device plans (proportional / lanes)
        keep the aux at its stored placement and replicate per device
        lazily — :meth:`_device_aux` — instead of mesh-replicating up
        front."""
        app = self.process.getApp()
        self._base_aux = _prepare_aux(
            app, self.launchable, self.sharded and not self.per_device)
        return self._base_aux

    def _device_aux(self, device: jax.Device,
                    aux_blobs: Sequence[jax.Array]) -> Tuple[jax.Array, ...]:
        """Aux blobs replicated onto one device (cached per device)."""
        if not aux_blobs:
            return ()
        cached = self._device_aux_cache.get(device.id)
        if cached is None:
            target = self.lane_sharding(device)
            cached = tuple(jax.device_put(b, target) for b in aux_blobs)
            self._device_aux_cache[device.id] = cached
        return cached

    # -------------------------------------------------- live rate recording
    def _time_completion(self, device: Optional[jax.Device], items: int,
                         t0: float, out: Any) -> None:
        """Record ``items / (ready - t0)`` into the registry once this
        device's output is ready — from a daemon thread, so the dispatch
        loop (and the double buffer) never blocks on a timer.  With a
        profile attached, the same dispatch→ready wall time also lands in
        the ``"compute"`` phase bucket (``device=None`` records the phase
        only — the single-executable path has no per-device rate)."""
        registry = self.registry
        prof = self.profile

        def timer():
            try:
                jax.block_until_ready(out)
            except Exception:
                return      # output donated/deleted before it was ready
            dt = time.perf_counter() - t0
            if device is not None:
                registry.record(device, items, dt)
            if prof is not None and prof.enable:
                prof.record_phase("compute", dt)

        t = threading.Thread(target=timer, name="device-profile-timer",
                             daemon=True)
        t.start()
        # prune finished timers on every append so the list stays bounded
        # by in-flight launches, not stream length (long-lived proportional
        # servers spawn one timer per device per flush, forever)
        self._timers = [x for x in self._timers if x.is_alive()]
        self._timers.append(t)

    def join_timers(self, timeout: Optional[float] = None) -> None:
        """Wait for outstanding completion timers (callers that already
        blocked on the results pay ~nothing; async callers should skip
        this — the timers record on their own)."""
        for t in self._timers:
            t.join(timeout)
        self._timers = [t for t in self._timers if t.is_alive()]


def _parts(batches: Sequence[Any]) -> List[jax.Array]:
    """The device arrays of launched batches, a SplitBatch's parts each."""
    return [p for b in batches
            for p in (b.parts if isinstance(b, SplitBatch) else (b,))]


def _source(item: Any) -> Any:
    """What one item of one edge is stacked from.  A Data that lives ONLY
    on the device (device-resident pipeline output, or any device-fresh
    Data whose host arrays were never materialised) gives its device blob
    when it sits whole on a single device — the device-to-device
    streaming fast path: chained ``stream()`` calls never bounce
    intermediates through the host.  Multi-device blobs sync to the host
    first (stacking sharded rows device-side would shuffle items across
    devices).  Any other Data is packed from its host arrays; a blob
    packed at admission is stacked as it is."""
    if not isinstance(item, Data):
        return item
    if item.layout is None:
        item.plan()
    if any(a.host is None for a in item):
        blob = item.device_blob
        if (isinstance(blob, jax.Array) and not _is_deleted(blob)
                and blob.ndim == 1 and len(blob.devices()) == 1):
            return blob                         # device-resident: no host trip
        item.sync_to_host()  # raises if there is no device copy either
    return item


def _on_one_device(col: Sequence[Any]) -> bool:
    return (all(isinstance(b, jax.Array) for b in col)
            and len({d for b in col for d in b.devices()}) == 1)


def _stack_device(blobs: Sequence[jax.Array], layout) -> jax.Array:
    """Stack a group resident entirely on ONE device there (``jnp.stack``
    — the device-to-device edge: zero host2device traffic, and the
    downstream :class:`StreamQueue` placement becomes a device-side move
    recorded under the ``"transfer_d2d"`` phase)."""
    want = blob_spec(layout)
    for b in blobs:
        if tuple(b.shape) != want.shape or b.dtype != want.dtype:
            raise ValueError(
                f"device blob shape {tuple(b.shape)}/{b.dtype} does not "
                f"match the arena layout {want.shape}/{want.dtype}")
    import jax.numpy as jnp
    return jnp.stack(blobs)


def _write_row(row: np.ndarray, src: Any) -> int:
    """Write one item into its row of a staging buffer: a Data's host
    arrays packed in place, a blob (stray device blobs pulled back once)
    copied.  Returns the row's bytes."""
    if isinstance(src, Data):
        src.pack_host(out=row)
        return row.nbytes
    blob = np.asarray(src)
    if blob.shape != row.shape or blob.dtype != row.dtype:
        raise ValueError(
            f"blob shape {blob.shape}/{blob.dtype} does not match layout "
            f"{row.shape}/{row.dtype}")
    row[...] = blob
    return row.nbytes


def normalize_stream_item(item: Any, la: PureLaunchable,
                          *, what: str = "dataset") -> Tuple[Data, ...]:
    """One stream item -> one Data per streaming input, positionally
    ordered to match ``la.in_names``/``la.in_layouts``.

    Accepted forms: a lone :class:`Data` (single-input launchables only),
    a ``{input name -> Data}`` mapping, or a positional tuple/list.  The
    error messages name the input edges so a mis-shaped join is
    diagnosable."""
    names = la.in_names
    if isinstance(item, Data):
        if la.n_inputs != 1:
            raise ValueError(
                f"{what} is a single Data but the launchable has "
                f"{la.n_inputs} streaming inputs {list(names)}; pass one "
                "Data per input edge as a mapping {name: Data} or a "
                "positional tuple")
        return (item,)
    if isinstance(item, Mapping):
        missing = [n for n in names if n not in item]
        extra = [n for n in item if n not in names]
        if missing or extra:
            raise ValueError(
                f"{what} mapping does not match the streaming inputs "
                f"{list(names)}: missing {missing}, unknown {extra}")
        return tuple(item[n] for n in names)
    if isinstance(item, (tuple, list)):
        if len(item) != la.n_inputs:
            raise ValueError(
                f"{what} supplies {len(item)} Data for {la.n_inputs} "
                f"streaming inputs {list(names)}")
        return tuple(item)
    raise TypeError(
        f"{what} must be a Data, a {{input name -> Data}} mapping, or a "
        f"tuple (got {type(item).__name__})")


def _checked_edges(item: Tuple[Data, ...], la: PureLaunchable,
                   *, what: str = "dataset",
                   names: Optional[Sequence[str]] = None,
                   err: type = ValueError) -> Tuple[Data, ...]:
    """One normalized item, layout-checked against every input edge
    (mismatches name the offending edge).  The ONE validate loop shared
    by streaming and serving — ``names`` overrides the display names
    (serving shows graph edge names instead of launchable input names),
    ``err`` the exception type."""
    for name, layout, d in zip(names or la.in_names, la.in_layouts, item):
        if d.layout is None:
            d.plan()
        if d.layout != layout:
            raise err(
                f"{what} layout for input edge {name!r} ({d.layout}) does "
                f"not match the wired layout {layout}; all streamed Data "
                "sets must be homogeneous per edge")
    return item


def _edge_blobs(item: Tuple[Data, ...], la: PureLaunchable,
                **check: Any) -> Tuple[Any, ...]:
    """Per-edge blobs of one normalized, checked item, packed now (a
    serving request is packed at admission, before its batch exists): a
    packed host blob, or a device-resident Data's device blob."""
    blobs = []
    for d in _checked_edges(item, la, **check):
        src = _source(d)
        blobs.append(src.pack_host() if isinstance(src, Data) else src)
    return tuple(blobs)


def _pad_rows(blobs: List[Any], rows: int) -> List[Any]:
    """Pad a group's blob list to ``rows`` by repeating the last item
    (padded outputs are dropped downstream)."""
    return blobs + [blobs[-1]] * (rows - len(blobs))


class _JoinFeed:
    """Row-aligned per-edge batch feeds sharing ONE group plan.

    ``groups`` yields lists of per-item tuples (one Data or blob per
    input edge, at most ``plan.batch`` items per list).  Each edge's
    :meth:`feed` generator yields that edge's stacked batch for exactly
    the same item groups — built by :meth:`_BatchPlan.stack_group`, so
    row count and padding are decided once for ALL edges — and zipping
    the per-edge StreamQueues produces row-aligned batches for a joined
    launch.  Whichever queue prefetches furthest forms the shared groups;
    a group's stacked blobs are released once every edge consumed them
    (memory stays bounded by queue depth, not stream length).
    """

    def __init__(self, plan: _BatchPlan,
                 groups: Iterator[List[Tuple[Any, ...]]]):
        self.plan = plan
        self.n_edges = plan.launchable.n_inputs
        self._it = groups
        self._formed: List[Optional[List[np.ndarray]]] = []
        self._reads: List[int] = []
        self._done = False

    def _ensure(self, pos: int) -> bool:
        while len(self._formed) <= pos and not self._done:
            try:
                items = next(self._it)
            except StopIteration:
                self._done = True
                return False
            self._formed.append(self.plan.stack_group(items))
            self._reads.append(0)
        return pos < len(self._formed)

    def feed(self, edge: int) -> Iterator[np.ndarray]:
        pos = 0
        while self._ensure(pos):
            stacked = self._formed[pos][edge]
            self._reads[pos] += 1
            if self._reads[pos] == self.n_edges:
                self._formed[pos] = None     # all edges consumed: release
            pos += 1
            yield stacked


class _Fanout:
    """Lockstep tee of one iterator into ``n`` branches.  Items are
    buffered only while some branch still needs them — the head is
    released once EVERY branch has consumed it, so memory stays bounded
    by the branches' skew (lane queue depth), not stream length."""

    def __init__(self, it: Iterator[Any], n: int):
        self._it = iter(it)
        self._buf: deque = deque()
        self._base = 0              # absolute stream index of _buf[0]
        self._pos = [0] * n         # absolute per-branch read positions
        self._done = False

    def branch(self, j: int) -> Iterator[Any]:
        while True:
            idx = self._pos[j]
            while idx - self._base >= len(self._buf):
                if self._done:
                    return
                try:
                    self._buf.append(next(self._it))
                except StopIteration:
                    self._done = True
                    return
            item = self._buf[idx - self._base]
            self._pos[j] = idx + 1
            while self._buf and self._base < min(self._pos):
                self._buf.popleft()       # every branch is past the head
                self._base += 1
            yield item


class _UploadLanes:
    """Per-device double-buffered upload lanes for ONE input edge.

    The ``lanes=True`` upload topology: instead of one global mesh
    scatter (``sharded=True``) or one placement call carving the whole
    stacked blob (:meth:`_BatchPlan.place`), the edge's feed of
    :class:`_SplitStack` groups is teed across one pinned
    :class:`StreamQueue` PER mesh device — lane *j* uploads rows
    ``off_j : off_j + split[j]`` of every group to its device, so each
    device's host2device transfer is dispatched (and double-buffered)
    independently, overlapping every other device's upload and compute.
    ``__next__`` zips the lanes' heads back into one :class:`SplitBatch`
    for :meth:`_BatchPlan.launch` (zero-row lanes ship an empty slice to
    stay in lockstep but are excluded from the batch).  Quacks like
    :class:`StreamQueue` where ``stream_launch`` cares: iteration +
    ``sync()``.
    """

    def __init__(self, plan: _BatchPlan, feed: Iterator[_SplitStack],
                 depth: int = 2,
                 profile: ProfileParameters | None = None):
        devices = plan._devices
        if not devices:
            raise RuntimeError("_UploadLanes needs an initialized per-device "
                               "plan (lanes=True)")
        # one extra branch re-reads each group's split vector for __next__
        fan = _Fanout(feed, len(devices) + 1)

        def lane_rows(j: int) -> Iterator[Any]:
            for ss in fan.branch(j):
                off = sum(ss.split[:j])
                yield ss.blob[off:off + ss.split[j]]

        self._devices = devices
        self._lanes = [
            StreamQueue(lane_rows(j), depth=depth, profile=profile,
                        device=functools.partial(
                            plan.put, target=plan.lane_sharding(dev)))
            for j, dev in enumerate(devices)]
        self._splits = fan.branch(len(devices))

    def __iter__(self) -> "_UploadLanes":
        return self

    def __next__(self) -> SplitBatch:
        ss = next(self._splits)
        heads = [next(q) for q in self._lanes]
        parts, counts, devs = [], [], []
        for blob, c, dev in zip(heads, ss.split, self._devices):
            if c:
                parts.append(blob)
                counts.append(c)
                devs.append(dev)
        return SplitBatch(parts, counts, devs)

    def sync(self) -> None:
        for q in self._lanes:
            q.sync()


def _prepare_aux(app, la: PureLaunchable, sharded: bool) -> List[jax.Array]:
    """Device aux blobs in positional order, replicated over the mesh when
    sharded.  Shared by stream_launch and the serving loop."""
    replicated = app.data_sharding() if sharded else None
    aux_blobs: List[jax.Array] = []
    for h in la.aux_handles:
        d = app.getData(h)
        if d.device_blob is None:
            # dispatch-only upload: the aux transfer rides alongside the
            # first input batch's transfer; the launch consuming the blob is
            # the implicit sync point (CLapp tracks the handle in flight)
            app.host2device(h, wait=False)
        blob = d.device_blob
        if replicated is not None and not blob.sharding.is_equivalent_to(
                replicated, blob.ndim):
            # the sharded program broadcasts aux across the whole mesh.  The
            # replicated copy is CALL-LOCAL: the Data keeps its stored blob
            # at the default placement, so later unsharded launch()/stream()
            # calls (compiled for single-device inputs) still match.
            blob = jax.device_put(blob, replicated)
        aux_blobs.append(blob)
    return aux_blobs


def stream_launch(process, datasets: Sequence[Any], *, batch: int = 1,
                  depth: int = 2, sync: bool = False, sharded: bool = False,
                  tail_waste_threshold: float = 0.5, split: str = "equal",
                  lanes: bool = False,
                  profile: ProfileParameters | None = None) -> List[Data]:
    """Run ``datasets`` through ``process`` batched + double-buffered.

    See :meth:`repro.core.process.Process.stream` for the public contract
    (including multi-input items: one Data per input edge, as a mapping or
    tuple), the module docstring for the ``sharded=True`` placement
    contract, the per-edge join feeds, the ragged-tail policy
    (``tail_waste_threshold``), the ``split="proportional"`` batch-
    carving policy and the ``lanes=True`` per-device upload lanes.
    """
    datasets = list(datasets)
    if not datasets:
        return []
    app = process.getApp()
    with trace.span("stream.plan"):
        plan = _BatchPlan(process, batch, sharded=sharded,
                          tail_waste_threshold=tail_waste_threshold,
                          split=split, lanes=lanes, depth=depth,
                          profile=profile).init()
        la = plan.launchable

        aux_blobs = plan.prepare_aux()

        tail = len(datasets) % batch
        if tail:
            # compile the tail executable(s) (if the policy wants them)
            # BEFORE the launch loop, so compilation never stalls the
            # double buffer
            plan.precompile(tail)

    # one row-aligned feed per input edge — a multi-input launchable gets
    # per-edge StreamQueues whose batches are zipped before each launch.
    # Items are packed lazily, into their rows, as the queues pull (memory
    # stays bounded by queue depth, as in the single-input path)
    def groups() -> Iterator[List[Tuple[Data, ...]]]:
        buf: List[Tuple[Data, ...]] = []
        for i, d in enumerate(datasets):
            what = f"datasets[{i}]"
            buf.append(_checked_edges(
                normalize_stream_item(d, la, what=what), la, what=what))
            if len(buf) == batch:
                yield buf
                buf = []
        if buf:
            yield buf

    feed = _JoinFeed(plan, groups())
    if plan.lanes:
        # per-device upload lanes: one pinned double-buffered queue per
        # mesh device per edge, instead of one placement point per edge
        queues: List[Any] = [
            _UploadLanes(plan, feed.feed(e), depth=depth, profile=profile)
            for e in range(la.n_inputs)]
    else:
        queues = [StreamQueue(feed.feed(e), device=plan.place,
                              depth=depth, profile=profile)
                  for e in range(la.n_inputs)]
    t0 = time.perf_counter()
    out_batches: List[Any] = []
    for dev_blobs in zip(*queues):    # batch i+1 transfers while i computes
        out_batches.append(plan.launch(dev_blobs, aux_blobs))
    # settle the aux uploads' coherence bookkeeping: by now every launch has
    # consumed the aux blobs, so this only waits on the transfers themselves
    app.wait_transfers(la.aux_handles)

    # per-item output blobs: rows sliced shard-locally, so with sharded=True
    # (and per-device under split="proportional") each item's result stays
    # on the device that computed it
    with trace.span("stream.split"):
        per_item: List[jax.Array] = []
        for b in out_batches:
            per_item.extend(plan.split_output(b))

        results: List[Data] = []
        for i in range(len(datasets)):
            out = Data.from_layout(la.out_layout)
            out.device_blob = per_item[i]
            out.coherence = Coherence.DEVICE_FRESH
            results.append(out)
    if sync:
        for r in results:
            r.sync_to_host()          # np.asarray blocks per result
    if profile is not None and profile.enable:
        jax.block_until_ready([r.device_blob for r in results])
        profile.record(time.perf_counter() - t0)
    if sync or (profile is not None and profile.enable):
        # the results are ready, so the per-device completion timers are
        # about to finish — settle them now and callers observe a fully
        # refined DeviceProfileRegistry on return.  Async callers
        # (sync=False, no profile) keep the no-blocking contract; their
        # timers record on their own as results land.
        plan.join_timers()
    return results
