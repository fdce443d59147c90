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

* :func:`launch_rows` / :class:`_BatchPlan` — the ragged-tail policy.  A
  final batch with fewer than ``batch`` items is either padded by
  repeating the last item (cheap when the waste is small — no second
  compile) or, when the padding waste fraction exceeds
  :data:`TAIL_WASTE`, executed through a SECOND, smaller executable
  compiled just for the tail size.  Tail executables go through the same
  global compile cache, so a recurring tail size (e.g. a serving loop
  that often flushes half-full batches) compiles once.  Under
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

Phase profiling
---------------

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

#: transfers a :class:`StreamQueue` of the executor dispatches ahead
#: (double buffering)
DEPTH = 2
#: largest padding waste fraction a ragged tail is padded at; above it the
#: tail runs through an executable compiled for its own row count
TAIL_WASTE = 0.5


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
    ``device_put``, and reports every staging buffer it placed to the
    app's pool.

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
        phase = "transfer" if isinstance(item, np.ndarray) else "transfer_d2d"
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
    """

    def __init__(self, process, batch: int, *, sharded: bool = False,
                 profile: ProfileParameters | None = None):
        if batch < 1:
            raise ValueError(f"batch must be >= 1, got {batch}")
        self.process = process
        self.batch = batch
        self.sharded = sharded
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
        if self.sharded:
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


def launch_rows(rows: int, batch: int, data_axis: int = 1) -> int:
    """Rows the stacked blob of a ``rows``-item group carries: ``batch``
    (the group padded by repetition) or ``rows`` (a tail executable of
    its own).  A group that fills the batch, or an empty one, takes
    ``batch``; a ragged tail pads while its waste is at most
    :data:`TAIL_WASTE`, or when ``rows`` is not a multiple of
    ``data_axis`` (a sharded launch gives every device whole items)."""
    if rows >= batch or rows < 1:
        return batch
    if (batch - rows) / batch <= TAIL_WASTE or rows % data_axis:
        return batch
    return rows


class _BatchPlan:
    """Batch executables + ragged-tail policy (see module docstring).

    :meth:`launch_rows` decides how many rows the stacked blob of a group
    carries (:func:`launch_rows`); ``executable(rows)`` returns the
    matching :class:`BatchedProcess` — tail executables are built lazily
    and cached per size (backed by the global compile cache)."""

    def __init__(self, process, batch: int, *, sharded: bool = False,
                 profile: ProfileParameters | None = None):
        self.process = process
        self.batch = batch
        self.sharded = sharded
        self.profile = profile
        # staging buffers a batch shape may hold: the queue's depth, the
        # batch being launched and the one being written
        self.staging_cap = DEPTH + 2
        self.main = BatchedProcess(process, batch, sharded=sharded,
                                   profile=profile)
        self._tails: dict = {}

    def init(self) -> "_BatchPlan":
        self.main.init()
        return self

    @property
    def launchable(self) -> PureLaunchable:
        return self.main.launchable

    @property
    def batch_sharding(self):
        return self.main.batch_sharding

    @property
    def staging(self):
        return self.process.getApp().staging

    def launch_rows(self, rows: int) -> int:
        """Rows the stacked blob for a ``rows``-item group should carry."""
        mesh = self.process.getApp().mesh
        data_axis = (int(mesh.shape.get("data", 1))
                     if self.sharded and mesh is not None else 1)
        return launch_rows(rows, self.batch, data_axis)

    def executable(self, rows: int) -> BatchedProcess:
        if rows == self.batch:
            return self.main
        bp = self._tails.get(rows)
        if bp is None:
            bp = BatchedProcess(self.process, rows, sharded=self.sharded,
                                profile=self.profile).init()
            self._tails[rows] = bp
        return bp

    def precompile(self, rows: int) -> None:
        """Build the (tail) batch executable a ``rows``-item group will
        need BEFORE the launch loop, so compilation never stalls it."""
        self.executable(self.launch_rows(rows))

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
        ``stream.pack`` span an item), a packed blob copied."""
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
        return stacks

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

    def place(self, item: Any) -> jax.Array:
        """Place one edge's stacked blob on the plan's sharding (or the
        app's device) in one ``device_put``."""
        return self.put(item, self.batch_sharding
                        or self.process.getApp().device)

    def launch(self, dev_blobs: Sequence[jax.Array],
               aux_blobs: Sequence[jax.Array]) -> jax.Array:
        """One batched launch for one group, through the executable of
        its row count."""
        with trace.span("stream.launch"):
            out = self.executable(int(dev_blobs[0].shape[0]))(
                tuple(dev_blobs), aux_blobs)
            # a donated input can no longer be waited for: the staging
            # buffers placed as the inputs are fenced by the outputs
            self.staging.consumed(list(dev_blobs), [out])
        return out

    def prepare_aux(self) -> List[jax.Array]:
        """Device aux blobs in positional order, replicated over the mesh
        when sharded.  Shared by stream_launch and the serving loop."""
        app = self.process.getApp()
        replicated = app.data_sharding() if self.sharded else None
        aux_blobs: List[jax.Array] = []
        for h in self.launchable.aux_handles:
            d = app.getData(h)
            if d.device_blob is None:
                # dispatch-only upload: the aux transfer rides alongside the
                # first input batch's transfer; the launch consuming the
                # blob is the implicit sync point (CLapp tracks the handle
                # in flight)
                app.host2device(h, wait=False)
            blob = d.device_blob
            if replicated is not None and not blob.sharding.is_equivalent_to(
                    replicated, blob.ndim):
                # the sharded program broadcasts aux across the whole mesh.
                # The replicated copy is CALL-LOCAL: the Data keeps its
                # stored blob at the default placement, so later unsharded
                # launch()/stream() calls (compiled for single-device
                # inputs) still match.
                blob = jax.device_put(blob, replicated)
            aux_blobs.append(blob)
        return aux_blobs


def _time_compute(profile: ProfileParameters, t0: float,
                  out: jax.Array) -> threading.Thread:
    """Record ``out``'s dispatch→ready wall time (from ``t0``) into the
    ``"compute"`` phase once it is ready — from a daemon thread, so the
    dispatch loop never blocks on it."""
    def timer():
        try:
            jax.block_until_ready(out)
        except Exception:
            return      # output donated/deleted before it was ready
        profile.record_phase("compute", time.perf_counter() - t0)

    t = threading.Thread(target=timer, name="compute-timer", daemon=True)
    t.start()
    return t


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


def stream_launch(process, datasets: Sequence[Any], *, batch: int = 1,
                  sync: bool = False, sharded: bool = False,
                  profile: ProfileParameters | None = None) -> List[Data]:
    """Run ``datasets`` through ``process`` batched + double-buffered.

    See :meth:`repro.core.process.Process.stream` for the public contract
    (including multi-input items: one Data per input edge, as a mapping or
    tuple), the module docstring for the ``sharded=True`` placement
    contract, the per-edge join feeds and the ragged-tail policy.
    """
    datasets = list(datasets)
    if not datasets:
        return []
    app = process.getApp()
    profiling = profile is not None and profile.enable
    with trace.span("stream.plan"):
        plan = _BatchPlan(process, batch, sharded=sharded,
                          profile=profile).init()
        la = plan.launchable

        aux_blobs = plan.prepare_aux()

        tail = len(datasets) % batch
        if tail:
            # compile the tail executable (if the policy wants one) BEFORE
            # the launch loop, so compilation never stalls the double buffer
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
    queues = [StreamQueue(feed.feed(e), device=plan.place, depth=DEPTH,
                          profile=profile)
              for e in range(la.n_inputs)]
    t0 = time.perf_counter()
    out_batches: List[jax.Array] = []
    timers: List[threading.Thread] = []
    for dev_blobs in zip(*queues):    # batch i+1 transfers while i computes
        t_launch = time.perf_counter()
        out_batches.append(plan.launch(dev_blobs, aux_blobs))
        if profiling:
            timers.append(_time_compute(profile, t_launch, out_batches[-1]))
    # settle the aux uploads' coherence bookkeeping: by now every launch has
    # consumed the aux blobs, so this only waits on the transfers themselves
    app.wait_transfers(la.aux_handles)

    # per-item output blobs: rows sliced shard-locally, so with sharded=True
    # each item's result stays on the device that computed it
    with trace.span("stream.split"):
        per_item: List[jax.Array] = []
        for b in out_batches:
            per_item.extend(split_batched_blob(b))

        results: List[Data] = []
        for i in range(len(datasets)):
            out = Data.from_layout(la.out_layout)
            out.device_blob = per_item[i]
            out.coherence = Coherence.DEVICE_FRESH
            results.append(out)
    if sync:
        for r in results:
            r.sync_to_host()          # np.asarray blocks per result
    if profiling:
        jax.block_until_ready([r.device_blob for r in results])
        profile.record(time.perf_counter() - t0)
        for t in timers:              # the results are ready: timers end now
            t.join()
    return results
