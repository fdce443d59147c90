"""Process — the paper's algorithm abstraction (§III-A.3b, §III-B).

A Process is a mathematical operator: typed input/output **ports**, launch
parameters, and a pure :meth:`Process.apply`.  A process can have **many
streaming inputs**, not just one: every non-aux port other than ``"out"``
is an input port, ordered with the primary ``"in"`` first.  Input ports are
*streamed* (batched per item in the stream/serve modes, joinable to other
nodes' output edges in a Pipeline); ``Port(aux=True)`` ports remain
genuinely static side parameters (bound to concrete Data, broadcast across
every batch).  There are two ways to wire operators to Data, and one
engine underneath both:

* **Declarative (preferred)** — a Process declares its contract as typed
  ports (``ports = {"in": Port(...), "out": Port(...), "smaps":
  Port(optional=True)}``) and is wired *functionally*::

      fft  = FFT(app).bind(infile="kspace", outfile="xspace",
                           params=FFTParams("backward", var="kdata"))
      prod = ComplexElementProd(app).bind(infile="xspace",
                                          smaps="smaps")  # fan-in join
      pipe = Pipeline.from_graph(app, [fft, prod, coil_combine])
      out  = pipe.run({"kspace": kd, "smaps": sm})  # mode="launch"
      outs = pipe.run(items, mode="stream", batch=8, sharded=True)
      outs = pipe.run(requests, mode="serve", batch=8)

  ``bind()`` maps ports to named graph edges (or concrete Data); an input
  port bound to a named edge becomes a true streaming input (a pipeline
  *join*), while concrete Data on the same port reproduces the legacy
  static-broadcast behaviour bit-identically.  The
  :class:`~repro.core.graph.Pipeline` shape/dtype-checks the whole graph
  against every port at *bind/build* time — a mis-wired graph is rejected
  with :class:`PortError`/:class:`~repro.core.graph.GraphError` before
  anything compiles or launches.  See :mod:`repro.core.graph` and
  ``docs/pipeline.md``.

* **Imperative (legacy, deprecated)** — the paper-style mutate-then-init
  protocol: ``set_in_handle``/``set_out_handle``/``set_aux_handle`` followed
  by ``init()``/``launch()``.  The setters still work (bit-identical
  results) but emit a ``DeprecationWarning`` once per process instance.

The paper's two key properties hold under both front-ends:

* **init/launch split** — ``init()`` does the one-time expensive setup.  In
  OpenCL that is kernel argument setup and (for clFFT) plan baking; in JAX
  it is tracing + XLA compilation.  ``init()`` AOT-compiles
  (``jit(...).lower(...).compile()``) and caches the executable;
  ``launch()`` only executes it.  ``Pipeline`` runs the same init at
  ``build()``, so chains and loops keep the zero-per-iteration-overhead
  property in all three execution modes.

* **zero-copy chaining** — Data stays on the device as one arena blob.
  A stage's output handle doubling as the next stage's input handle moves
  no bytes; in-place processes (out == in) *donate* the input buffer to
  XLA so not even a device-side copy is made.

Beyond the paper: a :class:`ProcessChain` can be *fused* — the composed
stages are traced as one program, letting XLA fuse across stage boundaries
(impossible with OpenCL's per-kernel dispatch); and every Process exposes
:meth:`Process.stream` — many independent Data sets through one compiled
program, batched via ``vmap`` and double-buffered (see
:mod:`repro.core.stream`), with ragged-tail batches recompiled small when
padding would be wasteful.  A multi-input process streams *tuples* (or
``{input name -> Data}`` mappings): every input edge gets its own
row-aligned batch queue, zipped into one joined launch.

The lowered form, :class:`PureLaunchable`, is genuinely multi-input:
``fn(*in_blobs, *aux_args) -> blob_out`` with ordered ``in_names`` /
``in_layouts`` / ``in_handles`` instead of a privileged primary input —
the primary ``"in"`` port is simply position 0.  An aux arg is an arena
blob the program unpacks, or, for a static Data (the LM weights), the
tuple of its entry arrays, taken as they are.  Secondary input views are
delivered to :meth:`Process.apply` through the same ``aux`` argument slot
the static-broadcast path uses, which is what makes a streamed join
bit-identical to the legacy aux binding by construction.

Donation safety: a program compiled in-place (``out_handle`` equal to one
of its input handles) donates that input buffer to XLA.  ``launch()``
refuses to run such a program after the handles were re-wired so the
donated input is no longer the output without ``init()`` (use-after-donate
would silently hand the caller's live blob to XLA); see
:class:`DonatedBufferError`.
"""
from __future__ import annotations

import dataclasses
import functools
import re
import time
import warnings
from typing import Any, Callable, Dict, List, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import trace
from .app import CLapp, DataHandle, INVALID_HANDLE
from .arena import (ArenaLayout, blob_spec, entry_specs, pack_device,
                    unpack_device)
from .sync import Coherence


@dataclasses.dataclass
class ProfileParameters:
    """Collects per-launch wall times when enabled (paper's profiling arg).

    All statistics are total functions: with zero recorded samples (e.g.
    ``launch()`` was never profiled) they return ``float("nan")`` instead
    of dividing by zero.

    Beyond the plain per-launch wall times (``samples``), a profile can
    carry a **phase breakdown**: named wall-time buckets recorded via
    :meth:`record_phase` — the streaming executor and ``aot_compile`` use
    the conventional names ``"transfer"`` (host→device uploads),
    ``"compile"`` (trace+lower+compile on a cache miss) and ``"compute"``
    (executable run to completion), so benchmarks can show where a
    launch's time actually goes.
    """

    enable: bool = False
    samples: List[float] = dataclasses.field(default_factory=list)
    phases: Dict[str, List[float]] = dataclasses.field(default_factory=dict)

    def record(self, seconds: float) -> None:
        if self.enable:
            self.samples.append(seconds)

    def record_phase(self, phase: str, seconds: float) -> None:
        """Append one wall-time sample to the named phase bucket."""
        if self.enable:
            self.phases.setdefault(phase, []).append(seconds)

    def phase_total(self, phase: str) -> float:
        """Total seconds recorded under ``phase`` (0.0 when absent — a
        phase that never ran costs nothing, unlike the nan statistics)."""
        return float(sum(self.phases.get(phase, ())))

    def phase_totals(self) -> Dict[str, float]:
        """``{phase -> total seconds}`` over every recorded bucket."""
        return {k: self.phase_total(k) for k in self.phases}

    def mean(self) -> float:
        """Mean recorded wall time; ``nan`` when nothing was profiled."""
        if not self.samples:
            return float("nan")
        return float(sum(self.samples) / len(self.samples))

    def percentile(self, p: float) -> float:
        """p-th percentile of the samples; ``nan`` when nothing was
        profiled.  Used by the serving-latency benchmark (p50/p99)."""
        if not self.samples:
            return float("nan")
        return float(np.percentile(np.asarray(self.samples), p))

    def p50(self) -> float:
        return self.percentile(50.0)

    def p99(self) -> float:
        return self.percentile(99.0)


@dataclasses.dataclass
class _PhaseView:
    """Phase-only view of a parent profile: :meth:`record_phase` forwards,
    :meth:`record` is dropped.  A staged chain hands this to its per-stage
    launches so the chain records ONE wall-time sample per launch (the
    contract ``benchmarks/paper_tables.py`` averages over) while the
    stages still contribute their transfer/compute phase breakdown."""

    parent: ProfileParameters

    @property
    def enable(self) -> bool:
        return self.parent.enable

    def record(self, seconds: float) -> None:
        pass

    def record_phase(self, phase: str, seconds: float) -> None:
        self.parent.record_phase(phase, seconds)


class PortError(TypeError):
    """A Data set does not satisfy a Process port declaration, or a node
    was bound to a port that does not exist.  Raised at bind/build time —
    before any compilation or launch."""


@dataclasses.dataclass(frozen=True)
class Port:
    """Typed declaration of one Process input/output/aux slot.

    Processes declare their wiring contract as a class attribute::

        class ComplexElementProd(Process):
            ports = {"in":    Port(names=("kdata",)),
                     "out":   Port(names=("kdata",)),
                     "smaps": Port(optional=True)}

    The reserved port names ``"in"`` and ``"out"`` are the primary input
    and output.  Every other ``Port()`` entry (``aux=False``) is an
    **additional streaming input** keyed by its own name: it may be bound
    to a named graph edge (a pipeline join — batched per item in the
    stream/serve modes) or to concrete Data (static, broadcast — the
    legacy aux behaviour, bit-identical).  ``Port(aux=True)`` entries are
    aux-only side parameters: always static, never an edge.  ``validate()``
    checks a candidate Data's specs against the declaration and raises
    :class:`PortError` on mismatch — this is what lets
    :class:`~repro.core.graph.Pipeline` reject mis-wired graphs at bind
    time instead of at launch.
    """

    aux: bool = False            # static side input (broadcast, never an edge)
    optional: bool = False       # non-primary ports: may stay unbound
    names: Optional[Tuple[str, ...]] = None  # NDArray names the Data must hold
    dtype: Any = None            # required dtype (concrete or abstract kind)
    ndim: Optional[int] = None   # required rank of the checked arrays
    doc: str = ""

    def __post_init__(self):
        if self.names is not None:
            object.__setattr__(self, "names", tuple(self.names))

    def validate(self, specs: Mapping[str, jax.ShapeDtypeStruct], *,
                 owner: str = "?", port: str = "?") -> None:
        """Check ``{array name -> ShapeDtypeStruct}`` against this port."""
        where = f"{owner}.ports[{port!r}]"
        if self.names:
            missing = [n for n in self.names if n not in specs]
            if missing:
                raise PortError(
                    f"{where}: Data is missing required arrays {missing} "
                    f"(got {sorted(specs)})")
        for name in (self.names or tuple(specs)):
            s = specs[name]
            if self.dtype is not None and not jnp.issubdtype(
                    jnp.dtype(s.dtype), self.dtype):
                raise PortError(
                    f"{where}: array {name!r} has dtype {s.dtype}, "
                    f"expected {self.dtype}")
            if self.ndim is not None and len(s.shape) != self.ndim:
                raise PortError(
                    f"{where}: array {name!r} has shape {tuple(s.shape)} "
                    f"(ndim {len(s.shape)}), expected ndim {self.ndim}")


# --------------------------------------------------------------------------
# AOT compile cache: the framework-level analogue of clFFT plan reuse.
# --------------------------------------------------------------------------
_COMPILE_CACHE: Dict[Any, Any] = {}

# Mesh the current aot_compile() is lowering under (None outside a compile).
# This is the sharding-propagation hook behind the logical-axis annotation
# layer: `repro.launch.mesh.shard_by_logical` resolves it at trace time, so
# ONE annotated apply() body lowers model-sharded under the app's 2D mesh,
# and unsharded (a total no-op) under a mesh of one device.  A plain module
# global (not a contextvar): aot_compile holds no locks and the compile
# cache is only mutated from the thread that traces, which is the thread
# that reads this.
_CURRENT_COMPILE_MESH: Any = None


def current_compile_mesh():
    """The mesh of the in-progress AOT lowering (None outside one)."""
    return _CURRENT_COMPILE_MESH


def compile_cache_stats() -> Tuple[int, int]:
    """``(hits, misses)`` of :func:`aot_compile`'s executable cache over
    the process's life (``repro_compile_cache_{hits,misses}_total``)."""
    return (int(trace.CACHE_HITS.total()), int(trace.CACHE_MISSES.total()))


def program_name(tag: str) -> str:
    """The name of the function :func:`aot_compile` jits for ``tag``: the
    tag without its leading module path, the characters XLA does not keep
    in a module name (``[]@=,:`` and the like) replaced by ``_``.  So a
    program in a profiler trace reads ``jit_DecodeStep`` or
    ``jit_ProcessChain_FusedMRIRecon_vmap``, not ``jit_fn``."""
    name = re.sub(r"^(?:\w+\.)+(?=\w)", "", tag)
    return re.sub(r"[^\w.-]+", "_", name).strip("_") or "program"


def _sharding_key(sharding) -> Any:
    """Hashable fingerprint of one sharding annotation (or None)."""
    if sharding is None:
        return None
    if isinstance(sharding, jax.sharding.NamedSharding):
        return (_mesh_key(sharding.mesh), str(sharding.spec))
    return repr(sharding)


def _mesh_key(mesh) -> Any:
    """Full mesh fingerprint: axis names/sizes AND every device id, in mesh
    order.  Two meshes over different device sets — or the same set reordered
    — must NOT share a cached executable (it would be pinned to the wrong
    devices), so fingerprinting only the first device is not enough."""
    if mesh is None:
        return None
    return (
        tuple(mesh.axis_names),
        tuple(mesh.devices.shape),
        tuple(int(d.id) for d in mesh.devices.flat),
    )


def _cache_key(tag: str, specs, donate: bool, static_key: Any, mesh,
               in_shardings=None, out_shardings=None) -> Any:
    spec_key = tuple(
        (s.shape, str(s.dtype)) for s in jax.tree_util.tree_leaves(specs)
    )
    shard_key = (
        tuple(_sharding_key(s) for s in jax.tree_util.tree_leaves(in_shardings)),
        tuple(_sharding_key(s) for s in jax.tree_util.tree_leaves(out_shardings)),
    )
    return (tag, spec_key, donate, static_key, _mesh_key(mesh), shard_key)


def aot_compile(fn: Callable, specs: Sequence[Any], *, tag: str,
                donate_argnums: Tuple[int, ...] = (), static_key: Any = None,
                mesh=None, in_shardings=None, out_shardings=None,
                profile: "ProfileParameters | None" = None):
    """AOT-compile ``fn`` for ``specs``; cached (the paper's "init once").

    ``profile`` records the trace+lower+compile wall time into the
    ``"compile"`` phase bucket on a cache MISS (hits cost nothing and
    record nothing), so per-launch phase breakdowns can separate one-time
    compilation from steady-state compute."""
    key = _cache_key(tag, specs, bool(donate_argnums), static_key, mesh,
                     in_shardings, out_shardings)
    cached = _COMPILE_CACHE.get(key)
    if cached is not None:
        trace.CACHE_HITS.inc()
        return cached
    trace.CACHE_MISSES.inc()
    kwargs: Dict[str, Any] = {}
    if in_shardings is not None:
        kwargs["in_shardings"] = in_shardings
    if out_shardings is not None:
        kwargs["out_shardings"] = out_shardings

    @functools.wraps(fn)
    def named(*args):
        return fn(*args)

    named.__name__ = named.__qualname__ = program_name(tag)
    jitted = jax.jit(named, donate_argnums=donate_argnums, **kwargs)
    t0 = time.perf_counter()
    global _CURRENT_COMPILE_MESH
    prev_mesh = _CURRENT_COMPILE_MESH
    _CURRENT_COMPILE_MESH = mesh
    try:
        if mesh is not None:
            with mesh:
                compiled = jitted.lower(*specs).compile()
        else:
            compiled = jitted.lower(*specs).compile()
    finally:
        _CURRENT_COMPILE_MESH = prev_mesh
    if profile is not None:
        profile.record_phase("compile", time.perf_counter() - t0)
    _COMPILE_CACHE[key] = compiled
    return compiled


def _input_shardings(compiled) -> Optional[List[Any]]:
    """The shardings ``compiled`` expects of its arguments, one per array
    leaf (None where the executable does not say)."""
    try:
        return jax.tree_util.tree_leaves(compiled.input_shardings[0])
    except Exception:
        return None


def _conform_blobs(expected, blobs):
    """device_put any blob (or static aux entry) whose placement doesn't
    match ``expected`` (:func:`_input_shardings` of the program).

    A program whose apply body is ``shard_map``-partitioned over the mesh's
    ``model`` axis (see :func:`repro.launch.mesh.shard_by_logical`) lowers
    with its unspecified inputs replicated across the whole mesh — but in
    single-launch mode the arena blobs live on the primary device only.
    Conforming here (instead of eagerly replicating every upload) keeps the
    1D fast path untouched and moves data at most once per blob: the
    conformed output blob already matches on the next stage's launch.
    Returns ``(blobs, moved_any)``."""
    leaves, tree = jax.tree_util.tree_flatten(list(blobs))
    if expected is None or len(expected) != len(leaves):
        return blobs, False
    out, moved = [], False
    for b, s in zip(leaves, expected):
        try:
            have = b.sharding
            ok = have == s or have.is_equivalent_to(s, b.ndim)
        except Exception:
            ok = True
        if ok:
            out.append(b)
        else:
            out.append(jax.device_put(b, s))
            moved = True
    return jax.tree_util.tree_unflatten(tree, out), moved


def count_aux(aux_datas: Sequence[Any]) -> None:
    """Count the aux one launch is handed, by form: a static Data's entries
    as native bytes, any other Data's arena blob as packed bytes."""
    for d in aux_datas:
        if d.static:
            trace.AUX_NATIVE_BYTES.inc(d.layout.payload_bytes)
        else:
            trace.AUX_PACKED_BYTES.inc(d.layout.total_bytes)


def _layout_fingerprint(app, la: "PureLaunchable") -> Any:
    """Hashable fingerprint of every arena layout a compiled program bakes
    in (inputs, output, aux), each aux with its form (static entries or a
    packed blob).  Folded into the compile-cache static key: the blob
    *specs* only carry total byte sizes, and two different layouts can
    round up to the same arena size — without this they would collide on
    one executable that unpacks the wrong shapes."""
    aux_layouts = []
    for h in la.aux_handles:
        d = app.getData(h)
        if d.layout is None:
            d.plan()
        aux_layouts.append((d.layout, d.static))
    return (la.in_layouts, la.out_layout, tuple(aux_layouts))


class DonatedBufferError(RuntimeError):
    """A process compiled with input donation (in-place) was launched after
    its handles were re-wired so the donated input no longer doubles as the
    output.  Running it would donate the caller's live input blob to XLA;
    call ``init()`` again to recompile for the new wiring."""


@dataclasses.dataclass(frozen=True)
class PureLaunchable:
    """A Process lowered to its pure, launchable form.

    ``fn(*in_blobs, *aux_args) -> blob_out`` (an aux arg is a blob, or a
    static Data's tuple of entry arrays) plus everything needed to
    compile and feed it: the ordered streaming inputs (names, arena
    layouts, Data handles — position 0 is the primary ``"in"`` port), the
    aux Data handles in positional order, the compile-cache tag/static
    key, and which input (if any) is donated because it doubles as the
    output.  This is the unit shared by ``init()`` (single-shot AOT),
    fused chains, and the batched/streaming executor — all of which treat
    every streaming input symmetrically (per-edge batch queues, zipped
    row-aligned; see :mod:`repro.core.stream`).
    """

    fn: Callable
    in_names: Tuple[str, ...]
    in_layouts: Tuple[ArenaLayout, ...]
    in_handles: Tuple[DataHandle, ...]
    out_layout: ArenaLayout
    aux_handles: Tuple[DataHandle, ...]
    tag: str
    static_key: Any
    donate_idx: Optional[int]    # input position donated to XLA (None = none)

    @property
    def n_inputs(self) -> int:
        return len(self.in_layouts)

    @property
    def in_layout(self) -> ArenaLayout:
        """Layout of the primary input (compat accessor)."""
        return self.in_layouts[0]

    @property
    def in_place(self) -> bool:
        """True when some input buffer is donated (out doubles as input)."""
        return self.donate_idx is not None


class Process:
    """Base class for operators.  Subclasses implement :meth:`apply` (a pure
    function from named device views to named output arrays), declare their
    wiring contract in :attr:`ports`, and optionally override :meth:`init`
    to add their own one-time work."""

    #: kernels this process needs from the registry (loaded lazily in init)
    kernel_names: Sequence[str] = ()

    #: typed wiring contract: ``"in"``/``"out"`` are the primary input and
    #: output; every other non-aux entry is an additional streaming input;
    #: entries with ``Port(aux=True)`` are static side parameters keyed by
    #: their own name.  Subclasses override to tighten the contract.
    ports: Dict[str, Port] = {"in": Port(), "out": Port()}

    def __init__(self, app: Optional[CLapp] = None):
        self._app = app
        #: ordered wiring of the streaming input ports (``"in"`` first).
        #: Secondary input ports appear here only when wired as streaming
        #: inputs; wired via ``aux_handles`` instead they stay static.
        self.in_handles: Dict[str, DataHandle] = {"in": INVALID_HANDLE}
        self.out_handle: DataHandle = INVALID_HANDLE
        self.aux_handles: Dict[str, DataHandle] = {}
        self.launch_params: Any = None
        self.kernel: Optional[Callable] = None
        #: input ports whose buffer may be donated to XLA even when the
        #: handle does NOT double as the output — set by Pipeline.build's
        #: residency plan on internal (device-resident, single-consumer)
        #: edges so the upstream blob is consumed in place of being copied.
        self.donate_ports: frozenset = frozenset()
        #: name of this process's node in an owning Pipeline (set by
        #: Pipeline.build); used to attribute donations in error messages
        self.graph_name: Optional[str] = None
        self._compiled = None
        self._compiled_in_names: Tuple[str, ...] = ()
        self._compiled_donate_name: Optional[str] = None
        self._compiled_donate_reason: Optional[str] = None  # 'in_place'|'port'
        self._compiled_shardings: Optional[List[Any]] = None
        self._initialized = False
        self._legacy_warned = False

    # -- wiring ---------------------------------------------------------------
    @property
    def in_handle(self) -> DataHandle:
        """The primary (``"in"`` port) input handle — position 0 of the
        multi-input wiring; kept as an attribute-style accessor because the
        single-input protocol predates multi-input launchables."""
        return self.in_handles.get("in", INVALID_HANDLE)

    @in_handle.setter
    def in_handle(self, h: DataHandle) -> None:
        self.in_handles["in"] = h

    @property
    def input_names(self) -> Tuple[str, ...]:
        """The wired streaming inputs in positional order: declared input
        ports first (declaration order, ``"in"`` always position 0), then
        any extra wired names in insertion order."""
        wired = [n for n, h in self.in_handles.items() if h != INVALID_HANDLE]
        declared = [n for n in self.ports
                    if n != "out" and not self.ports[n].aux]
        ordered = [n for n in declared if n in wired]
        ordered += [n for n in wired if n not in ordered]
        if "in" in ordered and ordered[0] != "in":
            ordered.remove("in")
            ordered.insert(0, "in")
        if not ordered:
            ordered = ["in"]        # unwired: fail later with INVALID_HANDLE
        return tuple(ordered)

    def getApp(self) -> CLapp:
        if self._app is None:
            raise RuntimeError("process not bound to a CLapp")
        return self._app

    def bind(self, infile: Any = None, outfile: Any = None, *,
             params: Any = None, **aux: Any):
        """Declaratively wire this process; returns a
        :class:`~repro.core.graph.Node` for :class:`~repro.core.graph.
        Pipeline` composition.

        ``infile``/``outfile`` bind the ``"in"``/``"out"`` ports; every
        other keyword binds the same-named secondary input or aux port.  A
        binding is either a **named edge** (str) connecting to other nodes
        in the graph, or a concrete :class:`~repro.core.data.Data`
        (/registered DataHandle).  An *input* port bound to an edge becomes
        a true streaming input (a fan-in join); bound to concrete Data it
        is static (broadcast in batched modes — bit-identical results).
        Aux ports only accept concrete bindings.  Concrete bindings are
        port-validated immediately — a mis-typed Data raises
        :class:`PortError` here, at bind time.  ``params`` forwards to
        :meth:`set_launch_parameters`.
        """
        from .graph import Node  # local import: graph builds on Process

        if params is not None:
            self.set_launch_parameters(params)
        return Node(self, in_bind=infile, out_bind=outfile, aux_bind=aux)

    def out_specs(self, in_specs: Mapping[str, jax.ShapeDtypeStruct],
                  aux_specs: Optional[Mapping[str, Mapping[str, jax.ShapeDtypeStruct]]] = None,
                  ) -> Dict[str, jax.ShapeDtypeStruct]:
        """Infer the named output specs from input specs WITHOUT running or
        compiling anything (``jax.eval_shape`` over :meth:`apply`).  The
        Pipeline uses this to allocate intermediate/output Data and to
        shape/dtype-check the whole graph at build time.  Composite
        processes that override :meth:`launch` instead of :meth:`apply`
        must override this too."""
        params = self.launch_params
        out = jax.eval_shape(
            lambda v, a: self.apply(v, a, params),
            {k: jax.ShapeDtypeStruct(s.shape, s.dtype) for k, s in in_specs.items()},
            {n: {k: jax.ShapeDtypeStruct(s.shape, s.dtype) for k, s in d.items()}
             for n, d in (aux_specs or {}).items()})
        return {k: jax.ShapeDtypeStruct(v.shape, v.dtype) for k, v in out.items()}

    # -- legacy imperative wiring (paper: setInHandle / setOutHandle) ---------
    def _warn_legacy_setters(self) -> None:
        if not self._legacy_warned:
            self._legacy_warned = True
            warnings.warn(
                f"{type(self).__name__}.set_in_handle/set_out_handle/"
                "set_aux_handle are deprecated: declare ports and wire with "
                "Process.bind(...) + Pipeline (see docs/pipeline.md).  The "
                "legacy protocol keeps working and stays bit-identical.",
                DeprecationWarning, stacklevel=3)

    def set_in_handle(self, h: DataHandle) -> None:
        self._warn_legacy_setters()
        self.in_handle = h

    def set_out_handle(self, h: DataHandle) -> None:
        self._warn_legacy_setters()
        self.out_handle = h

    def set_aux_handle(self, name: str, h: DataHandle) -> None:
        self._warn_legacy_setters()
        self.aux_handles[name] = h

    def set_launch_parameters(self, params: Any) -> None:
        if params != self.launch_params:
            self.launch_params = params
            self._compiled = None  # parameters are baked in; re-init needed

    # paper-style camelCase aliases
    setInHandle = set_in_handle
    setOutHandle = set_out_handle
    setLaunchParameters = set_launch_parameters

    # -- the pure computation -------------------------------------------------
    def apply(self, views: Dict[str, jax.Array], aux: Dict[str, Dict[str, jax.Array]],
              params: Any) -> Dict[str, jax.Array]:
        """Pure: input views (+ aux Data views) -> named output arrays.
        Output names/shapes must match the output Data's layout."""
        raise NotImplementedError

    # -- layouts ---------------------------------------------------------------
    def _layouts(self) -> Tuple[Tuple[ArenaLayout, ...], ArenaLayout,
                                Dict[str, ArenaLayout]]:
        app = self.getApp()
        owner = type(self).__name__
        in_layouts = []
        for name in self.input_names:
            d = app.getData(self.in_handles.get(name, INVALID_HANDLE))
            if d.static:
                raise PortError(f"{owner}: input {name!r} is a static Data; "
                                "static Data binds only to aux ports")
            if d.layout is None:
                d.plan()
            in_layouts.append(d.layout)
        dout = app.getData(self.out_handle)
        if dout.static:
            raise PortError(f"{owner}: the output is a static Data, which "
                            "is read-only")
        if dout.layout is None:
            dout.plan()
        aux_layouts = {}
        for name, h in self.aux_handles.items():
            d = app.getData(h)
            if d.static and not self.ports.get(name, Port()).aux:
                raise PortError(f"{owner}: port {name!r} is not an aux "
                                "port; static Data binds only to aux ports")
            if d.layout is None:
                d.plan()
            aux_layouts[name] = d.layout
        return tuple(in_layouts), dout.layout, aux_layouts

    def _static_key(self) -> Any:
        p = self.launch_params
        if p is None:
            return None
        if dataclasses.is_dataclass(p):
            return repr(p)
        return repr(p)

    def pure_fn(self) -> Tuple[Callable, Tuple[ArenaLayout, ...], ArenaLayout,
                               List[str]]:
        """(fn(*in_blobs, *aux_args) -> blob_out, in_layouts, out_layout,
        aux names) — the fusable unit used by both init() and ProcessChain.

        An aux arg is its Data's arena blob, unpacked in the program, or,
        for a static Data, the tuple of its entry arrays, taken as they
        are.

        The primary input's views become :meth:`apply`'s ``views`` argument;
        every SECONDARY streaming input is delivered through the ``aux``
        argument under its port name — the same slot a static aux binding
        uses — so switching a port between streamed and static wiring
        cannot change the math (bit-identity by construction)."""
        in_layouts, out_layout, aux_layouts = self._layouts()
        in_names = self.input_names
        aux_names = sorted(aux_layouts)
        params = self.launch_params
        n_in = len(in_names)
        app = self.getApp()
        static = {n for n in aux_names
                  if app.getData(self.aux_handles[n]).static}

        def fn(*blobs):
            in_blobs, aux_args = blobs[:n_in], blobs[n_in:]
            views = unpack_device(in_blobs[0], in_layouts[0])
            aux = {
                name: unpack_device(blob, lay)
                for name, blob, lay in zip(in_names[1:], in_blobs[1:],
                                           in_layouts[1:])
            }
            aux.update({
                name: (dict(zip(aux_layouts[name].names, arg))
                       if name in static
                       else unpack_device(arg, aux_layouts[name]))
                for name, arg in zip(aux_names, aux_args)
            })
            outs = self.apply(views, aux, params)
            missing = set(out_layout.names) - set(outs)
            if missing:
                raise ValueError(f"{type(self).__name__}.apply missing outputs {missing}")
            return pack_device(outs, out_layout)

        return fn, in_layouts, out_layout, aux_names

    def _donate_idx(self, in_names: Sequence[str]) -> Optional[int]:
        """Input position whose buffer the program may donate: the first
        wired input whose handle IS the output handle (in-place), else the
        first input whose port the residency plan marked donatable
        (:attr:`donate_ports` — a device-resident internal edge with this
        process as its only consumer)."""
        for i, name in enumerate(in_names):
            if self.in_handles.get(name) == self.out_handle:
                return i
        for i, name in enumerate(in_names):
            if name in self.donate_ports:
                return i
        return None

    def _donate_reason(self, name: str) -> str:
        """Why input ``name`` is donated: genuine in-place wiring beats a
        residency-plan donation when both hold."""
        return ("in_place" if self.in_handles.get(name) == self.out_handle
                else "port")

    def launchable(self) -> PureLaunchable:
        """Lower this process to its :class:`PureLaunchable` form — the one
        representation used by ``init()``, fused chains, and streaming."""
        fn, in_layouts, out_layout, aux_names = self.pure_fn()
        in_names = self.input_names
        return PureLaunchable(
            fn=fn,
            in_names=in_names,
            in_layouts=in_layouts,
            in_handles=tuple(self.in_handles[n] for n in in_names),
            out_layout=out_layout,
            aux_handles=tuple(self.aux_handles[n] for n in aux_names),
            tag=f"{type(self).__module__}.{type(self).__name__}",
            static_key=self._static_key(),
            donate_idx=self._donate_idx(in_names),
        )

    def _current_aux_handles(self) -> Tuple[DataHandle, ...]:
        """The aux handles the compiled program's positional aux args map to,
        read from the CURRENT wiring (sorted-name order, matching
        :meth:`launchable`)."""
        return tuple(self.aux_handles[n] for n in sorted(self.aux_handles))

    # -- init / launch ----------------------------------------------------------
    def _aux_specs(self, la: PureLaunchable) -> List[Any]:
        """AOT specs of the aux args: a blob spec, or a static Data's
        tuple of entry specs."""
        app = self.getApp()
        specs = []
        for h in la.aux_handles:
            d = app.getData(h)
            if d.layout is None:
                d.plan()
            specs.append(entry_specs(d.layout) if d.static
                         else blob_spec(d.layout))
        return specs

    def init(self) -> None:
        """One-time work: resolve kernels, trace and AOT-compile."""
        app = self.getApp()
        for name in self.kernel_names:
            app.kernels.load(name)  # module names; idempotent
        la = self.launchable()
        specs = [blob_spec(lay) for lay in la.in_layouts]
        specs += self._aux_specs(la)
        self._compiled = aot_compile(
            la.fn,
            specs,
            tag=la.tag,
            donate_argnums=(la.donate_idx,) if la.donate_idx is not None
            else (),
            static_key=(la.static_key, _layout_fingerprint(app, la)),
            mesh=app.mesh,
        )
        self._compiled_in_names = la.in_names
        self._compiled_shardings = _input_shardings(self._compiled)
        self._compiled_donate_name = (
            la.in_names[la.donate_idx] if la.donate_idx is not None else None)
        self._compiled_donate_reason = (
            self._donate_reason(self._compiled_donate_name)
            if self._compiled_donate_name is not None else None)
        self._initialized = True

    def _check_donation(self) -> None:
        name = self._compiled_donate_name
        if name is None:
            return
        if self._compiled_donate_reason == "port":
            # residency-plan donation: legal as long as the port is still
            # marked donatable (the plan, not the handles, is the contract)
            if name not in self.donate_ports:
                raise DonatedBufferError(
                    f"{type(self).__name__} was compiled with input {name!r} "
                    "donated by the pipeline residency plan, but the port is "
                    "no longer marked donatable; call init() to recompile.")
            return
        if self.out_handle != self.in_handles.get(name):
            raise DonatedBufferError(
                f"{type(self).__name__} was compiled in-place (input "
                f"{name!r} donated) but is now wired out_handle="
                f"{self.out_handle} != in_handles[{name!r}]="
                f"{self.in_handles.get(name)}; launching would donate the "
                "caller's live input blob.  Call init() to recompile for "
                "the new wiring.")

    def launch(self, profile: ProfileParameters | None = None) -> None:
        """Hot path: execute the compiled program.  No tracing, no transfer."""
        with trace.span("process.launch",
                        process=self.graph_name or type(self).__name__):
            if not self._initialized or self._compiled is None:
                self.init()  # lazily; callers should init() explicitly
            self._check_donation()
            app = self.getApp()
            # input and aux handles are read live (not snapshotted at init)
            # so re-wiring to a same-layout Data between launches takes
            # effect, as it always did; order matches launchable()'s
            # positional order
            in_blobs = []
            in_datas = []
            t_up = time.perf_counter()
            uploaded = False
            for name in self._compiled_in_names:
                d = app.getData(self.in_handles[name])
                if d.device_blob is None:
                    if d.donated_by is not None and \
                            d.coherence is not Coherence.HOST_FRESH:
                        # re-uploading would fabricate a zero blob for a
                        # buffer a downstream stage consumed; fail with
                        # graph context
                        d._raise_donated()
                    app.host2device(self.in_handles[name])
                    uploaded = True
                in_blobs.append(d.device_blob)
                in_datas.append(d)
            aux_args = []
            aux_datas = []
            for h in self._current_aux_handles():
                d = app.getData(h)
                if not d.on_device:
                    app.host2device(h)
                    uploaded = True
                aux_args.append(d.device_args())
                aux_datas.append(d)
            count_aux(aux_datas)
            blobs, moved = _conform_blobs(self._compiled_shardings,
                                          in_blobs + aux_args)
            if (uploaded or moved) and profile is not None \
                    and profile.enable:
                profile.record_phase("transfer", time.perf_counter() - t_up)
            t0 = time.perf_counter()
            out_blob = self._compiled(*blobs)
            if profile is not None and profile.enable:
                jax.block_until_ready(out_blob)
                dt = time.perf_counter() - t0
                profile.record(dt)
                profile.record_phase("compute", dt)
            if self._compiled_donate_name is not None:
                # the donated input's blob is dead; mark it so a later read
                # raises DonatedBufferError with this stage's graph context
                in_datas[
                    self._compiled_in_names.index(self._compiled_donate_name)
                ].mark_donated(self.graph_name or type(self).__name__)
            app._set_device_blob(self.out_handle, out_blob)

    # -- streaming (beyond paper; see repro.core.stream) -----------------------
    def stream(self, datasets: Sequence[Any], batch: int = 1, *,
               sync: bool = False, sharded: bool = False,
               profile: ProfileParameters | None = None) -> List[Any]:
        """Run many independent input Data sets through this process.

        Batches of ``batch`` data sets are packed host-side, double-buffered
        to the device (:class:`repro.core.stream.StreamQueue`), and executed
        as ONE launch per batch via a vmapped AOT program
        (:class:`repro.core.stream.BatchedProcess`) that reuses the global
        compile cache and the donation rules of this process.  Returns one
        output Data per input, device-fresh (``sync=True`` also copies each
        result back to its host arrays).

        For a multi-input process each item supplies one Data per streaming
        input: a ``{input name -> Data}`` mapping or a positional tuple
        (order = :attr:`input_names`).  Every input edge gets its own
        row-aligned batch queue; the per-edge batches are zipped into one
        joined launch (see :mod:`repro.core.stream`).  Single-input
        processes keep taking plain Data items.

        ``sharded=True`` additionally splits every stacked batch across the
        ``data`` axis of the app mesh — one launch computes ``batch`` items
        spread over ALL selected devices, aux blobs replicated; results are
        bit-identical and each item's output stays on the device that
        computed it.  Requires ``batch`` divisible by the device count.

        Ragged tail: when the final batch has fewer than ``batch`` items
        and the padding waste fraction exceeds
        :data:`repro.core.stream.TAIL_WASTE` (0.5), a second, smaller
        executable is compiled for the tail instead of padding by
        repetition (see :func:`repro.core.stream.launch_rows`).
        """
        from .stream import stream_launch  # local import: avoid cycle

        return stream_launch(self, datasets, batch=batch, sync=sync,
                             sharded=sharded, profile=profile)


class ProcessChain(Process):
    """Compose processes.  ``mode='staged'`` is the paper-faithful pipeline
    (independently compiled stages, zero-copy handle passing);
    ``mode='fused'`` traces the whole chain as one XLA program."""

    def __init__(self, app: Optional[CLapp] = None,
                 stages: Sequence[Process] = (), mode: str = "staged"):
        super().__init__(app)
        if mode not in ("staged", "fused"):
            raise ValueError(mode)
        self.stages = list(stages)
        self.mode = mode

    def add(self, p: Process) -> "ProcessChain":
        self.stages.append(p)
        return self

    def _chain_inputs(self) -> Tuple[List[DataHandle], List[str]]:
        """The chain-level streaming inputs, in first-consumption order: a
        handle a stage reads that no EARLIER stage produced must be fed
        from outside the chain.  A multi-input stage whose secondary
        inputs are external edges therefore makes the whole chain
        multi-input (this is how a Pipeline join lowers to one launchable).

        Each input is named after the port that first consumes it, so a
        composite lowering to this chain keeps its own mapping contract
        (``{"in": ..., "smaps": ...}``); a name that would collide with
        an earlier input falls back to its positional ``in<i>`` form.
        """
        produced: set = set()
        inputs: List[DataHandle] = []
        names: List[str] = []
        for s in self.stages:
            for pname in s.input_names:
                h = s.in_handles.get(pname, INVALID_HANDLE)
                if h not in produced and h not in inputs:
                    if pname in names:
                        pname = f"in{len(inputs)}"
                    inputs.append(h)
                    names.append(pname)
            produced.add(s.out_handle)
        return inputs, names

    def launchable(self) -> PureLaunchable:
        """Fused composition of the stages' pure fns as ONE launchable unit.

        Used by fused ``init()``, and by :meth:`Process.stream` for chains in
        *either* mode — streaming always executes the fused composition,
        which is mathematically identical to running the stages one by one
        (stage outputs feed stage inputs by handle, zero copies).
        """
        if not self.stages:
            raise ValueError("empty chain")
        app = self.getApp()
        parts = []
        for s in self.stages:
            for name in s.kernel_names:
                app.kernels.load(name)
            fn, in_layouts, out_layout, aux_names = s.pure_fn()
            stage_ins = tuple(s.in_handles[n] for n in s.input_names)
            parts.append((s, fn, in_layouts, out_layout, stage_ins, aux_names))
        chain_inputs, chain_in_names = self._chain_inputs()
        n_in = len(chain_inputs)
        last_out = self.stages[-1].out_handle

        def fused(*blobs):
            # leading blobs are the chain inputs; the rest is the
            # concatenation of each stage's aux args, in order
            env: Dict[DataHandle, Any] = dict(zip(chain_inputs, blobs[:n_in]))
            all_aux = blobs[n_in:]
            i = 0
            for s, fn, _ils, _ol, stage_ins, aux_names in parts:
                aux = all_aux[i : i + len(aux_names)]
                i += len(aux_names)
                srcs = [env[h] for h in stage_ins]
                env[s.out_handle] = fn(*srcs, *aux)
            return env[last_out]

        aux_handles: List[DataHandle] = []
        static_parts = []
        # canonical wiring topology: handles renumbered by first occurrence,
        # so logically identical chains share a cache entry while chains
        # that route the same stages differently (e.g. p2 reading stage-1's
        # output vs the chain input) do NOT collide on one executable
        handle_ids: Dict[DataHandle, int] = {}
        def _hid(h: DataHandle) -> int:
            return handle_ids.setdefault(h, len(handle_ids))
        for s, _fn, ils, ol, stage_ins, aux_names in parts:
            static_parts.append((
                f"{type(s).__module__}.{type(s).__qualname__}",
                s._static_key(),
                (tuple(_hid(h) for h in stage_ins), _hid(s.out_handle)),
                # per-stage layouts: intermediate edges with equal arena
                # sizes but different shapes must not share one executable
                (ils, ol),
            ))
            aux_handles += [s.aux_handles[n] for n in aux_names]
        in_layouts = tuple(
            app.getData(h).layout or app.getData(h).plan()
            for h in chain_inputs)
        out_layout = app.getData(last_out).layout or app.getData(last_out).plan()
        return PureLaunchable(
            fn=fused,
            in_names=tuple(chain_in_names),
            in_layouts=in_layouts,
            in_handles=tuple(chain_inputs),
            out_layout=out_layout,
            aux_handles=tuple(aux_handles),
            tag="ProcessChain[" + ",".join(
                type(s).__name__ for s, *_ in parts) + "]",
            static_key=tuple(static_parts),
            donate_idx=(chain_inputs.index(last_out)
                        if last_out in chain_inputs else None),
        )

    def init(self) -> None:
        if not self.stages:
            raise ValueError("empty chain")
        if self.mode == "staged":
            for s in self.stages:
                s.init()
            self._initialized = True
            return
        # fused: the chain becomes a single Process over its chain-level
        # inputs (first stage's primary input + any interior fan-in edges
        # fed from outside) and the last stage's output
        la = self.launchable()
        self.in_handles = dict(zip(la.in_names, la.in_handles))
        self.out_handle = self.stages[-1].out_handle
        specs = [blob_spec(lay) for lay in la.in_layouts]
        specs += self._aux_specs(la)
        self._compiled = aot_compile(
            la.fn, specs, tag=la.tag,
            donate_argnums=(la.donate_idx,) if la.donate_idx is not None
            else (),
            static_key=(la.static_key,
                        _layout_fingerprint(self.getApp(), la)),
            mesh=self.getApp().mesh,
        )
        self._compiled_in_names = la.in_names
        self._compiled_shardings = _input_shardings(self._compiled)
        self._compiled_donate_name = (
            la.in_names[la.donate_idx] if la.donate_idx is not None else None)
        # a fused chain only donates when its output handle IS a chain input
        self._compiled_donate_reason = (
            "in_place" if self._compiled_donate_name is not None else None)
        self._initialized = True

    def _current_aux_handles(self) -> Tuple[DataHandle, ...]:
        handles: List[DataHandle] = []
        for s in self.stages:
            handles += [s.aux_handles[n] for n in sorted(s.aux_handles)]
        return tuple(handles)

    def launch(self, profile: ProfileParameters | None = None) -> None:
        if not self._initialized:
            self.init()
        if self.mode == "staged":
            t0 = time.perf_counter()
            stage_prof = _PhaseView(profile) \
                if profile is not None and profile.enable else None
            for s in self.stages:
                s.launch(stage_prof)
            if profile is not None and profile.enable:
                app = self.getApp()
                jax.block_until_ready(app.getData(self.stages[-1].out_handle).device_blob)
                profile.record(time.perf_counter() - t0)
            return
        Process.launch(self, profile)
