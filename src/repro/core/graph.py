"""Declarative operator graphs: :class:`Node`, :class:`Pipeline`.

The paper promises that algorithms read as mathematical operators — input,
output, parameters — chained "easily and efficiently".  This module is that
front-end.  A :class:`~repro.core.process.Process` declares typed ports and
is wired *functionally* with :meth:`~repro.core.process.Process.bind`, which
maps ports to **named edges** (or concrete Data)::

    fft  = FFT(app).bind(infile="kspace", outfile="xspace",
                         params=FFTParams("backward", var="kdata"))
    prod = ComplexElementProd(app).bind(infile="xspace", outfile="weighted")
    comb = XImageSum(app).bind(infile="weighted", outfile="image")

    pipe = Pipeline(app) | fft | prod | comb          # linear: auto-wires too
    pipe = Pipeline.from_graph(app, [fft, prod, comb])  # explicit DAG

Graphs are true fan-in DAGs: a node with secondary input ports joins
several streams.  Binding a secondary input port to a **named edge** makes
it a real streaming input — per-item in the batched modes — while binding
it to concrete Data keeps the legacy static-broadcast behaviour
(bit-identical results either way)::

    prod = ComplexElementProd(app).bind(infile="xspace", smaps="smaps")
    pipe = Pipeline.from_graph(app, [fft, prod, comb])
    out  = pipe.run({"kspace": kd, "smaps": sm})          # fan-in launch
    outs = pipe.run(items, mode="stream", batch=8)        # items: mappings

A graph may therefore have SEVERAL input edges (every edge consumed but
never produced).  Multi-input graphs take a ``{edge name -> Data}`` mapping
per item in every mode; single-input graphs keep taking plain Data.

One validated graph, three execution modes through a single front-end::

    out  = pipe.run(kdata)                                  # AOT launch
    outs = pipe.run(slices,   mode="stream", batch=8, sharded=True)
    outs = pipe.run(requests, mode="serve",  batch=8)

Validation happens at **bind/build time**, never at launch:

* binding an undeclared port, or concrete Data that violates a
  :class:`~repro.core.process.Port` spec -> :class:`~repro.core.process.
  PortError` from ``bind()`` itself;
* consuming an edge no node produces (linear mode), producing one edge
  twice, cycles, ambiguous anonymous inputs, a join item missing one of
  its input edges -> :class:`GraphError` (mis-wired joins name the
  offending edges) from ``|`` / ``from_graph`` / ``build``;
* inter-node shape/dtype mismatches -> :class:`~repro.core.process.
  PortError` from ``build()``, via each process's ``out_specs`` inference
  (``jax.eval_shape`` — nothing is compiled or executed to reject a graph).

``build()`` allocates intermediate/output Data from the inferred specs,
wires the node processes over arena handles (zero-copy chaining, exactly as
the imperative protocol did; join ports become additional streaming input
handles), AOT-compiles once, and caches the built state — repeated
``run()`` calls reuse the compiled executable, preserving the paper's
zero-per-iteration-overhead property in all three modes.  In the stream
and serve modes every input edge gets its own row-aligned batch queue,
zipped into one joined launch per batch (see :mod:`repro.core.stream`).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Mapping, Optional, Sequence, Tuple

import jax

from . import trace
from .app import CLapp, DataHandle
from .data import Data
from .process import (Port, PortError, Process, ProcessChain,
                      ProfileParameters)


class GraphError(ValueError):
    """The operator graph is mis-wired (unknown edge, duplicate producer,
    cycle, ambiguous input/output, a join missing one of its input edges).
    Raised while the graph is being composed or built — never at launch."""


def _is_edge(b: Any) -> bool:
    return isinstance(b, str)


def _is_data(b: Any) -> bool:
    return isinstance(b, Data)


def _is_handle(b: Any) -> bool:
    return isinstance(b, int) and not isinstance(b, bool)


class Node:
    """One bound operator: a Process plus port->edge/Data bindings.

    Create via :meth:`Process.bind`.  Construction validates the bindings
    against the process's declared ports — unknown port names and
    port-violating concrete Data raise :class:`PortError` immediately.

    Keyword bindings are routed by the port's declaration: a non-aux
    secondary **input port** accepts a named edge (a streaming join input)
    or concrete Data (static broadcast); an ``aux=True`` port only accepts
    concrete Data.
    """

    def __init__(self, process: Process, in_bind: Any = None,
                 out_bind: Any = None,
                 aux_bind: Optional[Mapping[str, Any]] = None):
        self.process = process
        self.in_bind = in_bind
        self.out_bind = out_bind
        bindings = dict(aux_bind or {})
        self.name = type(process).__name__
        #: static bindings: aux ports + input ports bound to concrete Data
        self.aux_bind: Dict[str, Any] = {}
        #: streaming join bindings: input ports bound to named edges
        self.input_bind: Dict[str, str] = {}
        self._route_bindings(bindings)
        self._validate_bindings()

    def _route_bindings(self, bindings: Dict[str, Any]) -> None:
        ports = self.process.ports
        aux_ports = {k for k, p in ports.items() if p.aux}
        input_ports = {k for k in ports
                       if k not in ("in", "out") and not ports[k].aux}
        unknown = set(bindings) - aux_ports - input_ports
        if unknown:
            raise PortError(
                f"{self.name}.bind: no input or aux port(s) named "
                f"{sorted(unknown)}; declared input ports: "
                f"{sorted(input_ports)}, aux ports: {sorted(aux_ports)}")
        for pname, bound in bindings.items():
            if pname in input_ports and _is_edge(bound):
                self.input_bind[pname] = bound       # streaming join input
            else:
                self.aux_bind[pname] = bound         # static (broadcast)

    def _validate_bindings(self) -> None:
        ports = self.process.ports
        for slot, bind in (("in", self.in_bind), ("out", self.out_bind)):
            if bind is not None and slot not in ports:
                raise PortError(f"{self.name}.bind: process declares no "
                                f"{slot!r} port")
            if not (bind is None or _is_edge(bind) or _is_data(bind)
                    or _is_handle(bind)):
                raise PortError(
                    f"{self.name}.bind: {slot!r} must be an edge name, a "
                    f"Data, or a DataHandle, got {type(bind).__name__}")
        for aname, bind in self.aux_bind.items():
            if not (_is_data(bind) or _is_handle(bind)):
                raise PortError(
                    f"{self.name}.bind: port {aname!r} is bound statically "
                    f"and must be a concrete Data or DataHandle, got "
                    f"{type(bind).__name__}.  Aux ports are always static; "
                    "a non-aux input port accepts a named edge instead to "
                    "become a streaming join input.")
            if _is_data(bind):
                ports[aname].validate(bind.specs(), owner=self.name,
                                      port=aname)
        if _is_data(self.in_bind):
            ports["in"].validate(self.in_bind.specs(), owner=self.name,
                                 port="in")

    def __repr__(self):
        joins = {p: e for p, e in self.input_bind.items()}
        return (f"Node({self.name}, in={self.in_bind!r}, "
                f"out={self.out_bind!r}, joins={joins}, "
                f"aux={sorted(self.aux_bind)})")


@dataclasses.dataclass
class _Built:
    """State cached by :meth:`Pipeline.build`."""

    executor: Process                       # single node or ProcessChain
    handles: Dict[str, DataHandle]          # edge name -> registered handle
    input_edges: Tuple[str, ...]            # graph input edges (discovery order)
    input_handles: Dict[str, DataHandle]    # input edge -> handle
    input_layouts: Dict[str, Any]           # input edge -> ArenaLayout
    input_order: Tuple[str, ...]            # edges in launchable position order
    output_handle: DataHandle
    #: residency plan: edge name -> 'host' (graph input/output edges, the
    #: pinned host path) or 'device' (internal edge; the blob never lands
    #: on the host between stages)
    residency: Dict[str, str] = dataclasses.field(default_factory=dict)
    #: internal edges whose upstream blob is DONATED to their single
    #: consumer: edge name -> consuming node name
    donated_edges: Dict[str, str] = dataclasses.field(default_factory=dict)

    @property
    def input_handle(self) -> DataHandle:
        """Primary (first) input edge's handle (compat accessor)."""
        return self.input_handles[self.input_edges[0]]

    @property
    def input_layout(self) -> Any:
        return self.input_layouts[self.input_edges[0]]


class Pipeline:
    """A validated DAG of bound operator nodes with one front-end for all
    execution modes (see the module docstring for the full story).

    Linear composition: ``Pipeline(app) | node | node``.  Unbound ports are
    auto-wired — a node without an ``in`` binding consumes the previous
    node's output edge; missing edge names are generated.  Non-linear DAGs
    (forks and fan-in joins over named edges) go through :meth:`from_graph`.

    ``fuse=True`` traces the whole graph as ONE XLA program (the
    beyond-paper fusion win); the default is the paper-faithful staged
    chain.  Both are bit-identical to the legacy imperative protocol.
    """

    def __init__(self, app: CLapp, nodes: Sequence[Node | Process] = (), *,
                 fuse: bool = False, output: Optional[str] = None,
                 _graph_input_edges: Optional[Sequence[str]] = None):
        self.app = app
        self.fuse = fuse
        self.nodes: List[Node] = [self._as_node(n) for n in nodes]
        self._requested_output = output
        # edges from_graph classified as graph inputs: a non-first node may
        # consume one of these as its PRIMARY input (fan-in DAG).  Linear
        # '|' composition leaves this empty, keeping its stricter
        # produced-upstream rule for primary edges.
        self._declared_inputs = set(_graph_input_edges or ())
        self._built: Optional[_Built] = None
        self._plan_edges()

    @staticmethod
    def _as_node(n: Node | Process) -> Node:
        if isinstance(n, Node):
            return n
        if isinstance(n, Process):
            return Node(n)
        raise GraphError(f"cannot compose {type(n).__name__} into a "
                         "Pipeline (expected Node or Process)")

    def __or__(self, other: Node | Process) -> "Pipeline":
        return Pipeline(self.app, self.nodes + [self._as_node(other)],
                        fuse=self.fuse, output=self._requested_output,
                        _graph_input_edges=self._declared_inputs)

    # ------------------------------------------------------------- planning
    def _plan_edges(self) -> None:
        """Resolve every node's in/out edge names; validate single-producer,
        known-consumer wiring.  Raises GraphError on mis-wiring.

        Secondary input ports bound to edges (joins) either consume an
        upstream node's output edge or — when nothing produces the edge —
        become ADDITIONAL graph input edges alongside the primary input.
        """
        self._in_edges: List[str] = []
        self._out_edges: List[str] = []
        self._join_edges: List[Dict[str, str]] = []  # per node: port -> edge
        self._input_edges: List[str] = []            # graph inputs, ordered
        self._input_data: Optional[Data] = None
        self._output_data: Optional[Data] = None
        self._input_handle: Optional[DataHandle] = None
        self._output_handle: Optional[DataHandle] = None
        self._output_edge: Optional[str] = None
        if not self.nodes:
            return
        producers: Dict[str, int] = {}
        for i, node in enumerate(self.nodes):
            b = node.in_bind
            if i == 0:
                if _is_data(b):
                    self._input_data = b
                    edge = "_in"
                elif _is_handle(b):
                    self._input_handle = b
                    edge = "_in"
                else:
                    edge = b if _is_edge(b) else "_in"
                self._input_edges.append(edge)
                producers[edge] = -1
            else:
                if b is None:
                    edge = self._out_edges[i - 1]
                elif _is_edge(b):
                    if b not in producers:
                        if b in self._declared_inputs:
                            # from_graph classified this edge as a graph
                            # input: another root of the fan-in DAG
                            self._input_edges.append(b)
                            producers[b] = -1
                        else:
                            raise GraphError(
                                f"node {i} ({node.name}) consumes edge "
                                f"{b!r} which no upstream node produces "
                                f"(known edges: {sorted(producers)})")
                    edge = b
                else:
                    raise GraphError(
                        f"node {i} ({node.name}): only the first node may "
                        "bind a concrete input Data/handle.  Wire an "
                        "additional streaming input by binding one of the "
                        "node's secondary input ports to a named edge (a "
                        "join), or bind a static side parameter as an aux "
                        "port.")
            # secondary input ports bound to edges: joins.  An edge no
            # upstream node produces becomes an additional graph input.
            joins: Dict[str, str] = {}
            for pname, jedge in node.input_bind.items():
                if jedge not in producers:
                    self._input_edges.append(jedge)
                    producers[jedge] = -1
                joins[pname] = jedge
            self._join_edges.append(joins)
            out = node.out_bind
            if _is_data(out) or _is_handle(out):
                if i != len(self.nodes) - 1:
                    raise GraphError(
                        f"node {i} ({node.name}): only the last node may "
                        "bind a concrete output Data/handle")
                if _is_data(out):
                    self._output_data = out
                else:
                    self._output_handle = out
                out_edge = "_out"
            else:
                out_edge = out if _is_edge(out) else f"_e{i}"
            if out_edge in producers:
                if producers[out_edge] == -1:
                    raise GraphError(
                        f"edge {out_edge!r} is consumed as a graph input "
                        f"edge upstream but produced by node {i} "
                        f"({node.name}); in a linear '|' pipeline a join "
                        "edge must be produced before it is consumed — use "
                        "Pipeline.from_graph for order-independent wiring")
                raise GraphError(
                    f"edge {out_edge!r} has two producers (node "
                    f"{producers[out_edge]} and node {i} ({node.name}))")
            producers[out_edge] = i
            self._in_edges.append(edge)
            self._out_edges.append(out_edge)
        requested = self._requested_output
        if requested is not None:
            if requested not in producers or producers[requested] < 0:
                raise GraphError(f"requested output edge {requested!r} is "
                                 "not produced by any node")
            self._output_edge = requested
        else:
            self._output_edge = self._out_edges[-1]
        if self.fuse and self._output_edge != self._out_edges[-1]:
            raise GraphError(
                f"fuse=True requires the output edge ({self._output_edge!r})"
                " to be produced by the last node; reorder the nodes or use "
                "staged mode")

    @classmethod
    def from_graph(cls, app: CLapp, nodes: Sequence[Node | Process], *,
                   output: Optional[str] = None,
                   fuse: bool = False) -> "Pipeline":
        """Build a Pipeline from explicitly-bound nodes forming a DAG with
        named edges (order-independent; topologically sorted here).

        Every edge that is consumed — by a primary ``in`` binding or a
        secondary input port (a join) — without being produced is a
        **graph input edge**; a graph may have several (fan-in).  At most
        one node may leave its input anonymous (no ``in`` binding, or a
        concrete Data/handle) since anonymous inputs cannot be named in a
        multi-input ``run()`` mapping.  Cycles and duplicate producers
        raise :class:`GraphError` naming the offending edges.  ``output``
        selects the output edge when more than one edge is left
        unconsumed.
        """
        node_list = [cls._as_node(n) for n in nodes]
        produced: Dict[str, int] = {}
        for i, node in enumerate(node_list):
            out = node.out_bind
            edge = out if _is_edge(out) else f"_n{i}"
            if edge in produced:
                raise GraphError(
                    f"edge {edge!r} has two producers (node "
                    f"{produced[edge]} and node {i} ({node.name}))")
            produced[edge] = i

        # classify inputs: every consumed-but-unproduced edge is a graph
        # input; anonymous (None / concrete Data / handle) primary inputs
        # cannot be named in a run() mapping, so at most one is allowed
        anon_nodes: List[int] = []
        input_edges: List[str] = []
        deps: Dict[int, List[int]] = {i: [] for i in range(len(node_list))}
        for i, node in enumerate(node_list):
            b = node.in_bind
            if _is_data(b) or _is_handle(b) or b is None:
                anon_nodes.append(i)
            elif _is_edge(b):
                if b in produced:
                    deps[i].append(produced[b])
                elif b not in input_edges:
                    input_edges.append(b)
            else:
                raise GraphError(
                    f"node {i} ({node.name}): in binding must be an edge "
                    "name or (for the input node) a concrete Data/handle")
            for pname, jedge in node.input_bind.items():
                if jedge in produced:
                    deps[i].append(produced[jedge])
                elif jedge not in input_edges:
                    input_edges.append(jedge)
        if len(anon_nodes) > 1:
            names = [f"node {i} ({node_list[i].name})" for i in anon_nodes]
            raise GraphError(
                "graph has more than one anonymous input (" +
                ", ".join(names) + "); give each input node a named 'in' "
                "edge so run() can address every input edge by name")
        if anon_nodes and deps[anon_nodes[0]]:
            i = anon_nodes[0]
            raise GraphError(
                f"node {i} ({node_list[i].name}) leaves its 'in' binding "
                "anonymous but joins produced edges "
                f"{sorted(node_list[i].input_bind.values())}; name its "
                "'in' edge so the graph input can be addressed")

        # Kahn topological sort (stable: prefers given order; the
        # anonymous input node, if any, must come first — linear planning
        # assigns the anonymous '_in' edge to node 0)
        remaining = set(range(len(node_list)))
        order: List[int] = []
        while remaining:
            ready = [i for i in sorted(remaining)
                     if all(d not in remaining for d in deps[i])]
            if not order and anon_nodes and anon_nodes[0] in ready:
                ready.remove(anon_nodes[0])
                ready.insert(0, anon_nodes[0])
            if not ready:
                cyc = sorted(node_list[i].name for i in remaining)
                edges = sorted({node_list[i].in_bind for i in remaining
                                if _is_edge(node_list[i].in_bind)} |
                               {e for i in remaining
                                for e in node_list[i].input_bind.values()})
                raise GraphError(
                    f"operator graph has a cycle through {cyc} "
                    f"(edges involved: {edges})")
            order.extend(ready)
            remaining -= set(ready)
        ordered = [node_list[i] for i in order]
        if output is not None:
            # place the output producer last when nothing depends on it, so
            # fused mode (chain output = last stage output) stays possible.
            # NEVER move the anonymous-input node: linear planning assigns
            # the anonymous '_in' edge to node 0 only, so relocating it
            # would silently rewire its input to the previous node's output
            def consumes(n: Node, edge: str) -> bool:
                return (n.in_bind == edge and _is_edge(n.in_bind)) or \
                    edge in n.input_bind.values()
            prod_idx = order.index(produced[output]) if output in produced \
                else -1
            if prod_idx >= 0 and \
                    order[prod_idx] not in anon_nodes and \
                    not any(consumes(n, output) for n in node_list):
                ordered.append(ordered.pop(prod_idx))
        return cls(app, ordered, fuse=fuse, output=output,
                   _graph_input_edges=input_edges)

    # ---------------------------------------------------------------- build
    @property
    def built(self) -> bool:
        return self._built is not None

    @property
    def input_edges(self) -> Tuple[str, ...]:
        """The graph's input edges (discovery order; first is primary)."""
        return tuple(self._input_edges)

    def _example_inputs(self, inputs: Any) -> Dict[str, Data]:
        """Resolve one Data per graph input edge from ``inputs`` (None / a
        single Data / a ``{edge -> Data}`` mapping / a positional tuple in
        :attr:`input_edges` order) plus any concrete/handle bindings.
        Missing edges raise GraphError naming them."""
        app = self.app
        examples: Dict[str, Data] = {}
        primary = self._input_edges[0] if self._input_edges else None
        mapping: Mapping[str, Any] = {}
        if isinstance(inputs, Mapping) and not isinstance(inputs, Data):
            unknown = [e for e in inputs if e not in self._input_edges]
            if unknown:
                raise GraphError(
                    f"inputs name unknown edges {unknown}; this graph's "
                    f"input edges are {list(self._input_edges)}")
            mapping = inputs
        elif isinstance(inputs, (tuple, list)):
            if len(inputs) != len(self._input_edges):
                raise GraphError(
                    f"inputs supply {len(inputs)} Data for "
                    f"{len(self._input_edges)} input edges "
                    f"{list(self._input_edges)} (positional tuples follow "
                    "Pipeline.input_edges order)")
            mapping = dict(zip(self._input_edges, inputs))
        elif inputs is not None:
            if len(self._input_edges) > 1:
                raise GraphError(
                    "graph has multiple input edges "
                    f"{list(self._input_edges)}; pass one Data per edge as "
                    "a {edge name: Data} mapping")
            mapping = {primary: inputs}
        for edge in self._input_edges:
            src = mapping.get(edge)
            if src is None and edge == primary:
                src = self._input_data
                if src is None and self._input_handle is not None:
                    src = app.getData(self._input_handle)
            if src is not None and not _is_data(src):
                src = app.getData(src) if _is_handle(src) else src
            if src is None:
                raise GraphError(
                    f"no Data for input edge {edge!r}: bind it to a "
                    "concrete Data/handle or include it in the inputs "
                    f"mapping (input edges: {list(self._input_edges)})")
            examples[edge] = src
        return examples

    def build(self, input_data: Any = None) -> _Built:
        """Validate the full graph against every port, allocate edge Data,
        wire the processes, and AOT-compile — the expensive one-time work
        (the paper's ``init()``), done once and cached.

        ``input_data`` is one example Data (single-input graphs) or a
        ``{input edge -> Data}`` mapping (fan-in graphs).  All validation
        (ports, inferred inter-node specs, join batch-axis compatibility)
        happens BEFORE anything is registered or compiled, so a mis-wired
        graph is rejected without side effects.
        """
        if self._built is not None:
            return self._built
        if not self.nodes:
            raise GraphError("cannot build an empty pipeline")
        app = self.app
        examples = self._example_inputs(input_data)

        # ---- pure validation pass: specs flow edge to edge ----------------
        edge_specs: Dict[str, Dict[str, jax.ShapeDtypeStruct]] = {
            e: d.specs() for e, d in examples.items()}
        node_aux: List[Dict[str, Any]] = []
        for i, node in enumerate(self.nodes):
            p = node.process
            ports = p.ports
            in_specs = edge_specs[self._in_edges[i]]
            ports.get("in", Port()).validate(in_specs, owner=node.name,
                                             port="in")
            aux_specs: Dict[str, Dict[str, jax.ShapeDtypeStruct]] = {}
            aux_bound: Dict[str, Any] = {}
            joins = self._join_edges[i]
            for aname, aport in ports.items():
                if aname in ("in", "out"):
                    continue
                jedge = joins.get(aname)
                if jedge is not None:
                    # streaming join input: specs flow from the joined edge
                    specs = edge_specs[jedge]
                    aport.validate(specs, owner=node.name, port=aname)
                    aux_specs[aname] = specs
                    continue
                bound = node.aux_bind.get(aname)
                if bound is None:
                    if not aport.optional:
                        kind = "aux" if aport.aux else "input"
                        raise PortError(
                            f"{node.name}.ports[{aname!r}]: required "
                            f"{kind} port is unbound")
                    continue
                adata = bound if _is_data(bound) else app.getData(bound)
                specs = adata.specs()
                aport.validate(specs, owner=node.name, port=aname)
                aux_specs[aname] = specs
                aux_bound[aname] = bound
            node_aux.append(aux_bound)
            try:
                out_specs = p.out_specs(in_specs, aux_specs)
            except PortError:
                raise
            except Exception as e:
                raise PortError(
                    f"{node.name}: output spec inference failed for input "
                    f"specs {sorted(in_specs)} — the graph is mis-wired "
                    f"({e})") from e
            ports.get("out", Port()).validate(out_specs, owner=node.name,
                                              port="out")
            edge_specs[self._out_edges[i]] = out_specs
        bound_out = self._output_data
        if self._output_handle is not None:
            bound_out = app.getData(self._output_handle)
        if bound_out is not None:
            want = edge_specs[self._output_edge]
            got = bound_out.specs()
            if {k: (tuple(s.shape), jax.numpy.dtype(s.dtype)) for k, s in got.items()} != \
               {k: (tuple(s.shape), jax.numpy.dtype(s.dtype)) for k, s in want.items()}:
                raise PortError(
                    f"bound output Data specs {got} do not match the "
                    f"inferred pipeline output specs {want}")

        # ---- registration + wiring (validation passed) --------------------
        # every input edge gets a PRIVATE buffer (spec clone of its example
        # input): the caller's Data is only read, never adopted — run()
        # points the buffer's host arrays at each new input (zero-copy).
        # An explicitly handle-bound input IS the buffer (the caller
        # registered it; paper addData semantics).
        handles: Dict[str, DataHandle] = {}
        primary = self._input_edges[0]
        for edge in self._input_edges:
            if edge == primary and self._input_handle is not None:
                handles[edge] = self._input_handle
            else:
                handles[edge] = app.addData(
                    Data.from_specs(examples[edge].specs()), to_device=False)
        for i, node in enumerate(self.nodes):
            edge = self._out_edges[i]
            if edge in handles:
                continue
            if edge == self._output_edge and self._output_handle is not None:
                handles[edge] = self._output_handle
                continue
            if edge == self._output_edge and self._output_data is not None:
                d = self._output_data
            else:
                d = Data.from_specs(edge_specs[edge])
            handles[edge] = app.addData(d, to_device=False)
        aux_handle_of: Dict[int, DataHandle] = {}  # id(Data) -> handle
        procs: List[Process] = []
        for i, node in enumerate(self.nodes):
            p = node.process
            if p._app is None:
                p._app = app
            p.in_handles["in"] = handles[self._in_edges[i]]
            for pname, jedge in self._join_edges[i].items():
                p.in_handles[pname] = handles[jedge]    # streaming join
            p.out_handle = handles[self._out_edges[i]]
            for aname, bound in node_aux[i].items():
                if _is_handle(bound):
                    h = bound
                else:
                    h = aux_handle_of.get(id(bound))
                    if h is None:
                        h = app.addData(bound)
                        aux_handle_of[id(bound)] = h
                p.aux_handles[aname] = h
            procs.append(p)

        # ---- residency plan -----------------------------------------------
        # Edge classification drives where intermediates live (the paper's
        # pinned-memory/zero-copy streaming promise): graph INPUT and
        # OUTPUT edges keep the pinned host path (the caller reads/writes
        # them), every other edge is INTERNAL — its blob stays device-
        # resident end to end and never lands in the host arena mid-chain.
        # An internal edge with exactly ONE consuming port (and a staged
        # executor, where stages really are separate XLA programs) is
        # additionally DONATED: the consumer's compiled program takes the
        # upstream blob with donate_argnums, so XLA may reuse the buffer
        # in place of allocating a fresh output.  Fused executors
        # internalise these edges inside one traced program, so there is
        # nothing to donate.  Set BEFORE init(): donation is compiled in.
        name_counts: Dict[str, int] = {}
        node_names: List[str] = []
        for node in self.nodes:
            k = name_counts.get(node.name, 0)
            name_counts[node.name] = k + 1
            node_names.append(node.name if k == 0 else f"{node.name}#{k}")
        for i, p in enumerate(procs):
            p.graph_name = node_names[i]
        producer_of: Dict[str, int] = {
            self._out_edges[i]: i for i in range(len(self.nodes))}
        consumers: Dict[str, List[Tuple[int, str]]] = {}
        for i in range(len(self.nodes)):
            consumers.setdefault(self._in_edges[i], []).append((i, "in"))
            for pname, jedge in self._join_edges[i].items():
                consumers.setdefault(jedge, []).append((i, pname))
        residency: Dict[str, str] = {}
        donated_edges: Dict[str, str] = {}
        for edge, h in handles.items():
            d = app.getData(h)
            d.residency_edge = edge
            pi = producer_of.get(edge)
            d.producer_name = node_names[pi] if pi is not None else None
            internal = (edge not in self._input_edges
                        and edge != self._output_edge)
            # persistent-state Data (a decode cache bound as both the input
            # and the output edge of a step graph) keeps the device path
            # even though it sits on an input/output edge: the caller never
            # reads it between steps, so there is no pinned host round-trip
            # to preserve and every step result stays DEVICE_RESIDENT.
            d.residency = "device" if (internal or d.persistent) else "host"
            residency[edge] = d.residency
            if internal and not self.fuse and len(procs) > 1:
                cons = consumers.get(edge, ())
                if len(cons) == 1:
                    ci, port = cons[0]
                    if procs[ci].in_handles.get(port) != procs[ci].out_handle:
                        procs[ci].donate_ports = \
                            procs[ci].donate_ports | {port}
                        donated_edges[edge] = node_names[ci]

        if len(procs) == 1:
            executor: Process = procs[0]
        else:
            executor = ProcessChain(
                app, procs, mode="fused" if self.fuse else "staged")
        executor.init()
        input_handles = {e: handles[e] for e in self._input_edges}
        # positional order of the executor's launchable inputs (the order
        # stream/serve must supply per-edge batches in).  An edge may
        # appear TWICE (a self-join: one edge bound to two input ports of
        # a node) — the launchable then has more inputs than the graph has
        # input edges, and the same Data feeds both positions.
        la = executor.launchable()
        h2e = {h: e for e, h in input_handles.items()}
        missing = [h for h in la.in_handles if h not in h2e]
        if missing:
            raise GraphError(
                f"executor consumes handles {missing} that are not "
                f"graph input edges {list(self._input_edges)}; the "
                "join is mis-wired")
        input_order = tuple(h2e[h] for h in la.in_handles)
        self._built = _Built(
            executor=executor,
            handles=handles,
            input_edges=tuple(self._input_edges),
            input_handles=input_handles,
            input_layouts={
                e: (app.getData(h).layout or app.getData(h).plan())
                for e, h in input_handles.items()},
            input_order=input_order,
            output_handle=handles[self._output_edge],
            residency=residency,
            donated_edges=donated_edges,
        )
        return self._built

    @property
    def residency_plan(self) -> Dict[str, str]:
        """``{edge -> 'host' | 'device'}`` from the last :meth:`build`."""
        if self._built is None:
            raise GraphError("pipeline not built yet")
        return dict(self._built.residency)

    # ------------------------------------------------------------------ run
    def _item_tuple(self, built: _Built, item: Any, *,
                    what: str = "item") -> Any:
        """Normalize one stream/serve item for the executor: the user
        supplies one Data per graph INPUT EDGE (a lone Data, a ``{edge ->
        Data}`` mapping, or a positional tuple in :attr:`input_edges`
        order — the one order that exists before AND after build); the
        result is a positional tuple in ``built.input_order``, the
        executor's launchable argument order, in which a self-joined edge
        appears once per consuming input port."""
        edges = built.input_edges
        n = len(edges)
        if isinstance(item, Data):
            if n != 1:
                raise GraphError(
                    f"{what} is a single Data but this graph joins "
                    f"{n} input edges {list(edges)}; pass one Data per "
                    "edge as a {edge name: Data} mapping")
            by_edge = {edges[0]: item}
        elif isinstance(item, Mapping):
            missing = [e for e in edges if e not in item]
            extra = [e for e in item if e not in edges]
            if missing or extra:
                raise GraphError(
                    f"{what} does not cover the graph input edges: missing "
                    f"{missing}, unknown {extra} (input edges: "
                    f"{list(edges)})")
            by_edge = item
        elif isinstance(item, (tuple, list)):
            if len(item) != n:
                raise GraphError(
                    f"{what} supplies {len(item)} Data for {n} input "
                    f"edge(s) {list(edges)}")
            by_edge = dict(zip(edges, item))
        else:
            raise GraphError(
                f"{what} must be a Data or a {{edge name: Data}} mapping, "
                f"got {type(item).__name__}")
        if len(built.input_order) == 1:
            return by_edge[built.input_order[0]]
        return tuple(by_edge[e] for e in built.input_order)

    def run(self, inputs: Any = None, *, mode: str = "launch",
            batch: int = 1, sharded: bool = False, sync: bool = True,
            profile: Optional[ProfileParameters] = None) -> Any:
        """Route the validated graph through one of three execution modes.

        ======== =========================== ================================
        mode     inputs                      returns
        ======== =========================== ================================
        launch   one Data (or None if bound) the output Data
        stream   sequence of Data            one output Data per input
        serve    sequence of Data (requests) one output Data per request, in
                                             submit order; per-request
                                             latency recorded on ``profile``
        ======== =========================== ================================

        Fan-in graphs (several input edges) take a ``{edge name -> Data}``
        mapping wherever a single Data is listed above — one mapping for
        ``launch``, one per item/request for ``stream``/``serve``; each
        edge is batched independently and the per-edge batches are zipped
        row-aligned into one joined launch.

        ``batch``/``sharded`` apply to the stream and serve modes (see
        :meth:`Process.stream`).  With ``sync=True`` (default) results are
        copied back to host arrays; otherwise they stay device-fresh.  All
        three modes execute the SAME compiled per-item computation —
        outputs are bit-identical across modes and to the legacy imperative
        protocol, and a streamed join is bit-identical to the same port
        bound as a static aux broadcast.
        """
        with trace.span("pipeline.run", mode=mode):
            if mode == "launch":
                if inputs is not None and not isinstance(
                        inputs, (Data, Mapping, tuple)):
                    raise TypeError(
                        f"mode='launch' takes one Data (or a {{edge: Data}} "
                        f"mapping / positional tuple for fan-in graphs), got "
                        f"{type(inputs).__name__}; use mode='stream' for "
                        "sequences")
                built = self.build(inputs)
                app = self.app
                sources = self._example_inputs(inputs)
                t_up = time.perf_counter()
                uploaded = []
                for edge in built.input_edges:
                    src = sources[edge]
                    d_reg = app.getData(built.input_handles[edge])
                    if src is not d_reg:
                        self._copy_into(d_reg, src, edge=edge)
                        app.host2device(built.input_handles[edge])
                        uploaded.append(edge)
                    elif d_reg.device_blob is None:
                        # handle-bound input: the caller manages the
                        # registered Data; only transfer if it has never
                        # reached the device
                        app.host2device(built.input_handles[edge])
                        uploaded.append(edge)
                if uploaded and profile is not None and profile.enable:
                    # phase covers the landed transfers: with the residency
                    # plan these graph-input uploads are the ONLY
                    # host2device traffic of the whole chain (internal
                    # edges stay device-resident)
                    for edge in uploaded:
                        jax.block_until_ready(
                            app.getData(built.input_handles[edge]).device_blob)
                    profile.record_phase("transfer",
                                         time.perf_counter() - t_up)
                built.executor.launch(profile)
                out = app.getData(built.output_handle)
                if sync:
                    out.sync_to_host()
                return out
            if mode == "stream":
                datasets = list(inputs or ())
                if not datasets:
                    return []
                with trace.span("stream.plan"):
                    built = self.build(datasets[0])
                    items = [self._item_tuple(built, d, what=f"inputs[{i}]")
                             for i, d in enumerate(datasets)]
                return built.executor.stream(
                    items, batch=batch, sync=sync, sharded=sharded,
                    profile=profile)
            if mode == "serve":
                requests = list(inputs or ())
                if not requests:
                    return []
                server = self.serve(batch=batch, sharded=sharded)
                rids = [server.submit(d) for d in requests]
                by_rid = {r.rid: r for r in server.drain()}
                outs = []
                for rid in rids:
                    resp = by_rid[rid]
                    if profile is not None and profile.enable:
                        profile.record(resp.latency_s)
                    if sync:
                        resp.data.sync_to_host()
                    outs.append(resp.data)
                return outs
            raise ValueError(f"unknown mode {mode!r}: expected "
                             "'launch' | 'stream' | 'serve'")

    def serve(self, *, batch: int = 8, sharded: bool = False,
              flush_timeout: Optional[float] = None):
        """A standing request/response loop over this pipeline (admission
        queue -> dynamic batcher -> batched (sharded) joined launches); see
        :class:`repro.serve.pipeline.PipelineServer`.  ``flush_timeout``
        (seconds) enables the background drain thread: a partial batch is
        flushed once its oldest request has waited that long instead of
        waiting for a full batch."""
        from repro.serve.pipeline import PipelineServer  # lazy: serve layer

        return PipelineServer(self, batch=batch, sharded=sharded,
                              flush_timeout=flush_timeout)

    @staticmethod
    def _copy_into(dst: Data, src: Data, *, edge: str = "?") -> None:
        if src.layout is None:
            src.plan()
        if dst.layout is None:
            dst.plan()
        if dst.layout != src.layout:
            raise PortError(
                f"input Data layout {src.layout} for edge {edge!r} does "
                f"not match the layout the pipeline was built for "
                f"({dst.layout})")
        for a_dst, a_src in zip(dst, src):
            if a_src.host is None:
                raise PortError(
                    f"input array {a_src.name!r} has no host values")
            a_dst.set_host(a_src.host)

    def __repr__(self):
        stages = " | ".join(n.name for n in self.nodes) or "<empty>"
        return f"Pipeline[{stages}]"
