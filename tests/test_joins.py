"""Fan-in DAGs: multi-input launchables and true pipeline joins.

Covers the multi-input contract end to end: two-input bind/build-time
validation, batch-axis (missing/mis-keyed edge) errors, three-mode
bit-identity of a streamed join vs the legacy static aux-broadcast
binding (the ComplexElementProd proof case), ragged tails on joined
edges, direct Process-level multi-input streaming, the joined
SimpleMRIRecon composite, and the flush-timeout serving policy.  The
sharded joined stream runs in the multi-device subprocess harness of
tests/test_mesh_stream.py.
"""
import time

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import (CLapp, Data, GraphError, Pipeline, Port, PortError,
                        Process, ProfileParameters, XData,
                        compile_cache_stats)
from repro.processes import (FFT, ComplexElementProd, SimpleMRIRecon,
                             XImageSum)
from repro.processes.coil_combine import CombineParams
from repro.processes.complex_elementprod import ComplexElementProdParams
from repro.processes.fft import FFTParams


class AddConst(Process):
    def apply(self, views, aux, params):
        c = params if params is not None else 1.0
        return {k: v + c for k, v in views.items()}


class Scale(Process):
    def apply(self, views, aux, params):
        return {k: v * params for k, v in views.items()}


class AddTwo(Process):
    """Primary input + a second streaming input port 'rhs'."""

    ports = {"in": Port(names=("img",)), "out": Port(names=("img",)),
             "rhs": Port(names=("img",))}

    def apply(self, views, aux, params):
        return {"img": views["img"] + aux["rhs"]["img"]}


class AddStatic(Process):
    """Primary input + an aux-only (always static) port 'bias'."""

    ports = {"in": Port(), "out": Port(),
             "bias": Port(aux=True, names=("img",))}

    def apply(self, views, aux, params):
        return {k: v + aux["bias"]["img"] for k, v in views.items()}


@pytest.fixture
def app():
    return CLapp().init()


def _img(rng, shape=(6, 5)):
    return XData({"img": rng.standard_normal(shape).astype(np.float32)})


# ---------------------------------------------------------------------------
# bind/build-time validation
# ---------------------------------------------------------------------------

def test_aux_port_rejects_edge_binding(app):
    """Aux ports are genuinely static: an edge binding must fail at bind
    time, pointing at the input-port alternative."""
    with pytest.raises(PortError, match="static"):
        AddStatic(app).bind(bias="some_edge")


def test_input_port_accepts_edge_or_concrete(app, rng):
    AddTwo(app).bind(rhs="an_edge")                     # streaming join
    AddTwo(app).bind(rhs=_img(rng))                     # static broadcast
    with pytest.raises(PortError, match="missing required arrays"):
        AddTwo(app).bind(rhs=XData({"wrong": np.zeros((2, 2), np.float32)}))


def test_required_input_port_unbound_fails_at_build(app, rng):
    pipe = Pipeline(app) | AddTwo(app).bind()
    with pytest.raises(PortError, match="required input port is unbound"):
        pipe.build(_img(rng))


def test_join_edge_specs_validated_at_build(app, rng):
    """The joined edge's specs flow through Port.validate: a rhs Data
    without the required array name is rejected before any compile."""
    a = AddConst(app).bind(infile="x", outfile="lhs", params=1.0)
    j = AddTwo(app).bind(infile="lhs", outfile="sum", rhs="r")
    pipe = Pipeline.from_graph(app, [a, j], output="sum")
    h0, m0 = compile_cache_stats()
    with pytest.raises(PortError, match="missing required arrays"):
        pipe.build({"x": _img(rng),
                    "r": XData({"nope": np.zeros((6, 5), np.float32)})})
    assert compile_cache_stats() == (h0, m0), "rejection must not compile"


def test_join_shape_mismatch_rejected_at_build(app, rng):
    a = AddConst(app).bind(infile="x", outfile="lhs", params=1.0)
    j = AddTwo(app).bind(infile="lhs", outfile="sum", rhs="r")
    pipe = Pipeline.from_graph(app, [a, j], output="sum")
    with pytest.raises(PortError):
        pipe.build({"x": _img(rng, (6, 5)), "r": _img(rng, (3, 3))})


def test_linear_pipeline_join_must_be_produced_upstream(app):
    """In '|' composition a join edge produced LATER is mis-wired; the
    GraphError names the offending edge."""
    j = AddTwo(app).bind(rhs="late")
    mk = AddConst(app).bind(outfile="late", params=0.0)
    with pytest.raises(GraphError, match="'late'.*graph input|graph input.*'late'"):
        Pipeline(app) | AddConst(app).bind(params=1.0) | j | mk


def test_linear_pipeline_join_of_produced_edge(app, rng):
    """A '|' pipeline CAN join an upstream edge: diamond over 'src'."""
    base = rng.standard_normal((5, 5)).astype(np.float32)
    pipe = (Pipeline(app)
            | AddConst(app).bind(infile="src", outfile="plus", params=2.0)
            | AddTwo(app).bind(infile="plus", rhs="src"))
    out = pipe.run(XData({"img": base.copy()}))
    np.testing.assert_allclose(out.get_ndarray(0).host, (base + 2.0) + base,
                               rtol=1e-6)


def test_run_mapping_missing_edge_names_edges(app, rng):
    a = AddConst(app).bind(infile="x", outfile="lhs", params=1.0)
    j = AddTwo(app).bind(infile="lhs", outfile="sum", rhs="r")
    pipe = Pipeline.from_graph(app, [a, j], output="sum")
    with pytest.raises(GraphError, match="'r'"):
        pipe.run({"x": _img(rng)})
    with pytest.raises(GraphError, match="unknown edges.*typo"):
        pipe.run({"x": _img(rng), "r": _img(rng), "typo": _img(rng)})


def test_stream_item_batch_axis_mismatch(app, rng):
    """Stream items must cover every input edge; mismatches name the
    edges (a single Data for a two-edge graph, a short tuple, a mis-keyed
    mapping)."""
    a = AddConst(app).bind(infile="x", outfile="lhs", params=1.0)
    j = AddTwo(app).bind(infile="lhs", outfile="sum", rhs="r")
    pipe = Pipeline.from_graph(app, [a, j], output="sum")
    good = {"x": _img(rng), "r": _img(rng)}
    with pytest.raises(GraphError, match="input edges"):
        pipe.run([good, _img(rng)], mode="stream", batch=2)
    with pytest.raises(GraphError, match="missing \\['r'\\]"):
        pipe.run([good, {"x": _img(rng)}], mode="stream", batch=2)
    with pytest.raises(GraphError, match="supplies 1 Data for 2"):
        pipe.run([good, (_img(rng),)], mode="stream", batch=2)


# ---------------------------------------------------------------------------
# the proof case: ComplexElementProd as a true two-input node
# ---------------------------------------------------------------------------

FRAMES, COILS, H, W = 4, 4, 64, 64   # vmapped FFT is bitwise-stable here


def _smaps():
    rng = np.random.default_rng(7)
    return (rng.standard_normal((COILS, H, W))
            + 1j * rng.standard_normal((COILS, H, W))).astype(np.complex64)


def _kspace(n):
    out = []
    for i in range(n):
        r = np.random.default_rng(60 + i)
        k = (r.standard_normal((FRAMES, COILS, H, W))
             + 1j * r.standard_normal((FRAMES, COILS, H, W))).astype(np.complex64)
        out.append(Data({"kdata": k}))
    return out


def _aux_pipeline(app, smaps_data):
    """Legacy static binding: smaps broadcast across every batch."""
    return (Pipeline(app)
            | FFT(app).bind(infile="kspace", outfile="xspace",
                            params=FFTParams("backward", var="kdata"))
            | ComplexElementProd(app).bind(
                smaps=smaps_data,
                params=ComplexElementProdParams(conjugate=True))
            | XImageSum(app).bind(params=CombineParams()))


def _join_pipeline(app):
    """True two-input wiring: smaps stream as a second input edge."""
    fft = FFT(app).bind(infile="kspace", outfile="xspace",
                        params=FFTParams("backward", var="kdata"))
    prod = ComplexElementProd(app).bind(
        infile="xspace", outfile="weighted", smaps="smaps",
        params=ComplexElementProdParams(conjugate=True))
    comb = XImageSum(app).bind(infile="weighted", outfile="image",
                               params=CombineParams())
    return Pipeline.from_graph(app, [fft, prod, comb], output="image")


def test_two_input_elementprod_three_modes_bit_identical_to_aux(app):
    """ISSUE 4 acceptance: a two-input ComplexElementProd wired via
    Pipeline.from_graph is bit-identical to the legacy aux-broadcast
    binding in launch, stream (with a ragged tail) and serve."""
    smaps = _smaps()
    kspace = _kspace(5)                     # 5 items at batch=2: ragged tail
    smaps_stream = [Data({"sensitivity_maps": smaps.copy()})
                    for _ in range(5)]
    items = [{"kspace": k, "smaps": s}
             for k, s in zip(kspace, smaps_stream)]

    aux_pipe = _aux_pipeline(app, Data({"sensitivity_maps": smaps}))
    join_pipe = _join_pipeline(app)
    assert join_pipe.input_edges == ("kspace", "smaps")

    want_launch = [aux_pipe.run(k).get_ndarray(0).host.copy()
                   for k in kspace]
    got_launch = [join_pipe.run(it).get_ndarray(0).host.copy()
                  for it in items]
    want_stream = aux_pipe.run(kspace, mode="stream", batch=2)
    got_stream = join_pipe.run(items, mode="stream", batch=2)
    prof = ProfileParameters(enable=True)
    want_serve = aux_pipe.run(kspace, mode="serve", batch=2)
    got_serve = join_pipe.run(items, mode="serve", batch=2, profile=prof)

    for i in range(len(items)):
        np.testing.assert_array_equal(got_launch[i], want_launch[i],
                                      err_msg=f"launch[{i}]")
        np.testing.assert_array_equal(
            got_stream[i].get_ndarray(0).host,
            want_stream[i].get_ndarray(0).host, err_msg=f"stream[{i}]")
        np.testing.assert_array_equal(
            got_serve[i].get_ndarray(0).host,
            want_serve[i].get_ndarray(0).host, err_msg=f"serve[{i}]")
    assert len(prof.samples) == len(items)
    assert prof.p99() >= prof.p50() > 0


def test_join_streams_per_item_maps(app):
    """The join is genuinely per-item: DIFFERENT maps per item must give
    different (per-item correct) results — a broadcast aux cannot."""
    kspace = _kspace(4)
    maps = []
    for i in range(4):
        r = np.random.default_rng(90 + i)
        maps.append((r.standard_normal((COILS, H, W))
                     + 1j * r.standard_normal((COILS, H, W))
                     ).astype(np.complex64))
    items = [{"kspace": k, "smaps": Data({"sensitivity_maps": m})}
             for k, m in zip(kspace, maps)]
    join_pipe = _join_pipeline(app)
    got = join_pipe.run(items, mode="stream", batch=2)
    for i, (k, m) in enumerate(zip(kspace, maps)):
        aux_pipe = _aux_pipeline(app, Data({"sensitivity_maps": m}))
        want = aux_pipe.run(k).get_ndarray(0).host
        np.testing.assert_allclose(got[i].get_ndarray(0).host, want,
                                   rtol=1e-4, atol=1e-4,
                                   err_msg=f"item {i}")


def test_joined_simple_mri_recon_composite(app):
    """SimpleMRIRecon(join=True): k-space stream ⋈ sensitivity-map stream
    through ONE composite node, bit-identical to the aux-broadcast graph."""
    smaps = _smaps()
    kspace = _kspace(3)
    items = [{"kspace": k, "smaps": Data({"sensitivity_maps": smaps.copy()})}
             for k in kspace]
    aux_pipe = _aux_pipeline(app, Data({"sensitivity_maps": smaps}))
    want = aux_pipe.run(kspace, mode="stream", batch=2)

    recon = SimpleMRIRecon(app, in_place=False, join=True).bind(
        infile="kspace", smaps="smaps")
    pipe = Pipeline.from_graph(app, [recon])
    assert set(pipe.input_edges) == {"kspace", "smaps"}
    got = pipe.run(items, mode="stream", batch=2)
    for i in range(len(items)):
        np.testing.assert_array_equal(got[i].get_ndarray(0).host,
                                      want[i].get_ndarray(0).host,
                                      err_msg=f"item {i}")
    # and single-shot launch through the same composite
    one = pipe.run(items[0])
    np.testing.assert_array_equal(
        one.get_ndarray(0).host,
        aux_pipe.run(kspace[0]).get_ndarray(0).host)


def test_composite_streams_mappings_by_its_own_port_names(app):
    """A composite lowering to a ProcessChain keeps its mapping contract:
    chain-level inputs are named after the consuming ports, so
    recon.stream([{"in": ..., "smaps": ...}]) works directly (no
    Pipeline)."""
    smaps = _smaps()
    kspace = _kspace(3)
    recon = SimpleMRIRecon(app, in_place=False, join=True)
    recon.in_handles["in"] = app.addData(Data({"kdata": kspace[0].get_ndarray(0).host.copy()}))
    recon.in_handles["smaps"] = app.addData(Data({"sensitivity_maps": smaps.copy()}))
    out_spec = Data({"xdata": np.zeros((FRAMES, H, W), np.complex64)})
    recon.out_handle = app.addData(out_spec)
    recon.init()
    assert recon.launchable().in_names == ("in", "smaps")
    items = [{"in": k, "smaps": Data({"sensitivity_maps": smaps.copy()})}
             for k in kspace]
    got = recon.stream(items, batch=2, sync=True)
    aux_pipe = _aux_pipeline(app, Data({"sensitivity_maps": smaps}))
    want = aux_pipe.run(kspace, mode="stream", batch=2)
    for i in range(3):
        np.testing.assert_array_equal(got[i].get_ndarray(0).host,
                                      want[i].get_ndarray(0).host,
                                      err_msg=f"item {i}")


# ---------------------------------------------------------------------------
# ragged tails on joined edges
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sharded", [False, True])
def test_joined_ragged_tail_compiles_one_shared_executable(app, rng, sharded):
    """9 items at batch=8 on a two-edge join: waste 7/8 > 0.5 -> ONE tail
    executable spanning both edges (not one per edge), and per-item math
    still holds."""
    shape = (3, 23)                      # unique shape: fresh cache entries
    a = AddConst(app).bind(infile="x", outfile="lhs", params=1.5)
    j = AddTwo(app).bind(infile="lhs", outfile="sum", rhs="r")
    pipe = Pipeline.from_graph(app, [a, j], output="sum")
    lhs = [_img(rng, shape) for _ in range(9)]
    rhs = [_img(rng, shape) for _ in range(9)]
    items = [{"x": l, "r": r} for l, r in zip(lhs, rhs)]
    pipe.build(items[0])                 # single-shot compile outside count
    h0, m0 = compile_cache_stats()
    outs = pipe.run(items, mode="stream", batch=8, sharded=sharded)
    h1, m1 = compile_cache_stats()
    assert m1 - m0 == 2, "main batched program + ONE shared tail program"
    assert len(outs) == 9
    for l, r, o in zip(lhs, rhs, outs):
        np.testing.assert_allclose(
            o.get_ndarray(0).host,
            (l.get_ndarray(0).host + 1.5) + r.get_ndarray(0).host,
            rtol=1e-6)
    # same tail again: everything from the cache
    h2, m2 = compile_cache_stats()
    pipe.run(items, mode="stream", batch=8, sharded=sharded)
    assert compile_cache_stats()[1] == m2, "repeat stream compiles nothing"


@pytest.mark.parametrize("sharded", [False, True])
def test_joined_small_tail_pads_rows_aligned(app, rng, sharded):
    """10 items at batch=4: the padded tail must stay row-aligned across
    edges (item i of edge A multiplied with item i of edge B, never a
    padded row of one edge against a real row of the other)."""
    shape = (4, 19)
    a = AddConst(app).bind(infile="x", outfile="lhs", params=0.0)
    j = AddTwo(app).bind(infile="lhs", outfile="sum", rhs="r")
    pipe = Pipeline.from_graph(app, [a, j], output="sum")
    lhs = [_img(rng, shape) for _ in range(10)]
    rhs = [_img(rng, shape) for _ in range(10)]
    items = [{"x": l, "r": r} for l, r in zip(lhs, rhs)]
    outs = pipe.run(items, mode="stream", batch=4, sharded=sharded)
    for l, r, o in zip(lhs, rhs, outs):
        np.testing.assert_allclose(
            o.get_ndarray(0).host,
            l.get_ndarray(0).host + r.get_ndarray(0).host, rtol=1e-6)


# ---------------------------------------------------------------------------
# direct Process-level multi-input streaming (no Pipeline)
# ---------------------------------------------------------------------------

def test_process_stream_multi_input_mappings_and_tuples(app, rng):
    d_in = XData({"img": np.zeros((6, 6), np.float32)})
    d_rhs = XData(d_in, copy_values=False)
    d_out = XData(d_in, copy_values=False)
    p = AddTwo(app)
    p.in_handles["in"] = app.addData(d_in)
    p.in_handles["rhs"] = app.addData(d_rhs)
    p.out_handle = app.addData(d_out)
    assert p.input_names == ("in", "rhs")
    lhs = [_img(rng, (6, 6)) for _ in range(5)]
    rhs = [_img(rng, (6, 6)) for _ in range(5)]
    got = p.stream([{"in": a, "rhs": b} for a, b in zip(lhs, rhs)],
                   batch=2, sync=True)
    for a, b, o in zip(lhs, rhs, got):
        np.testing.assert_array_equal(
            o.get_ndarray(0).host,
            a.get_ndarray(0).host + b.get_ndarray(0).host)
    got2 = p.stream(list(zip(lhs, rhs)), batch=2, sync=True)  # positional
    for o, o2 in zip(got, got2):
        np.testing.assert_array_equal(o.get_ndarray(0).host,
                                      o2.get_ndarray(0).host)
    with pytest.raises(ValueError, match="streaming inputs"):
        p.stream(lhs, batch=2)           # single Data for a 2-input process


# ---------------------------------------------------------------------------
# serving: multi-tensor requests + flush timeout
# ---------------------------------------------------------------------------

def test_server_multi_tensor_requests(app, rng):
    a = AddConst(app).bind(infile="x", outfile="lhs", params=1.0)
    j = AddTwo(app).bind(infile="lhs", outfile="sum", rhs="r")
    pipe = Pipeline.from_graph(app, [a, j], output="sum")
    server = pipe.serve(batch=4)
    reqs = [{"x": _img(rng), "r": _img(rng)} for _ in range(6)]
    rids = [server.submit(q) for q in reqs]
    assert server.input_edges == ("x", "r")
    responses = {r.rid: r for r in server.drain()}
    assert server.launches == 2
    for rid, q in zip(rids, reqs):
        resp = responses[rid]
        resp.data.sync_to_host()
        np.testing.assert_allclose(
            resp.data.get_ndarray(0).host,
            (q["x"].get_ndarray(0).host + 1.0) + q["r"].get_ndarray(0).host,
            rtol=1e-6)
    with pytest.raises(PortError, match="layout"):
        server.submit({"x": _img(rng, (2, 2)), "r": _img(rng, (2, 2))})


@pytest.mark.parametrize("sharded", [False, True])
def test_server_flush_timeout_background_drain(app, rng, sharded):
    """A partial batch is flushed by the background thread after
    flush_timeout instead of waiting for a full batch; results match and
    latency reflects the timeout wait."""
    pipe = Pipeline(app) | Scale(app).bind(params=-3.0)
    server = pipe.serve(batch=8, sharded=sharded, flush_timeout=0.05)
    try:
        # warm up the tail executables outside the timed window
        server.submit(_img(rng))
        server.collect(1, timeout=30.0)
        ds = [_img(rng) for _ in range(3)]
        rids = [server.submit(d) for d in ds]
        t0 = time.perf_counter()
        resp = server.collect(3, timeout=30.0)
        waited = time.perf_counter() - t0
        assert len(resp) == 3, f"flush_timeout never flushed ({waited:.2f}s)"
        by_rid = {r.rid: r for r in resp}
        for rid, d in zip(rids, ds):
            r = by_rid[rid]
            r.data.sync_to_host()
            np.testing.assert_allclose(r.data.get_ndarray(0).host,
                                       d.get_ndarray(0).host * -3.0,
                                       rtol=1e-6)
            assert r.latency_s >= 0.04, \
                "partial batch must wait ~flush_timeout before flushing"
        # a FULL batch flushes without waiting for the timeout
        rids = [server.submit(_img(rng)) for _ in range(8)]
        resp = server.collect(8, timeout=30.0)
        assert {r.rid for r in resp} == set(rids)
        lat = sorted(r.latency_s for r in resp)
        assert lat[0] < 0.05, "a full batch must not wait for the timeout"
        # drain() forces an immediate partial flush
        server.submit(_img(rng))
        out = server.drain()
        assert len(out) == 1
    finally:
        server.close()


def test_server_flush_timeout_validation(app):
    pipe = Pipeline(app) | Scale(app).bind(params=1.0)
    with pytest.raises(ValueError, match="flush_timeout"):
        pipe.serve(flush_timeout=0.0)


def test_collect_without_background_thread_fails_fast(app, rng):
    """collect() can never succeed without the background drain (only
    drain() produces responses) — it must raise, not sleep and return []."""
    pipe = Pipeline(app) | Scale(app).bind(params=1.0)
    server = pipe.serve(batch=4)            # no flush_timeout
    server.submit(_img(rng))
    with pytest.raises(RuntimeError, match="flush_timeout"):
        server.collect(1, timeout=5.0)


def test_worker_death_surfaces_to_callers(app, rng):
    """A launch failure in the background thread must surface as an error
    on collect()/submit()/drain() instead of hanging or dropping silently."""
    pipe = Pipeline(app) | Scale(app).bind(params=1.0)
    server = pipe.serve(batch=8, flush_timeout=0.02)
    try:
        server.submit(_img(rng))
        server.collect(1, timeout=30.0)     # built + worker running

        def boom(items):
            raise RuntimeError("injected launch failure")
        server._plan.stack_group = boom
        server.submit(_img(rng))
        with pytest.raises(RuntimeError, match="drain thread died"):
            server.collect(1, timeout=30.0)
        with pytest.raises(RuntimeError, match="drain thread died"):
            server.submit(_img(rng))
        with pytest.raises(RuntimeError, match="drain thread died"):
            server.drain()
    finally:
        server.close()


# ---------------------------------------------------------------------------
# positional tuple inputs (input_edges order), pre-build
# ---------------------------------------------------------------------------

def test_self_join_same_edge_into_two_ports(app, rng):
    """One edge bound to BOTH input ports of a node (x + x): the
    launchable has two streaming inputs fed by one graph input edge, in
    every mode."""
    j = AddTwo(app).bind(infile="x", outfile="sum", rhs="x")
    pipe = Pipeline.from_graph(app, [j], output="sum")
    assert pipe.input_edges == ("x",)
    ds = [_img(rng) for _ in range(3)]
    want = [2.0 * d.get_ndarray(0).host for d in ds]
    out = pipe.run({"x": ds[0]})
    np.testing.assert_allclose(out.get_ndarray(0).host, want[0], rtol=1e-6)
    streamed = pipe.run(ds, mode="stream", batch=2)
    served = pipe.run([{"x": d} for d in ds], mode="serve", batch=2)
    for i in range(3):
        np.testing.assert_allclose(streamed[i].get_ndarray(0).host,
                                   want[i], rtol=1e-6, err_msg=f"stream {i}")
        np.testing.assert_allclose(served[i].get_ndarray(0).host,
                                   want[i], rtol=1e-6, err_msg=f"serve {i}")


def test_from_graph_output_reorder_keeps_anonymous_input_first(app, rng):
    """Regression: relocating the output producer to the end must never
    move the anonymous-input node off position 0 — linear planning would
    silently rewire it to consume the previous node's output."""
    a = AddConst(app).bind(outfile="y", params=1.0)       # anonymous input
    b = Scale(app).bind(infile="in2", outfile="z", params=3.0)
    pipe = Pipeline.from_graph(app, [a, b], output="y")
    assert set(pipe.input_edges) == {"_in", "in2"}, \
        "the anonymous input must survive the output reorder"
    ones = XData({"img": np.ones((3, 3), np.float32)})
    out = pipe.run({"_in": ones, "in2": _img(rng)})
    np.testing.assert_allclose(out.get_ndarray(0).host,
                               np.full((3, 3), 2.0), rtol=1e-6)


def test_positional_tuple_inputs_before_build(app, rng):
    """Tuples in Pipeline.input_edges order work in every mode, including
    as the FIRST call on an unbuilt fan-in pipeline."""
    def graph():
        a = AddConst(app).bind(infile="x", outfile="lhs", params=1.0)
        j = AddTwo(app).bind(infile="lhs", outfile="sum", rhs="r")
        return Pipeline.from_graph(app, [a, j], output="sum")

    lhs = [_img(rng) for _ in range(3)]
    rhs = [_img(rng) for _ in range(3)]

    pipe = graph()                           # unbuilt: stream of tuples
    assert pipe.input_edges == ("x", "r")
    outs = pipe.run(list(zip(lhs, rhs)), mode="stream", batch=2)
    for l, r, o in zip(lhs, rhs, outs):
        np.testing.assert_allclose(
            o.get_ndarray(0).host,
            (l.get_ndarray(0).host + 1.0) + r.get_ndarray(0).host,
            rtol=1e-6)

    pipe2 = graph()                          # unbuilt: tuple launch
    out = pipe2.run((lhs[0], rhs[0]))
    np.testing.assert_allclose(
        out.get_ndarray(0).host,
        (lhs[0].get_ndarray(0).host + 1.0) + rhs[0].get_ndarray(0).host,
        rtol=1e-6)
    with pytest.raises(GraphError, match="supply 1 Data|supplies 1 Data"):
        pipe2.run((lhs[0],))
