"""Declarative operator-graph API: typed-port validation at bind time,
Pipeline launch/stream/serve bit-identity with the legacy imperative
protocol, deprecation shims, ragged-tail executables, profile statistics."""
import warnings

import numpy as np
import pytest

import jax

from repro.core import (CLapp, Data, GraphError, KData, Node, Pipeline, Port,
                        PortError, Process, ProfileParameters, XData,
                        compile_cache_stats)
from repro.core.stream import TAIL_WASTE, launch_rows
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


class AddAux(Process):
    ports = {"in": Port(), "out": Port(),
             "bias": Port(aux=True, names=("img",))}

    def apply(self, views, aux, params):
        return {k: v + aux["bias"]["img"] for k, v in views.items()}


@pytest.fixture
def app():
    return CLapp().init()


def _xdata(rng, shape=(6, 5)):
    return XData({"img": rng.standard_normal(shape).astype(np.float32)})


# ---------------------------------------------------------------------------
# wiring + validation (must reject at bind/build time, not at launch)
# ---------------------------------------------------------------------------

def test_bind_rejects_unknown_port(app):
    with pytest.raises(PortError, match="no input or aux port"):
        AddAux(app).bind(nope=Data({"img": np.zeros((2, 2), np.float32)}))


def test_bind_validates_concrete_aux_data(app):
    # aux port requires an array named 'img'
    with pytest.raises(PortError, match="missing required arrays"):
        AddAux(app).bind(bias=Data({"wrong": np.zeros((2, 2), np.float32)}))


def test_bind_validates_concrete_input_data(app):
    bad = Data({"kdata": np.zeros((2, 2), np.float32)})  # not complex
    with pytest.raises(PortError, match="dtype"):
        ComplexElementProd(app).bind(infile=bad)


def test_pipeline_rejects_unknown_edge_at_composition(app):
    fft = FFT(app).bind(outfile="x")
    with pytest.raises(GraphError, match="no upstream node produces"):
        Pipeline(app) | fft | XImageSum(app).bind(infile="typo_edge")


def test_pipeline_rejects_duplicate_producer(app):
    with pytest.raises(GraphError, match="two producers"):
        (Pipeline(app)
         | AddConst(app).bind(outfile="e")
         | Scale(app).bind(infile="e", outfile="e"))


def test_build_rejects_spec_mismatch_before_any_compile(app, rng):
    """A mis-wired graph fails port validation in build() with NO side
    effects — nothing is compiled, nothing is registered."""
    pipe = Pipeline(app) | XImageSum(app).bind(params=CombineParams())
    h0, m0 = compile_cache_stats()
    n_data = len(app.data_handles)
    with pytest.raises(PortError, match="missing required arrays"):
        pipe.build(_xdata(rng))               # XImageSum needs 'kdata', 4-D
    h1, m1 = compile_cache_stats()
    assert (h1, m1) == (h0, m0), "validation must not compile anything"
    assert len(app.data_handles) == n_data, "validation must not register"


def test_build_rejects_rank_mismatch(app):
    bad = KData({"kdata": np.zeros((2, 3, 4), np.complex64),  # 3-D, needs 4
                 "sensitivity_maps": np.zeros((3, 4), np.complex64)})
    pipe = Pipeline(app) | XImageSum(app)
    with pytest.raises(PortError, match="ndim"):
        pipe.build(bad)


def test_from_graph_detects_cycle(app):
    a = AddConst(app).bind(infile="x", outfile="y")
    b = Scale(app).bind(infile="y", outfile="x")
    with pytest.raises(GraphError, match="cycle|exactly one input"):
        Pipeline.from_graph(app, [a, b])


def test_from_graph_rejects_multiple_anonymous_inputs(app):
    # two nodes leaving 'in' anonymous cannot be addressed by a run()
    # mapping; named input edges (a fan-in graph) are fine — see
    # tests/test_joins.py for the multi-input contract
    a = AddConst(app)
    b = Scale(app).bind(params=2.0)
    with pytest.raises(GraphError, match="anonymous input"):
        Pipeline.from_graph(app, [a.bind(outfile="y"), b])


def test_from_graph_accepts_multiple_named_inputs(app, rng):
    a = AddConst(app).bind(infile="in1", outfile="y", params=1.0)
    b = Scale(app).bind(infile="in2", outfile="z", params=3.0)
    pipe = Pipeline.from_graph(app, [a, b], output="z")
    assert set(pipe.input_edges) == {"in1", "in2"}
    d1, d2 = _xdata(rng), _xdata(rng)
    out = pipe.run({"in1": d1, "in2": d2})
    np.testing.assert_allclose(out.get_ndarray(0).host,
                               d2.get_ndarray(0).host * 3.0, rtol=1e-6)


# ---------------------------------------------------------------------------
# execution: linear pipelines, DAGs, auto-wiring
# ---------------------------------------------------------------------------

def test_linear_pipeline_matches_manual_math(app, rng):
    base = rng.standard_normal((6, 5)).astype(np.float32)
    pipe = (Pipeline(app)
            | AddConst(app).bind(params=1.5)
            | Scale(app).bind(params=-2.0))
    out = pipe.run(XData({"img": base.copy()}))
    np.testing.assert_allclose(out.get_ndarray(0).host, (base + 1.5) * -2.0,
                               rtol=1e-6)


def test_pipeline_run_reuses_compiled_executable(app, rng):
    """Second run() with a fresh input Data must not recompile (the
    paper's zero-per-iteration-overhead property)."""
    pipe = Pipeline(app) | AddConst(app).bind(params=2.0)
    first = pipe.run(_xdata(rng, (7, 3)))
    h0, m0 = compile_cache_stats()
    d2 = _xdata(rng, (7, 3))
    second = pipe.run(d2)
    h1, m1 = compile_cache_stats()
    assert m1 == m0, "repeat run must not trace/compile again"
    np.testing.assert_allclose(second.get_ndarray(0).host,
                               d2.get_ndarray(0).host + 2.0, rtol=1e-6)
    assert first is second, "output Data is the registered output edge"


def test_aux_port_broadcast(app, rng):
    bias = rng.standard_normal((4, 4)).astype(np.float32)
    pipe = (Pipeline(app)
            | AddAux(app).bind(bias=XData({"img": bias})))
    d = _xdata(rng, (4, 4))
    out = pipe.run(d)
    np.testing.assert_allclose(out.get_ndarray(0).host,
                               d.get_ndarray(0).host + bias, rtol=1e-6)


def test_from_graph_fork_and_order_independence(app, rng):
    """Nodes arrive shuffled; from_graph topologically sorts them.  The
    fork (Scale reads the graph input edge, not AddConst's output) must be
    honoured — same wiring as the imperative forked-chain test."""
    base = rng.standard_normal((5, 5)).astype(np.float32)
    add = AddConst(app).bind(infile="src", outfile="plus1", params=1.0)
    scale = Scale(app).bind(infile="src", outfile="tripled", params=3.0)
    pipe = Pipeline.from_graph(app, [scale, add], output="tripled")
    out = pipe.run(XData({"img": base.copy()}))
    np.testing.assert_allclose(out.get_ndarray(0).host, base * 3.0,
                               rtol=1e-6)

    series = Pipeline.from_graph(
        app, [Scale(app).bind(infile="mid", outfile="done", params=3.0),
              AddConst(app).bind(infile="src2", outfile="mid", params=1.0)],
        output="done")
    out2 = series.run(XData({"img": base.copy()}))
    np.testing.assert_allclose(out2.get_ndarray(0).host, (base + 1.0) * 3.0,
                               rtol=1e-6)


def test_handle_bound_input_and_output(app, rng):
    """Explicit DataHandle bindings are honoured: the registered Data ARE
    the pipeline's input/output buffers (paper addData semantics)."""
    d_in = _xdata(rng, (4, 6))
    d_out = XData(d_in, copy_values=False)
    h_in, h_out = app.addData(d_in), app.addData(d_out)
    pipe = (Pipeline(app)
            | Scale(app).bind(infile=h_in, outfile=h_out, params=2.0))
    out = pipe.run()                       # no inputs: the handle is bound
    assert out is d_out, "results must land in the handle-bound Data"
    np.testing.assert_allclose(d_out.get_ndarray(0).host,
                               d_in.get_ndarray(0).host * 2.0, rtol=1e-6)
    # handle-bound output with a mismatched layout is rejected at build
    h_bad = app.addData(XData({"img": np.zeros((3, 3), np.float32)}))
    bad = Pipeline(app) | Scale(app).bind(outfile=h_bad, params=2.0)
    with pytest.raises(PortError, match="output"):
        bad.build(_xdata(rng, (4, 6)))


def test_fused_pipeline_matches_staged(app, rng):
    base = rng.standard_normal((6, 6)).astype(np.float32)

    def build(fuse):
        pipe = Pipeline(app, fuse=fuse) \
            | AddConst(app).bind(params=0.5) | Scale(app).bind(params=4.0)
        return pipe.run(XData({"img": base.copy()})).get_ndarray(0).host

    np.testing.assert_allclose(build(False), build(True), rtol=1e-6)


# ---------------------------------------------------------------------------
# acceptance: all three modes bit-identical to the legacy protocol
# ---------------------------------------------------------------------------

FRAMES, COILS, H, W = 4, 4, 64, 64   # vmapped FFT is bitwise-stable here


def _mri_inputs(n):
    rng = np.random.default_rng(7)
    smaps = (rng.standard_normal((COILS, H, W))
             + 1j * rng.standard_normal((COILS, H, W))).astype(np.complex64)
    out = []
    for i in range(n):
        r = np.random.default_rng(50 + i)
        k = (r.standard_normal((FRAMES, COILS, H, W))
             + 1j * r.standard_normal((FRAMES, COILS, H, W))).astype(np.complex64)
        out.append(KData({"kdata": k, "sensitivity_maps": smaps}))
    return out


def test_three_modes_bit_identical_to_legacy(app):
    """ISSUE 3 acceptance: Pipeline.run == legacy init()/launch() for
    SimpleMRIRecon, bitwise, in launch / stream(batch>1) / serve."""
    inputs = _mri_inputs(5)

    # legacy imperative reference, one launch per input
    d_in = _mri_inputs(1)[0]
    d_out = XData({"xdata": np.zeros(d_in.x_shape(), np.complex64)})
    h_in, h_out = app.addData(d_in), app.addData(d_out)
    legacy = SimpleMRIRecon(app, in_place=False)
    legacy.in_handle, legacy.out_handle = h_in, h_out
    legacy.init()
    want = []
    for src in inputs:
        for dst, s in zip(d_in, src):
            dst.set_host(s.host)
        app.host2device(h_in)
        legacy.launch()
        app.device2Host(h_out)
        want.append(d_out.get_ndarray(0).host.copy())

    # declarative: same operators, explicit graph
    pipe = (Pipeline(app)
            | FFT(app).bind(infile="kspace", outfile="xspace",
                            params=FFTParams("backward", var="kdata"))
            | ComplexElementProd(app).bind(
                params=ComplexElementProdParams(conjugate=True))
            | XImageSum(app).bind(params=CombineParams()))

    got_launch = [
        pipe.run(src).get_ndarray(0).host.copy() for src in inputs]
    got_stream = pipe.run(inputs, mode="stream", batch=2, sync=True)
    prof = ProfileParameters(enable=True)
    got_serve = pipe.run(inputs, mode="serve", batch=2, profile=prof)

    for i in range(len(inputs)):
        # launch mode runs one unbatched program per input — the exact
        # executable the legacy path compiles — and stays bitwise.
        np.testing.assert_array_equal(got_launch[i], want[i],
                                      err_msg=f"launch[{i}]")
        # stream/serve dispatch BATCHED programs (batch=2): XLA fuses the
        # fft→elementprod→sum chain differently under the extra leading
        # axis, reassociating the complex mults/adds.  Observed drift tops
        # out near 2.5e-5 relative (~2e-6 absolute) on CPU — numerical
        # noise, not a semantic divergence — so the batched modes assert
        # allclose at rtol=1e-4, and bitwise against each other below.
        np.testing.assert_allclose(
            got_stream[i].get_ndarray(0).host, want[i],
            rtol=1e-4, atol=1e-5, err_msg=f"stream[{i}]")
        np.testing.assert_allclose(
            got_serve[i].get_ndarray(0).host, want[i],
            rtol=1e-4, atol=1e-5, err_msg=f"serve[{i}]")
        # both batched modes run the SAME compiled program: bitwise equal.
        np.testing.assert_array_equal(
            got_serve[i].get_ndarray(0).host,
            got_stream[i].get_ndarray(0).host,
            err_msg=f"serve[{i}] vs stream[{i}]")
    assert len(prof.samples) == len(inputs), "one latency per request"
    assert all(s > 0 for s in prof.samples)
    assert prof.p99() >= prof.p50() > 0

    # the composite process is itself a valid single pipeline node
    solo = Pipeline(app) | SimpleMRIRecon(app, in_place=False).bind()
    got_solo = solo.run(inputs[0])
    np.testing.assert_array_equal(got_solo.get_ndarray(0).host, want[0])


# ---------------------------------------------------------------------------
# deprecation shims
# ---------------------------------------------------------------------------

def test_legacy_setters_bit_identical_and_warn_exactly_once(app, rng):
    base = rng.standard_normal((6, 5)).astype(np.float32)

    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        d_in = XData({"img": base.copy()})
        d_out = XData(d_in, copy_values=False)
        h_in, h_out = app.addData(d_in), app.addData(d_out)
        p = Scale(app)
        p.set_in_handle(h_in)           # deprecated protocol
        p.set_out_handle(h_out)
        p.set_launch_parameters(2.5)
        p.init()
        p.launch()
        app.device2Host(h_out)
        legacy = d_out.get_ndarray(0).host.copy()
    dep = [w for w in rec if issubclass(w.category, DeprecationWarning)]
    assert len(dep) == 1, "the legacy sequence must warn exactly once"
    assert "bind" in str(dep[0].message)

    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        pipe = Pipeline(app) | Scale(app).bind(params=2.5)
        new = pipe.run(XData({"img": base.copy()})).get_ndarray(0).host
    assert not [w for w in rec if issubclass(w.category, DeprecationWarning)], \
        "the declarative path must not warn"
    np.testing.assert_array_equal(new, legacy)


def test_camelcase_aliases_also_warn(app, rng):
    d = _xdata(rng)
    h = app.addData(d)
    p = AddConst(app)
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        p.setInHandle(h)
        p.setOutHandle(h)
    assert len([w for w in rec
                if issubclass(w.category, DeprecationWarning)]) == 1


# ---------------------------------------------------------------------------
# ragged-tail executable (ROADMAP open item)
# ---------------------------------------------------------------------------

def _wired_scale(app, shape):
    d_in = XData({"img": np.zeros(shape, np.float32)})
    d_out = XData(d_in, copy_values=False)
    p = Scale(app)
    p.in_handle, p.out_handle = app.addData(d_in), app.addData(d_out)
    p.set_launch_parameters(3.0)
    return p


@pytest.mark.parametrize("sharded", [False, True])
def test_ragged_tail_compiles_second_executable(app, rng, sharded):
    """9 items at batch=8: waste 7/8 > 0.5 -> the tail runs through a
    second executable compiled for 1 row (one extra cache miss), and the
    results still match the per-item math."""
    shape = (3, 17)                       # unique shape: fresh cache entries
    p = _wired_scale(app, shape)
    datasets = [XData({"img": rng.standard_normal(shape).astype(np.float32)})
                for _ in range(9)]
    h0, m0 = compile_cache_stats()
    outs = p.stream(datasets, batch=8, sync=True, sharded=sharded)
    h1, m1 = compile_cache_stats()
    assert m1 - m0 == 2, "main batched program + tail program"
    assert len(outs) == 9
    for d, o in zip(datasets, outs):
        np.testing.assert_allclose(o.get_ndarray(0).host,
                                   d.get_ndarray(0).host * 3.0, rtol=1e-6)
    # same tail size again: both executables come from the cache
    h2, m2 = compile_cache_stats()
    p.stream(datasets, batch=8, sync=True, sharded=sharded)
    h3, m3 = compile_cache_stats()
    assert m3 == m2, "repeat stream compiles nothing new"


@pytest.mark.parametrize("sharded", [False, True])
def test_small_waste_still_pads(app, rng, sharded):
    """10 items at batch=4: waste 2/4 <= 0.5 -> the tail is padded by
    repetition (no second executable, exactly one compile)."""
    shape = (5, 13)
    p = _wired_scale(app, shape)
    datasets = [XData({"img": rng.standard_normal(shape).astype(np.float32)})
                for _ in range(10)]
    h0, m0 = compile_cache_stats()
    outs = p.stream(datasets, batch=4, sync=True, sharded=sharded)
    h1, m1 = compile_cache_stats()
    assert m1 - m0 == 1, "padding path must not compile a tail program"
    for d, o in zip(datasets, outs):
        np.testing.assert_allclose(o.get_ndarray(0).host,
                                   d.get_ndarray(0).host * 3.0, rtol=1e-6)


@pytest.mark.parametrize("rows,batch,data_axis,want", [
    (8, 8, 1, 8),       # a full batch
    (9, 8, 1, 8),       # more rows than the batch: a full one
    (0, 8, 1, 8),       # no rows: nothing to shrink to
    (4, 8, 1, 8),       # waste 0.5, at the limit: pad
    (6, 8, 1, 8),       # waste 0.25: pad
    (3, 8, 1, 3),       # waste 0.625 unsharded: a tail executable
    (2, 8, 2, 2),       # waste 0.75, a multiple of the data axis: a tail
    (3, 8, 2, 8),       # waste 0.625, not a multiple of it: pad
])
def test_launch_rows_decision_table(rows, batch, data_axis, want):
    """The ragged-tail rule: pad while the waste is at most TAIL_WASTE,
    or when a sharded tail is not a multiple of the data axis."""
    assert TAIL_WASTE == 0.5
    assert launch_rows(rows, batch, data_axis) == want


@pytest.mark.parametrize("entry", ["Process.stream", "Pipeline.run",
                                   "Pipeline.serve", "PipelineServer"])
def test_removed_stream_options_raise_type_error(app, rng, entry):
    """The batch carve and queue depth are fixed: each former option is
    refused by name, not silently ignored."""
    from repro.serve.pipeline import PipelineServer
    datasets = [XData({"img": rng.standard_normal((2, 3)).astype(np.float32)})
                for _ in range(2)]
    pipe = Pipeline(app) | Scale(app).bind(params=2.0)
    call = {
        "Process.stream": lambda **kw: _wired_scale(app, (2, 3)).stream(
            datasets, batch=2, **kw),
        "Pipeline.run": lambda **kw: pipe.run(datasets, mode="stream",
                                              batch=2, **kw),
        "Pipeline.serve": lambda **kw: pipe.serve(batch=2, **kw),
        "PipelineServer": lambda **kw: PipelineServer(pipe, batch=2, **kw),
    }[entry]
    for name, value in (("depth", 2), ("split", "equal"), ("lanes", False)):
        with pytest.raises(TypeError, match=name):
            call(**{name: value})


# ---------------------------------------------------------------------------
# serving loop
# ---------------------------------------------------------------------------

def test_server_dynamic_batching_and_redrain(app, rng):
    shape = (4, 9)
    pipe = Pipeline(app) | Scale(app).bind(params=-1.5)
    server = pipe.serve(batch=4)
    datasets = [XData({"img": rng.standard_normal(shape).astype(np.float32)})
                for _ in range(6)]
    rids = [server.submit(d) for d in datasets]
    assert rids == list(range(6)) and server.pending == 6
    responses = server.drain()
    assert server.pending == 0 and server.served == 6
    assert server.launches == 2, "6 requests at batch=4 -> two launches"
    by_rid = {r.rid: r for r in responses}
    for rid, d in zip(rids, datasets):
        r = by_rid[rid]
        r.data.sync_to_host()
        np.testing.assert_allclose(r.data.get_ndarray(0).host,
                                   d.get_ndarray(0).host * -1.5, rtol=1e-6)
        assert r.latency_s > 0
    # the server keeps serving: a second wave reuses the compiled program
    h0, m0 = compile_cache_stats()
    more = [XData({"img": rng.standard_normal(shape).astype(np.float32)})
            for _ in range(3)]
    rids2 = [server.submit(d) for d in more]
    assert rids2 == [6, 7, 8]
    resp2 = server.drain()
    h1, m1 = compile_cache_stats()
    assert m1 == m0, "steady-state serving never recompiles"
    assert {r.rid for r in resp2} == {6, 7, 8}


def test_server_rejects_wrong_layout(app, rng):
    pipe = Pipeline(app) | Scale(app).bind(params=2.0)
    server = pipe.serve(batch=2)
    server.submit(_xdata(rng, (6, 5)))
    with pytest.raises(PortError, match="layout"):
        server.submit(_xdata(rng, (3, 3)))


# ---------------------------------------------------------------------------
# ProfileParameters statistics (satellite: no division by zero)
# ---------------------------------------------------------------------------

def test_profile_parameters_zero_samples_is_nan():
    prof = ProfileParameters(enable=True)   # launch() never profiled
    assert np.isnan(prof.mean())
    assert np.isnan(prof.percentile(50))
    assert np.isnan(prof.p50()) and np.isnan(prof.p99())


def test_profile_parameters_statistics():
    prof = ProfileParameters(enable=True)
    for s in (1.0, 2.0, 3.0, 10.0):
        prof.record(s)
    assert prof.mean() == 4.0
    assert prof.p50() == 2.5
    assert prof.p99() <= 10.0
    disabled = ProfileParameters(enable=False)
    disabled.record(5.0)                    # ignored when disabled
    assert np.isnan(disabled.mean())
