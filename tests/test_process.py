"""Process semantics: init/launch split, zero-copy chaining, staged==fused,
compile cache, donation — the paper's §III-A.3 behaviours."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (CLapp, Data, DeviceTraits, Pipeline, PlatformTraits,
                        Port, PortError, Process, ProcessChain,
                        ProfileParameters, SyncSource, XData,
                        compile_cache_stats, trace)


class AddConst(Process):
    def apply(self, views, aux, params):
        c = params if params is not None else 1.0
        return {k: v + c for k, v in views.items()}


class Scale(Process):
    def apply(self, views, aux, params):
        return {k: v * params for k, v in views.items()}


@pytest.fixture
def app():
    return CLapp().init(PlatformTraits(), DeviceTraits())


def _data(rng, shape=(16, 16)):
    return XData({"img": rng.standard_normal(shape).astype(np.float32)})


def test_init_launch_split_and_overhead(app, rng):
    """init() pays compilation; launch() must be orders faster."""
    d_in, d_out = _data(rng), None
    h_in = app.addData(d_in)
    d_out = XData(d_in, copy_values=False)
    h_out = app.addData(d_out)
    p = AddConst(app)
    p.set_in_handle(h_in)
    p.set_out_handle(h_out)
    p.set_launch_parameters(2.5)
    t0 = time.perf_counter()
    p.init()
    t_init = time.perf_counter() - t0
    prof = ProfileParameters(enable=True)
    for _ in range(20):
        p.launch(prof)
    assert prof.mean() < t_init, "launch must be cheaper than init (plan baking)"
    app.device2Host(h_out)
    np.testing.assert_allclose(d_out.get_ndarray(0).host,
                               d_in.get_ndarray(0).host + 2.5, rtol=1e-6)


def test_chain_staged_equals_fused(app, rng):
    base = rng.standard_normal((8, 8)).astype(np.float32)
    results = {}
    for mode in ("staged", "fused"):
        d_in = XData({"img": base.copy()})
        d_mid = XData(d_in, copy_values=False)
        d_out = XData(d_in, copy_values=False)
        h_in, h_mid, h_out = (app.addData(x) for x in (d_in, d_mid, d_out))
        p1 = AddConst(app); p1.set_in_handle(h_in); p1.set_out_handle(h_mid)
        p1.set_launch_parameters(1.0)
        p2 = Scale(app); p2.set_in_handle(h_mid); p2.set_out_handle(h_out)
        p2.set_launch_parameters(3.0)
        chain = ProcessChain(app, [p1, p2], mode=mode)
        chain.init()
        chain.launch()
        app.device2Host(h_out)
        results[mode] = d_out.get_ndarray(0).host.copy()
    np.testing.assert_allclose(results["staged"], results["fused"], rtol=1e-6)
    np.testing.assert_allclose(results["staged"], (base + 1.0) * 3.0, rtol=1e-6)


def test_in_place_donation(app, rng):
    """out_handle == in_handle: the blob is donated, result lands in place."""
    d = _data(rng)
    orig = d.get_ndarray(0).host.copy()
    h = app.addData(d)
    p = AddConst(app)
    p.set_in_handle(h)
    p.set_out_handle(h)
    p.set_launch_parameters(5.0)
    p.init()
    p.launch()
    app.device2Host(h)
    np.testing.assert_allclose(d.get_ndarray(0).host, orig + 5.0, rtol=1e-6)


def test_compile_cache_hits(app, rng):
    """Same process class + same layout + same params = one compilation."""
    h0, m0 = compile_cache_stats()
    for _ in range(3):
        d_in = _data(rng)
        d_out = XData(d_in, copy_values=False)
        h_in, h_out = app.addData(d_in), app.addData(d_out)
        p = Scale(app)
        p.set_in_handle(h_in); p.set_out_handle(h_out)
        p.set_launch_parameters(2.0)
        p.init()
        p.launch()
    h1, m1 = compile_cache_stats()
    assert m1 - m0 == 1, "one miss (first init)"
    assert h1 - h0 == 2, "subsequent inits must hit the cache"


def test_parameter_change_triggers_reinit(app, rng):
    d_in = _data(rng)
    d_out = XData(d_in, copy_values=False)
    h_in, h_out = app.addData(d_in), app.addData(d_out)
    p = Scale(app)
    p.set_in_handle(h_in); p.set_out_handle(h_out)
    p.set_launch_parameters(2.0)
    p.init(); p.launch()
    app.device2Host(h_out)
    r1 = d_out.get_ndarray(0).host.copy()
    p.set_launch_parameters(4.0)   # paper: parameters may vary per call
    p.init(); p.launch()
    app.device2Host(h_out)
    r2 = d_out.get_ndarray(0).host.copy()
    np.testing.assert_allclose(r2, r1 * 2.0, rtol=1e-6)


def test_fused_chain_cache_distinguishes_wiring(app, rng):
    """Two chains with identical stages/params/layouts but different
    inter-stage wiring must not share one compiled executable."""
    base = rng.standard_normal((8, 8)).astype(np.float32)

    def build(series_wiring):
        d_in = XData({"img": base.copy()})
        d_mid = XData(d_in, copy_values=False)
        d_out = XData(d_in, copy_values=False)
        h_in, h_mid, h_out = (app.addData(x) for x in (d_in, d_mid, d_out))
        p1 = AddConst(app); p1.set_in_handle(h_in); p1.set_out_handle(h_mid)
        p1.set_launch_parameters(1.0)
        p2 = Scale(app)
        p2.set_in_handle(h_mid if series_wiring else h_in)
        p2.set_out_handle(h_out)
        p2.set_launch_parameters(3.0)
        chain = ProcessChain(app, [p1, p2], mode="fused")
        chain.init()
        chain.launch()
        app.device2Host(h_out)
        return d_out.get_ndarray(0).host.copy()

    series = build(True)     # p2 reads p1's output: (x + 1) * 3
    forked = build(False)    # p2 reads the chain input:  x * 3
    np.testing.assert_allclose(series, (base + 1.0) * 3.0, rtol=1e-6)
    np.testing.assert_allclose(forked, base * 3.0, rtol=1e-6)


def test_aux_rewire_after_init_takes_effect(app, rng):
    """Re-wiring an aux handle to a same-layout Data between launches is
    honoured without re-init (aux handles are read live, not snapshotted)."""
    class AddBias(Process):
        def apply(self, views, aux, params):
            return {k: v + aux["bias"]["img"] for k, v in views.items()}

    b1 = rng.standard_normal((4, 4)).astype(np.float32)
    b2 = rng.standard_normal((4, 4)).astype(np.float32)
    d_in = _data(rng, (4, 4))
    d_out = XData(d_in, copy_values=False)
    h_in, h_out = app.addData(d_in), app.addData(d_out)
    h_b1 = app.addData(XData({"img": b1}))
    h_b2 = app.addData(XData({"img": b2}))
    p = AddBias(app)
    p.set_in_handle(h_in); p.set_out_handle(h_out)
    p.set_aux_handle("bias", h_b1)
    p.init(); p.launch()
    app.device2Host(h_out)
    np.testing.assert_allclose(d_out.get_ndarray(0).host,
                               d_in.get_ndarray(0).host + b1, rtol=1e-6)
    p.set_aux_handle("bias", h_b2)   # same layout, no re-init
    p.launch()
    app.device2Host(h_out)
    np.testing.assert_allclose(d_out.get_ndarray(0).host,
                               d_in.get_ndarray(0).host + b2, rtol=1e-6)


def test_heterogeneous_data_single_transfer(app, rng):
    """Arbitrarily heterogeneous Data moves as ONE buffer (paper §III-A.2)."""
    d = Data({"vol": rng.standard_normal((2, 3, 4)).astype(np.float32),
              "mask": rng.integers(0, 2, (3, 4)).astype(np.uint8),
              "kspace": (rng.standard_normal((4, 4))
                         + 1j * rng.standard_normal((4, 4))).astype(np.complex64)})
    h = app.addData(d)
    assert d.device_blob is not None and d.device_blob.ndim == 1
    views = d.device_views()
    assert set(views) == {"vol", "mask", "kspace"}
    for name in views:
        np.testing.assert_array_equal(
            np.asarray(views[name]),
            np.asarray([a.host for a in d if a.name == name][0]))


class AddWeight(Process):
    ports = {"in": Port(), "out": Port(), "w": Port(aux=True)}

    def apply(self, views, aux, params):
        return {k: v + aux["w"]["img"] for k, v in views.items()}


def _static(rng, shape=(4, 4)):
    d = Data({"img": rng.standard_normal(shape).astype(np.float32),
              "half": rng.standard_normal(shape).astype(jnp.bfloat16),
              "flag": np.ones((3,), bool)})
    d.static = True
    return d


def test_static_data_is_held_as_its_own_arrays(app, rng):
    """A static Data goes to the device as one array per entry, in the
    entry's dtype and shape, with no blob; its views are those arrays, read
    without a program, and delData drops them."""
    d = _static(rng)
    before = trace.H2D_BYTES.total()
    h = app.addData(d)
    assert d.device_blob is None
    assert [(e.shape, e.dtype) for e in d.device_entries] == \
        [(a.shape, a.dtype) for a in d]
    assert trace.H2D_BYTES.total() - before == sum(a.nbytes for a in d)
    views = d.device_views()
    assert all(views[a.name] is e for a, e in zip(d, d.device_entries))
    assert d.device_view("half") is d.device_entries[1]
    for a in d:
        np.testing.assert_array_equal(np.asarray(views[a.name]), a.host)
    app.delData(h)
    assert not d.on_device


@pytest.mark.parametrize("where", ["bind_out", "bind_in", "wired_out",
                                   "wired_in", "input_port"])
def test_binding_a_static_data_off_an_aux_port_raises(app, rng, where):
    """A static Data is read-only and binds only to aux ports: as an
    output, an in-place input or a non-aux port it is refused."""
    w = _static(rng)
    if where in ("bind_out", "bind_in"):
        x = Data({"img": np.zeros((4, 4), np.float32)})
        w = Data({"img": np.ones((4, 4), np.float32)})
        w.static = True
        node = AddConst(app).bind(infile=x if where == "bind_out" else w,
                                  outfile=w)
        with pytest.raises(PortError, match="static"):
            (Pipeline(app) | node).build()
        return
    h_w = app.addData(w)
    h_x = app.addData(XData({"img": np.zeros((4, 4), np.float32)}))
    p = AddWeight(app) if where != "input_port" else AddConst(app)
    p.in_handle = h_w if where == "wired_in" else h_x
    p.out_handle = h_w if where in ("wired_out", "wired_in") else h_x
    p.aux_handles["w"] = h_w if where == "input_port" else h_x
    with pytest.raises(PortError, match="static"):
        p.init()
    with pytest.raises(ValueError, match="static"):
        app._set_device_blob(h_w, jnp.zeros((4,), jnp.uint32))


@pytest.mark.parametrize("graph", ["single", "staged", "fused"])
def test_a_static_aux_reaches_the_program_as_its_arrays(app, rng, graph):
    """Launched through a Pipeline (one node, a staged chain or a fused
    one), a static aux is handed to the program as its entries (native
    bytes), an ordinary one as its blob (packed bytes); the two give the
    same bits from two executables."""
    x = XData({"img": rng.standard_normal((4, 4)).astype(np.float32)})
    outs, programs = {}, {}
    for static in (False, True):
        w = _static(np.random.default_rng(0))
        w.static = static
        pipe = Pipeline(app, fuse=graph == "fused") | AddWeight(app).bind(w=w)
        if graph != "single":
            pipe = pipe | Scale(app).bind(params=2.0)
        pipe.build(x)
        native, packed = (trace.AUX_NATIVE_BYTES.total(),
                          trace.AUX_PACKED_BYTES.total())
        outs[static] = pipe.run(x).get_ndarray(0).host.copy()
        got = (trace.AUX_NATIVE_BYTES.total() - native,
               trace.AUX_PACKED_BYTES.total() - packed)
        if static:
            assert got == (sum(a.nbytes for a in w), 0)
        else:
            assert got == (0, w.layout.total_words * 4)
        exe = pipe._built.executor
        programs[static] = (exe.stages[0] if graph == "staged"
                            else exe)._compiled
        args = jax.tree_util.tree_leaves(programs[static].args_info)[1:]
        assert [(a.shape, a.dtype) for a in args] == (
            [(a.shape, a.dtype) for a in w] if static
            else [((w.layout.total_words,), np.uint32)])
    assert programs[True] is not programs[False]
    np.testing.assert_array_equal(outs[True], outs[False])
    want = x.get_ndarray(0).host \
        + _static(np.random.default_rng(0)).get_ndarray(0).host
    np.testing.assert_array_equal(
        outs[True], want if graph == "single" else want * 2.0)
