"""Mesh-sharded multi-device streaming + the coherence/cache-key bugfixes.

Multi-device coverage needs more than one XLA device, and the host-platform
device count is locked at the first jax initialisation — so the tests come
in two layers:

* top-level tests run on whatever devices exist (they cover the
  single-device bugfix surface: Data coherence stamping, KData variable
  order, StreamQueue.sync bookkeeping, mesh cache-key fingerprints);
* ``@needs_8_devices`` tests only run when >= 8 devices are present, and
  ``test_rerun_forced_eight_devices`` guarantees they DO run in a normal
  single-CPU tier-1 pass by re-executing this module in a subprocess with
  ``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
"""
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.core import (BatchedProcess, CLapp, Coherence, Data, DeviceTraits,
                        KData, NDArray, Pipeline, Port, Process, ProcessChain,
                        StreamQueue, XData, aot_compile, compile_cache_stats)

_CHILD_ENV = "REPRO_MESH_TEST_CHILD"
_FORCE_FLAG = "--xla_force_host_platform_device_count=8"

needs_8_devices = pytest.mark.skipif(
    jax.device_count() < 8, reason="needs >= 8 devices (forced-host child run)")


class Scale(Process):
    def apply(self, views, aux, params):
        return {k: v * params for k, v in views.items()}


class AddAux(Process):
    def apply(self, views, aux, params):
        return {k: v + aux["bias"]["img"] for k, v in views.items()}


class MulTwo(Process):
    """Two streaming inputs: primary 'in' times the 'rhs' input edge."""

    ports = {"in": Port(names=("img",)), "out": Port(names=("img",)),
             "rhs": Port(names=("img",))}

    def apply(self, views, aux, params):
        return {"img": views["img"] * aux["rhs"]["img"]}


@pytest.fixture
def app():
    return CLapp().init()


def _mk_datasets(rng, n, shape=(8, 8)):
    return [XData({"img": rng.standard_normal(shape).astype(np.float32)})
            for _ in range(n)]


def _sequential(app, proc, h_in, h_out, d_in, d_out, datasets):
    out = []
    for d in datasets:
        d_in.get_ndarray(0).set_host(d.get_ndarray(0).host)
        app.host2device(h_in)
        proc.launch()
        app.device2Host(h_out)
        out.append(d_out.get_ndarray(0).host.copy())
    return out


# ---------------------------------------------------------------------------
# parent->child bridge: force 8 host devices in a subprocess
# ---------------------------------------------------------------------------

@pytest.mark.skipif(os.environ.get(_CHILD_ENV) == "1",
                    reason="already the forced-device child")
def test_rerun_forced_eight_devices():
    """Re-run this module with 8 forced host CPU devices so the
    @needs_8_devices tests execute even on a single-device machine."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " " + _FORCE_FLAG).strip()
    env[_CHILD_ENV] = "1"
    src = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        [sys.executable, "-m", "pytest", "-x", "-q", "--no-header",
         os.path.abspath(__file__)],
        env=env, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, (
        f"forced-8-device child run failed:\n{r.stdout}\n{r.stderr}")
    # the child must actually have run the multi-device tests, not skip them
    assert "passed" in r.stdout


# ---------------------------------------------------------------------------
# bugfix: spec-only Data must start EMPTY, not HOST_FRESH
# ---------------------------------------------------------------------------

def test_spec_only_data_starts_empty():
    spec_only = Data([NDArray(shape=(4, 4), dtype=np.float32, name="img")])
    assert spec_only.coherence is Coherence.EMPTY
    with pytest.raises(ValueError):
        spec_only.authoritative()       # nothing authoritative to read
    mixed = Data([NDArray(np.zeros((2, 2), np.float32), name="a"),
                  NDArray(shape=(2, 2), dtype=np.float32, name="b")])
    assert mixed.coherence is Coherence.EMPTY
    hosted = Data({"img": np.zeros((4, 4), np.float32)})
    assert hosted.coherence is Coherence.HOST_FRESH
    assert hosted.authoritative() == "host"


def test_data_add_updates_coherence():
    d = Data(None)
    assert d.coherence is Coherence.EMPTY
    d.add(NDArray(np.ones((3,), np.float32), name="a"))
    assert d.coherence is Coherence.HOST_FRESH
    d.add(NDArray(shape=(3,), dtype=np.float32, name="b"))
    assert d.coherence is Coherence.EMPTY


def test_spec_only_save_refuses(tmp_path):
    spec_only = Data([NDArray(shape=(4, 4), dtype=np.float32, name="img")])
    with pytest.raises(ValueError):
        spec_only.save(str(tmp_path / "x.npz"))


# ---------------------------------------------------------------------------
# bugfix: KData must order loaded variables by the REQUESTED names
# ---------------------------------------------------------------------------

def test_kdata_custom_variable_order(tmp_path, monkeypatch):
    k = (np.arange(2 * 3 * 4 * 4).reshape(2, 3, 4, 4)).astype(np.complex64)
    s = (np.arange(3 * 4 * 4).reshape(3, 4, 4) * 1j).astype(np.complex64)
    path = str(tmp_path / "acq.npz")
    np.savez(path, my_smaps=s, my_kdata=k)

    from repro.data import io as repro_io
    real_load = repro_io.load_any

    def file_order_load(path, variables=None):
        # adversarial loader: honours the variable FILTER but returns the
        # dict in file order, not requested order
        full = real_load(path)
        return {n: v for n, v in full.items()
                if variables is None or n in variables}

    monkeypatch.setattr(repro_io, "load_any", file_order_load)
    d = KData(path, variables=["my_kdata", "my_smaps"])
    np.testing.assert_array_equal(d.kdata.host, k)
    np.testing.assert_array_equal(d.smaps.host, s)

    with pytest.raises(KeyError):
        KData(path, variables=["nope", "my_smaps"])
    with pytest.raises(ValueError):
        KData(path, variables=["my_kdata"])


# ---------------------------------------------------------------------------
# bugfix: StreamQueue.sync must cover popped-but-unlanded transfers
# ---------------------------------------------------------------------------

def test_stream_queue_sync_tracks_popped_blobs(app):
    blobs = [np.full((16,), i, np.uint8) for i in range(4)]
    q = StreamQueue(iter(blobs), device=app.device, depth=2)
    popped = [next(q), next(q), next(q)]
    # popped blobs are STILL in flight until sync() retires them — the old
    # implementation only blocked on the FIFO and forgot these three
    assert q.in_flight >= len(popped)
    q.sync()
    assert q.in_flight == 0
    for i, b in enumerate(popped):
        np.testing.assert_array_equal(np.asarray(b), blobs[i])
    # a consumed-and-donated (deleted) blob has no buffer left to wait on;
    # sync() must skip it rather than raise
    last = next(q)
    last.delete()
    q.sync()
    assert q.in_flight == 0


# ---------------------------------------------------------------------------
# bugfix: compile-cache mesh fingerprints (single-device part)
# ---------------------------------------------------------------------------

def test_cache_key_axis_names_distinct():
    from repro.core.process import _mesh_key
    d = jax.devices()[0]
    m1 = jax.sharding.Mesh(np.array([[d]], dtype=object), ("data", "model"))
    m2 = jax.sharding.Mesh(np.array([[d]], dtype=object), ("rows", "cols"))
    assert _mesh_key(m1) != _mesh_key(m2)
    assert _mesh_key(None) is None


def test_default_placement_is_primary_device(app, rng):
    d = XData({"img": rng.standard_normal((4, 4)).astype(np.float32)})
    h = app.addData(d)
    assert set(d.device_blob.devices()) == {app.device}


# ---------------------------------------------------------------------------
# multi-device: mesh construction, sharded streaming, cache separation
# ---------------------------------------------------------------------------

@needs_8_devices
def test_clapp_builds_data_model_mesh():
    app = CLapp().init(device_traits=DeviceTraits(min_count=8))
    assert len(app.devices) == 8
    assert dict(app.mesh.shape) == {"data": 8, "model": 1}
    assert list(app.mesh.devices.flat) == list(app.devices)
    sh = app.data_sharding(("data",))
    assert sh.device_set == set(app.devices)
    repl = app.data_sharding()
    assert repl.spec == jax.sharding.PartitionSpec()


@needs_8_devices
def test_sharded_stream_bit_identical_and_spread(rng):
    app = CLapp().init()
    datasets = _mk_datasets(rng, 16)
    d_in = XData({"img": np.zeros((8, 8), np.float32)})
    d_out = XData(d_in, copy_values=False)
    h_in, h_out = app.addData(d_in), app.addData(d_out)
    p = Scale(app)
    p.set_in_handle(h_in); p.set_out_handle(h_out)
    p.set_launch_parameters(-1.5)
    p.init()
    want = _sequential(app, p, h_in, h_out, d_in, d_out, datasets)

    bp = BatchedProcess(p, 8, sharded=True).init()
    # each stacked batch is placed across ALL 8 devices on the data axis
    assert bp.batch_sharding.device_set == set(app.devices)
    assert bp.batch_sharding.spec == jax.sharding.PartitionSpec("data")

    got = p.stream(datasets, batch=8, sharded=True, sync=True)
    assert len(got) == len(datasets)
    out_devices = set()
    for i, o in enumerate(got):
        np.testing.assert_array_equal(
            o.get_ndarray(0).host, want[i], err_msg=f"dataset {i}")
        out_devices |= set(o.device_blob.devices())
    # per-item outputs live on the device that computed them — all 8 in use
    assert out_devices == set(app.devices)


@needs_8_devices
def test_sharded_stream_aux_replicated(rng):
    app = CLapp().init()
    bias = rng.standard_normal((8, 8)).astype(np.float32)
    d_bias = XData({"img": bias})
    h_bias = app.addData(d_bias)           # uploaded single-device first
    d_in = XData({"img": np.zeros((8, 8), np.float32)})
    d_out = XData(d_in, copy_values=False)
    h_in, h_out = app.addData(d_in), app.addData(d_out)
    p = AddAux(app)
    p.set_in_handle(h_in); p.set_out_handle(h_out)
    p.set_aux_handle("bias", h_bias)
    datasets = _mk_datasets(rng, 8)
    got = p.stream(datasets, batch=8, sharded=True, sync=True)
    for d, o in zip(datasets, got):
        np.testing.assert_array_equal(
            o.get_ndarray(0).host, d.get_ndarray(0).host + bias)
    # the replicated aux copy is call-local: the stored blob keeps its
    # default single-device placement so unsharded paths still match it
    assert set(d_bias.device_blob.devices()) == {app.device}
    # regression: sharded stream must not poison later unsharded use of the
    # same aux handle (launch + stream compiled for single-device inputs)
    p.init()
    p.launch()
    got2 = p.stream(datasets[:4], batch=2, sharded=False, sync=True)
    for d, o in zip(datasets[:4], got2):
        np.testing.assert_array_equal(
            o.get_ndarray(0).host, d.get_ndarray(0).host + bias)


@needs_8_devices
def test_reinit_rebuilds_mesh():
    """Re-running init() with different traits must rebuild the auto mesh —
    a stale mesh would scatter data onto deselected devices."""
    app = CLapp().init()
    assert dict(app.mesh.shape) == {"data": 8, "model": 1}
    app.init(device_traits=DeviceTraits(count=2))
    assert dict(app.mesh.shape) == {"data": 2, "model": 1}
    assert app.data_sharding(("data",)).device_set == set(app.devices)
    # an explicit set_mesh survives re-init
    mesh = jax.sharding.Mesh(
        np.array(jax.devices()[:4], dtype=object).reshape(4, 1),
        ("data", "model"))
    app.set_mesh(mesh)
    app.init(device_traits=DeviceTraits(count=1))
    assert app.mesh is mesh


@needs_8_devices
def test_sharded_in_place_chain_donation(rng):
    app = CLapp().init()
    d = XData({"img": np.zeros((8, 8), np.float32)})
    h = app.addData(d)
    p1 = Scale(app); p1.set_in_handle(h); p1.set_out_handle(h)
    p1.set_launch_parameters(2.0)
    p2 = Scale(app); p2.set_in_handle(h); p2.set_out_handle(h)
    p2.set_launch_parameters(0.5)
    chain = ProcessChain(app, [p1, p2], mode="fused")
    chain.init()
    datasets = _mk_datasets(rng, 8)
    got = chain.stream(datasets, batch=8, sharded=True, sync=True)
    for x, o in zip(datasets, got):
        np.testing.assert_allclose(
            o.get_ndarray(0).host, x.get_ndarray(0).host, rtol=1e-6)


@needs_8_devices
def test_sharded_batch_divisibility_enforced(rng):
    app = CLapp().init()
    d_in = XData({"img": np.zeros((8, 8), np.float32)})
    d_out = XData(d_in, copy_values=False)
    h_in, h_out = app.addData(d_in), app.addData(d_out)
    p = Scale(app)
    p.set_in_handle(h_in); p.set_out_handle(h_out)
    p.set_launch_parameters(1.0)
    with pytest.raises(ValueError, match="divisible"):
        p.stream(_mk_datasets(rng, 6), batch=3, sharded=True)


@needs_8_devices
def test_compile_cache_no_mesh_collision():
    """Two meshes over different device subsets (or the same set reordered)
    must not share one cached executable pinned to the wrong devices."""
    devs = jax.devices()

    def mesh_of(ds):
        return jax.sharding.Mesh(
            np.array(ds, dtype=object).reshape(len(ds), 1), ("data", "model"))

    def fn(x):
        return x + 1

    spec = [jax.ShapeDtypeStruct((8,), np.float32)]
    h0, m0 = compile_cache_stats()
    c_front = aot_compile(fn, spec, tag="meshkey", mesh=mesh_of(devs[:4]))
    c_back = aot_compile(fn, spec, tag="meshkey", mesh=mesh_of(devs[4:8]))
    c_rev = aot_compile(fn, spec, tag="meshkey", mesh=mesh_of(devs[3::-1]))
    h1, m1 = compile_cache_stats()
    assert m1 - m0 == 3, "each device set/order compiles its own executable"
    assert c_front is not c_back and c_front is not c_rev
    # identical mesh -> cache hit
    aot_compile(fn, spec, tag="meshkey", mesh=mesh_of(devs[:4]))
    h2, m2 = compile_cache_stats()
    assert (h2 - h1, m2 - m1) == (1, 0)


@needs_8_devices
def test_sharded_joined_stream_bit_identical_and_spread(rng):
    """A fan-in join under sharded=True: both input edges' batches are
    split row-aligned over the mesh's data axis (row i of every edge on
    the same device), results bit-identical to sequential launches, and
    per-item outputs stay resident where they were computed."""
    app = CLapp().init()
    a = Scale(app).bind(infile="x", outfile="lhs", params=2.0)
    j = MulTwo(app).bind(infile="lhs", outfile="prod", rhs="r")
    pipe = Pipeline.from_graph(app, [a, j], output="prod")
    lhs = _mk_datasets(rng, 16)
    rhs = _mk_datasets(rng, 16)
    items = [{"x": l, "r": r} for l, r in zip(lhs, rhs)]
    want = [pipe.run(it).get_ndarray(0).host.copy() for it in items]

    got = pipe.run(items, mode="stream", batch=8, sharded=True)
    assert len(got) == 16
    out_devices = set()
    for i, o in enumerate(got):
        np.testing.assert_array_equal(o.get_ndarray(0).host, want[i],
                                      err_msg=f"item {i}")
        out_devices |= set(o.device_blob.devices())
    assert out_devices == set(app.devices), \
        "joined sharded stream must use every selected device"

    # serve mode over the same sharded join
    served = pipe.run(items, mode="serve", batch=8, sharded=True)
    for i, o in enumerate(served):
        np.testing.assert_array_equal(o.get_ndarray(0).host, want[i],
                                      err_msg=f"served item {i}")


@needs_8_devices
def test_single_device_traits_on_multi_device_host(rng):
    """DeviceTraits(count=1) on an 8-device host: the mesh is trivial and
    sharded=True degrades to the single-device path — the algorithm call
    site is device-count-agnostic, as the paper promises."""
    app = CLapp().init(device_traits=DeviceTraits(count=1))
    assert len(app.devices) == 1
    assert dict(app.mesh.shape) == {"data": 1, "model": 1}
    d_in = XData({"img": np.zeros((8, 8), np.float32)})
    d_out = XData(d_in, copy_values=False)
    h_in, h_out = app.addData(d_in), app.addData(d_out)
    p = Scale(app)
    p.set_in_handle(h_in); p.set_out_handle(h_out)
    p.set_launch_parameters(4.0)
    datasets = _mk_datasets(rng, 4)
    got = p.stream(datasets, batch=2, sharded=True, sync=True)
    for d, o in zip(datasets, got):
        np.testing.assert_array_equal(
            o.get_ndarray(0).host, d.get_ndarray(0).host * 4.0)
        assert set(o.device_blob.devices()) == {app.device}


# ---------------------------------------------------------------------------
# 2D sharding: the model axis, end to end (PR 10 tentpole)
# ---------------------------------------------------------------------------

def test_logical_axis_table_contract():
    """The logical-axis table is the single binding point: batch rides the
    data axis, frame/slot ride the model axis, per-item working axes are
    never partitioned, and unknown names are an error — not silently
    replicated."""
    from repro.launch.mesh import (LOGICAL_AXES, logical_pspec, mesh_axis,
                                   model_axis_size, shard_by_logical)
    P = jax.sharding.PartitionSpec
    assert LOGICAL_AXES["batch"] == "data"
    assert LOGICAL_AXES["frame"] == "model"
    assert LOGICAL_AXES["slot"] == "model"
    assert all(LOGICAL_AXES[a] is None
               for a in ("coil", "height", "width", "layer", "head"))
    assert logical_pspec(("frame", "coil", None)) == P("model", None, None)
    assert logical_pspec(None) == P()
    with pytest.raises(KeyError, match="logical axis"):
        mesh_axis("no_such_axis")
    assert model_axis_size(None) == 1
    # no mesh anywhere -> the wrapper is a total no-op (calls fn directly)
    f = shard_by_logical(lambda x: x * 2, [("frame", None)], ("frame", None))
    np.testing.assert_array_equal(
        f(np.ones((4, 2), np.float32)), np.full((4, 2), 2.0, np.float32))


@needs_8_devices
def test_model_axis_mesh_construction():
    """CLapp().init(model_axis=m) folds the selected devices into a
    (data, model) grid; indivisible folds are a loud error."""
    from repro.launch.mesh import make_data_mesh, model_axis_size
    app = CLapp().init(model_axis=4)
    assert dict(app.mesh.shape) == {"data": 2, "model": 4}
    assert model_axis_size(app.mesh) == 4
    # consecutive devices form one model group (row-major grid)
    grid = np.asarray(app.mesh.devices, dtype=object)
    assert grid.shape == (2, 4)
    assert [d.id for d in grid[0]] == sorted(d.id for d in grid[0])
    with pytest.raises(ValueError, match="divide"):
        make_data_mesh(jax.devices(), model=3)


@needs_8_devices
def test_recon_2d_bit_identical_three_modes(rng):
    """The shard_map'd fused MRI recon on a (data=2, model=4) mesh is
    BIT-identical to the same program on a trivial mesh — in launch,
    sharded stream and serve.  The
    frames axis (F=8) splits 2-per-device over each model group; shard_map
    partitioning must not change a single ulp vs the unpartitioned jit."""
    from repro.core import KData
    from repro.processes import SimpleMRIRecon

    F, C, H, W = 8, 3, 16, 16
    def _c(shape):
        return (rng.standard_normal(shape)
                + 1j * rng.standard_normal(shape)).astype(np.complex64)
    smaps = _c((C, H, W))
    inputs = [KData({"kdata": _c((F, C, H, W)),
                     "sensitivity_maps": smaps.copy()}) for _ in range(6)]

    app1 = CLapp().init(device_traits=DeviceTraits(count=1))
    oracle = Pipeline(app1) | SimpleMRIRecon(app1, mode="fused_pallas")
    want = [oracle.run(d).get_ndarray(0).host.copy() for d in inputs]

    app = CLapp().init(model_axis=4)
    assert dict(app.mesh.shape) == {"data": 2, "model": 4}
    fused = Pipeline(app) | SimpleMRIRecon(app, mode="fused_pallas")

    got_launch = [fused.run(d).get_ndarray(0).host.copy() for d in inputs]
    got_stream = fused.run(inputs, mode="stream", batch=2, sharded=True)
    got_serve = fused.run(inputs, mode="serve", batch=2, sharded=True)
    for i in range(len(inputs)):
        np.testing.assert_array_equal(got_launch[i], want[i],
                                      err_msg=f"launch[{i}]")
        np.testing.assert_array_equal(got_stream[i].get_ndarray(0).host,
                                      want[i], err_msg=f"stream[{i}]")
        np.testing.assert_array_equal(got_serve[i].get_ndarray(0).host,
                                      want[i], err_msg=f"serve[{i}]")


@needs_8_devices
def test_recon_2d_outputs_resident_on_every_device(rng):
    """On a (data=2, model=4) mesh each streamed item is computed by a
    whole model group and stays resident on all of it: the per-item
    outputs together span every selected device, not one per group."""
    from repro.processes import SimpleMRIRecon

    def _c(shape):
        return (rng.standard_normal(shape)
                + 1j * rng.standard_normal(shape)).astype(np.complex64)
    inputs = [KData({"kdata": _c((8, 3, 16, 16)),
                     "sensitivity_maps": _c((3, 16, 16))}) for _ in range(4)]
    app = CLapp().init(model_axis=4)
    pipe = Pipeline(app) | SimpleMRIRecon(app, mode="fused_pallas")
    outs = pipe.run(inputs, mode="stream", batch=2, sharded=True)
    per_item = [set(o.device_blob.devices()) for o in outs]
    assert all(len(d) == 4 for d in per_item)
    assert set().union(*per_item) == set(app.devices)


@needs_8_devices
def test_sharded_stream_stages_each_batch_in_a_reused_buffer(rng):
    """The sharded stream's batches (10 scans at batch 8 over data=8, the
    tail padded) are stacked in reused staging buffers too: images equal
    their own launch() bit for bit, buffers are reused from the second
    call on, and no more than depth + 2 are made for the one shape."""
    from repro.core import trace
    from repro.processes import SimpleMRIRecon

    def _c(shape):
        return (rng.standard_normal(shape)
                + 1j * rng.standard_normal(shape)).astype(np.complex64)
    items = [KData({"kdata": _c((4, 2, 16, 16)),
                    "sensitivity_maps": _c((2, 16, 16))}) for _ in range(10)]
    app = CLapp().init()
    pipe = Pipeline(app) | SimpleMRIRecon(app, mode="fused_pallas")
    want = [pipe.run(d).get_ndarray(0).host.copy() for d in items]

    def counts():
        return trace.STAGING_ALLOCS.value(), trace.STAGING_REUSES.value()
    a0, r0 = counts()
    for call in range(3):
        _, reused = counts()
        got = pipe.run(items, mode="stream", batch=8, sharded=True)
        for i, (g, w) in enumerate(zip(got, want)):
            np.testing.assert_array_equal(g.get_ndarray(0).host, w,
                                          err_msg=f"call {call} item {i}")
        if call:
            assert counts()[1] > reused
    allocs, reuses = counts()
    assert allocs - a0 <= 2 + 2
    assert (allocs - a0) + (reuses - r0) == 3 * 2     # one a batch


@needs_8_devices
def test_decode_2d_bit_identical():
    """DecodeStep on a (2, 4) mesh: the B=4 decode batch shard_maps one
    slot per model-group device (position via exact integer pmax) and the
    emitted tokens match the single-device session bit for bit."""
    from repro.models import build_model
    from repro.models.common import ArchConfig
    from repro.processes.lm import DecodeSession

    cfg = ArchConfig(name="tiny", family="dense", n_layers=2, d_model=16,
                     n_heads=2, n_kv_heads=2, d_ff=32, vocab=48, remat=False,
                     dtype="float32", param_dtype="float32")
    model = build_model(cfg)
    params = model.init_params(jax.random.key(0))
    B, steps = 4, 5
    prompts = np.asarray(
        np.random.default_rng(7).integers(0, cfg.vocab, (B, 4)), np.int32)

    def _drive(app):
        sess = DecodeSession(app, model, params, batch=B, max_len=32)
        sess.prefill(prompts)
        toks = [sess.tokens().copy()]
        for _ in range(steps):
            sess.step()
            toks.append(sess.tokens().copy())
        return toks

    want = _drive(CLapp().init(device_traits=DeviceTraits(count=1)))
    got = _drive(CLapp().init(model_axis=4))
    for i, (w, g) in enumerate(zip(want, got)):
        np.testing.assert_array_equal(g, w, err_msg=f"step {i}")


@needs_8_devices
def test_sharded_ckpt_2d_roundtrip_and_elastic(rng, tmp_path):
    """Gather-free checkpointing on a real (2, 4) mesh: the save writes one
    shard blob per device holding only the UNIQUE pieces it owns (no host
    gather — asserted via the profile's phase records), the same-mesh
    restore device_puts pieces straight to their targets, and the elastic
    fallback reassembles on the host for a single device and for a
    DIFFERENT (4, 2) mesh shape — always matching the host-gather oracle
    bit for bit."""
    from repro.ckpt import restore_checkpoint, save_checkpoint
    from repro.core import ProfileParameters
    from repro.launch.mesh import make_data_mesh

    app = CLapp().init(model_axis=4)
    mesh = app.mesh
    NS, P = jax.sharding.NamedSharding, jax.sharding.PartitionSpec
    shardings = {
        "rows": NS(mesh, P("data")),            # 2 unique pieces
        "cols": NS(mesh, P(None, "model")),     # 4 unique pieces
        "rep": NS(mesh, P()),                   # replicated -> host.arena
    }
    host_state = {
        "rows": rng.standard_normal((4, 8)).astype(np.float32),
        "cols": rng.standard_normal((3, 8)).astype(np.float32),
        "rep": rng.standard_normal((5,)).astype(np.float32),
    }
    state = {k: jax.device_put(v, shardings[k]) for k, v in host_state.items()}
    state["step_count"] = np.int32(41)          # non-Array leaf rides host.arena
    oracle = jax.tree.map(np.asarray, state)    # the host-gather oracle

    prof = ProfileParameters(enable=True)
    path = save_checkpoint(str(tmp_path), 41, state, sharded=True,
                           profile=prof)
    assert prof.phase_total("gather") == 0.0, "sharded save must never gather"
    assert prof.phase_total("shard_write") > 0
    import os as _os
    shard_files = [n for n in _os.listdir(path) if n.startswith("shard_")]
    assert 2 <= len(shard_files) <= 8, shard_files

    like = jax.tree.map(lambda a: np.zeros(np.shape(a), np.asarray(a).dtype),
                        oracle)

    # same-mesh restore: direct per-device placement, zero gather
    prof2 = ProfileParameters(enable=True)
    back = restore_checkpoint(str(tmp_path), like,
                              shardings={**shardings, "step_count": None},
                              profile=prof2)
    assert prof2.phase_total("gather") == 0.0, \
        "same-shape restore must device_put shards directly"
    for k in ("rows", "cols", "rep"):
        assert back[k].sharding.is_equivalent_to(shardings[k], back[k].ndim)
        np.testing.assert_array_equal(np.asarray(back[k]), oracle[k],
                                      err_msg=k)
    np.testing.assert_array_equal(back["step_count"], oracle["step_count"])

    # elastic restore 1: everything onto ONE device
    single = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    prof3 = ProfileParameters(enable=True)
    back1 = restore_checkpoint(
        str(tmp_path), like,
        shardings={k: single for k in shardings} | {"step_count": None},
        profile=prof3)
    assert prof3.phase_total("gather") > 0, "elastic path reassembles on host"
    for k in ("rows", "cols", "rep"):
        assert set(back1[k].devices()) == {jax.devices()[0]}
        np.testing.assert_array_equal(np.asarray(back1[k]), oracle[k],
                                      err_msg=f"single[{k}]")

    # elastic restore 2: a DIFFERENT 2D mesh shape (4, 2)
    mesh42 = make_data_mesh(jax.devices(), model=2)
    sh42 = {"rows": NS(mesh42, P("data")), "cols": NS(mesh42, P(None, "model")),
            "rep": NS(mesh42, P()), "step_count": None}
    back2 = restore_checkpoint(str(tmp_path), like, shardings=sh42)
    for k in ("rows", "cols", "rep"):
        assert back2[k].sharding.is_equivalent_to(sh42[k], back2[k].ndim)
        np.testing.assert_array_equal(np.asarray(back2[k]), oracle[k],
                                      err_msg=f"mesh42[{k}]")
