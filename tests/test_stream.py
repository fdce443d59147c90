"""Streaming executor: double-buffer correctness (streamed == sequential
launch(), bitwise), batch-axis compile-cache hits, donation across streamed
in-place chains, in-flight transfer tracking, the loader->queue feed, and
the host staging buffers batches are stacked in."""
import jax
import numpy as np
import pytest

from repro.core import (BatchedProcess, CLapp, Coherence, Data,
                        DonatedBufferError, Port, Process, ProcessChain,
                        ProfileParameters, StreamQueue, XData,
                        compile_cache_stats, trace, unpack_device)
from repro.data.pipeline import ArenaFeed, StreamConfig, TokenStream


class AddConst(Process):
    def apply(self, views, aux, params):
        c = params if params is not None else 1.0
        return {k: v + c for k, v in views.items()}


class Scale(Process):
    def apply(self, views, aux, params):
        return {k: v * params for k, v in views.items()}


class AddAux(Process):
    def apply(self, views, aux, params):
        return {k: v + aux["bias"]["img"] for k, v in views.items()}


@pytest.fixture
def app():
    return CLapp().init()


def _chain(app, h_in, h_mid, h_out, mode="staged"):
    p1 = AddConst(app); p1.set_in_handle(h_in); p1.set_out_handle(h_mid)
    p1.set_launch_parameters(1.5)
    p2 = Scale(app); p2.set_in_handle(h_mid); p2.set_out_handle(h_out)
    p2.set_launch_parameters(-2.0)
    return ProcessChain(app, [p1, p2], mode=mode)


def _mk_datasets(rng, n, shape=(8, 8)):
    return [XData({"img": rng.standard_normal(shape).astype(np.float32)})
            for _ in range(n)]


def _sequential(app, chain, h_in, h_out, d_in, d_out, datasets):
    """One-at-a-time launch() reference results (host copies)."""
    out = []
    for d in datasets:
        d_in.get_ndarray(0).set_host(d.get_ndarray(0).host)
        app.host2device(h_in)
        chain.launch()
        app.device2Host(h_out)
        out.append(d_out.get_ndarray(0).host.copy())
    return out


@pytest.mark.parametrize("sharded", [False, True])
@pytest.mark.parametrize("mode", ["staged", "fused"])
@pytest.mark.parametrize("batch,n", [(1, 3), (4, 8), (4, 10)])  # incl. ragged
def test_stream_matches_sequential_launch(app, rng, mode, batch, n, sharded):
    datasets = _mk_datasets(rng, n)
    d_in = XData({"img": np.zeros((8, 8), np.float32)})
    d_mid = XData(d_in, copy_values=False)
    d_out = XData(d_in, copy_values=False)
    h_in, h_mid, h_out = (app.addData(x) for x in (d_in, d_mid, d_out))
    chain = _chain(app, h_in, h_mid, h_out, mode=mode)
    chain.init()
    want = _sequential(app, chain, h_in, h_out, d_in, d_out, datasets)
    got = chain.stream(datasets, batch=batch, sync=True, sharded=sharded)
    assert len(got) == n
    for i in range(n):
        np.testing.assert_array_equal(got[i].get_ndarray(0).host, want[i],
                                      err_msg=f"dataset {i}")


def test_stream_with_aux_broadcast(app, rng):
    """Aux Data (bias) is broadcast across the batch axis, not batched."""
    bias = rng.standard_normal((8, 8)).astype(np.float32)
    d_bias = XData({"img": bias})
    h_bias = app.addData(d_bias)
    d_in = XData({"img": np.zeros((8, 8), np.float32)})
    d_out = XData(d_in, copy_values=False)
    h_in, h_out = app.addData(d_in), app.addData(d_out)
    p = AddAux(app)
    p.set_in_handle(h_in); p.set_out_handle(h_out)
    p.set_aux_handle("bias", h_bias)
    p.init()
    datasets = _mk_datasets(rng, 5)
    got = p.stream(datasets, batch=2, sync=True)
    for d, o in zip(datasets, got):
        np.testing.assert_array_equal(
            o.get_ndarray(0).host, d.get_ndarray(0).host + bias)


class AddAuxPort(AddAux):
    ports = {"in": Port(), "out": Port(), "bias": Port(aux=True)}


@pytest.mark.parametrize("static", [False, True])
@pytest.mark.parametrize("sharded", [False, True])
def test_stream_aux_keeps_its_form(app, rng, sharded, static):
    """An ordinary aux Data reaches the batched program as one packed
    ``uint32`` blob, as it always did; only a static Data comes as its own
    arrays.  Either way the streamed results are launch()'s bits."""
    bias = rng.standard_normal((8, 8)).astype(np.float32)
    d_bias = XData({"img": bias})
    d_bias.static = static
    h_bias = app.addData(d_bias)
    d_in = XData({"img": np.zeros((8, 8), np.float32)})
    d_out = XData(d_in, copy_values=False)
    h_in, h_out = app.addData(d_in), app.addData(d_out)
    p = AddAuxPort(app)
    p.set_in_handle(h_in); p.set_out_handle(h_out)
    p.set_aux_handle("bias", h_bias)
    p.init()
    datasets = _mk_datasets(rng, 4)
    want = _sequential(app, p, h_in, h_out, d_in, d_out, datasets)
    native, packed = (trace.AUX_NATIVE_BYTES.total(),
                      trace.AUX_PACKED_BYTES.total())
    got = p.stream(datasets, batch=2, sync=True, sharded=sharded)
    nbytes = bias.nbytes if static else d_bias.layout.total_words * 4
    assert (trace.AUX_NATIVE_BYTES.total() - native,
            trace.AUX_PACKED_BYTES.total() - packed) == \
        ((2 * nbytes, 0) if static else (0, 2 * nbytes))
    for d, o, w in zip(datasets, got, want):
        np.testing.assert_array_equal(o.get_ndarray(0).host, w)
        np.testing.assert_array_equal(o.get_ndarray(0).host,
                                      d.get_ndarray(0).host + bias)
    bp = BatchedProcess(p, 2, sharded=sharded).init()
    (aux,) = jax.tree_util.tree_leaves(bp._compiled.args_info)[1:]
    assert (aux.shape, aux.dtype) == (
        ((8, 8), np.float32) if static
        else ((d_bias.layout.total_words,), np.uint32))


def test_stream_profile_records_a_compute_phase_per_launch(app, rng):
    """With a profile, each batched launch has left one "compute" sample
    by the time stream() returns."""
    d_in = XData({"img": np.zeros((8, 8), np.float32)})
    d_out = XData(d_in, copy_values=False)
    p = Scale(app)
    p.set_in_handle(app.addData(d_in)); p.set_out_handle(app.addData(d_out))
    p.set_launch_parameters(2.0)
    prof = ProfileParameters(enable=True)
    p.stream(_mk_datasets(rng, 7), batch=2, profile=prof)
    assert len(prof.phases["compute"]) == 4      # 3 full batches + a tail
    assert all(s > 0 for s in prof.phases["compute"])
    assert len(prof.samples) == 1                # the whole call


def test_stream_batch_axis_compile_cache_hits(app, rng):
    """The batched program compiles once; re-streaming (and re-wrapping in
    BatchedProcess) with the same batch size must hit the compile cache."""
    d_in = XData({"img": np.zeros((8, 8), np.float32)})
    d_out = XData(d_in, copy_values=False)
    h_in, h_out = app.addData(d_in), app.addData(d_out)
    p = Scale(app)
    p.set_in_handle(h_in); p.set_out_handle(h_out)
    p.set_launch_parameters(3.0)
    datasets = _mk_datasets(rng, 4)
    p.stream(datasets, batch=2)                   # compiles launch + batched
    h0, m0 = compile_cache_stats()
    p.stream(datasets, batch=2)                   # same batch -> cache hit
    BatchedProcess(p, 2).init()                   # explicit wrap -> cache hit
    h1, m1 = compile_cache_stats()
    assert m1 - m0 == 0, "no new compilations for a repeated batch size"
    assert h1 - h0 >= 2
    h0, m0 = compile_cache_stats()
    p.stream(datasets, batch=4)                   # new batch axis -> one miss
    h1, m1 = compile_cache_stats()
    assert m1 - m0 == 1


def test_stream_donation_in_place_chain(app, rng):
    """An in-place chain (last out == first in) donates the stacked input
    blob; streamed results must still equal sequential in-place launches."""
    d = XData({"img": np.zeros((8, 8), np.float32)})
    h = app.addData(d)
    p1 = AddConst(app); p1.set_in_handle(h); p1.set_out_handle(h)
    p1.set_launch_parameters(2.0)
    p2 = Scale(app); p2.set_in_handle(h); p2.set_out_handle(h)
    p2.set_launch_parameters(0.5)
    chain = ProcessChain(app, [p1, p2], mode="fused")
    chain.init()
    assert chain.launchable().in_place
    datasets = _mk_datasets(rng, 6)
    want = [(x.get_ndarray(0).host + 2.0) * 0.5 for x in datasets]
    got = chain.stream(datasets, batch=3, sync=True)
    for w, o in zip(want, got):
        np.testing.assert_allclose(o.get_ndarray(0).host, w, rtol=1e-6)
    # the input datasets' own host copies were never consumed by donation
    for x in datasets:
        assert x.get_ndarray(0).host is not None


def test_use_after_donate_guard(app, rng):
    """Re-wiring an in-place-compiled process to out != in without re-init
    must raise instead of silently donating the live input blob."""
    d = XData({"img": rng.standard_normal((4, 4)).astype(np.float32)})
    h = app.addData(d)
    p = AddConst(app)
    p.set_in_handle(h); p.set_out_handle(h)
    p.init()
    p.launch()
    d2 = XData(d, copy_values=False)
    h2 = app.addData(d2)
    p.set_out_handle(h2)           # re-wired, no init()
    app.host2device(h)
    with pytest.raises(DonatedBufferError):
        p.launch()
    p.init()                       # recompile for the new wiring
    p.launch()                     # now fine
    app.device2Host(h2)
    assert d2.get_ndarray(0).host is not None


def test_stream_queue_prefetch_depth():
    blobs = [np.full((16,), i, np.uint8) for i in range(5)]
    q = StreamQueue(iter(blobs), depth=2)
    first = next(q)
    # after consuming item 0, items 1 and 2 must already be dispatched
    assert q.transfers == 3
    np.testing.assert_array_equal(np.asarray(first), blobs[0])
    rest = list(q)
    assert len(rest) == 4
    assert q.transfers == 5
    q.sync()                      # no-op on a drained queue
    with pytest.raises(ValueError):
        StreamQueue([], depth=0)


def test_host2device_in_flight_tracking(app, rng):
    d = XData({"img": rng.standard_normal((4, 4)).astype(np.float32)})
    h = app.addData(d, to_device=False)
    app.host2device(h, wait=False)
    assert d.coherence is Coherence.TRANSFERRING
    assert app.in_flight_handles == [h]
    app.wait_transfers()
    assert d.coherence is Coherence.IN_SYNC
    assert app.in_flight_handles == []
    # device2Host settles a still-in-flight transfer implicitly
    app.host2device(h, wait=False)
    app.device2Host(h)
    assert d.coherence is Coherence.IN_SYNC
    assert app.in_flight_handles == []


def test_data_from_layout_and_spec_clone(app, rng):
    d = Data({"a": rng.standard_normal((3, 4)).astype(np.float32),
              "b": rng.integers(0, 9, (5,)).astype(np.int32)})
    d.plan()
    spec = Data.from_layout(d.layout)
    assert spec.names == d.names
    assert all(a.host is None for a in spec)
    assert spec.layout == d.layout
    clone = d.spec_clone()
    assert clone.names == d.names
    assert [a.shape for a in clone] == [a.shape for a in d]


def test_arena_feed_streams_loader_batches(app):
    """TokenStream -> ArenaFeed -> StreamQueue: device blobs unpack to the
    exact loader batches (the training-loader feed path)."""
    cfg = StreamConfig(vocab=97, seq=16, batch=2, seed=3)
    ts = TokenStream(cfg)
    feed = ArenaFeed(ts, steps=4)
    q = StreamQueue(feed, device=app.device, depth=2)
    for step, dev_blob in enumerate(q):
        views = unpack_device(dev_blob, feed.layout)
        want = ts.batch_at(step)
        for name in want:
            np.testing.assert_array_equal(np.asarray(views[name]), want[name])
    assert step == 3
    # data_at mirrors the same batch as a registrable Data
    d = feed.data_at(1)
    assert set(d.names) == {"tokens", "labels"}


# ---------------------------------------------------------------------------
# host staging buffers: one per stacked batch, reused across calls
# ---------------------------------------------------------------------------

def _staging_counts():
    return (trace.STAGING_ALLOCS.value(), trace.STAGING_REUSES.value())


def test_staging_pool_hands_a_buffer_out_again_once_placed_and_landed():
    from repro.core.app import StagingPool
    pool, shape = StagingPool(), (2, 32)
    a0, r0 = _staging_counts()
    first = pool.acquire(shape, cap=3)
    second = pool.acquire(shape, cap=3)        # the first is being written
    assert first.base is not second.base
    pool.placed(first, jax.device_put(np.asarray(first)))
    third = pool.acquire(shape, cap=3)          # landed: the first again
    assert third.base is first.base
    del second                                  # dropped unplaced: free
    fourth = pool.acquire(shape, cap=3)
    assert _staging_counts() == (a0 + 2, r0 + 2)
    # past the cap, with every buffer still being written, a buffer is
    # made but not kept
    fifth = pool.acquire(shape, cap=2)
    assert fifth.base is None and fourth.base is not None
    assert _staging_counts() == (a0 + 3, r0 + 2)


def test_staging_pool_fences_a_donated_placement_by_the_launch_output():
    from repro.core.app import StagingPool
    pool, shape = StagingPool(), (1, 16)
    buf = pool.acquire(shape, cap=1)
    placed = jax.device_put(np.asarray(buf))
    pool.placed(buf, placed)
    out = jax.jit(lambda x: x + 1, donate_argnums=0)(placed)
    pool.consumed([placed], [out])
    (slot,) = pool._pool[shape]
    assert slot.fences == [out]
    jax.block_until_ready(out)
    assert pool.acquire(shape, cap=1).base is buf.base


def _recon_items(rng, n):
    from repro.core import KData
    def c(shape):
        return (rng.standard_normal(shape)
                + 1j * rng.standard_normal(shape)).astype(np.complex64)
    return [KData({"kdata": c((4, 2, 16, 16)),
                   "sensitivity_maps": c((2, 16, 16))}) for _ in range(n)]


def test_stream_stages_each_batch_in_a_reused_buffer(rng):
    """Calls of 10 distinct scans at batch 4 (a padded ragged tail, more
    batches over the calls than the pool holds): every image equals its
    own launch() bit for bit, buffers are reused from the second call on,
    and no more than depth + 2 are ever made for the one batch shape."""
    from repro.core import Pipeline
    from repro.processes import SimpleMRIRecon
    app = CLapp().init()
    pipe = Pipeline(app) | SimpleMRIRecon(app, mode="fused_pallas")
    items = _recon_items(rng, 10)
    want = [pipe.run(d).get_ndarray(0).host.copy() for d in items]
    a0, r0 = _staging_counts()
    for call in range(3):
        _, reused = _staging_counts()
        got = pipe.run(items, mode="stream", batch=4)
        for i, (g, w) in enumerate(zip(got, want)):
            np.testing.assert_array_equal(g.get_ndarray(0).host, w,
                                          err_msg=f"call {call} item {i}")
        if call:
            assert _staging_counts()[1] > reused
    allocs, reuses = _staging_counts()
    assert allocs - a0 <= 2 + 2
    assert (allocs - a0) + (reuses - r0) == 3 * 3     # one a batch
