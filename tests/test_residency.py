"""Device-resident pipeline execution (ISSUE 6 tentpole).

Internal edges of a chained pipeline stay device-resident end to end:
the residency plan classifies edges at build time, staged executors
donate single-consumer internal blobs to the downstream XLA program,
and reads of a donated edge fail loudly with graph context.  All of it
must be numerically invisible — a device-resident run is bit-identical
to an explicit stage-by-stage host round trip in every execution mode,
including ragged tails and joined (fan-in) edges.
"""
import numpy as np
import pytest

from repro.core import (CLapp, Coherence, DonatedBufferError, Pipeline, Port,
                        Process, ProfileParameters, XData)


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


@pytest.fixture
def app():
    return CLapp().init()


def _img(rng, shape=(6, 5)):
    return XData({"img": rng.standard_normal(shape).astype(np.float32)})


def _chain(app, *, fuse=False):
    """src --AddConst--> mid1 --Scale--> mid2 --AddConst--> out"""
    return (Pipeline(app, fuse=fuse)
            | AddConst(app).bind(infile="src", outfile="mid1", params=1.5)
            | Scale(app).bind(infile="mid1", outfile="mid2", params=-2.0)
            | AddConst(app).bind(infile="mid2", outfile="final", params=0.25))


def _roundtrip_reference(datasets):
    """Stage-by-stage host round trip: each stage is its OWN single-node
    pipeline on its OWN app, results synced to host between stages — the
    exact traffic pattern the residency plan eliminates."""
    outs = []
    for d in datasets:
        x = d.get_ndarray(0).host.copy()
        for params, cls in ((1.5, AddConst), (-2.0, Scale), (0.25, AddConst)):
            stage_app = CLapp().init()
            pipe = Pipeline(stage_app) | cls(stage_app).bind(params=params)
            out = pipe.run(XData({"img": x}))          # sync=True -> host
            x = out.get_ndarray(0).host.copy()
        outs.append(x)
    return outs


# ---------------------------------------------------------------------------
# residency plan classification
# ---------------------------------------------------------------------------

def test_residency_plan_classifies_edges(app, rng):
    pipe = _chain(app)
    built = pipe.build(_img(rng))
    assert pipe.residency_plan == {"src": "host", "mid1": "device",
                                   "mid2": "device", "final": "host"}
    # single-consumer internal edges are donated to their consuming stage
    assert built.donated_edges == {"mid1": "Scale", "mid2": "AddConst#1"}


def test_fused_pipeline_donates_nothing(app, rng):
    """A fused executor internalises internal edges inside one traced
    program — nothing is staged, so nothing can be donated."""
    pipe = _chain(app, fuse=True)
    built = pipe.build(_img(rng))
    assert built.donated_edges == {}
    assert pipe.residency_plan["mid1"] == "device"


def test_forked_edge_is_not_donated(app, rng):
    """An internal edge with TWO consumers must not be donated (the
    second consumer still needs the blob)."""
    pipe = (Pipeline(app)
            | AddConst(app).bind(infile="src", outfile="lhs", params=2.0)
            | AddTwo(app).bind(infile="lhs", rhs="src", outfile="sum")
            | Scale(app).bind(infile="sum", outfile="done", params=3.0))
    built = pipe.build(_img(rng))
    # 'src' is a graph input (host); 'lhs' and 'sum' are single-consumer
    assert built.donated_edges == {"lhs": "AddTwo", "sum": "Scale"}
    base = rng.standard_normal((6, 5)).astype(np.float32)
    out = pipe.run(XData({"img": base.copy()}))
    np.testing.assert_allclose(out.get_ndarray(0).host,
                               ((base + 2.0) + base) * 3.0, rtol=1e-6)


# ---------------------------------------------------------------------------
# bit-identity: device-resident vs explicit host round trip, three modes
# ---------------------------------------------------------------------------

def test_launch_bit_identical_to_host_roundtrip(app, rng):
    datasets = [_img(rng) for _ in range(3)]
    want = _roundtrip_reference(datasets)
    pipe = _chain(app)
    for i, d in enumerate(datasets):
        got = pipe.run(d).get_ndarray(0).host
        np.testing.assert_array_equal(got, want[i], err_msg=f"launch[{i}]")


@pytest.mark.parametrize("sharded", [False, True])
def test_stream_bit_identical_with_ragged_tail(app, rng, sharded):
    """7 items at batch=3: a ragged tail rides through the residency
    plan's fused streaming path and still matches the host round trip."""
    datasets = [_img(rng) for _ in range(7)]
    want = _roundtrip_reference(datasets)
    pipe = _chain(app)
    outs = pipe.run(datasets, mode="stream", batch=3, sync=True,
                    sharded=sharded)
    assert len(outs) == 7
    for i, o in enumerate(outs):
        np.testing.assert_array_equal(o.get_ndarray(0).host, want[i],
                                      err_msg=f"stream[{i}]")


@pytest.mark.parametrize("sharded", [False, True])
def test_serve_bit_identical_with_ragged_tail(app, rng, sharded):
    datasets = [_img(rng) for _ in range(5)]
    want = _roundtrip_reference(datasets)
    pipe = _chain(app)
    outs = pipe.run(datasets, mode="serve", batch=2, sync=True,
                    sharded=sharded)
    for i, o in enumerate(outs):
        np.testing.assert_array_equal(o.get_ndarray(0).host, want[i],
                                      err_msg=f"serve[{i}]")


def test_joined_edge_stream_bit_identical(app, rng):
    """Fan-in graph: the join edge 'r' is a graph input (host residency),
    the produced edge 'lhs' is internal; the streamed join must match the
    per-item host math."""
    a = AddConst(app).bind(infile="x", outfile="lhs", params=1.0)
    j = AddTwo(app).bind(infile="lhs", outfile="sum", rhs="r")
    pipe = Pipeline.from_graph(app, [a, j], output="sum")
    built = pipe.build({"x": _img(rng), "r": _img(rng)})
    assert pipe.residency_plan == {"x": "host", "r": "host",
                                   "lhs": "device", "sum": "host"}
    assert built.donated_edges == {"lhs": "AddTwo"}
    items = [{"x": _img(rng), "r": _img(rng)} for _ in range(5)]
    outs = pipe.run(items, mode="stream", batch=2, sync=True)
    for i, (item, o) in enumerate(zip(items, outs)):
        want = (item["x"].get_ndarray(0).host + 1.0) \
            + item["r"].get_ndarray(0).host
        np.testing.assert_array_equal(o.get_ndarray(0).host, want,
                                      err_msg=f"join[{i}]")


# ---------------------------------------------------------------------------
# coherence: internal edges never become host-valid mid-chain
# ---------------------------------------------------------------------------

def test_internal_edge_is_device_resident_mid_chain(app, rng):
    """Launch stage 0 by hand: its output edge must sit in the
    DEVICE_RESIDENT coherence state with NO host arrays — the blob never
    touched the host arena."""
    pipe = _chain(app)
    d = _img(rng)
    built = pipe.build(d)
    reg = app.getData(built.input_handles["src"])
    for dst, s in zip(reg, d):
        dst.set_host(s.host)
    app.host2device(built.input_handles["src"])
    built.executor.stages[0].launch()
    mid1 = app.getData(built.handles["mid1"])
    assert mid1.coherence is Coherence.DEVICE_RESIDENT
    assert all(a.host is None for a in mid1), \
        "internal edge must never materialise host arrays mid-chain"
    assert mid1.device_blob is not None
    # the OUTPUT edge keeps the host path: after the remaining stages +
    # sync it is host-valid like any launch result
    built.executor.stages[1].launch()
    built.executor.stages[2].launch()
    out = app.getData(built.output_handle)
    out.sync_to_host()
    assert out.coherence is Coherence.IN_SYNC


def test_stream_never_materialises_internal_hosts(app, rng):
    """The streaming path runs the fused launchable — internal edge Data
    stay spec-only (no host arrays, never HOST_FRESH) for the whole run."""
    pipe = _chain(app)
    datasets = [_img(rng) for _ in range(4)]
    pipe.run(datasets, mode="stream", batch=2, sync=True)
    built = pipe._built
    for edge in ("mid1", "mid2"):
        d = app.getData(built.handles[edge])
        assert all(a.host is None for a in d), edge
        assert d.coherence not in (Coherence.HOST_FRESH, Coherence.IN_SYNC), \
            f"internal edge {edge} became host-valid during streaming"


# ---------------------------------------------------------------------------
# donation: use-after-donate fails loudly with graph context
# ---------------------------------------------------------------------------

def test_use_after_donate_names_edge_and_stages(app, rng):
    pipe = _chain(app)
    pipe.run(_img(rng))
    built = pipe._built
    mid1 = app.getData(built.handles["mid1"])
    assert mid1.donated_by == "Scale"
    with pytest.raises(DonatedBufferError) as exc:
        mid1.sync_to_host()
    msg = str(exc.value)
    assert "'mid1'" in msg, "error must name the donated edge"
    assert "'AddConst'" in msg, "error must name the producing stage"
    assert "'Scale'" in msg, "error must name the donating consumer"
    with pytest.raises(DonatedBufferError):
        mid1.device_views()


def test_rerun_resurrects_donated_edges(app, rng):
    """Donation is per-launch: a second run() re-executes the producer,
    which re-creates the donated blob — repeat runs stay correct."""
    pipe = _chain(app)
    datasets = [_img(rng) for _ in range(2)]
    want = _roundtrip_reference(datasets)
    for i, d in enumerate(datasets):
        got = pipe.run(d).get_ndarray(0).host
        np.testing.assert_array_equal(got, want[i], err_msg=f"run[{i}]")


def test_launch_profile_phases_cover_transfer_and_compute(app, rng):
    """One upload per launch-mode run (the graph input edge), one compute
    sample per stage; internal edges contribute NO transfer records."""
    pipe = _chain(app)
    prof = ProfileParameters(enable=True)
    n_runs = 3
    for _ in range(n_runs):
        pipe.run(_img(rng), profile=prof)
    assert len(prof.phases.get("transfer", ())) == n_runs
    assert len(prof.phases.get("compute", ())) == 3 * n_runs
    assert prof.phase_total("transfer") > 0
