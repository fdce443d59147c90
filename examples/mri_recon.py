"""MRI reconstruction example — the paper's §IV-A / listings 5-6.

Builds synthetic multicoil cine K-space (16 frames, 8 coils, 160x160,
matching §IV-B), reconstructs M = sum_i conj(S_i) . IFFT(Y_i) through the
SimpleMRIRecon process chain, verifies against a pure-numpy oracle, and
saves the output in the .mat-analogue (npz) container.

``--stream N`` additionally reconstructs a stack of N independent slice
acquisitions through the streaming executor (``Process.stream``): host
blobs are double-buffered to the device while earlier batches compute, and
each batch of slices runs as ONE vmapped launch.  Results are verified to
be bit-identical to the sequential launch() path.

``--sharded`` makes the streamed path mesh-aware: each batch of slices is
placed across EVERY device the app selected (the ``data`` axis of the
CLapp mesh) and one launch computes the whole batch device-parallel.  The
reconstruction call site does not change — that is the paper's
housekeeping promise.  Force a multi-device host CPU with, e.g.::

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    PYTHONPATH=src python examples/mri_recon.py --stream 16 --batch 8 --sharded

``--pipeline`` additionally demonstrates the declarative operator-graph
API (docs/pipeline.md): the same reconstruction wired as ``Pipeline(app) |
FFT | ComplexElementProd | XImageSum`` and routed through all three
execution modes of the unified front-end — ``pipe.run(kdata)``,
``pipe.run(slices, mode="stream", batch=k)``, and ``pipe.run(requests,
mode="serve", batch=k)`` — each verified bit-identical to the legacy
imperative launch above.

``--join`` demonstrates a true fan-in pipeline: the sensitivity maps are
STREAMED as a second input edge (``ComplexElementProd.bind(smaps=
"smaps")`` + ``Pipeline.from_graph``) instead of riding in the KData
arena or being broadcast as a static aux — each item is a ``{"kspace":
..., "smaps": ...}`` mapping, both edges batched row-aligned and joined
in one launch.  The joined outputs are asserted bit-identical to the
``--pipeline`` graph in every mode.

Run:  PYTHONPATH=src python examples/mri_recon.py [--fused] [--pallas]
          [--stream N] [--batch K] [--sharded] [--pipeline] [--join]
"""
import sys
import time

import numpy as np

from repro.configs.mri_recon import CONFIG
from repro.core import (CLapp, Data, DeviceTraits, KData, Pipeline,
                        PlatformTraits, ProfileParameters, SyncSource, XData,
                        enable_compile_cache)
from repro.data.phantom import oracle_recon, synthetic_kdata
from repro.processes import (FFT, ComplexElementProd, SimpleMRIRecon,
                             XImageSum)
from repro.processes.coil_combine import CombineParams
from repro.processes.complex_elementprod import ComplexElementProdParams
from repro.processes.fft import FFTParams


def _argval(flag: str, default: int) -> int:
    if flag not in sys.argv:
        return default
    idx = sys.argv.index(flag) + 1
    if idx >= len(sys.argv) or sys.argv[idx].startswith("-"):
        sys.exit(f"usage: {flag} requires an integer value, e.g. {flag} 8")
    try:
        return int(sys.argv[idx])
    except ValueError:
        sys.exit(f"usage: {flag} requires an integer value, "
                 f"got {sys.argv[idx]!r}")


def stream_slice_stack(app, proc, cfg, n_slices: int, batch: int,
                       sharded: bool = False) -> None:
    """Reconstruct a stack of independent slice acquisitions via the
    streaming executor and verify bit-identity with sequential launch()."""
    slices = []
    for s in range(n_slices):
        k, smaps, _ = synthetic_kdata(cfg.frames, cfg.coils, cfg.height,
                                      cfg.width, seed=100 + s)
        slices.append(KData({"kdata": k, "sensitivity_maps": smaps}))

    import jax
    t0 = time.perf_counter()
    outs = proc.stream(slices, batch=batch, sharded=sharded)
    jax.block_until_ready([o.device_blob for o in outs])
    t_stream = time.perf_counter() - t0
    tag = "sharded stream" if sharded else "stream"
    print(f"[{tag}] {n_slices} slices, batch={batch}: "
          f"{t_stream * 1e3:.1f} ms total, "
          f"{t_stream / n_slices * 1e3:.2f} ms/slice")
    if sharded:
        used = set()
        for o in outs:
            used |= set(o.device_blob.devices())
        print(f"[sharded stream] outputs resident on {len(used)} device(s) "
              f"of {len(app.devices)} selected "
              f"(mesh {dict(app.mesh.shape)})")

    # spot-check one slice against the sequential oracle, bitwise via the
    # framework and numerically via numpy
    d_in = app.getData(proc.in_handle)
    for dst, src in zip(d_in, slices[-1]):
        dst.set_host(src.host)
    app.host2device(proc.in_handle)
    proc.launch()
    seq = np.asarray(app.getData(proc.out_handle).device_views()["xdata"])
    got = np.asarray(outs[-1].device_view("xdata"))
    assert np.array_equal(got, seq), "streamed result must be bit-identical"
    want = oracle_recon(np.asarray(slices[-1].kdata.host),
                        np.asarray(slices[-1].smaps.host))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    print("[stream] bit-identical to sequential launch(), oracle verified")


def pipeline_demo(app, cfg, reference: np.ndarray, exact: bool = True) -> None:
    """The declarative front-end: one validated graph, three modes, all
    bit-identical to the legacy imperative launch (``reference``).
    ``exact=False`` (legacy ran fused or with Pallas kernels) relaxes the
    cross-check to numerical closeness."""
    kdata, smaps, _ = synthetic_kdata(cfg.frames, cfg.coils, cfg.height,
                                      cfg.width)
    pipe = (Pipeline(app)
            | FFT(app).bind(infile="kspace", outfile="xspace",
                            params=FFTParams("backward", var="kdata"))
            | ComplexElementProd(app).bind(
                params=ComplexElementProdParams(conjugate=True))
            | XImageSum(app).bind(params=CombineParams()))

    t0 = time.perf_counter()
    out = pipe.run(KData({"kdata": kdata, "sensitivity_maps": smaps}))
    t_build = time.perf_counter() - t0
    got = out.get_ndarray(0).host
    if exact:
        assert np.array_equal(got, reference), \
            "pipeline launch must be bit-identical to the legacy protocol"
        print(f"[pipeline] {pipe}: build+launch {t_build * 1e3:.1f} ms, "
              "bit-identical to init()/launch()")
    else:
        np.testing.assert_allclose(got, reference, rtol=1e-4, atol=1e-4)
        print(f"[pipeline] {pipe}: build+launch {t_build * 1e3:.1f} ms, "
              "matches the fused/pallas legacy launch numerically")

    slices = []
    for s in range(4):
        k, sm, _ = synthetic_kdata(cfg.frames, cfg.coils, cfg.height,
                                   cfg.width, seed=300 + s)
        slices.append(KData({"kdata": k, "sensitivity_maps": sm}))
    streamed = pipe.run(slices, mode="stream", batch=2)
    prof = ProfileParameters(enable=True)
    served = pipe.run(slices, mode="serve", batch=2, profile=prof)
    for st, sv in zip(streamed, served):
        assert np.array_equal(st.get_ndarray(0).host, sv.get_ndarray(0).host)
    print(f"[pipeline] stream == serve for {len(slices)} slices; "
          f"serve p50 {prof.p50() * 1e3:.1f} ms / "
          f"p99 {prof.p99() * 1e3:.1f} ms")


def join_demo(app, cfg, reference: np.ndarray, exact: bool = True) -> None:
    """Fan-in: the maps stream as a second input edge (a real join) and the
    result is bit-identical to the single-arena ``--pipeline`` graph."""
    kdata, smaps, _ = synthetic_kdata(cfg.frames, cfg.coils, cfg.height,
                                      cfg.width)
    # the single-input reference graph (smaps inside the KData arena)
    arena_pipe = (Pipeline(app)
                  | FFT(app).bind(infile="kspace", outfile="xspace",
                                  params=FFTParams("backward", var="kdata"))
                  | ComplexElementProd(app).bind(
                      params=ComplexElementProdParams(conjugate=True))
                  | XImageSum(app).bind(params=CombineParams()))
    # the fan-in graph: kspace stream ⋈ smaps stream
    fft = FFT(app).bind(infile="kspace", outfile="xspace",
                        params=FFTParams("backward", var="kdata"))
    prod = ComplexElementProd(app).bind(
        infile="xspace", outfile="weighted", smaps="smaps",
        params=ComplexElementProdParams(conjugate=True))
    comb = XImageSum(app).bind(infile="weighted", outfile="image",
                               params=CombineParams())
    join_pipe = Pipeline.from_graph(app, [fft, prod, comb], output="image")
    print(f"[join] input edges: {list(join_pipe.input_edges)}")

    out = join_pipe.run({"kspace": Data({"kdata": kdata}),
                         "smaps": Data({"sensitivity_maps": smaps})})
    got = out.get_ndarray(0).host
    if exact:
        assert np.array_equal(got, reference), \
            "joined launch must be bit-identical to the --pipeline output"
        print("[join] launch bit-identical to the single-arena pipeline")
    else:
        np.testing.assert_allclose(got, reference, rtol=1e-4, atol=1e-4)
        print("[join] launch matches the fused/pallas reference numerically")

    # shared maps: the joined stream must be BIT-identical to the same
    # port bound as a static aux broadcast (the legacy batched path)
    aux_pipe = (Pipeline(app)
                | FFT(app).bind(infile="kspace", outfile="xspace",
                                params=FFTParams("backward", var="kdata"))
                | ComplexElementProd(app).bind(
                    smaps=Data({"sensitivity_maps": smaps}),
                    params=ComplexElementProdParams(conjugate=True))
                | XImageSum(app).bind(params=CombineParams()))
    kstack = []
    for s in range(5):                       # 5 at batch 2: ragged tail too
        k, _, _ = synthetic_kdata(cfg.frames, cfg.coils, cfg.height,
                                  cfg.width, seed=700 + s)
        kstack.append(Data({"kdata": k}))
    shared = [{"kspace": k, "smaps": Data({"sensitivity_maps": smaps.copy()})}
              for k in kstack]
    want = aux_pipe.run(kstack, mode="stream", batch=2)
    got_stream = join_pipe.run(shared, mode="stream", batch=2)
    prof = ProfileParameters(enable=True)
    got_serve = join_pipe.run(shared, mode="serve", batch=2, profile=prof)
    for i in range(len(shared)):
        assert np.array_equal(got_stream[i].get_ndarray(0).host,
                              want[i].get_ndarray(0).host), f"stream[{i}]"
        assert np.array_equal(got_serve[i].get_ndarray(0).host,
                              want[i].get_ndarray(0).host), f"serve[{i}]"
    print(f"[join] stream+serve of {len(shared)} slices bit-identical to "
          "the aux-broadcast binding; "
          f"serve p50 {prof.p50() * 1e3:.1f} ms / "
          f"p99 {prof.p99() * 1e3:.1f} ms")

    # per-slice maps: only a join can stream these (a broadcast aux is one
    # Data for every item); verified against the single-arena graph
    slices, items = [], []
    for s in range(4):
        k, sm, _ = synthetic_kdata(cfg.frames, cfg.coils, cfg.height,
                                   cfg.width, seed=800 + s)
        slices.append(KData({"kdata": k, "sensitivity_maps": sm}))
        items.append({"kspace": Data({"kdata": k}),
                      "smaps": Data({"sensitivity_maps": sm})})
    want_arena = arena_pipe.run(slices, mode="stream", batch=2)
    got_items = join_pipe.run(items, mode="stream", batch=2)
    for i in range(len(items)):
        np.testing.assert_allclose(
            got_items[i].get_ndarray(0).host,
            want_arena[i].get_ndarray(0).host, rtol=1e-4, atol=1e-4,
            err_msg=f"per-slice maps item {i}")
    print(f"[join] {len(items)} PER-SLICE map sets streamed through the "
          "smaps edge, matching the single-arena graph")


def main() -> None:
    mode = "fused" if "--fused" in sys.argv else "staged"
    use_pallas = "--pallas" in sys.argv
    sharded = "--sharded" in sys.argv
    n_stream = _argval("--stream", 0)
    batch = _argval("--batch", 4)
    cfg = CONFIG

    enable_compile_cache()
    app = CLapp()
    # any device: the accelerator where there is one (listing 5's traits)
    app.init(PlatformTraits(), DeviceTraits())
    app.loadKernels(["complex_elementprod", "coil_combine"])

    kdata, smaps, _ = synthetic_kdata(cfg.frames, cfg.coils, cfg.height, cfg.width)
    data_in = KData({"kdata": kdata, "sensitivity_maps": smaps})
    data_out = XData({"xdata": np.zeros(data_in.x_shape(), np.complex64)})

    h_in = app.addData(data_in)      # sends to device in one call
    h_out = app.addData(data_out)

    proc = SimpleMRIRecon(app, mode=mode, use_pallas=use_pallas)
    proc.set_in_handle(h_in)
    proc.set_out_handle(h_out)

    t0 = time.perf_counter()
    proc.init()                       # "plan baking": trace + XLA compile
    t_init = time.perf_counter() - t0

    prof = ProfileParameters(enable=True)
    proc.launch(prof)                 # hot path
    print(f"[{mode}] init {t_init * 1e3:.1f} ms, "
          f"launch {prof.samples[-1] * 1e3:.3f} ms")

    app.device2Host(h_out, SyncSource.BUFFER_ONLY)
    recon = data_out.get_ndarray(0).host

    want = oracle_recon(kdata, smaps)
    np.testing.assert_allclose(recon, want, rtol=1e-4, atol=1e-4)
    print("reconstruction verified against numpy oracle")

    data_out.matlab_save("outputFrames.npz", "XData", SyncSource.HOST_ONLY)
    print("saved outputFrames.npz")

    if "--pipeline" in sys.argv:
        pipeline_demo(app, cfg, recon,
                      exact=(mode == "staged" and not use_pallas))

    if "--join" in sys.argv:
        join_demo(app, cfg, recon,
                  exact=(mode == "staged" and not use_pallas))

    if n_stream:
        stream_slice_stack(app, proc, cfg, n_stream, batch, sharded=sharded)


if __name__ == "__main__":
    main()
