"""Smoke run of the system's two real paths on TPU chips.

    python chip_smoke.py [--seed N] [--chips 4]

One process; it starts no other process and needs no network.  All data
and weights are made from ``--seed``.  With no option it needs one TPU chip
and runs, through the entry points a user calls:

1. MRI reconstruction at the paper's size (``configs/mri_recon.py``: 16
   frames x 8 coils x 160x160 complex64): ``SimpleMRIRecon`` in each mode,
   with the default backend choice and with the Pallas kernels forced, each
   checked against the numpy oracle; a 32-scan stack streamed and served
   at batch 8 (stream == serve, bit for bit); the in-kernel DFT path at
   128x128.
2. h2o-danube-1.8b at its published widths with random bf16 weights, served
   through ``LMServer`` (4 requests, batch 4) and checked against a greedy
   loop of full forwards of the same model on the same chip.
3. The kernel chooser's records: every one compiled, none interpreted.

``--chips 4`` runs only the multi-chip path: the 32-scan stack streamed
sharded over four chips, and again over a 2x2 (data, model) grid, each
compared with the same scans streamed on one chip.

Times printed here are smoke timings, not benchmark numbers.  Any failure
exits non-zero.  The last line of a passing run is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
On anything but a TPU the script exits non-zero and names what it found.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

#: the oracle tolerance the examples and tests hold every recon mode to
MRI_TOL = 1e-4
N_SCANS, SCAN_BATCH = 32, 8
DFT_SIZE = 128                  # frames this size take the in-kernel DFT
LM_BATCH, LM_PROMPT, LM_NEW, LM_MAX_LEN = 4, 48, 32, 256
#: reference top-1 minus top-2 logit gap below which a step is a near-tie
#: and its token is not compared.  Random weights give logits of unit
#: spread and near-ties; the cache path and the full forward differ by
#: about one bf16 ulp at the top logit (1/32 at magnitude 4, measured on
#: the CPU), so a gap of 8 ulps is clearly apart
LM_MARGIN = 0.25


def log(msg: str) -> None:
    print(msg, flush=True)


def twice(fn):
    """``(result, first-call seconds, second-call seconds)``."""
    t0 = time.perf_counter()
    fn()
    t1 = time.perf_counter()
    out = fn()
    return out, t1 - t0, time.perf_counter() - t1


def log_timing(label: str, first: float, steady: float) -> None:
    log(f"[smoke timing, not a benchmark] {label}: first call {first:.3f} s "
        f"(compile included), steady {steady * 1e3:.3f} ms")


def require_tpu(chips: int):
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX found {len(devs)} "
                 f"{d.platform} device(s) ({d.device_kind!r}). It never "
                 "runs on the CPU.")
    if len(devs) < chips:
        sys.exit(f"chip_smoke: needs {chips} TPU chip(s), JAX found "
                 f"{len(devs)}")
    log(f"[device] platform={d.platform} kind={d.device_kind!r} "
        f"count={len(devs)}")
    return devs


def make_scans(cfg, n: int, seed: int):
    """``n`` independent scans at ``cfg``'s size from ``seed``."""
    from repro.core import KData
    from repro.data.phantom import synthetic_kdata
    scans = [synthetic_kdata(cfg.frames, cfg.coils, cfg.height, cfg.width,
                             seed=seed + i)[:2] for i in range(n)]
    items = [KData({"kdata": k, "sensitivity_maps": s}) for k, s in scans]
    return scans, items


def check_recon(got, kdata, smaps, label: str) -> None:
    from repro.data.phantom import oracle_recon
    np.testing.assert_allclose(got, oracle_recon(kdata, smaps), rtol=MRI_TOL,
                               atol=MRI_TOL, err_msg=label)


def stream_hosts(pipe, items, **kw):
    return [o.get_ndarray(0).host.copy()
            for o in pipe.run(items, mode="stream", batch=SCAN_BATCH, **kw)]


def mri_modes(app, cfg, seed: int) -> None:
    """Every recon mode, "auto" and forced Pallas, against the oracle."""
    from repro.core import Pipeline
    from repro.processes import SimpleMRIRecon
    (scan,), (item,) = make_scans(cfg, 1, seed)
    for mode in ("staged", "fused", "fused_pallas"):
        for use_pallas in ("auto", True):
            label = f"{mode} use_pallas={use_pallas!r}"
            pipe = Pipeline(app) | SimpleMRIRecon(
                app, mode=mode, use_pallas=use_pallas, in_place=False)
            got, first, steady = twice(
                lambda: pipe.run(item).get_ndarray(0).host.copy())
            check_recon(got, *scan, label)
            log(f"[mri] {label} {cfg.frames}x{cfg.coils}x{cfg.height}x"
                f"{cfg.width}: matches the numpy oracle at rtol=atol="
                f"{MRI_TOL}")
            log_timing(f"mri {label} launch", first, steady)


def mri_stream_serve(app, cfg, seed: int) -> None:
    """The scan stack through stream and serve: oracle spot checks and
    stream == serve bit for bit."""
    from repro.core import Pipeline, ProfileParameters
    from repro.processes import SimpleMRIRecon
    scans, items = make_scans(cfg, N_SCANS, seed)
    gb = sum(k.nbytes for k, _ in scans) / 1e9
    pipe = Pipeline(app) | SimpleMRIRecon(app, mode="fused_pallas")
    streamed, first, steady = twice(lambda: stream_hosts(pipe, items))
    for i in (0, N_SCANS // 2, N_SCANS - 1):
        check_recon(streamed[i], *scans[i], f"stream[{i}]")
    log(f"[mri] stream: {N_SCANS} scans ({gb:.2f} GB of k-space) at batch "
        f"{SCAN_BATCH}; scans 0, {N_SCANS // 2}, {N_SCANS - 1} match the "
        "oracle")
    log_timing(f"mri stream of {N_SCANS} scans", first, steady)
    prof = ProfileParameters(enable=True)
    served = pipe.run(items, mode="serve", batch=SCAN_BATCH, profile=prof)
    for i, (st, sv) in enumerate(zip(streamed, served)):
        np.testing.assert_array_equal(st, sv.get_ndarray(0).host,
                                      err_msg=f"serve[{i}]")
    log(f"[mri] serve: {N_SCANS} requests, stream == serve bit for bit; "
        f"smoke request latency p50 {prof.p50() * 1e3:.3f} ms")


def mri_dft(app, cfg, seed: int) -> None:
    """Frames small enough for the whole-frame in-kernel DFT kernel."""
    import dataclasses

    from repro.core import Pipeline
    from repro.kernels.mri_fused import _dft_fits
    from repro.processes import SimpleMRIRecon
    small = dataclasses.replace(cfg, height=DFT_SIZE, width=DFT_SIZE)
    if not _dft_fits(small.coils, small.height, small.width):
        raise RuntimeError(f"{DFT_SIZE}x{DFT_SIZE} frames no longer take "
                           "the in-kernel DFT path")
    (scan,), (item,) = make_scans(small, 1, seed)
    pipe = Pipeline(app) | SimpleMRIRecon(app, mode="fused_pallas",
                                          use_pallas=True)
    got, first, steady = twice(lambda: pipe.run(item).get_ndarray(0).host.copy())
    check_recon(got, *scan, "in-kernel DFT")
    log(f"[mri] in-kernel DFT fused_pallas at {small.frames}x{small.coils}x"
        f"{DFT_SIZE}x{DFT_SIZE}: matches the numpy oracle at rtol=atol="
        f"{MRI_TOL}")
    log_timing("mri in-kernel DFT launch", first, steady)


def lm_serve(cfg, seed: int) -> None:
    """``LMServer`` answers LM_BATCH requests, checked token by token
    against a greedy loop of full forwards with the same weights."""
    import jax

    from repro.core import CLapp, DeviceTraits, trace
    from repro.models import build_model
    from repro.serve import LMServer, SamplingConfig
    app = CLapp().init(device_traits=DeviceTraits(count=1))
    model = build_model(cfg)
    t0 = time.perf_counter()
    params = jax.jit(model.init_params)(jax.random.key(seed))
    host = jax.tree.map(np.asarray, params)
    del params
    leaves = jax.tree.leaves(host)
    log(f"[lm] {cfg.name}: {cfg.n_layers} layers, d={cfg.d_model}, "
        f"{cfg.n_heads}/{cfg.n_kv_heads} heads of {cfg.head_dim}, "
        f"ff={cfg.d_ff}, vocab={cfg.vocab}; "
        f"{sum(a.size for a in leaves) / 1e9:.3f} B random "
        f"{cfg.param_dtype} params "
        f"({sum(a.nbytes for a in leaves) / 1e9:.2f} GB) from seed {seed} "
        f"in {time.perf_counter() - t0:.1f} s")

    prompts = np.random.default_rng(seed).integers(
        0, cfg.vocab, (LM_BATCH, LM_PROMPT), dtype=np.int32)
    t0 = time.perf_counter()
    server = LMServer(model, host, batch=LM_BATCH, max_len=LM_MAX_LEN,
                      sampling=SamplingConfig(max_new_tokens=LM_NEW), app=app)
    t_build = time.perf_counter() - t0
    for p in prompts:
        server.submit(p.tolist())
    before = {c.span.id for c in trace.calls("lm.step")}
    t0 = time.perf_counter()
    outs = server.run()
    t_run = time.perf_counter() - t0
    if [len(o) for o in outs] != [LM_NEW] * LM_BATCH:
        raise RuntimeError(f"LMServer answered {[len(o) for o in outs]} "
                           f"tokens, expected {LM_NEW} each")
    tokens = np.asarray(outs, np.int32)
    steady = sorted(c.duration_s for c in trace.calls("lm.step")
                    if c.span.id not in before and "lm.admit" not in c.counts)
    step_s = steady[len(steady) // 2]
    log(f"[lm] LMServer batch={LM_BATCH} max_len={LM_MAX_LEN}: {LM_BATCH} "
        f"requests of {LM_PROMPT} prompt tokens, {LM_NEW} tokens each, "
        f"{server.steps} decode steps")
    log(f"[smoke timing, not a benchmark] lm server build (weight upload + "
        f"decode compile) {t_build:.3f} s; run (prefill compile + "
        f"{server.steps} steps) {t_run:.3f} s; steady decode step "
        f"{step_s * 1e3:.3f} ms")
    device = app.device
    del server, app
    gc.collect()                    # frees the served weights on the chip

    # the plain reference: the model's full forward over the whole padded
    # sequence (causal, so padding never reaches the position read),
    # teacher-forced on the server's tokens so a near-tie at one step
    # cannot derail every later comparison
    import jax.numpy as jnp
    params = jax.device_put(host, device)
    fwd = jax.jit(lambda p, toks, i: jax.lax.dynamic_index_in_dim(
        model.logits(p, toks)[0], i, axis=1, keepdims=False))
    seq = np.zeros((LM_BATCH, LM_PROMPT + LM_NEW), np.int32)
    seq[:, :LM_PROMPT] = prompts
    compared, excluded, wrong = 0, 0, []
    t0 = time.perf_counter()
    for t in range(LM_NEW):
        pos = LM_PROMPT - 1 + t
        logits = np.asarray(fwd(params, jnp.asarray(seq), pos), np.float32)
        top2 = np.sort(logits, axis=-1)[:, -2:]
        clear = top2[:, 1] - top2[:, 0] > LM_MARGIN
        ref_tok = logits.argmax(-1)
        compared += int(clear.sum())
        excluded += int((~clear).sum())
        wrong += [(b, t, int(ref_tok[b]), int(tokens[b, t]))
                  for b in np.where(clear & (ref_tok != tokens[:, t]))[0]]
        seq[:, pos + 1] = tokens[:, t]
    t_ref = time.perf_counter() - t0
    log(f"[lm] reference: greedy loop of {LM_NEW} full forwards; margin "
        f"{LM_MARGIN} (top-1 minus top-2 logit) excluded {excluded} of "
        f"{LM_BATCH * LM_NEW} steps; {compared} compared, {len(wrong)} differ")
    log(f"[smoke timing, not a benchmark] lm reference loop {t_ref:.3f} s")
    if wrong:
        raise RuntimeError(f"LMServer tokens differ from the reference at "
                           f"(row, step, reference, server): {wrong}")
    if compared < LM_BATCH * LM_NEW // 4:
        raise RuntimeError(f"only {compared} steps cleared the margin: the "
                           "comparison says too little")


def chooser_records() -> None:
    """Every "auto" verdict was calibrated on the chip, compiled, with a
    roofline bound from the peak table."""
    from repro.launch.roofline import default_chooser
    recs = default_chooser().records()
    for r in recs:
        d = r.to_dict()
        log("[chooser] " + json.dumps(
            {k: d[k] for k in ("kernel", "layout", "backend", "interpreted",
                               "bound", "t_pallas_s", "t_xla_s", "reason")}))
    if not recs:
        raise RuntimeError("no KernelChooser record: 'auto' never calibrated")
    bad = [r.kernel for r in recs if r.interpreted or r.bound == "unknown"]
    if bad:
        raise RuntimeError(f"chooser records interpreted or without a bound "
                           f"(device kind missing from the peak table?): {bad}")


def mesh_paths(devs, cfg, seed: int) -> None:
    """The scan stack sharded over four chips, then over a 2x2 (data,
    model) grid, each bit for bit against the same stack on one chip."""
    from repro.core import CLapp, DeviceTraits, Pipeline
    from repro.processes import SimpleMRIRecon
    scans, items = make_scans(cfg, N_SCANS, seed)
    one = CLapp().init(device_traits=DeviceTraits(count=1))
    want = stream_hosts(Pipeline(one) | SimpleMRIRecon(one, mode="fused_pallas"),
                        items)
    for i in (0, N_SCANS - 1):
        check_recon(want[i], *scans[i], f"one-chip stream[{i}]")
    for label, axis in (("data=4", 1), ("data=2 x model=2", 2)):
        app = CLapp().init(model_axis=axis)
        pipe = Pipeline(app) | SimpleMRIRecon(app, mode="fused_pallas")
        outs, first, steady = twice(lambda: pipe.run(
            items, mode="stream", batch=SCAN_BATCH, sharded=True))
        used = set()
        for o in outs:
            used |= set(o.device_blob.devices())
        log(f"[mesh] {label} mesh {dict(app.mesh.shape)}: outputs resident "
            f"on devices {sorted(d.id for d in used)}")
        if used != set(devs):
            raise RuntimeError(f"{label}: outputs on {len(used)} of "
                               f"{len(devs)} chips")
        for i, (o, w) in enumerate(zip(outs, want)):
            np.testing.assert_array_equal(o.get_ndarray(0).host, w,
                                          err_msg=f"{label} stream[{i}]")
        log(f"[mesh] {label}: {N_SCANS} scans bit-identical to the one-chip "
            "stream")
        log_timing(f"mesh {label} sharded stream of {N_SCANS} scans",
                   first, steady)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    devs = require_tpu(args.chips)[:args.chips]
    from repro.configs.h2o_danube_1_8b import CONFIG as DANUBE
    from repro.configs.mri_recon import CONFIG as MRI
    from repro.core import CLapp, DeviceTraits, enable_compile_cache
    log(f"[cache] persistent compile cache at {enable_compile_cache()}")

    if args.chips == 4:
        mesh_paths(devs, MRI, args.seed)
    else:
        app = CLapp().init(device_traits=DeviceTraits(count=1))
        mri_modes(app, MRI, args.seed)
        mri_stream_serve(app, MRI, args.seed + 1)
        mri_dft(app, MRI, args.seed)
        del app
        gc.collect()                # frees the scans before the LM loads
        lm_serve(DANUBE, args.seed)
        chooser_records()
    import jax
    d = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
