"""End-to-end behaviour tests for the paper's system.

Validates the paper's §IV case study against a numpy oracle through the
full framework path (CLapp -> KData arena -> SimpleMRIRecon chain) in both
staged (paper-faithful) and fused (beyond-paper) modes, plus the RSS
reconstruction of §IV-B and the multi-pod dry-run machinery on a reduced
mesh in a subprocess (device count must be set before jax init)."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.configs.mri_recon import SMOKE as MRI_SMOKE
from repro.core import (CLapp, DeviceTraits, KData, PlatformTraits,
                        ProfileParameters, SyncSource, XData)
from repro.processes import RSSCombine, SimpleMRIRecon


def _synthetic(frames, coils, h, w, seed=0):
    rng = np.random.default_rng(seed)
    img = rng.standard_normal((frames, h, w)).astype(np.complex64)
    smaps = (rng.standard_normal((coils, h, w))
             + 1j * rng.standard_normal((coils, h, w))).astype(np.complex64)
    coil_imgs = img[:, None] * smaps[None]
    kdata = np.fft.fft2(coil_imgs, norm="ortho").astype(np.complex64)
    return kdata, smaps


@pytest.fixture(scope="module")
def app():
    return CLapp().init(PlatformTraits(), DeviceTraits())


@pytest.mark.parametrize("mode", ["staged", "fused"])
@pytest.mark.parametrize("use_pallas", [False, True])
def test_mri_recon_matches_oracle(app, mode, use_pallas):
    c = MRI_SMOKE
    kdata, smaps = _synthetic(c.frames, c.coils, c.height, c.width)
    d_in = KData({"kdata": kdata, "sensitivity_maps": smaps})
    d_out = XData({"xdata": np.zeros(d_in.x_shape(), np.complex64)})
    h_in, h_out = app.addData(d_in), app.addData(d_out)

    proc = SimpleMRIRecon(app, mode=mode, use_pallas=use_pallas)
    proc.set_in_handle(h_in)
    proc.set_out_handle(h_out)
    proc.init()
    proc.launch()
    app.device2Host(h_out, SyncSource.BUFFER_ONLY)

    want = (np.conj(smaps)[None] * np.fft.ifft2(kdata, norm="ortho")).sum(axis=1)
    np.testing.assert_allclose(d_out.get_ndarray(0).host, want,
                               rtol=1e-4, atol=1e-4)


def test_rss_recon_matches_oracle(app):
    """§IV-B: RSS of the x-space coil images."""
    c = MRI_SMOKE
    kdata, smaps = _synthetic(c.frames, c.coils, c.height, c.width, seed=1)
    x = np.fft.ifft2(kdata, norm="ortho").astype(np.complex64)
    d_in = KData({"kdata": x, "sensitivity_maps": smaps})
    d_out = XData({"xdata": np.zeros(d_in.x_shape(), np.float32)})
    h_in, h_out = app.addData(d_in), app.addData(d_out)
    p = RSSCombine(app)
    p.set_in_handle(h_in)
    p.set_out_handle(h_out)
    p.init()
    p.launch()
    app.device2Host(h_out)
    want = np.sqrt((np.abs(x) ** 2).sum(axis=1))
    np.testing.assert_allclose(d_out.get_ndarray(0).host, want, rtol=1e-4, atol=1e-4)


def test_process_launch_overhead_is_small(app):
    """Paper claim: chains and loops incur no per-call penalty.  The launch
    path must be far cheaper than init (compile)."""
    import time
    c = MRI_SMOKE
    kdata, smaps = _synthetic(c.frames, c.coils, c.height, c.width)
    d_in = KData({"kdata": kdata, "sensitivity_maps": smaps})
    d_out = XData({"xdata": np.zeros(d_in.x_shape(), np.complex64)})
    h_in, h_out = app.addData(d_in), app.addData(d_out)
    proc = SimpleMRIRecon(app, mode="fused")
    proc.set_in_handle(h_in)
    proc.set_out_handle(h_out)
    from repro.core.process import _COMPILE_CACHE
    _COMPILE_CACHE.clear()          # guarantee a cold init (prior tests warm it)
    t0 = time.perf_counter()
    proc.init()
    t_init = time.perf_counter() - t0
    prof = ProfileParameters(enable=True)
    for _ in range(5):
        app.host2device(h_in)   # re-stream input (blob donated in-place)
        proc.launch(prof)
    assert prof.mean() < t_init, "launch must be much cheaper than init"


DRYRUN_SNIPPET = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
import json
import jax
from repro.launch.dryrun import run_cell
from repro.configs import get_smoke

mesh = jax.make_mesh((4, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
cfg = get_smoke("granite-moe-1b-a400m").scaled(param_dtype="bfloat16",
                                               dtype="bfloat16")
rec = run_cell("granite-moe-1b-a400m", "train_4k", mesh=mesh, verbose=False,
               cfg_override=cfg, microbatches=1)
print("RESULT " + json.dumps({
    "status": rec["status"], "bottleneck": rec["roofline"]["bottleneck"],
    "flops": rec["roofline"]["flops_per_chip"],
    "coll": rec["roofline"]["coll_bytes_per_chip"]}))
"""


def test_dryrun_pipeline_subprocess():
    """Full dry-run machinery (lower+compile+cost reconstruction) on a
    16-fake-device mesh with a reduced config."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", DRYRUN_SNIPPET], env=env,
                         capture_output=True, text=True, timeout=560)
    assert out.returncode == 0, out.stderr[-2000:]
    line = [l for l in out.stdout.splitlines() if l.startswith("RESULT ")][-1]
    rec = json.loads(line[len("RESULT "):])
    assert rec["status"] == "ok"
    assert rec["flops"] > 0 and rec["coll"] >= 0


def test_compile_cache_dir_follows_env(monkeypatch):
    """``JAX_COMPILATION_CACHE_DIR`` wins when set; otherwise the cache is
    the fixed ``<checkout>/.jax_cache`` (never a temp, pid or time name)."""
    from repro.core import compile_cache_dir
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert compile_cache_dir() == "/elsewhere/cache"
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert compile_cache_dir() == os.path.join(root, ".jax_cache")
    assert compile_cache_dir() == compile_cache_dir()


_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chip_smoke_refuses_cpu():
    """``chip_smoke.py`` never runs on the CPU: it exits non-zero, names
    the platform it found and prints no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, os.path.join(_ROOT, "chip_smoke.py")],
                         env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert "JAX found 1 cpu device" in out.stderr
    assert '"ok"' not in out.stdout


def test_chip_smoke_phases_at_smoke_size(monkeypatch):
    """The smoke script's phases, run on the CPU at smoke sizes (interpret
    mode), so the script keeps working between chip runs."""
    import dataclasses
    import importlib.util

    from repro.configs.h2o_danube_1_8b import SMOKE as DANUBE_SMOKE
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(_ROOT, "chip_smoke.py"))
    cs = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cs)
    for name, value in (("N_SCANS", 4), ("SCAN_BATCH", 2), ("DFT_SIZE", 32),
                        ("LM_PROMPT", 8), ("LM_NEW", 6), ("LM_MAX_LEN", 32)):
        monkeypatch.setattr(cs, name, value)
    app = CLapp().init(device_traits=DeviceTraits(count=1))
    mri = dataclasses.replace(MRI_SMOKE, height=32, width=32)
    cs.mri_modes(app, mri, 0)
    cs.mri_stream_serve(app, mri, 1)
    cs.mri_dft(app, mri, 0)
    cs.lm_serve(DANUBE_SMOKE, 0)
