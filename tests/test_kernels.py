"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracles.
All kernels run in interpret mode on CPU (same blocking/grid semantics).

The ``sharded_pallas`` section validates every kernel INSIDE the streaming
executor — vmapped :class:`BatchedProcess`, ``sharded=True`` — on 8
devices; ``test_rerun_forced_eight_devices_pallas`` re-runs just that
section in a forced-8-host-device subprocess so it executes in a plain
tier-1 pass.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import common as kcommon
from repro.kernels import ref
from repro.kernels.coil_combine import VMEM_BUDGET, rss, ximage_sum
from repro.kernels.common import vmem_tile_plan
from repro.kernels.complex_elementprod import complex_elementprod
from repro.kernels.flash_attention import flash_attention
from repro.kernels.mri_fused import _dft_fits, fused_epilogue, fused_recon
from repro.kernels.negate import negate
from repro.kernels.rmsnorm import rmsnorm
from repro.kernels.wkv6 import wkv6

_CHILD_ENV = "REPRO_MESH_TEST_CHILD"
_FORCE_FLAG = "--xla_force_host_platform_device_count=8"

needs_8_devices = pytest.mark.skipif(
    jax.device_count() < 8, reason="needs >= 8 devices (forced-host child run)")


def _c(rng, shape):
    return (rng.standard_normal(shape) + 1j * rng.standard_normal(shape)
            ).astype(np.complex64)


@pytest.mark.parametrize("shape", [(7,), (128,), (3, 5, 17), (160, 160), (1,)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_negate(rng, shape, dtype):
    x = jnp.asarray(rng.random(shape), dtype)
    np.testing.assert_allclose(
        np.asarray(negate(x), np.float32),
        np.asarray(ref.negate(x), np.float32), rtol=1e-6)


@pytest.mark.parametrize("fcwh", [(16, 8, 160, 160), (2, 3, 24, 20), (1, 1, 8, 8)])
@pytest.mark.parametrize("conj", [False, True])
def test_complex_elementprod(rng, fcwh, conj):
    f, c, h, w = fcwh
    a = _c(rng, (f, c, h, w))
    b = _c(rng, (c, h, w))
    got = np.asarray(complex_elementprod(jnp.asarray(a), jnp.asarray(b), conj))
    want = np.asarray(ref.complex_elementprod(jnp.asarray(a), jnp.asarray(b), conj))
    np.testing.assert_allclose(got, want, rtol=2e-6, atol=1e-5)


def test_complex_elementprod_same_shape(rng):
    a, b = _c(rng, (4, 6, 6)), _c(rng, (4, 6, 6))
    got = np.asarray(complex_elementprod(jnp.asarray(a), jnp.asarray(b), True))
    np.testing.assert_allclose(got, a * np.conj(b), rtol=2e-6, atol=1e-5)


@pytest.mark.parametrize("fcwh", [(16, 8, 160, 160), (3, 4, 33, 17)])
def test_coil_combine(rng, fcwh):
    x = _c(rng, fcwh)
    np.testing.assert_allclose(
        np.asarray(ximage_sum(jnp.asarray(x))),
        np.asarray(ref.ximage_sum(jnp.asarray(x))), rtol=2e-6, atol=2e-5)
    np.testing.assert_allclose(
        np.asarray(rss(jnp.asarray(x))),
        np.asarray(ref.rss(jnp.asarray(x))), rtol=2e-6, atol=2e-5)


def test_rss_real_input(rng):
    x = rng.standard_normal((3, 4, 9, 11)).astype(np.float32)
    np.testing.assert_allclose(
        np.asarray(rss(jnp.asarray(x))),
        np.asarray(ref.rss(jnp.asarray(x))), rtol=2e-6, atol=2e-5)


@pytest.mark.parametrize("shape", [(4, 64), (2, 3, 96), (17, 128), (1, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm(rng, shape, dtype):
    x = jnp.asarray(rng.standard_normal(shape), dtype)
    w = jnp.asarray(rng.standard_normal(shape[-1]), jnp.float32)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(
        np.asarray(rmsnorm(x, w), np.float32),
        np.asarray(ref.rmsnorm(x, w), np.float32), rtol=tol, atol=tol)


@pytest.mark.parametrize(
    "b,hq,hkv,sq,skv,d,causal,window",
    [
        (2, 4, 2, 32, 32, 16, True, None),    # GQA causal
        (1, 4, 4, 24, 24, 8, False, None),    # MHA bidirectional + padding
        (2, 8, 2, 16, 48, 16, True, None),    # kv longer than q (chunked KV)
        (1, 2, 2, 1, 40, 8, True, None),      # single-token decode
        (1, 4, 2, 32, 32, 16, True, 8),       # sliding window
        (1, 4, 2, 33, 47, 16, True, 13),      # ragged + window
    ])
def test_flash_attention(rng, b, hq, hkv, sq, skv, d, causal, window):
    q = jnp.asarray(rng.standard_normal((b, hq, sq, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, hkv, skv, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, hkv, skv, d)), jnp.float32)
    got = np.asarray(flash_attention(q, k, v, causal=causal, window=window,
                                     block_q=16, block_k=16))
    want = np.asarray(ref.attention(q, k, v, causal=causal, window=window))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_flash_attention_bf16(rng):
    q = jnp.asarray(rng.standard_normal((1, 2, 16, 32)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((1, 2, 16, 32)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((1, 2, 16, 32)), jnp.bfloat16)
    got = np.asarray(flash_attention(q, k, v, block_q=8, block_k=8), np.float32)
    want = np.asarray(ref.attention(q, k, v), np.float32)
    np.testing.assert_allclose(got, want, rtol=5e-2, atol=5e-2)


def test_ref_attention_chunked_equals_dense(rng, monkeypatch):
    """The q-chunked long-context path must equal the dense path."""
    monkeypatch.setattr(ref, "ATTN_CHUNK_THRESHOLD", 64)
    monkeypatch.setattr(ref, "ATTN_CHUNK", 32)
    b, h, s, d = 1, 2, 64, 16
    q = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32)
    chunked = ref.attention(q, k, v, causal=True)   # takes the scan path
    with ref.unchunked_attention():
        dense = ref.attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(chunked), np.asarray(dense),
                               rtol=2e-5, atol=2e-5)
    # windowed variant too
    cw = ref.attention(q, k, v, causal=True, window=10)
    with ref.unchunked_attention():
        dw = ref.attention(q, k, v, causal=True, window=10)
    np.testing.assert_allclose(np.asarray(cw), np.asarray(dw),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("b,t,h,d,bt", [(2, 20, 3, 8, 8), (1, 16, 2, 16, 4)])
def test_wkv6(rng, b, t, h, d, bt):
    r, k, v = (jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.float32)
               for _ in range(3))
    w = jnp.asarray(rng.standard_normal((b, t, h, d)) * 0.5, jnp.float32)
    u = jnp.asarray(rng.standard_normal((h, d)), jnp.float32)
    got, gs = wkv6(r, k, v, w, u, block_t=bt)
    want, ws = ref.wkv6(r, k, v, w, u)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(gs), np.asarray(ws), rtol=2e-5, atol=2e-5)


def test_wkv6_chunked_state_passing(rng):
    b, t, h, d = 2, 16, 2, 8
    r, k, v = (jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.float32)
               for _ in range(3))
    w = jnp.asarray(rng.standard_normal((b, t, h, d)) * 0.5, jnp.float32)
    u = jnp.asarray(rng.standard_normal((h, d)), jnp.float32)
    s0 = jnp.asarray(rng.standard_normal((b, h, d, d)), jnp.float32)
    o1, s1 = wkv6(r[:, :8], k[:, :8], v[:, :8], w[:, :8], u, s0, block_t=4)
    o2, s2 = wkv6(r[:, 8:], k[:, 8:], v[:, 8:], w[:, 8:], u, s1, block_t=4)
    wo, wsf = ref.wkv6(r, k, v, w, u, s0)
    np.testing.assert_allclose(np.concatenate([o1, o2], 1), np.asarray(wo),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(wsf), rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# interpret_mode env override (REPRO_PALLAS_INTERPRET)
# ---------------------------------------------------------------------------

def test_interpret_mode_env_override(monkeypatch):
    """Interpret off the TPU, ``=0`` forces real lowering there, and ``=1``
    is refused on a TPU backend (reported as such through a monkeypatch)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
    assert kcommon.interpret_mode() is True
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    assert kcommon.interpret_mode() is True
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    assert kcommon.interpret_mode() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert kcommon.interpret_mode() is False
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET")
    assert kcommon.interpret_mode() is False
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    with pytest.raises(RuntimeError, match="TPU"):
        kcommon.interpret_mode()


# ---------------------------------------------------------------------------
# VMEM tile planning: W-tiled fallback when a single row exceeds the budget
# ---------------------------------------------------------------------------

def test_vmem_tile_plan_row_fallback():
    # fast path: rows fit, full-width tiles
    bh, bw = vmem_tile_plan(4, 64, 64, budget=VMEM_BUDGET, arrays=2)
    assert bw == 64 and bh >= 1
    assert 2 * 4 * bh * bw * 4 <= VMEM_BUDGET
    # pathological: one (C=64, W=20000) row is ~9.8 MiB > 8 MiB budget —
    # must fall back to lane-aligned column tiles, not overflow
    c, w = 64, 20000
    bh, bw = vmem_tile_plan(c, 4, w, budget=VMEM_BUDGET, arrays=2)
    assert bh == 1 and bw < w
    assert bw % 128 == 0
    assert 2 * c * bw * 4 <= VMEM_BUDGET


def test_coil_combine_single_row_over_budget(rng):
    """Regression: (C=64, W huge) used to pick a (64, 1, W) tile larger
    than VMEM_BUDGET; the planner now W-tiles the grid instead."""
    x = _c(rng, (1, 64, 2, 17000))    # per_row = 2*64*17000*4 > 8 MiB
    np.testing.assert_allclose(
        np.asarray(ximage_sum(jnp.asarray(x))),
        np.asarray(ref.ximage_sum(jnp.asarray(x))), rtol=2e-5, atol=2e-4)
    np.testing.assert_allclose(
        np.asarray(rss(jnp.asarray(x))),
        np.asarray(ref.rss(jnp.asarray(x))), rtol=2e-5, atol=2e-4)


# ---------------------------------------------------------------------------
# fused MRI kernels (kernels/mri_fused.py)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("combine", ["sum", "rss"])
def test_mri_fused_epilogue(rng, combine):
    x = jnp.asarray(_c(rng, (3, 8, 40, 24)))
    s = jnp.asarray(_c(rng, (8, 40, 24)))
    got = np.asarray(fused_epilogue(x, s, combine=combine))
    want = np.asarray(ref.mri_fused_epilogue(x, s, combine))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_mri_fused_epilogue_wide_row_fallback(rng):
    # arrays=4 planning: 4*16*35000*4 > 8 MiB forces the W-tiled grid
    x = jnp.asarray(_c(rng, (1, 16, 2, 35000)))
    s = jnp.asarray(_c(rng, (16, 2, 35000)))
    got = np.asarray(fused_epilogue(x, s, combine="sum"))
    want = np.asarray(ref.mri_fused_epilogue(x, s, "sum"))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-4)


@pytest.mark.parametrize("combine", ["sum", "rss"])
@pytest.mark.parametrize("norm", ["ortho", "backward"])
def test_mri_fused_recon_dft_in_kernel(rng, combine, norm):
    """Tile-sized grids run IFFT+epilogue as ONE kernel (DFT-as-matmul).
    f32 matmul accumulation differs from the radix FFT's order, hence the
    1e-4 band (documented in kernels/mri_fused.py)."""
    assert _dft_fits(4, 32, 48)
    k = jnp.asarray(_c(rng, (2, 4, 32, 48)))
    s = jnp.asarray(_c(rng, (4, 32, 48)))
    got = np.asarray(fused_recon(k, s, combine=combine, norm=norm))
    want = np.asarray(ref.mri_fused_recon(k, s, combine, norm))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_mri_fused_recon_dft_matmuls_at_full_precision():
    """The in-kernel DFT matmuls ask for ``Precision.HIGHEST``.  Interpret
    mode computes f32 dots in f32 whatever they ask for, but the chip's
    default is one bf16 pass, which put the 128x128 recon 0.028 off."""
    import re
    k = jax.ShapeDtypeStruct((2, 4, 32, 48), jnp.complex64)
    s = jax.ShapeDtypeStruct((4, 32, 48), jnp.complex64)
    text = str(jax.make_jaxpr(fused_recon)(k, s))
    precisions = re.findall(r"dot_general\[.*?precision=(\([^)]*\)|None)",
                            text, flags=re.S)
    assert len(precisions) == 8
    assert all(p.count("HIGHEST") == 2 for p in precisions), precisions


def test_mri_fused_recon_large_grid_falls_back(rng):
    """Frames too big for whole-frame VMEM residency use XLA IFFT + the
    fused epilogue pass (still one kernel for the epilogue)."""
    assert not _dft_fits(2, 300, 300)
    k = jnp.asarray(_c(rng, (1, 2, 300, 300)))
    s = jnp.asarray(_c(rng, (2, 300, 300)))
    got = np.asarray(fused_recon(k, s))
    want = np.asarray(ref.mri_fused_recon(k, s))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# KernelChooser: use_pallas="auto" backend selection
# ---------------------------------------------------------------------------

def test_kernel_chooser_calibrates_and_caches():
    from repro.launch.roofline import PEAKS, KernelChooser, resolve_backend
    ch = KernelChooser(reps=1)
    x = jnp.zeros((2, 4, 16, 16), jnp.complex64)
    rec = ch.calibrate("xImageSum", x, force_timing=True)
    assert rec.backend in ("pallas", "xla")
    assert rec.t_xla_s < float("inf") and rec.t_pallas_s < float("inf")
    if jax.devices()[0].device_kind not in PEAKS:
        assert rec.bound == "unknown"   # no peaks: no tie is broken by a bound
    else:
        assert rec.bound in ("compute", "memory")
    assert rec.interpreted == (jax.default_backend() != "tpu")
    if rec.interpreted:
        # interpret-mode pallas timings are never allowed to win
        assert rec.backend == "xla"
    # cached per (kernel, layout, device): second call is the same record
    assert ch.calibrate("xImageSum", x, force_timing=True) is rec
    # the "auto" contract resolves through the same (global) cache
    assert resolve_backend("auto", "xImageSum", x) == rec.use_pallas
    assert resolve_backend(True, "xImageSum", x) is True
    assert resolve_backend(False, "xImageSum", x) is False


def test_kernel_chooser_calibrates_inside_jit():
    """A first "auto" query happens while a process's program is traced:
    the timed calibration must run on concrete examples, not tracers."""
    from repro.launch.roofline import KernelChooser
    ch = KernelChooser(reps=1)
    recs = []

    def f(x):
        recs.append(ch.calibrate("xImageSum", x, force_timing=True))
        return x.sum(axis=1)

    x = jnp.ones((1, 2, 8, 8), jnp.complex64)
    np.testing.assert_allclose(np.asarray(jax.jit(f)(x)), 2.0)
    (rec,) = recs
    assert rec.t_xla_s < float("inf") and rec.t_pallas_s < float("inf")
    assert ch.lookup("xImageSum", x) is rec


def test_kernel_chooser_interpret_short_circuit():
    if jax.default_backend() == "tpu":
        pytest.skip("interpret-mode short-circuit is an off-TPU behaviour")
    from repro.launch.roofline import default_chooser
    ch = default_chooser()
    y = jnp.zeros((1, 2, 8, 8), jnp.complex64)
    # no timed calibration runs: the verdict is immediate and cached
    assert ch.use_pallas("rss", y) is False
    rec = ch.lookup("rss", y)
    assert rec is not None and rec.interpreted and rec.backend == "xla"


# ---------------------------------------------------------------------------
# SimpleMRIRecon(mode="fused_pallas"): launch / stream / serve parity
# ---------------------------------------------------------------------------

_MRI_F, _MRI_C, _MRI_H, _MRI_W = 2, 3, 16, 16


def _mri_sets(rng, n):
    from repro.core import KData
    smaps = _c(rng, (_MRI_C, _MRI_H, _MRI_W))
    return smaps, [KData({"kdata": _c(rng, (_MRI_F, _MRI_C, _MRI_H, _MRI_W)),
                          "sensitivity_maps": smaps.copy()}) for _ in range(n)]


def test_fused_pallas_three_modes_match_staged(rng):
    """mode="fused_pallas" vs the staged chain in launch / stream / serve,
    ragged tails included.  The fused formulation is ONE program (different
    XLA fusion/reduction order than three staged programs), so parity is
    rtol=1e-5 — not bitwise — by design; see docs/kernels.md."""
    from repro.core import CLapp, Pipeline, ProfileParameters
    from repro.processes import SimpleMRIRecon
    app = CLapp().init()
    smaps, inputs = _mri_sets(rng, 5)

    staged = Pipeline(app) | SimpleMRIRecon(app, mode="staged", in_place=False)
    fused = Pipeline(app) | SimpleMRIRecon(app, mode="fused_pallas")

    want_launch = [staged.run(d).get_ndarray(0).host.copy() for d in inputs]
    got_launch = [fused.run(d).get_ndarray(0).host.copy() for d in inputs]
    # 5 items at batch=2 -> ragged tail executable on the last batch
    got_stream = fused.run(inputs, mode="stream", batch=2, sync=True)
    prof = ProfileParameters(enable=True)
    got_serve = fused.run(inputs, mode="serve", batch=2, profile=prof)
    for i in range(len(inputs)):
        np.testing.assert_allclose(got_launch[i], want_launch[i],
                                   rtol=1e-5, atol=1e-5, err_msg=f"launch[{i}]")
        np.testing.assert_allclose(got_stream[i].get_ndarray(0).host,
                                   want_launch[i],
                                   rtol=1e-4, atol=1e-4, err_msg=f"stream[{i}]")
        np.testing.assert_allclose(got_serve[i].get_ndarray(0).host,
                                   want_launch[i],
                                   rtol=1e-4, atol=1e-4, err_msg=f"serve[{i}]")


def test_fused_pallas_forced_backend_matches(rng):
    """use_pallas=True routes through the Pallas kernel (interpret mode on
    CPU, in-kernel DFT IFFT for this tile-sized grid) and stays in the
    documented band vs the staged chain."""
    from repro.core import CLapp, Pipeline
    from repro.processes import SimpleMRIRecon
    app = CLapp().init()
    smaps, inputs = _mri_sets(rng, 2)
    staged = Pipeline(app) | SimpleMRIRecon(app, mode="staged", in_place=False)
    forced = Pipeline(app) | SimpleMRIRecon(app, mode="fused_pallas",
                                            use_pallas=True)
    for d in inputs:
        want = staged.run(d).get_ndarray(0).host.copy()
        got = forced.run(d).get_ndarray(0).host.copy()
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_fused_pallas_join_streams_maps(rng):
    """join=True: k-space ⋈ smaps as separate streaming inputs through the
    fused composite, vs the staged joined composite."""
    from repro.core import CLapp, Data, Pipeline
    from repro.processes import SimpleMRIRecon
    app = CLapp().init()
    smaps, inputs = _mri_sets(rng, 3)
    items = [{"kspace": Data({"kdata": next(iter(d)).host.copy()}),
              "smaps": Data({"sensitivity_maps": smaps.copy()})}
             for d in inputs]

    staged = SimpleMRIRecon(app, mode="staged", in_place=False,
                            join=True).bind(infile="kspace", smaps="smaps")
    fusedp = SimpleMRIRecon(app, mode="fused_pallas",
                            join=True).bind(infile="kspace", smaps="smaps")
    want = Pipeline.from_graph(app, [staged]).run(items, mode="stream", batch=2)
    got = Pipeline.from_graph(app, [fusedp]).run(items, mode="stream", batch=2)
    for i in range(len(items)):
        np.testing.assert_allclose(got[i].get_ndarray(0).host,
                                   want[i].get_ndarray(0).host,
                                   rtol=1e-4, atol=1e-4, err_msg=f"item {i}")


# ---------------------------------------------------------------------------
# sharded/vmapped validation: every Pallas kernel inside the streaming
# executor on 8 devices (``-k sharded_pallas`` section; see module docstring)
# ---------------------------------------------------------------------------

@pytest.mark.skipif(os.environ.get(_CHILD_ENV) == "1",
                    reason="already the forced-device child")
def test_rerun_forced_eight_devices_pallas():
    """Run the sharded_pallas section under 8 forced host CPU devices so the
    sharded/vmapped kernel validation executes in a single-device tier-1
    pass."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " " + _FORCE_FLAG).strip()
    env[_CHILD_ENV] = "1"
    src = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        [sys.executable, "-m", "pytest", "-x", "-q", "--no-header",
         os.path.abspath(__file__), "-k", "sharded_pallas"],
        env=env, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, (
        f"forced-8-device child run failed:\n{r.stdout}\n{r.stderr}")
    assert "passed" in r.stdout


def _kernel_stream_case(app, rng, proc_cls, mk_item, ref_fn, n=16):
    """Stream ``n`` independent items through a kernel-wrapper Process and
    check every output against the pure-jnp oracle."""
    from repro.core import Data
    datasets = [Data(mk_item(rng)) for _ in range(n)]
    zero = {k: np.zeros_like(v) for k, v in mk_item(rng).items()}
    want0 = np.asarray(ref_fn({k: jnp.asarray(v) for k, v in zero.items()}))
    p = proc_cls(app)
    p.in_handle = app.addData(Data(zero))
    p.out_handle = app.addData(Data({"y": np.zeros_like(want0)}))
    p.init()
    got = p.stream(datasets, batch=8, sharded=True, sync=True)
    assert len(got) == len(datasets)
    for i, (d, o) in enumerate(zip(datasets, got)):
        arrs = {nd.name: jnp.asarray(nd.host) for nd in d}
        want = np.asarray(ref_fn(arrs))
        np.testing.assert_allclose(o.get_ndarray(0).host, want,
                                   rtol=2e-5, atol=2e-5, err_msg=f"item {i}")
    return got


# module-level wrapper processes: each routes one Pallas kernel through the
# typed-port Process machinery so BatchedProcess can vmap + shard it
def _make_kernel_procs():
    from repro.core import Port, Process

    class RmsnormProc(Process):
        ports = {"in": Port(names=("x",)), "out": Port(names=("y",))}

        def apply(self, views, aux, params):
            w = jnp.asarray(np.linspace(0.5, 1.5, views["x"].shape[-1],
                                        dtype=np.float32))
            return {"y": rmsnorm(views["x"], w)}

    class AttnProc(Process):
        ports = {"in": Port(names=("q", "k", "v")), "out": Port(names=("y",))}

        def apply(self, views, aux, params):
            return {"y": flash_attention(views["q"], views["k"], views["v"],
                                         block_q=8, block_k=8)}

    class Wkv6Proc(Process):
        ports = {"in": Port(names=("r", "k", "v", "w")),
                 "out": Port(names=("y",))}

        def apply(self, views, aux, params):
            u = jnp.asarray(np.linspace(-0.5, 0.5, 2 * 8,
                                        dtype=np.float32).reshape(2, 8))
            out, _ = wkv6(views["r"], views["k"], views["v"], views["w"], u,
                          block_t=4)
            return {"y": out}

    class CoilSumProc(Process):
        ports = {"in": Port(names=("x",)), "out": Port(names=("y",))}

        def apply(self, views, aux, params):
            return {"y": ximage_sum(views["x"])}

    class ElemprodProc(Process):
        ports = {"in": Port(names=("x", "s")), "out": Port(names=("y",))}

        def apply(self, views, aux, params):
            return {"y": complex_elementprod(views["x"], views["s"], True)}

    return RmsnormProc, AttnProc, Wkv6Proc, CoilSumProc, ElemprodProc


def _rms_item(rng):
    return {"x": rng.standard_normal((16, 128)).astype(np.float32)}


def _attn_item(rng):
    return {k: rng.standard_normal((1, 2, 16, 16)).astype(np.float32)
            for k in ("q", "k", "v")}


def _wkv_item(rng):
    return {k: rng.standard_normal((1, 8, 2, 8)).astype(np.float32)
            for k in ("r", "k", "v", "w")}


def _coil_item(rng):
    return {"x": _c(rng, (4, 16, 16))}


def _elem_item(rng):
    return {"x": _c(rng, (2, 16, 16)), "s": _c(rng, (2, 16, 16))}


def _rms_ref(a):
    w = jnp.asarray(np.linspace(0.5, 1.5, 128, dtype=np.float32))
    return ref.rmsnorm(a["x"], w)


def _attn_ref(a):
    return ref.attention(a["q"], a["k"], a["v"])


def _wkv_ref(a):
    u = jnp.asarray(np.linspace(-0.5, 0.5, 16, dtype=np.float32).reshape(2, 8))
    return ref.wkv6(a["r"], a["k"], a["v"], a["w"], u)[0]


def _coil_ref(a):
    return ref.ximage_sum(a["x"])


def _elem_ref(a):
    return ref.complex_elementprod(a["x"], a["s"], True)


_KERNEL_CASES = {
    "rmsnorm": (0, _rms_item, _rms_ref),
    "flash_attention": (1, _attn_item, _attn_ref),
    "wkv6": (2, _wkv_item, _wkv_ref),
    "coil_combine": (3, _coil_item, _coil_ref),
    "complex_elementprod": (4, _elem_item, _elem_ref),
}


@needs_8_devices
@pytest.mark.parametrize("case", sorted(_KERNEL_CASES))
def test_sharded_pallas_stream_parity(rng, case):
    """Every Pallas kernel under stream(sharded=True) over 8 devices,
    vmapped by BatchedProcess, matches its oracle per item."""
    from repro.core import CLapp
    app = CLapp().init()
    idx, mk, rf = _KERNEL_CASES[case]
    _kernel_stream_case(app, rng, _make_kernel_procs()[idx], mk, rf)


@needs_8_devices
def test_sharded_pallas_vmapped_batchedprocess(rng):
    """Direct BatchedProcess check: the vmapped AOT program is built over
    the data axis and the Pallas path adds no h2d transfers beyond the
    XLA-oracle path (same batches, same phase records)."""
    from repro.core import BatchedProcess, CLapp, Data, Port, Process, ProfileParameters
    app = CLapp().init()
    RmsnormProc = _make_kernel_procs()[0]

    class RmsnormRefProc(Process):
        ports = {"in": Port(names=("x",)), "out": Port(names=("y",))}

        def apply(self, views, aux, params):
            w = jnp.asarray(np.linspace(0.5, 1.5, views["x"].shape[-1],
                                        dtype=np.float32))
            return {"y": ref.rmsnorm(views["x"], w)}

    datasets = [Data(_rms_item(rng)) for _ in range(16)]
    outs = {}
    profs = {}
    for name, cls in (("pallas", RmsnormProc), ("xla", RmsnormRefProc)):
        p = cls(app)
        p.in_handle = app.addData(Data({"x": np.zeros((16, 128), np.float32)}))
        p.out_handle = app.addData(Data({"y": np.zeros((16, 128), np.float32)}))
        bp = BatchedProcess(p, 8, sharded=True).init()
        assert bp.batch_sharding.spec == jax.sharding.PartitionSpec("data")
        prof = ProfileParameters(enable=True)
        outs[name] = p.stream(datasets, batch=8, sharded=True, sync=True,
                              profile=prof)
        profs[name] = prof
    for a, b in zip(outs["pallas"], outs["xla"]):
        np.testing.assert_allclose(a.get_ndarray(0).host,
                                   b.get_ndarray(0).host,
                                   rtol=2e-5, atol=2e-5)
    # no extra host->device traffic from the Pallas path: identical
    # transfer record counts, and no d2d records on either side
    t_pallas = profs["pallas"].phases.get("transfer", [])
    t_xla = profs["xla"].phases.get("transfer", [])
    assert len(t_pallas) == len(t_xla)
    assert not profs["pallas"].phases.get("transfer_d2d")
    assert not profs["xla"].phases.get("transfer_d2d")


@needs_8_devices
def test_sharded_pallas_fused_recon_stream(rng):
    """The fused MRI composite itself under a sharded stream: 8 devices,
    ragged-free batch, parity vs the staged chain."""
    from repro.core import CLapp
    from repro.processes import SimpleMRIRecon
    app = CLapp().init()
    _, inputs = _mri_sets(rng, 8)
    staged = SimpleMRIRecon(app, mode="staged", in_place=False)
    fused = SimpleMRIRecon(app, mode="fused_pallas")
    from repro.core import KData, XData
    for p in (staged, fused):
        d_in = KData({"kdata": np.zeros((_MRI_F, _MRI_C, _MRI_H, _MRI_W),
                                        np.complex64),
                      "sensitivity_maps": np.zeros((_MRI_C, _MRI_H, _MRI_W),
                                                   np.complex64)})
        p.in_handle = app.addData(d_in)
        p.out_handle = app.addData(
            XData({"xdata": np.zeros((_MRI_F, _MRI_H, _MRI_W), np.complex64)}))
    want = staged.stream(inputs, batch=8, sharded=True, sync=True)
    got = fused.stream(inputs, batch=8, sharded=True, sync=True)
    for i in range(len(inputs)):
        np.testing.assert_allclose(got[i].get_ndarray(0).host,
                                   want[i].get_ndarray(0).host,
                                   rtol=1e-4, atol=1e-4, err_msg=f"item {i}")
