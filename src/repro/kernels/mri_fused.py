"""Fused MRI-reconstruction Pallas kernels (the OpenCLIPER pitch, taken
literally: the chained per-stage processes collapse into one device pass).

Two entry points, both reducing the (F, C, H, W) multicoil stack to
(F, H, W):

* ``fused_epilogue``: the post-IFFT epilogue — multiply the per-coil
  x-images by conj(sensitivity maps) and reduce the coil axis (``"sum"``:
  paper eq. 1 / §IV-A; ``"rss"``: §IV-B) — as ONE VMEM-resident pass.
  The staged chain writes the (F, C, H, W) product back to HBM and reads
  it again for the reduction; the fused kernel keeps the product in VMEM,
  saving 2*F*C*H*W complex round-trips.
* ``fused_recon``: the whole chain including the IFFT.  For tile-sized
  grids (H, W small enough that the full (C, H, W) frame plus two DFT
  matrices fit VMEM) the 2D IFFT is expressed as two matmuls against
  precomputed inverse-DFT matrices *inside the kernel*, so
  IFFT -> conj-product -> coil-combine runs as a single ``pallas_call``.
  Larger grids fall back to ``jnp.fft.ifft2`` + ``fused_epilogue`` (still
  one fused epilogue pass, FFT handled by XLA).

Numerics note: the DFT-as-matmul path multiplies at full f32 precision
(``Precision.HIGHEST``; the chip's default is one bf16 pass) and
accumulates in f32 with a different reduction order than the radix FFT,
so it matches ``jnp.fft.ifft2`` to ~1e-5 relative (f32 roundoff over an
N-term sum), not bitwise.  The
epilogue-only path does the same multiply/accumulate as the staged chain.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.registry import kernel
from . import ref
from .common import (LANE, interpret_mode, merge_complex, pad_dim, round_up,
                     split_complex, vmem_tile_plan)
from .coil_combine import VMEM_BUDGET

#: beyond this per-axis size the DFT matmul loses to the radix FFT
#: (O(N) extra flops per output point) regardless of VMEM fit.
DFT_MAX_DIM = 256


# ---------------------------------------------------------------------------
# fused epilogue: conj(smaps) product + coil reduction, one VMEM pass
# ---------------------------------------------------------------------------

def _epilogue_sum_kernel(xr_ref, xi_ref, sr_ref, si_ref, or_ref, oi_ref):
    xr = xr_ref[...].astype(jnp.float32)          # (1, C, bh, bw)
    xi = xi_ref[...].astype(jnp.float32)
    sr = sr_ref[...].astype(jnp.float32)          # (C, bh, bw), broadcast
    si = si_ref[...].astype(jnp.float32)
    or_ref[...] = jnp.sum(xr * sr + xi * si, axis=1)   # re(x * conj(s))
    oi_ref[...] = jnp.sum(xi * sr - xr * si, axis=1)   # im(x * conj(s))


def _epilogue_rss_kernel(xr_ref, xi_ref, sr_ref, si_ref, o_ref):
    xr = xr_ref[...].astype(jnp.float32)
    xi = xi_ref[...].astype(jnp.float32)
    sr = sr_ref[...].astype(jnp.float32)
    si = si_ref[...].astype(jnp.float32)
    pr = xr * sr + xi * si
    pi = xi * sr - xr * si
    o_ref[...] = jnp.sqrt(jnp.sum(pr * pr + pi * pi, axis=1))


@functools.partial(jax.jit, static_argnames=("combine",))
def fused_epilogue(x: jax.Array, smaps: jax.Array,
                   combine: str = "sum") -> jax.Array:
    """(…, C, H, W) x-images × conj(smaps (C, H, W)) → (…, H, W).

    Matches ``ref.mri_fused_epilogue`` (== ComplexElementProd(conjugate)
    followed by XImageSum / RSSCombine, without the HBM round-trip).
    """
    if x.ndim < 3:
        raise ValueError("need (..., C, H, W) x-images")
    if tuple(smaps.shape) != tuple(x.shape[-3:]):
        raise ValueError(
            f"smaps shape {smaps.shape} != x coil grid {x.shape[-3:]}")
    lead = x.shape[:-3]
    c, h, w = x.shape[-3:]
    f = 1
    for s in lead:
        f *= s
    xre, xim = split_complex(x.reshape(f, c, h, w))
    sre, sim = split_complex(smaps)
    # 4 live (C, bh, bw) f32 tiles: x re/im + smaps re/im
    bh, bw = vmem_tile_plan(c, h, w, budget=VMEM_BUDGET, arrays=4)
    hp, wp = round_up(h, bh), round_up(w, bw)
    xre = pad_dim(pad_dim(xre, 2, hp), 3, wp)
    xim = pad_dim(pad_dim(xim, 2, hp), 3, wp)
    sre = pad_dim(pad_dim(sre, 1, hp), 2, wp)
    sim = pad_dim(pad_dim(sim, 1, hp), 2, wp)
    grid = (f, hp // bh, wp // bw)
    x_spec = pl.BlockSpec((1, c, bh, bw), lambda fi, hi, wi: (fi, 0, hi, wi))
    # frame-invariant index map: the smaps tile stays VMEM-resident while
    # the frame coordinate advances
    s_spec = pl.BlockSpec((c, bh, bw), lambda fi, hi, wi: (0, hi, wi))
    out_spec = pl.BlockSpec((1, bh, bw), lambda fi, hi, wi: (fi, hi, wi))
    n_out = 2 if combine == "sum" else 1
    kern = _epilogue_sum_kernel if combine == "sum" else _epilogue_rss_kernel
    outs = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[x_spec, x_spec, s_spec, s_spec],
        out_specs=[out_spec] * n_out,
        out_shape=[jax.ShapeDtypeStruct((f, hp, wp), jnp.float32)] * n_out,
        interpret=interpret_mode(),
    )(xre, xim, sre, sim)
    outs = [o[:, :h, :w] for o in outs]
    if combine == "sum":
        res = merge_complex(outs[0], outs[1])
        if jnp.iscomplexobj(x):
            res = res.astype(x.dtype)
    else:
        res = outs[0]
    return res.reshape(lead + (h, w))


# ---------------------------------------------------------------------------
# whole-chain kernel: in-kernel DFT-as-matmul IFFT for tile-sized grids
# ---------------------------------------------------------------------------

def _idft_matrix(n: int, norm: str):
    """Inverse-DFT matrix M[a, b] = exp(2πi·ab/n) / scale as (re, im) f32."""
    j = np.arange(n)
    m = np.exp(2j * np.pi * np.outer(j, j) / n)
    scale = {"ortho": np.sqrt(n), "backward": float(n), "forward": 1.0}[norm]
    m = m / scale
    return (jnp.asarray(m.real, jnp.float32), jnp.asarray(m.imag, jnp.float32))


def _dft_fits(c: int, h: int, w: int) -> bool:
    """Whole-frame fusion gate: (C, Hp, Wp) k-space + smaps + product
    temporaries (~8 planes) plus both DFT matrices must fit VMEM."""
    if h > DFT_MAX_DIM or w > DFT_MAX_DIM:
        return False
    hp, wp = round_up(h, LANE), round_up(w, LANE)
    tile_bytes = 4 * (8 * c * hp * wp + 2 * hp * hp + 2 * wp * wp + 2 * hp * wp)
    return tile_bytes <= VMEM_BUDGET


def _dft_recon_kernel(kr_ref, ki_ref, sr_ref, si_ref,
                      mhr_ref, mhi_ref, mwr_ref, mwi_ref,
                      *out_refs, combine: str):
    kr = kr_ref[...][0].astype(jnp.float32)       # (C, Hp, Wp)
    ki = ki_ref[...][0].astype(jnp.float32)
    mhr, mhi = mhr_ref[...], mhi_ref[...]         # (Hp, Hp)
    mwr, mwi = mwr_ref[...], mwi_ref[...]         # (Wp, Wp)
    # full f32 passes: the MXU's default single bf16 pass loses ~1e-2 over
    # a 128-term sum, two orders past the recon's 1e-4 tolerance
    dot = functools.partial(jnp.einsum, preferred_element_type=jnp.float32,
                            precision=jax.lax.Precision.HIGHEST)
    # IFFT over rows: T[c,a,w] = Σ_h M_H[a,h]·K[c,h,w] (complex via 4 real
    # matmuls)
    tr = dot("ah,chw->caw", mhr, kr) - dot("ah,chw->caw", mhi, ki)
    ti = dot("ah,chw->caw", mhr, ki) + dot("ah,chw->caw", mhi, kr)
    # IFFT over cols: Y[c,a,b] = Σ_w T[c,a,w]·M_W[b,w]
    yr = dot("caw,bw->cab", tr, mwr) - dot("caw,bw->cab", ti, mwi)
    yi = dot("caw,bw->cab", ti, mwr) + dot("caw,bw->cab", tr, mwi)
    sr = sr_ref[...].astype(jnp.float32)
    si = si_ref[...].astype(jnp.float32)
    pr = yr * sr + yi * si                        # Y * conj(S)
    pi = yi * sr - yr * si
    if combine == "rss":
        out_refs[0][...] = jnp.sqrt(jnp.sum(pr * pr + pi * pi, axis=0))[None]
    else:
        out_refs[0][...] = jnp.sum(pr, axis=0)[None]
        out_refs[1][...] = jnp.sum(pi, axis=0)[None]


def _dft_recon(k: jax.Array, smaps: jax.Array, combine: str, norm: str):
    lead = k.shape[:-3]
    c, h, w = k.shape[-3:]
    f = 1
    for s in lead:
        f *= s
    kre, kim = split_complex(k.reshape(f, c, h, w))
    sre, sim = split_complex(smaps)
    hp, wp = round_up(h, LANE), round_up(w, LANE)
    kre = pad_dim(pad_dim(kre, 2, hp), 3, wp)
    kim = pad_dim(pad_dim(kim, 2, hp), 3, wp)
    sre = pad_dim(pad_dim(sre, 1, hp), 2, wp)
    sim = pad_dim(pad_dim(sim, 1, hp), 2, wp)
    mhr, mhi = _idft_matrix(h, norm)
    mwr, mwi = _idft_matrix(w, norm)
    mhr, mhi = pad_dim(pad_dim(mhr, 0, hp), 1, hp), pad_dim(pad_dim(mhi, 0, hp), 1, hp)
    mwr, mwi = pad_dim(pad_dim(mwr, 0, wp), 1, wp), pad_dim(pad_dim(mwi, 0, wp), 1, wp)
    k_spec = pl.BlockSpec((1, c, hp, wp), lambda fi: (fi, 0, 0, 0))
    s_spec = pl.BlockSpec((c, hp, wp), lambda fi: (0, 0, 0))
    mh_spec = pl.BlockSpec((hp, hp), lambda fi: (0, 0))
    mw_spec = pl.BlockSpec((wp, wp), lambda fi: (0, 0))
    out_spec = pl.BlockSpec((1, hp, wp), lambda fi: (fi, 0, 0))
    n_out = 2 if combine == "sum" else 1
    outs = pl.pallas_call(
        functools.partial(_dft_recon_kernel, combine=combine),
        grid=(f,),
        in_specs=[k_spec, k_spec, s_spec, s_spec,
                  mh_spec, mh_spec, mw_spec, mw_spec],
        out_specs=[out_spec] * n_out,
        out_shape=[jax.ShapeDtypeStruct((f, hp, wp), jnp.float32)] * n_out,
        interpret=interpret_mode(),
    )(kre, kim, sre, sim, mhr, mhi, mwr, mwi)
    outs = [o[:, :h, :w] for o in outs]
    if combine == "sum":
        res = merge_complex(outs[0], outs[1])
        if jnp.iscomplexobj(k):
            res = res.astype(k.dtype)
    else:
        res = outs[0]
    return res.reshape(lead + (h, w))


@functools.partial(jax.jit, static_argnames=("combine", "norm"))
def fused_recon(k: jax.Array, smaps: jax.Array, combine: str = "sum",
                norm: str = "ortho") -> jax.Array:
    """Whole SimpleMRIRecon chain, (…, C, H, W) k-space → (…, H, W).

    Single-kernel when the frame is tile-sized (``_dft_fits``); otherwise
    XLA IFFT + one fused epilogue pass.  Matches ``ref.mri_fused_recon``.
    """
    if k.ndim < 3:
        raise ValueError("need (..., C, H, W) k-space")
    if tuple(smaps.shape) != tuple(k.shape[-3:]):
        raise ValueError(
            f"smaps shape {smaps.shape} != k-space coil grid {k.shape[-3:]}")
    c, h, w = k.shape[-3:]
    if _dft_fits(c, h, w):
        return _dft_recon(k, smaps, combine, norm)
    x = jnp.fft.ifft2(k, norm=norm)
    return fused_epilogue(x, smaps, combine=combine)


kernel("mriFusedEpilogue", ref=ref.mri_fused_epilogue)(fused_epilogue)
kernel("mriFusedRecon", ref=ref.mri_fused_recon)(fused_recon)
