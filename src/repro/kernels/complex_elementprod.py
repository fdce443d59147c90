"""Complex element-wise product Pallas kernel (paper §IV-A, complexElementProd.cl).

Multiplies per-coil x-images by the (optionally conjugated) sensitivity
maps: ``out[f,c,...] = a[f,c,...] * conj?(b[c,...])`` — ``b`` broadcasts
over the leading (frame) axis of ``a``.  TPU Pallas has no complex dtype,
so the kernel operates on (re, im) float planes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.registry import kernel
from . import ref
from .common import (LANE, SUBLANE, interpret_mode, merge_complex, pad_dim,
                     round_up, split_complex)

DEFAULT_BLOCK = 32 * LANE


def _cprod_kernel(ar_ref, ai_ref, br_ref, bi_ref, or_ref, oi_ref, *, conj: bool):
    ar, ai = ar_ref[...].astype(jnp.float32), ai_ref[...].astype(jnp.float32)
    br, bi = br_ref[...].astype(jnp.float32), bi_ref[...].astype(jnp.float32)
    if conj:
        bi = -bi
    or_ref[...] = (ar * br - ai * bi).astype(or_ref.dtype)
    oi_ref[...] = (ar * bi + ai * br).astype(oi_ref.dtype)


@functools.partial(jax.jit, static_argnames=("conjugate_b", "block"))
def complex_elementprod(a: jax.Array, b: jax.Array, conjugate_b: bool = False,
                        block: int = DEFAULT_BLOCK) -> jax.Array:
    """a: (F, *S) complex; b: (*S) or (F, *S) complex; returns a * conj?(b).

    Grid is (frames, tiles-of-S); the b BlockSpec index map ignores the frame
    coordinate, so each sensitivity-map tile is reused across frames straight
    from VMEM (the TPU analogue of the paper's on-device data reuse).
    """
    broadcast = b.ndim == a.ndim - 1
    if not broadcast and b.shape != a.shape:
        raise ValueError(f"bad shapes {a.shape} vs {b.shape}")
    f = a.shape[0] if broadcast else 1
    m = int(jnp.size(b))
    ar, ai = split_complex(a)
    br, bi = split_complex(b)
    ar, ai = ar.reshape(f, m), ai.reshape(f, m)
    br, bi = br.reshape(1, m), bi.reshape(1, m)

    # TPU tiling: a block's last two dims are multiples of (8, 128) or the
    # whole array dims — so frames go 8 to a block (padded) beyond 8
    bf = f if f <= SUBLANE else SUBLANE
    fp = round_up(f, bf)
    blk = min(block, round_up(m, LANE))
    mp = round_up(m, blk)
    ar, ai = (pad_dim(pad_dim(x, 0, fp), 1, mp) for x in (ar, ai))
    br, bi = pad_dim(br, 1, mp), pad_dim(bi, 1, mp)

    grid = (fp // bf, mp // blk)
    a_spec = pl.BlockSpec((bf, blk), lambda fi, mi: (fi, mi))
    b_spec = pl.BlockSpec((1, blk), lambda fi, mi: (0, mi))  # frame-invariant
    out_re, out_im = pl.pallas_call(
        functools.partial(_cprod_kernel, conj=conjugate_b),
        grid=grid,
        in_specs=[a_spec, a_spec, b_spec, b_spec],
        out_specs=[a_spec, a_spec],
        out_shape=[jax.ShapeDtypeStruct(ar.shape, jnp.float32)] * 2,
        interpret=interpret_mode(),
    )(ar, ai, br, bi)
    out = merge_complex(out_re[:f, :m], out_im[:f, :m])
    return out.reshape(a.shape).astype(a.dtype)


kernel("complexElementProd", ref=ref.complex_elementprod)(complex_elementprod)
