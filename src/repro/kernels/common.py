"""Shared helpers for Pallas TPU kernels.

All kernels target TPU (``pl.pallas_call`` + explicit ``BlockSpec`` VMEM
tiling) and are *validated* on CPU in interpret mode — the kernel body runs
in Python with the same blocking/grid semantics.
"""
from __future__ import annotations

import functools
import os
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

# Large-negative float32 used instead of -inf so fully-masked rows degrade to
# finite garbage (they only occur in padding, which wrappers slice away)
# instead of NaN-poisoning the accumulator.
NEG_INF = -1.0e30

# TPU tiling constants: MXU is 128x128, VPU lanes are 8x128.
LANE = 128
SUBLANE = 8


def interpret_mode() -> bool:
    """Pallas interprets off the TPU and lowers for real on it.

    Auto-enabling interpret mode off-TPU is what lets ``use_pallas="auto"``
    resolve to the Pallas backend without hard-failing in a CPU container.
    ``REPRO_PALLAS_INTERPRET=0`` forces real lowering off the TPU, which is
    how the compile tests lower kernels for a described chip.  ``=1`` is
    refused on a TPU backend: a kernel there never runs interpreted.
    """
    env = os.environ.get("REPRO_PALLAS_INTERPRET", "")
    if env not in ("", "0", "1"):
        raise ValueError(f"REPRO_PALLAS_INTERPRET={env!r}: expected 0 or 1")
    on_tpu = jax.default_backend() == "tpu"
    if env == "1" and on_tpu:
        raise RuntimeError(
            "REPRO_PALLAS_INTERPRET=1 on a TPU backend: Pallas kernels are "
            "never interpreted on the chip; unset it")
    if env == "0":
        return False
    return not on_tpu


def vmem_tile_plan(c: int, h: int, w: int, *, budget: int,
                   arrays: int = 2) -> Tuple[int, int]:
    """Pick a ``(bh, bw)`` tile so ``arrays`` (C, bh, bw) f32 blocks fit in
    ``budget`` bytes of VMEM.

    Prefers full-width row tiles (``bw == w``, the fast path: one grid step
    per row band).  When a single row doesn't fit — ``arrays * C * W * 4 >
    budget``, e.g. C=64 with a very wide W — falls back to a W-tiled grid
    with lane-aligned column blocks instead of silently overflowing VMEM.
    """
    per_row = arrays * c * w * 4
    if per_row <= budget:
        bh = max(1, min(h, budget // per_row))
        return bh, w
    bw = budget // (arrays * c * 4)
    if bw >= LANE:
        bw = bw // LANE * LANE  # keep column tiles lane-aligned
    return 1, max(1, min(w, bw))


def round_up(n: int, m: int) -> int:
    return (n + m - 1) // m * m


def pad_dim(x: jax.Array, axis: int, target: int) -> jax.Array:
    """Zero-pad ``axis`` of ``x`` up to length ``target``."""
    cur = x.shape[axis]
    if cur == target:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, target - cur)
    return jnp.pad(x, pads)


def split_complex(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Complex -> (re, im) float pair (TPU Pallas has no complex dtype)."""
    if jnp.iscomplexobj(x):
        return jnp.real(x), jnp.imag(x)
    return x, jnp.zeros_like(x)


def merge_complex(re: jax.Array, im: jax.Array) -> jax.Array:
    return jax.lax.complex(re.astype(jnp.float32), im.astype(jnp.float32))
