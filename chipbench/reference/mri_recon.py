"""Plain numpy reference of the paper's reconstruction, and its phantom.

``synthetic_kdata`` and ``oracle_recon`` are copies of the repository's
``repro.data.phantom`` (kept here so that no change to the program can
move the yardstick).  The reconstruction is ``M = sum_c conj(S_c) .
IFFT2(Y_c)`` with an orthonormal inverse FFT, computed in float64.

``control_recon`` is the same reconstruction with every stored array
(k-space, maps, coil images, product, image) rounded to bfloat16: the
precision just below the float32 that complex64 data states.  A program
that computed at that precision must fail the benchmark's check.
"""
from __future__ import annotations

import ml_dtypes
import numpy as np


def synthetic_kdata(frames: int, coils: int, h: int, w: int, seed: int = 0):
    """Phantom: moving ellipse + smooth coil sensitivities -> K-space.

    Returns ``(kdata (F, C, H, W), smaps (C, H, W), images (F, H, W))``,
    all complex64."""
    rng = np.random.default_rng(seed)
    yy, xx = np.mgrid[0:h, 0:w].astype(np.float32)
    smaps = np.stack([
        np.exp(-(((yy - h * (0.2 + 0.6 * c / max(1, coils - 1))) / h) ** 2
                 + ((xx - w * 0.5) / w) ** 2) * 3.0)
        * np.exp(1j * 2 * np.pi * c / coils)
        for c in range(coils)
    ]).astype(np.complex64)
    frames_img = []
    for f in range(frames):
        cx = w * (0.4 + 0.2 * np.sin(2 * np.pi * f / frames))
        img = ((xx - cx) ** 2 / (0.1 * w) ** 2
               + (yy - h * 0.5) ** 2 / (0.2 * h) ** 2 < 1.0).astype(np.float32)
        img += 0.1 * rng.standard_normal((h, w)).astype(np.float32)
        frames_img.append(img.astype(np.complex64))
    imgs = np.stack(frames_img)                       # (F, H, W)
    coil_imgs = imgs[:, None] * smaps[None]           # (F, C, H, W)
    kdata = np.fft.fft2(coil_imgs, norm="ortho").astype(np.complex64)
    return kdata, smaps, imgs


def oracle_recon(kdata: np.ndarray, smaps: np.ndarray) -> np.ndarray:
    """The coil-combined reconstruction (F, H, W), in float64."""
    x = np.fft.ifft2(kdata.astype(np.complex128), norm="ortho")
    return (np.conj(smaps.astype(np.complex128))[None] * x).sum(axis=1)


def _bf16(z: np.ndarray) -> np.ndarray:
    """Round the real and imaginary parts to bfloat16."""
    r = z.real.astype(ml_dtypes.bfloat16).astype(np.float64)
    i = z.imag.astype(ml_dtypes.bfloat16).astype(np.float64)
    return r + 1j * i


def control_recon(kdata: np.ndarray, smaps: np.ndarray) -> np.ndarray:
    """The reconstruction with every stored array in bfloat16."""
    x = _bf16(np.fft.ifft2(_bf16(kdata), norm="ortho"))
    prod = _bf16(np.conj(_bf16(smaps))[None] * x)
    return _bf16(prod.sum(axis=1))


def max_rel_err(got: np.ndarray, want: np.ndarray) -> float:
    """Largest pixel error relative to the image's largest magnitude."""
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
