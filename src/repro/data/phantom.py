"""Synthetic multicoil cine K-space and its numpy reconstruction oracle.

The paper's §IV case study reconstructs ``M = sum_i conj(S_i) . IFFT(Y_i)``
from multicoil K-space ``Y`` and coil sensitivities ``S``.  No scanner data
ships with the repository, so the examples, the chip smoke run and the
benchmarks build a phantom from a seed and check the framework against
the plain numpy reconstruction below.
"""
from __future__ import annotations

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
    """The coil-combined reconstruction in plain numpy: (F, H, W)."""
    x = np.fft.ifft2(kdata, norm="ortho")
    return (np.conj(smaps)[None] * x).sum(axis=1)
