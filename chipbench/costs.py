"""Operations and bytes that the work itself requires, from shapes alone.

These counts are the numerator of every roofline share and MFU the
benchmark reports.  They describe the algorithm, not a compiled program
(no ``cost_analysis``), so they stay fixed whichever kernels a later
change uses: a fused kernel that reads k-space once and a staged chain
that reads it three times are held to the same least time.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Mapping

from chipbench.peaks import Peaks

COMPLEX64_BYTES = 8


@dataclasses.dataclass(frozen=True)
class Work:
    flops: float
    bytes: float

    def __add__(self, other: "Work") -> "Work":
        return Work(self.flops + other.flops, self.bytes + other.bytes)

    def __mul__(self, k: float) -> "Work":
        return Work(self.flops * k, self.bytes * k)

    __rmul__ = __mul__

    def least_time_s(self, peaks: Peaks) -> float:
        """The larger of operations over peak FLOP/s and bytes over peak
        bandwidth: no implementation on that chip can do it faster."""
        return max(self.flops / peaks.flops, self.bytes / peaks.hbm_bw)

    def bound(self, peaks: Peaks) -> str:
        return ("compute" if self.flops / peaks.flops
                >= self.bytes / peaks.hbm_bw else "memory")


# -- MRI reconstruction (paper §IV: M = sum_c conj(S_c) . IFFT2(Y_c)) -------

def fft2_flops(h: int, w: int) -> float:
    """The conventional 5 N log2 N operation count of one complex 2-D
    transform of N = h*w points."""
    n = h * w
    return 5.0 * n * math.log2(n)


def mri_recon_scan(frames: int, coils: int, height: int, width: int) -> Work:
    """One scan: read k-space (F, C, H, W) and maps (C, H, W) once, write
    the image (F, H, W) once, all complex64; inverse 2-D FFT of every
    frame and coil, a complex product by conj(maps) (6 flops) and the
    coil sum (C - 1 complex additions, 2 flops each) per image pixel."""
    pix = height * width
    nbytes = (frames * coils * pix + coils * pix + frames * pix) \
        * COMPLEX64_BYTES
    flops = (frames * coils * fft2_flops(height, width)
             + 6.0 * frames * coils * pix
             + 2.0 * (coils - 1) * frames * pix)
    return Work(flops, nbytes)


# -- dense decoder LM (GQA attention, SwiGLU MLP, untied unembedding) -------

def _dims(cfg: Mapping[str, Any]):
    d, h, hkv = cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"]
    dh = cfg.get("d_head") or d // h
    return d, h, hkv, dh, cfg["d_ff"], cfg["vocab"], cfg["n_layers"]


def lm_layer_params(cfg: Mapping[str, Any]) -> int:
    """Weights of one layer: q, k, v, o projections, the three SwiGLU
    matrices and two norm scales."""
    d, h, hkv, dh, ff, _, _ = _dims(cfg)
    return (d * h * dh + 2 * d * hkv * dh + h * dh * d
            + 3 * d * ff + 2 * d)


def lm_total_params(cfg: Mapping[str, Any]) -> int:
    """Every weight, the embedding table, unembedding and final norm
    included."""
    d, _, _, _, _, v, n_layers = _dims(cfg)
    return n_layers * lm_layer_params(cfg) + 2 * v * d + d


def lm_matmul_params(cfg: Mapping[str, Any]) -> int:
    """N of the 2 N FLOPs a token costs: every weight a token multiplies,
    i.e. all but the embedding table (a row lookup) and the norm scales."""
    d, _, _, _, _, v, n_layers = _dims(cfg)
    return n_layers * (lm_layer_params(cfg) - 2 * d) + d * v


def lm_token_flops(cfg: Mapping[str, Any]) -> float:
    """Model FLOPs of one processed token, prefill or decode: 2 N."""
    return 2.0 * lm_matmul_params(cfg)


def lm_kv_bytes_per_token(cfg: Mapping[str, Any]) -> int:
    """K and V of one position in every layer, in the cache dtype."""
    _, _, hkv, dh, _, _, n_layers = _dims(cfg)
    return n_layers * 2 * hkv * dh * cfg["cache_bytes"]


def lm_decode_step(cfg: Mapping[str, Any], batch: int, pos: int) -> Work:
    """One decode step of ``batch`` rows that all sit at position ``pos``
    (the token written this step): every weight read once, one embedding
    row per row, each row's live cache (positions 0..pos-1) read and its
    new entry written, f32 logits out; 2 N matmul FLOPs per row plus the
    attention's QK and PV products over pos + 1 keys."""
    d, h, _, dh, _, v, n_layers = _dims(cfg)
    wbytes = cfg["param_bytes"]
    weights = (lm_total_params(cfg) - v * d) * wbytes + batch * d * wbytes
    kv = batch * (pos + 1) * lm_kv_bytes_per_token(cfg)
    logits = batch * v * 4
    flops = (batch * lm_token_flops(cfg)
             + batch * n_layers * 4.0 * h * dh * (pos + 1))
    return Work(flops, weights + kv + logits)


def share_pct(least_time_s: float, time_s: float) -> float:
    """A roofline share in percent.  Not clipped: a share above 100 means
    the work is counted too high or the time leaves some of it out."""
    return 100.0 * least_time_s / time_s
