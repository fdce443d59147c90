"""The benchmark's hand counts and peak table."""
import math

import pytest

from chipbench import costs
from chipbench.peaks import PEAKS, UnknownDeviceError, peaks_for

DANUBE = {"n_layers": 24, "d_model": 2560, "n_heads": 32, "n_kv_heads": 8,
          "d_head": 80, "d_ff": 6912, "vocab": 32000, "param_bytes": 2,
          "cache_bytes": 2}
V5E = PEAKS["TPU v5 lite"]


def test_paper_scan_moves_31_1_mb():
    w = costs.mri_recon_scan(16, 8, 160, 160)
    # k-space 16*8*160*160, maps 8*160*160, image 16*160*160, 8 B each
    assert w.bytes == 26_214_400 + 1_638_400 + 3_276_800 == 31_129_600
    assert w.bound(V5E) == "memory"
    assert w.least_time_s(V5E) == pytest.approx(31_129_600 / 819e9)


def test_fft_count_is_5_n_log2_n():
    assert costs.fft2_flops(4, 4) == 5 * 16 * 4
    w = costs.mri_recon_scan(1, 2, 4, 4)
    assert w.flops == 2 * 5 * 16 * 4 + 6 * 2 * 16 + 2 * 1 * 16


def test_danube_weight_bytes_and_token_flops():
    layer = (2560 * 2560 + 2 * 2560 * 640 + 2560 * 2560
             + 3 * 2560 * 6912 + 2 * 2560)
    assert costs.lm_layer_params(DANUBE) == layer == 69_473_280
    total = 24 * layer + 2 * 32000 * 2560 + 2560
    assert costs.lm_total_params(DANUBE) == total == 1_831_201_280
    assert 2 * total == 3_662_402_560          # the 3.66 GB of bf16
    n = 24 * (layer - 2 * 2560) + 2560 * 32000
    assert costs.lm_token_flops(DANUBE) == 2 * n
    assert costs.lm_kv_bytes_per_token(DANUBE) == 24 * 2 * 8 * 80 * 2


def test_decode_step_reads_weights_once_and_the_live_cache():
    a = costs.lm_decode_step(DANUBE, 16, 100)
    b = costs.lm_decode_step(DANUBE, 16, 101)
    assert b.bytes - a.bytes == 16 * 61_440        # one more cached token
    weights = (1_831_201_280 - 32000 * 2560) * 2
    assert a.bytes == (weights + 16 * 2560 * 2 + 16 * 101 * 61_440
                       + 16 * 32000 * 4)
    assert a.bound(V5E) == "memory"


def test_unknown_device_kind_raises():
    with pytest.raises(UnknownDeviceError):
        peaks_for("cpu")
    assert peaks_for("TPU v5 lite") is V5E


@pytest.mark.parametrize("frames,coils,h,w", [(16, 8, 160, 160),
                                              (1, 1, 2, 2), (4, 15, 64, 36)])
@pytest.mark.parametrize("slower", [1.0, 1.5, 1e3])
def test_share_never_exceeds_100_at_or_above_the_least_time(
        frames, coils, h, w, slower):
    work = costs.mri_recon_scan(frames, coils, h, w)
    t = work.least_time_s(V5E) * slower
    share = costs.share_pct(work.least_time_s(V5E), t)
    assert 0 < share <= 100.0
    assert math.isclose(share, 100.0 / slower)
    step = costs.lm_decode_step(DANUBE, frames, h)
    assert costs.share_pct(step.least_time_s(V5E),
                           step.least_time_s(V5E) * slower) <= 100.0
