"""Roofline machinery: collective-byte HLO parser, cost_analysis semantics
(per-device, scan-body-once), spec fitting, microbatch sizing."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.launch.roofline import (Roofline, collective_bytes, cost_dict,
                                   count_params, model_flops)
from repro.launch.specs import default_microbatches, fit_pspec
from repro.configs import SHAPES, get_config


def test_collective_parser_on_synthetic_hlo():
    hlo = """
  %ar = f32[1024,256] all-reduce(f32[1024,256] %x), replica_groups={}
  %ag.1 = bf16[64,512]{1,0} all-gather(bf16[64,32]{1,0} %y), dimensions={1}
  %rs = f32[8,8] reduce-scatter(f32[64,8] %z), dimensions={0}
  %a2a = (s8[16,16], s8[16,16]) all-to-all(s8[16,16] %p, s8[16,16] %q)
  %cp-start = bf16[128] collective-permute-start(bf16[128] %w)
  %cp-done = bf16[128] collective-permute-done(bf16[128] %cp-start)
  %not-a-collective = f32[9] add(f32[9] %a, f32[9] %b)
"""
    got = collective_bytes(hlo)
    assert got["all-reduce"] == 1024 * 256 * 4
    assert got["all-gather"] == 64 * 512 * 2          # output larger
    assert got["reduce-scatter"] == 64 * 8 * 4        # input larger
    assert got["all-to-all"] == 2 * 16 * 16
    assert got["collective-permute"] == 128 * 2       # -start counted, -done not
    assert "add" not in got


def test_cost_analysis_is_per_device_and_body_once():
    """Documents the two facts the dry-run relies on."""
    a = jax.ShapeDtypeStruct((512, 512), jnp.float32)
    c = jax.jit(lambda x: x @ x).lower(a).compile()
    one = cost_dict(c)["flops"]
    assert one == pytest.approx(2 * 512 ** 3, rel=0.01)

    def scanned(x):
        y, _ = jax.lax.scan(lambda c_, _: (c_ @ c_, ()), x, None, length=10)
        return y

    cs = cost_dict(jax.jit(scanned).lower(a).compile())["flops"]
    assert cs == pytest.approx(one, rel=0.05), \
        "scan body must be counted ONCE (the reconstruction depends on this)"


def test_roofline_terms_and_bottleneck():
    r = Roofline(flops=197e12, hbm_bytes=819e9 / 2, coll_bytes=50e9 * 2,
                 coll_breakdown={}, model_flops=197e12 * 256 * 0.5)
    assert r.t_compute == pytest.approx(1.0)
    assert r.t_memory == pytest.approx(0.5)
    assert r.t_collective == pytest.approx(2.0)
    assert r.bottleneck == "collective"
    assert r.useful_flops_ratio(256) == pytest.approx(0.5)


def test_fit_pspec_divisibility():
    mesh_shape = {"data": 16, "model": 16, "pod": 2}
    # vocab 49155 not divisible by 16 -> dropped
    assert fit_pspec(P("model", None), (49155, 1024), mesh_shape) == P(None, None) or \
           fit_pspec(P("model", None), (49155, 1024), mesh_shape) == P()
    # divisible passes through
    assert fit_pspec(P("model", None), (151936, 1024), mesh_shape) == P("model")
    # tuple keeps largest divisible prefix: 256 % (2*16) == 0
    assert fit_pspec(P(("pod", "data"), None), (256, 8), mesh_shape) == P(("pod", "data"))
    # batch=1 decode -> fully replicated
    assert fit_pspec(P(("pod", "data"), None), (1, 8), mesh_shape) == P()
    # prefix only: 32 % 2 == 0 but 32 % 32 == 0 too; 48: pod keeps, data drops
    assert fit_pspec(P(("pod", "data"),), (48,), mesh_shape) == P("pod")


def test_count_params_moe_active():
    cfg = get_config("granite-moe-1b-a400m")
    from repro.models import build_model
    params = jax.eval_shape(build_model(cfg).init_params, jax.random.key(0))
    total, active = count_params(params, cfg)
    assert total > active, "MoE active params must be below total"
    # granite: 32 experts top-8 -> expert share scaled by 1/4
    assert active / total > 0.2
    mf_train = model_flops(cfg, params, "train", 256, 4096)
    mf_dec = model_flops(cfg, params, "decode", 128, 32768)
    assert mf_train == pytest.approx(6 * active * 256 * 4096)
    assert mf_dec == pytest.approx(2 * active * 128)


def test_default_microbatches_scaling():
    qwen = get_config("qwen3-14b")
    granite = get_config("granite-moe-1b-a400m")
    assert default_microbatches(qwen, SHAPES["train_4k"]) >= \
        default_microbatches(granite, SHAPES["train_4k"])
    assert default_microbatches(qwen, SHAPES["decode_32k"]) == 1


def test_peak_table_unknown_kind_leaves_bound_unknown(monkeypatch):
    """Peaks are looked up by ``device_kind``; a kind with no row (the CPU
    here) gets no bound, and adding a row for it is what gives one."""
    import repro.kernels.coil_combine  # noqa: F401  (registers xImageSum)
    from repro.launch import roofline
    kind = jax.devices()[0].device_kind
    chooser = roofline.KernelChooser(reps=1)
    monkeypatch.setattr(roofline, "PEAKS", {})
    rec = chooser.calibrate("xImageSum", jnp.zeros((1, 2, 8, 8), jnp.complex64),
                            force_timing=True)
    assert rec.bound == "unknown"
    assert np.isnan(rec.t_compute_est_s) and np.isnan(rec.t_memory_est_s)
    # a new layout (so no cached verdict) on a kind the table now holds
    monkeypatch.setattr(roofline, "PEAKS", {kind: roofline.V5E})
    rec = chooser.calibrate("xImageSum", jnp.zeros((1, 2, 8, 16), jnp.complex64),
                            force_timing=True)
    assert rec.bound in ("compute", "memory")
    assert rec.t_memory_est_s > 0
