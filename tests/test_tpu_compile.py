"""Compile the main-path Pallas kernels for a described TPU v5e chip.

Interpret mode (how every other test runs the kernels on the CPU) cannot
see what the chip's compiler refuses: tiles not aligned to the hardware
tiling, or more fast memory than a kernel may use.  These tests lower
each kernel for real (``REPRO_PALLAS_INTERPRET=0``) against a described
``v5e:2x2`` topology at the sizes the system runs and assert that the
compiled program holds the kernel (``tpu_custom_call``).  Nothing runs:
a compile that passes is not a chip run.

The topology is described inside a fixture, never at import time: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.deepseek_v2_lite_16b import CONFIG as DSV2
from repro.configs.h2o_danube_1_8b import CONFIG as DANUBE
from repro.configs.mri_recon import CONFIG as MRI


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler available to describe it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture
def one_chip(topo, monkeypatch):
    """One described chip, real Pallas lowering, no persistent cache (a
    compile for a described chip cannot be read back without the chip)
    and no kernel trace left over from an interpret-mode test."""
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    jax.clear_caches()
    yield SingleDeviceSharding(topo.devices[0])
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", enabled)


def _compiled_text(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


_F, _C, _H, _W = MRI.frames, MRI.coils, MRI.height, MRI.width
_C64 = jnp.complex64
_BF16 = jnp.bfloat16


def _fused_epilogue(x, s):
    from repro.kernels.mri_fused import fused_epilogue
    return fused_epilogue(x, s, combine="sum")


def _fused_recon_dft(k, s):
    from repro.kernels.mri_fused import _dft_fits, fused_recon
    assert _dft_fits(k.shape[1], k.shape[2], k.shape[3])
    return fused_recon(k, s)


def _complex_elementprod(a, b):
    from repro.kernels.complex_elementprod import complex_elementprod
    return complex_elementprod(a, b, conjugate_b=True)


def _coil_combine(x):
    from repro.kernels.coil_combine import ximage_sum
    return ximage_sum(x)


def _rmsnorm(x, w):
    from repro.kernels.rmsnorm import rmsnorm
    return rmsnorm(x, w)


def _flash_attention(q, k, v):
    from repro.kernels.flash_attention import flash_attention
    return flash_attention(q, k, v, causal=True, window=DANUBE.window)


_D, _HQ, _HKV, _DH, _S = (DANUBE.d_model, DANUBE.n_heads, DANUBE.n_kv_heads,
                          DANUBE.head_dim, 512)

CASES = {
    # the paper's cine size: XLA IFFT + the fused epilogue kernel
    "fused_epilogue": (_fused_epilogue,
                       [((_F, _C, _H, _W), _C64), ((_C, _H, _W), _C64)]),
    # frames of 128 take the in-kernel DFT path
    "fused_recon_dft": (_fused_recon_dft,
                        [((_F, _C, 128, 128), _C64), ((_C, 128, 128), _C64)]),
    "complex_elementprod": (_complex_elementprod,
                            [((_F, _C, _H, _W), _C64), ((_C, _H, _W), _C64)]),
    "coil_combine": (_coil_combine, [((_F, _C, _H, _W), _C64)]),
    # danube widths: 4 sequences of 512 tokens
    "rmsnorm": (_rmsnorm, [((4, _S, _D), _BF16), ((_D,), _BF16)]),
    "flash_attention": (_flash_attention,
                        [((4, _HQ, _S, _DH), _BF16), ((4, _HKV, _S, _DH), _BF16),
                         ((4, _HKV, _S, _DH), _BF16)]),
}


@pytest.mark.parametrize("name", list(CASES))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = CASES[name]
    assert "tpu_custom_call" in _compiled_text(fn, one_chip, *shapes)


def test_expert_share_is_a_grouped_matmul_kernel_for_v5e(one_chip):
    """DeepSeek-V2-Lite's expert layer at a 64-row decode step, 8 of 64
    experts held: the three projections lower to the chip's grouped-matmul
    kernel (ops named ``ragged-dot-*``, which ``moe_roofline.dsv2``
    reads), not to a dense matmul over every row and expert."""
    from repro.models.moe import expert_share
    cfg = DSV2.scaled(experts_held=8)
    d, f, t, k = cfg.d_model, cfg.d_ff, 64, cfg.top_k

    def share(x, gates, eids, w_gate, w_up, w_down):
        p = {"w_gate": w_gate, "w_up": w_up, "w_down": w_down}
        return expert_share(p, x, gates, eids, cfg)[0]

    text = _compiled_text(share, one_chip, ((t, d), _BF16),
                          ((t, k), jnp.float32), ((t, k), jnp.int32),
                          ((8, d, f), _BF16), ((8, d, f), _BF16),
                          ((8, f, d), _BF16))
    assert "tpu_custom_call" in text
    assert len(re.findall(r"%ragged-dot-none[.\d]* = ", text)) == 3


@pytest.mark.parametrize("grid", [(4, 1), (2, 2)], ids=["data4", "data2xmodel2"])
def test_sharded_mri_stream_compiles_for_v5e_host(one_chip, topo, monkeypatch,
                                                  grid):
    """The stream executor's sharded program at the paper's size, batch 8,
    on a described 2x2 v5e host with the Pallas kernel forced: the kernel
    must sit inside a ``shard_map`` (the automatic partitioner refuses
    Mosaic kernels), and the arena's views must not inflate the program's
    temporaries (a byte blob viewed as f32 pads 32x in the TPU layout)."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    import repro.core.process as process_mod
    from repro.core import CLapp, Data, DeviceTraits
    from repro.core.arena import batched_spec
    from repro.processes.simple_mri_recon import (FusedMRIRecon,
                                                  FusedReconParams)
    app = CLapp().init(device_traits=DeviceTraits(count=1))
    app.loadKernels(list(FusedMRIRecon.kernel_names))
    k = Data.from_specs({
        "kdata": jax.ShapeDtypeStruct((_F, _C, _H, _W), _C64),
        "sensitivity_maps": jax.ShapeDtypeStruct((_C, _H, _W), _C64)})
    x = Data.from_specs({"xdata": jax.ShapeDtypeStruct((_F, _H, _W), _C64)})
    p = FusedMRIRecon(app)
    p.in_handle = app.addData(k, to_device=False)
    p.out_handle = app.addData(x, to_device=False)
    p.set_launch_parameters(FusedReconParams(use_pallas=True))
    fn, (in_layout,), _, _ = p.pure_fn()

    mesh = Mesh(np.array(topo.devices).reshape(grid), ("data", "model"))
    rows = NamedSharding(mesh, PartitionSpec("data"))
    spec = batched_spec(in_layout, 8)
    monkeypatch.setattr(process_mod, "_CURRENT_COMPILE_MESH", mesh)
    with mesh:
        compiled = jax.jit(jax.vmap(fn, spmd_axis_name="data"),
                           in_shardings=rows, out_shardings=rows).lower(
            jax.ShapeDtypeStruct(spec.shape, spec.dtype, sharding=rows)
        ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 8 * mem.argument_size_in_bytes


def _danube_state_layout():
    from repro.models import build_model
    from repro.processes.lm import decode_state_data
    state, _ = decode_state_data(build_model(DANUBE), 2, 1024)
    return state.plan()


def _danube_weight_layout():
    from repro.core.arena import plan_layout
    return plan_layout([("w", (_D, DANUBE.d_ff), _BF16)])


#: a u32 or narrower array whose minor dimension is a packing factor: the
#: TPU pads that dimension to 128 lanes (an ``(n, 2)`` interleave, 64x)
_PAIRS = re.compile(r"\b(?:u32|bf16|u16|u8|s8)\[(?:\d+,)+[24]\]")


@pytest.mark.parametrize("direction", ["pack", "unpack"])
@pytest.mark.parametrize("layout_of", [_danube_state_layout,
                                       _danube_weight_layout],
                         ids=["decode_state_2x1024", "weight_2560x6912"])
def test_arena_codec_is_lane_dense_for_v5e(one_chip, layout_of, direction):
    """danube's decode state at 2 x 1024 (two bf16 cache leaves of 63 MB)
    and one bf16 weight at published widths, packed into and viewed out
    of arena words for a described v5e: the sub-word codec engages (its
    shifts are in the program), no array of the packing factor's minor
    dimension is left, and the temporaries stay within twice the
    arena."""
    from repro.core.arena import pack_device, unpack_device
    layout = layout_of()
    if direction == "pack":
        fn = lambda arrays: pack_device(arrays, layout)  # noqa: E731
        arg = {e.name: jax.ShapeDtypeStruct(e.shape, e.dtype,
                                            sharding=one_chip)
               for e in layout.entries}
        shift = "shift-left("
    else:
        fn = lambda blob: unpack_device(blob, layout)  # noqa: E731
        arg = jax.ShapeDtypeStruct((layout.total_words,), jnp.uint32,
                                   sharding=one_chip)
        shift = "shift-right-logical("
    compiled = jax.jit(fn).lower(arg).compile()
    text = compiled.as_text()
    assert shift in text
    assert not _PAIRS.findall(text), sorted(set(_PAIRS.findall(text)))
    assert compiled.memory_analysis().temp_size_in_bytes <= \
        2 * layout.total_bytes
