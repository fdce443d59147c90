"""The plain references, and their controls, at test size on the CPU."""
import jax
import numpy as np
import pytest

from chipbench.drivers import lm
from chipbench.reference import dense_decoder, mri_recon
from chipbench.tests import tiny

SMOKE_MRI = (2, 3, 24, 20)          # repro.configs.mri_recon.SMOKE


def test_phantom_copy_matches_the_program_s():
    from repro.data.phantom import synthetic_kdata
    for a, b in zip(mri_recon.synthetic_kdata(*SMOKE_MRI, seed=2 ** 33 + 5),
                    synthetic_kdata(*SMOKE_MRI, seed=2 ** 33 + 5)):
        np.testing.assert_array_equal(a, b)


def test_numpy_reference_matches_oracle_recon():
    from repro.data.phantom import oracle_recon
    k, s, _ = mri_recon.synthetic_kdata(*SMOKE_MRI, seed=3)
    want = oracle_recon(k, s)
    got = mri_recon.oracle_recon(k, s)
    assert got.dtype == np.complex128
    assert mri_recon.max_rel_err(want, got) < 1e-6


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_mri_control_fails_the_limit(seed):
    """Every stored array in bfloat16 misses the benchmark's limit by
    far; the float32 program at this size (see the harness test) is
    under 1e-6."""
    k, s, _ = mri_recon.synthetic_kdata(16, 8, 32, 32, seed=seed)
    err = mri_recon.max_rel_err(mri_recon.control_recon(k, s),
                                mri_recon.oracle_recon(k, s))
    assert err > 10 * tiny.MRI["limits"]["mri_max_rel_err"]


def test_float32_forward_matches_the_program_s_teacher_forcing():
    from repro.models import build_model
    cfg = dict(tiny.LM, window=8,            # windowed at these lengths
               param_dtype="float32", dtype="float32")
    model = build_model(lm.arch_config(cfg))
    w = dense_decoder.make_weights(cfg, 2 ** 32 + 11)
    lm.check_layout(model, w)
    tokens = np.random.default_rng(0).integers(0, cfg["vocab"], (1, 20))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(model.logits(w, tokens)[0])
    got = np.asarray(dense_decoder.logits(cfg, w, tokens))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


#: a bf16 model at test size, so that the control is float8 as for danube
BF16 = dict(tiny.LM, param_dtype="bfloat16", dtype="bfloat16", vocab=512,
            d_model=128, n_layers=4, max_len=64)
#: at this size the bf16 program's widest gap read 0.006-0.046 and the
#: float8 control's 0.42-0.90 over seeds 0-2 (CPU); the limit lies between
TEST_LIMIT = 0.2


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_lm_program_passes_and_float8_control_fails(seed):
    from repro.core import CLapp
    from repro.models import build_model
    from repro.serve import LMServer, SamplingConfig
    w = dense_decoder.make_weights(BF16, seed)
    server = LMServer(build_model(lm.arch_config(BF16)),
                      jax.tree.map(np.asarray, w), batch=4, max_len=64,
                      sampling=SamplingConfig(max_new_tokens=24),
                      app=CLapp().init())
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, BF16["vocab"], 16).tolist() for _ in range(4)]
    for p in prompts:
        server.submit(p)
    served = server.run()
    ctrl = dense_decoder.control_weights(w)
    program = max(float(dense_decoder.served_gaps(BF16, w, p, r).max())
                  for p, r in zip(prompts, served))
    control = max(float(dense_decoder.control_gaps(BF16, w, ctrl, p,
                                                   r).max())
                  for p, r in zip(prompts, served))
    assert program < TEST_LIMIT < control
