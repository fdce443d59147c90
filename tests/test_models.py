"""Per-arch smoke tests (reduced configs): one train step + serve path on
CPU, asserting output shapes and finiteness.  Also decode==full-forward
equivalence for each family."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_smoke
from repro.models import build_model
from repro.models import layers as L


def _batch_for(cfg, B, S, rng):
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (B, S)), jnp.int32),
             "labels": jnp.asarray(rng.integers(0, cfg.vocab, (B, S)), jnp.int32)}
    if cfg.family == "vlm":
        batch["patch_embeds"] = jnp.asarray(
            rng.standard_normal((B, cfg.n_patches, cfg.d_model)), jnp.float32)
    if cfg.family == "encdec":
        batch["frames"] = jnp.asarray(
            rng.standard_normal((B, S + 2, cfg.d_model)), jnp.float32)
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_train_step(arch, rng):
    cfg = get_smoke(arch)
    model = build_model(cfg)
    params = model.init_params(jax.random.key(0))
    B, S = 2, 16
    batch = _batch_for(cfg, B, S, rng)
    (loss, metrics), grads = jax.jit(
        jax.value_and_grad(model.loss_fn, has_aux=True))(params, batch)
    assert np.isfinite(float(loss)), arch
    gnorm = sum(float(jnp.sum(jnp.abs(g.astype(jnp.float32))))
                for g in jax.tree.leaves(grads))
    assert np.isfinite(gnorm) and gnorm > 0, arch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_serve_path(arch, rng):
    cfg = get_smoke(arch)
    model = build_model(cfg)
    params = model.init_params(jax.random.key(0))
    B, S = 2, 12
    batch = _batch_for(cfg, B, S, rng)
    if cfg.family == "encdec":
        cache = model.init_cache(B, 32, batch["frames"].shape[1])
        logits, cache = jax.jit(model.prefill)(
            params, batch["frames"], batch["tokens"], cache)
    else:
        cache = model.init_cache(B, 32)
        logits, cache = jax.jit(model.prefill)(params, batch["tokens"], cache)
    assert logits.shape == (B, 1, cfg.vocab), arch
    assert np.isfinite(np.asarray(logits)).all(), arch
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    logits2, cache = jax.jit(model.decode_step)(params, tok, jnp.int32(S), cache)
    assert logits2.shape == (B, 1, cfg.vocab), arch
    assert np.isfinite(np.asarray(logits2)).all(), arch


@pytest.mark.parametrize("arch", ["qwen3-14b", "h2o-danube-1.8b", "rwkv6-3b",
                                  "zamba2-2.7b", "granite-moe-1b-a400m"])
def test_prefill_matches_full_forward(arch, rng):
    """Last-token prefill logits == full-forward last-token logits."""
    cfg = get_smoke(arch)
    model = build_model(cfg)
    params = model.init_params(jax.random.key(0))
    B, S = 2, 12
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (B, S)), jnp.int32)
    cache = model.init_cache(B, S)
    lg, _ = jax.jit(model.prefill)(params, toks, cache)
    if hasattr(model, "logits"):
        full, _ = model.logits(params, toks)
    else:
        hs = model.hidden_states(params, toks)
        full = L.logits_from_hidden(params["embed"], hs, cfg)
    np.testing.assert_allclose(np.asarray(lg[:, 0]), np.asarray(full[:, -1]),
                               rtol=5e-4, atol=5e-4)


@pytest.mark.parametrize("arch", ["qwen2-7b", "deepseek-v2-lite-16b"])
def test_decode_matches_teacher_forcing(arch, rng):
    """Step-by-step decode logits == teacher-forced full forward."""
    cfg = get_smoke(arch)
    model = build_model(cfg)
    params = model.init_params(jax.random.key(0))
    B, S = 1, 8
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (B, S)), jnp.int32)
    cache = model.init_cache(B, S)
    for t in range(S):
        lg, cache = jax.jit(model.decode_step)(
            params, toks[:, t:t + 1], jnp.int32(t), cache)
    full, _ = model.logits(params, toks)
    np.testing.assert_allclose(np.asarray(lg[:, 0]), np.asarray(full[:, -1]),
                               rtol=1e-3, atol=1e-3)


def test_sliding_window_cache_is_rolling(rng):
    """h2o-danube: cache buffer length == window, decode past the window
    stays finite and equals full-context SWA attention."""
    cfg = get_smoke("h2o-danube-1.8b")   # window=8
    model = build_model(cfg)
    params = model.init_params(jax.random.key(0))
    B, S = 1, 20                          # S > window
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (B, S)), jnp.int32)
    cache = model.init_cache(B, S)
    assert cache["scan"]["k"].shape[3] == cfg.window, "rolling buffer sizing"
    for t in range(S):
        lg, cache = jax.jit(model.decode_step)(
            params, toks[:, t:t + 1], jnp.int32(t), cache)
    full, _ = model.logits(params, toks)
    np.testing.assert_allclose(np.asarray(lg[:, 0]), np.asarray(full[:, -1]),
                               rtol=2e-3, atol=2e-3)


def test_unroll_layers_matches_scan(rng):
    """Analysis-mode unrolled layers must be numerically identical."""
    cfg = get_smoke("qwen3-14b")
    model_scan = build_model(cfg)
    model_unroll = build_model(cfg.scaled(unroll_layers=True))
    params = model_scan.init_params(jax.random.key(0))
    B, S = 2, 8
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (B, S)), jnp.int32),
             "labels": jnp.ones((B, S), jnp.int32)}
    l1, _ = jax.jit(model_scan.loss_fn)(params, batch)
    l2, _ = jax.jit(model_unroll.loss_fn)(params, batch)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-5)


def _moe_cfg(**kw):
    from repro.models.common import ArchConfig
    base = dict(name="m", family="moe", n_layers=1, d_model=32, n_heads=2,
                n_kv_heads=2, d_ff=48, vocab=64, n_experts=4, top_k=2,
                param_dtype="float32", dtype="float32")
    return ArchConfig(**{**base, **kw})


def _dense_moe(p, x, cfg, experts=None):
    """Every expert computed for every token, the top-k pairs kept: the
    uncut layer's routed part (``experts`` restricts it to a share), with
    the router's gates as the config asks."""
    probs = jax.nn.softmax(x @ p["router"], -1)
    g, ids = jax.lax.top_k(probs, cfg.top_k)
    if cfg.norm_topk_prob:
        g = g / g.sum(-1, keepdims=True)
    want = jnp.zeros_like(x)
    for e in experts if experts is not None else range(cfg.n_experts):
        le = e - cfg.expert_offset
        h = jax.nn.silu(x @ p["w_gate"][le]) * (x @ p["w_up"][le])
        oe = h @ p["w_down"][le]
        for kk in range(cfg.top_k):
            want += jnp.where((ids[..., kk] == e)[..., None],
                              oe * g[..., kk][..., None], 0.0)
    return want


def test_moe_dispatch_matches_dense_reference(rng):
    from repro.models.moe import apply_moe, init_moe
    cfg = _moe_cfg()
    p = init_moe(jax.random.key(0), cfg)
    x = jnp.asarray(rng.standard_normal((3, 8, 32)), jnp.float32)
    y, aux = jax.jit(lambda pp, xx: apply_moe(pp, xx, cfg))(p, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(_dense_moe(p, x, cfg)),
                               rtol=2e-5, atol=2e-5)
    # every pair is held when the device holds every expert
    np.testing.assert_array_equal(np.asarray(aux["moe_counts"]),
                                  [[16, 16, 8]] * 3)


def test_moe_is_dropless_when_every_token_picks_one_expert(rng):
    """Replaces the capacity test: with the router pushed so that every
    token's first choice is expert 0, the layer still matches the dense
    reference (a capacity of 1.25 x the mean would drop most of them)."""
    from repro.models.moe import apply_moe, init_moe
    cfg = _moe_cfg(d_model=16, d_ff=32, n_experts=4, top_k=2)
    p = init_moe(jax.random.key(0), cfg)
    p["router"] = p["router"].at[:, 0].set(0.0)
    x = jnp.asarray(rng.standard_normal((1, 64, 16)), jnp.float32)
    x = x.at[..., 0].set(0.0) + jnp.eye(16)[0] * 4.0
    p["router"] = p["router"].at[0, 0].set(50.0)
    _, ids = jax.lax.top_k(jax.nn.softmax(x @ p["router"], -1), 2)
    assert bool(jnp.all(ids[..., 0] == 0))
    y, aux = jax.jit(lambda pp, xx: apply_moe(pp, xx, cfg))(p, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(_dense_moe(p, x, cfg)),
                               rtol=2e-5, atol=2e-5)
    assert int(aux["moe_counts"][0, 1]) == 64 * 2


@pytest.mark.parametrize("norm_topk_prob", [True, False])
def test_expert_shares_add_up_to_the_whole_layer(rng, norm_topk_prob):
    """Eight devices each hold E/8 experts (offsets 0, E/8, ...): the
    routed parts of the shares, with the shared expert counted once, add
    up to the uncut layer; each share's held count is its experts' pairs."""
    from repro.models.moe import apply_moe, init_moe
    e, shares = 16, 8
    full = _moe_cfg(n_experts=e, top_k=3, n_shared_experts=1,
                    norm_topk_prob=norm_topk_prob)
    p = init_moe(jax.random.key(1), full)
    x = jnp.asarray(rng.standard_normal((2, 5, 32)), jnp.float32)
    whole, _ = apply_moe(p, x, full)
    shared = L.apply_mlp(p["shared"], x, full)
    np.testing.assert_allclose(
        np.asarray(whole), np.asarray(_dense_moe(p, x, full) + shared),
        rtol=2e-5, atol=2e-5)
    total, held = shared, 0
    per = e // shares
    for i in range(shares):
        cfg = full.scaled(experts_held=per, expert_offset=i * per)
        part = {k: (v[i * per:(i + 1) * per] if k.startswith("w_") else v)
                for k, v in p.items()}
        y, aux = apply_moe(part, x, cfg)
        total = total + (y - shared)
        held += int(jnp.sum(aux["moe_counts"][:, 1]))
        assert int(jnp.sum(aux["moe_counts"][:, 0])) == 2 * 5 * 3
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               rtol=2e-5, atol=2e-5)
    assert held == 2 * 5 * 3


def test_yarn_range_and_softmax_scale_follow_the_formulas():
    """DeepSeek-V2-Lite's YaRN: the ramp runs over rotary pairs 10..23 of
    32 (floor and ceil of d ln(L0 / (2 pi beta)) / (2 ln theta)), pairs
    from 23 on are divided by the factor, and the MLA softmax scale is
    192 ** -0.5 times (0.1 * 0.707 * ln 40 + 1) ** 2."""
    import math
    from repro.configs import get_config
    from repro.models.layers import rope_freqs, yarn_freqs, yarn_range
    from repro.models.mla import softmax_scale
    cfg = get_config("deepseek-v2-lite-16b")
    ys = cfg.rope_scaling
    d, theta, l0 = 64, 10000.0, 4096
    low = math.floor(d * math.log(l0 / (2 * math.pi * 32)) / (2 * math.log(theta)))
    high = math.ceil(d * math.log(l0 / (2 * math.pi * 1)) / (2 * math.log(theta)))
    assert (low, high) == yarn_range(d, theta, ys) == (10, 23)
    f, y = np.asarray(rope_freqs(d, theta)), np.asarray(yarn_freqs(d, theta, ys))
    np.testing.assert_allclose(y[:11], f[:11], rtol=1e-6)
    np.testing.assert_allclose(y[23:], f[23:] / 40, rtol=1e-6)
    assert np.all((y[11:23] < f[11:23]) & (y[11:23] > f[11:23] / 40))
    mscale = 0.1 * 0.707 * math.log(40) + 1
    assert mscale ** 2 == pytest.approx(1.5896, abs=1e-4)
    assert softmax_scale(cfg) == pytest.approx(192 ** -0.5 * mscale ** 2)
    assert softmax_scale(cfg.scaled(rope_scaling=None)) == 192 ** -0.5


def test_mamba2_step_equals_forward(rng):
    from repro.models.mamba2 import (init_mamba2, init_mamba2_state,
                                     mamba2_forward, mamba2_step)
    cfg = get_smoke("zamba2-2.7b")
    p = init_mamba2(jax.random.key(1), cfg)
    x = jnp.asarray(rng.standard_normal((2, 16, cfg.d_model)), jnp.float32)
    y = mamba2_forward(p, x, cfg)
    st = init_mamba2_state(cfg, 2)
    outs = []
    for t in range(16):
        o, st = jax.jit(lambda pp, xx, ss: mamba2_step(pp, xx, cfg, ss))(p, x[:, t:t+1], st)
        outs.append(o)
    np.testing.assert_allclose(np.asarray(jnp.concatenate(outs, 1)),
                               np.asarray(y), rtol=1e-4, atol=1e-4)
