"""Transformer building blocks: norms, rotary embedding, GQA attention
(full / sliding-window / cached-decode), MLP variants, embeddings.

All functions are pure; parameters are nested dicts (leaf names drive the
partition-rule engine in ``common.py``).  Attention math runs through
``repro.kernels.ref`` by default — real HLO ops the dry-run cost model can
see — and through the Pallas kernels when ``cfg.use_pallas`` (tests, TPU).
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref as kref
from .common import (ArchConfig, KeyGen, YaRN, dense_init, embed_init, constrain,
                     MODEL, BATCH_AXES)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def init_norm(cfg: ArchConfig, dim: Optional[int] = None) -> Dict[str, Any]:
    d = dim or cfg.d_model
    p = {"scale": jnp.ones((d,), cfg.pdtype)}
    if cfg.norm == "layernorm":
        p["bias"] = jnp.zeros((d,), cfg.pdtype)
    return p


def apply_norm(p: Dict[str, Any], x: jax.Array, cfg: ArchConfig, eps: float = 1e-6) -> jax.Array:
    if cfg.norm == "layernorm":
        xf = x.astype(jnp.float32)
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
        return (y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)).astype(x.dtype)
    if cfg.use_pallas:
        from repro.kernels.rmsnorm import rmsnorm as pallas_rmsnorm
        return pallas_rmsnorm(x, p["scale"], eps=eps)
    return kref.rmsnorm(x, p["scale"], eps)


# ---------------------------------------------------------------------------
# Rotary position embedding (rotate-half)
# ---------------------------------------------------------------------------

def rope_freqs(dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature term ``0.1 m ln(factor) + 1``."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_range(dim: int, theta: float, ys: YaRN) -> Tuple[int, int]:
    """The rotary pairs ``[low, high]`` over which YaRN ramps: the pairs
    that turn ``beta_fast`` and ``beta_slow`` times over the original
    context."""
    def pair(rotations):
        return dim * math.log(ys.original_max_position_embeddings
                              / (rotations * 2 * math.pi)) / (2 * math.log(theta))
    return (max(math.floor(pair(ys.beta_fast)), 0),
            min(math.ceil(pair(ys.beta_slow)), dim - 1))


def yarn_freqs(dim: int, theta: float, ys: YaRN) -> jax.Array:
    """Frequencies ``freq / factor * (1 - m) + freq * m``, ``m`` one below
    pair ``low``, zero from pair ``high``, linear between."""
    freq = rope_freqs(dim, theta)
    low, high = yarn_range(dim, theta, ys)
    ramp = (np.arange(dim // 2, dtype=np.float32) - low) / max(high - low, 1e-3)
    m = jnp.asarray(1.0 - np.clip(ramp, 0.0, 1.0))
    return freq / ys.factor * (1.0 - m) + freq * m


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               rotary_pct: float = 1.0,
               scaling: Optional[YaRN] = None) -> jax.Array:
    """x: (B, H, S, D); positions: (B, S) int32.  ``scaling`` applies
    YaRN (:func:`yarn_freqs`) to the frequencies and scales cos and sin by
    ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)``."""
    d = x.shape[-1]
    rd = int(d * rotary_pct)
    rd -= rd % 2
    if rd == 0:
        return x
    if scaling is None:
        freqs = rope_freqs(rd, theta)                   # (rd/2,)
    else:
        freqs = yarn_freqs(rd, theta, scaling)
    ang = positions[:, None, :, None].astype(jnp.float32) * freqs  # (B,1,S,rd/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if scaling is not None:
        amp = (yarn_mscale(scaling.factor, scaling.mscale)
               / yarn_mscale(scaling.factor, scaling.mscale_all_dim))
        if amp != 1.0:
            cos, sin = cos * amp, sin * amp
    xr, xp = x[..., :rd], x[..., rd:]
    x1, x2 = xr[..., : rd // 2], xr[..., rd // 2 :]
    rot = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return jnp.concatenate([rot.astype(x.dtype), xp], axis=-1)


# ---------------------------------------------------------------------------
# GQA attention (train/prefill full-sequence + cached decode)
# ---------------------------------------------------------------------------

def init_attention(key, cfg: ArchConfig) -> Dict[str, Any]:
    kg = KeyGen(key)
    d, h, hkv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    p = {
        "w_q": dense_init(kg("w_q"), (d, h * dh), cfg.pdtype),
        "w_k": dense_init(kg("w_k"), (d, hkv * dh), cfg.pdtype),
        "w_v": dense_init(kg("w_v"), (d, hkv * dh), cfg.pdtype),
        "w_o": dense_init(kg("w_o"), (h * dh, d), cfg.pdtype),
    }
    if cfg.qkv_bias:
        p["b_q"] = jnp.zeros((h * dh,), cfg.pdtype)
        p["b_k"] = jnp.zeros((hkv * dh,), cfg.pdtype)
        p["b_v"] = jnp.zeros((hkv * dh,), cfg.pdtype)
    if cfg.qk_norm:
        p["q_norm"] = init_norm(cfg, dh)
        p["k_norm"] = init_norm(cfg, dh)
    return p


def _project_qkv(p, x, cfg: ArchConfig, positions):
    b, s, _ = x.shape
    h, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = x @ p["w_q"]
    k = x @ p["w_k"]
    v = x @ p["w_v"]
    if cfg.qkv_bias:
        q, k, v = q + p["b_q"], k + p["b_k"], v + p["b_v"]
    q = q.reshape(b, s, h, dh).transpose(0, 2, 1, 3)
    k = k.reshape(b, s, hkv, dh).transpose(0, 2, 1, 3)
    v = v.reshape(b, s, hkv, dh).transpose(0, 2, 1, 3)
    if cfg.qk_norm:
        q = apply_norm(p["q_norm"], q, cfg)
        k = apply_norm(p["k_norm"], k, cfg)
    if cfg.use_rope:
        q = apply_rope(q, positions, cfg.rope_theta, cfg.rotary_pct)
        k = apply_rope(k, positions, cfg.rope_theta, cfg.rotary_pct)
    return q, k, v


def attention_sp(q, k, v, cfg: ArchConfig, *, causal: bool) -> jax.Array:
    """Context-parallel attention (§Perf lever ``opt_seq_parallel``).

    Queries are sharded over `model` on the SEQUENCE dim (always divisible,
    unlike head counts: qwen3 has 40 q / 8 kv heads on 16 shards, which
    forces GSPMD to split the head_dim contraction and ALL-REDUCE the full
    (B,H,S,S) logits — measured 343 GB/chip on prefill_32k).  K/V are
    replicated (GQA keeps them small); logits, softmax and the PV product
    are then fully shard-local.  The local q rows are chunk-scanned with the
    shard dim exposed as its own axis so the scan never iterates a sharded
    dimension."""
    from .common import _ACTIVE_SIZES
    b, h, s, d = q.shape
    m = _ACTIVE_SIZES.get(MODEL, 1)
    if m <= 1 or s % m != 0:
        return kref.attention(q, k, v, causal=causal, window=cfg.window,
                              logit_cap=cfg.logit_softcap)
    s_local = s // m
    qm = q.reshape(b, h, m, s_local, d)
    qm = constrain(qm, BATCH_AXES, None, MODEL, None, None)
    # keep k/v in model dtype: a full f32 copy of the replicated context is
    # a multi-GB temp at 32k; the einsums accumulate in f32 instead
    kf = constrain(k, BATCH_AXES, None, None, None)
    vf = constrain(v, BATCH_AXES, None, None, None)
    group = h // k.shape[1]
    if group > 1:
        kf = jnp.repeat(kf, group, axis=1)
        vf = jnp.repeat(vf, group, axis=1)
    scale = float(d) ** -0.5
    qf = qm

    # small q blocks bound the (b,h,ck,S) f32 logits temp (256 rows x 32k
    # keys x 40 heads ~ 2.7 GB/chip)
    ck = s_local if s_local <= 256 else 256
    nq = s_local // ck if s_local % ck == 0 else 1
    if nq == 1:
        ck = s_local

    def block(qb, qi):
        # qb: (b, h, m, ck, d); global q position = mi*s_local + qi*ck + ci
        logits = jnp.einsum("bhmqd,bhkd->bhmqk", qb, kf,
                            preferred_element_type=jnp.float32) * scale
        if cfg.logit_softcap:
            logits = cfg.logit_softcap * jnp.tanh(logits / cfg.logit_softcap)
        mi = jax.lax.broadcasted_iota(jnp.int32, (m, ck, s), 0)
        ci = jax.lax.broadcasted_iota(jnp.int32, (m, ck, s), 1)
        kpos = jax.lax.broadcasted_iota(jnp.int32, (m, ck, s), 2)
        qpos = mi * s_local + qi * ck + ci
        mask = jnp.ones((m, ck, s), bool)
        if causal:
            mask &= kpos <= qpos
        if cfg.window is not None:
            mask &= kpos > qpos - cfg.window
        logits = jnp.where(mask[None, None], logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1)
        return jnp.einsum("bhmqk,bhkd->bhmqd", probs.astype(vf.dtype), vf,
                          preferred_element_type=jnp.float32)

    if nq == 1:
        o = block(qf, 0)
    else:
        qc = jnp.moveaxis(qf.reshape(b, h, m, nq, ck, d), 3, 0)

        def body(_, inp):
            qi, qb = inp
            return (), block(qb, qi)

        _, outs = jax.lax.scan(body, (), (jnp.arange(nq), qc))
        o = jnp.moveaxis(outs, 0, 3).reshape(b, h, m, s_local, d)

    o = constrain(o, BATCH_AXES, None, MODEL, None, None)
    return o.reshape(b, h, s, d).astype(q.dtype)


def gathered(p: Dict[str, Any]) -> Dict[str, Any]:
    """Replicate (all-gather) a layer's TP-sharded weights at use site.
    With seq-sharded activations this is the FSDP trade: weight bytes
    (tens of MB/layer, loop-invariant — XLA hoists the gathers) instead of
    activation reshards (GBs/layer)."""
    return {k: (constrain(v, *([None] * v.ndim)) if hasattr(v, "ndim") else
                gathered(v))
            for k, v in p.items()}


def attention_full(p, x, cfg: ArchConfig, positions, *, causal=True) -> jax.Array:
    """Full-sequence attention (train / prefill)."""
    b, s, _ = x.shape
    if cfg.opt_seq_parallel:
        # x STAYS seq-sharded; weights are gathered instead, so q/k/v come
        # out seq-sharded with no activation reshard at all
        pg = gathered(p)
        q, k, v = _project_qkv(pg, x, cfg, positions)
        o = attention_sp(q, k, v, cfg, causal=causal)
        o = o.transpose(0, 2, 1, 3).reshape(b, s, cfg.n_heads * cfg.head_dim)
        out = o @ pg["w_o"]
        return constrain(out, BATCH_AXES, MODEL, None)
    q, k, v = _project_qkv(p, x, cfg, positions)
    q = constrain(q, BATCH_AXES, MODEL, None, None)
    k = constrain(k, BATCH_AXES, MODEL, None, None)
    v = constrain(v, BATCH_AXES, MODEL, None, None)
    if cfg.use_pallas:
        from repro.kernels.flash_attention import flash_attention
        o = flash_attention(q, k, v, causal=causal, window=cfg.window)
    else:
        o = kref.attention(q, k, v, causal=causal, window=cfg.window,
                           logit_cap=cfg.logit_softcap)
    o = o.transpose(0, 2, 1, 3).reshape(b, s, cfg.n_heads * cfg.head_dim)
    return o @ p["w_o"]


def init_kv_cache(cfg: ArchConfig, n_layers: int, batch: int, max_len: int,
                  dtype) -> Dict[str, jax.Array]:
    """Unified KV cache.  ``kpos`` stores each slot's absolute position
    (-1 = empty), which makes full, sliding-window (rolling buffer) and
    padded caches all use one mask rule: ``0 <= kpos <= pos`` (+ window)."""
    hkv, dh = cfg.n_kv_heads, cfg.head_dim
    length = min(max_len, cfg.window) if cfg.window else max_len
    return {
        "k": jnp.zeros((n_layers, batch, hkv, length, dh), dtype),
        "v": jnp.zeros((n_layers, batch, hkv, length, dh), dtype),
        "kpos": jnp.full((n_layers, batch, length), -1, jnp.int32),
    }


def cache_write(cache_arr, new, slot, axis: int, local: bool):
    """Write ``new`` (extent 1 on ``axis``) into ``cache_arr`` at ``slot``.

    ``local=False``: dynamic_update_slice (baseline).  ``local=True``: one-hot
    masked select — when the cache dim is sharded (seq over `model`), DUS at
    a traced index forces GSPMD into a gather/update/re-scatter of the whole
    cache, while the masked select is purely shard-local elementwise work
    (§Perf lever `opt_local_cache_update`)."""
    if not local:
        idx = [0] * cache_arr.ndim
        idx[axis] = slot
        return jax.lax.dynamic_update_slice(cache_arr, new.astype(cache_arr.dtype),
                                            tuple(idx))
    iota = jax.lax.broadcasted_iota(jnp.int32, cache_arr.shape, axis)
    return jnp.where(iota == slot, new.astype(cache_arr.dtype), cache_arr)


def attention_decode(p, x, cfg: ArchConfig, pos, layer_cache):
    """One-token decode against a cache.  x: (B, 1, D); pos: scalar int32;
    layer_cache: dict with k (B,Hkv,C,dh), v, kpos (B,C).  Returns
    (out (B,1,D), updated layer_cache)."""
    b = x.shape[0]
    h, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    positions = jnp.broadcast_to(pos, (b, 1)).astype(jnp.int32)
    q, k_new, v_new = _project_qkv(p, x, cfg, positions)
    cache_len = layer_cache["k"].shape[2]
    slot = jnp.mod(pos, cache_len)
    loc = cfg.opt_local_cache_update
    k = cache_write(layer_cache["k"], k_new, slot, 2, loc)
    v = cache_write(layer_cache["v"], v_new, slot, 2, loc)
    kpos = cache_write(layer_cache["kpos"],
                       jnp.broadcast_to(pos, (b, 1)).astype(jnp.int32), slot, 1, loc)

    qf = q.astype(jnp.float32) * (dh ** -0.5)
    kf = k.astype(jnp.float32)
    if h != hkv:
        kf = jnp.repeat(kf, h // hkv, axis=1)
    logits = jnp.einsum("bhqd,bhkd->bhqk", qf, kf)
    if cfg.logit_softcap:
        logits = cfg.logit_softcap * jnp.tanh(logits / cfg.logit_softcap)
    mask = (kpos[:, None, None, :] >= 0) & (kpos[:, None, None, :] <= pos)
    if cfg.window:
        mask &= kpos[:, None, None, :] > pos - cfg.window
    logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    vf = v.astype(jnp.float32)
    if h != hkv:
        vf = jnp.repeat(vf, h // hkv, axis=1)
    o = jnp.einsum("bhqk,bhkd->bhqd", probs, vf).astype(x.dtype)
    o = o.transpose(0, 2, 1, 3).reshape(b, 1, h * dh)
    return o @ p["w_o"], {"k": k, "v": v, "kpos": kpos}


def prefill_kv(p, x, cfg: ArchConfig, positions, layer_cache):
    """Full-sequence prefill that also fills the cache (non-rolling region).
    Returns (out, updated cache).  Assumes S <= cache length."""
    b, s, _ = x.shape
    if cfg.opt_seq_parallel:
        x = constrain(x, BATCH_AXES, None, None)
    q, k, v = _project_qkv(p, x, cfg, positions)
    if cfg.opt_seq_parallel:
        o = attention_sp(q, k, v, cfg, causal=cfg.causal)
        # align new k/v with the cache sharding (seq over model): local write
        k = constrain(k, BATCH_AXES, None, MODEL, None)
        v = constrain(v, BATCH_AXES, None, MODEL, None)
    elif cfg.use_pallas:
        from repro.kernels.flash_attention import flash_attention
        o = flash_attention(q, k, v, causal=cfg.causal, window=cfg.window)
    else:
        o = kref.attention(q, k, v, causal=cfg.causal, window=cfg.window,
                           logit_cap=cfg.logit_softcap)
    o = o.transpose(0, 2, 1, 3).reshape(b, s, cfg.n_heads * cfg.head_dim)
    out = o @ p["w_o"]
    if cfg.opt_seq_parallel:
        out = constrain(out, BATCH_AXES, MODEL, None)
    cache_len = layer_cache["k"].shape[2]
    if cfg.window and s > cache_len:
        # keep only the last `window` keys in the rolling buffer, preserving
        # slot = position mod cache_len so decode continues seamlessly
        start = s - cache_len
        ks, vs = k[:, :, start:], v[:, :, start:]
        ps = positions[:, start:]
        shift = jnp.mod(start, cache_len)
        roll = lambda a, ax: jnp.roll(a, shift, axis=ax)
        k_c = roll(ks.astype(layer_cache["k"].dtype), 2)
        v_c = roll(vs.astype(layer_cache["v"].dtype), 2)
        p_c = roll(ps.astype(jnp.int32), 1)
        cache = {"k": k_c, "v": v_c, "kpos": p_c}
    else:
        k_c = jax.lax.dynamic_update_slice(layer_cache["k"], k.astype(layer_cache["k"].dtype), (0, 0, 0, 0))
        v_c = jax.lax.dynamic_update_slice(layer_cache["v"], v.astype(layer_cache["v"].dtype), (0, 0, 0, 0))
        p_c = jax.lax.dynamic_update_slice(layer_cache["kpos"], positions.astype(jnp.int32), (0, 0))
        cache = {"k": k_c, "v": v_c, "kpos": p_c}
    return out, cache


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def init_mlp(key, cfg: ArchConfig, d_ff: Optional[int] = None) -> Dict[str, Any]:
    kg = KeyGen(key)
    d, f = cfg.d_model, d_ff or cfg.d_ff
    if cfg.mlp == "swiglu":
        return {
            "w_gate": dense_init(kg("w_gate"), (d, f), cfg.pdtype),
            "w_up": dense_init(kg("w_up"), (d, f), cfg.pdtype),
            "w_down": dense_init(kg("w_down"), (f, d), cfg.pdtype),
        }
    return {  # gelu / relu2: two matrices
        "w_up": dense_init(kg("w_up"), (d, f), cfg.pdtype),
        "b_up": jnp.zeros((f,), cfg.pdtype),
        "w_down": dense_init(kg("w_down"), (f, d), cfg.pdtype),
        "b_down": jnp.zeros((d,), cfg.pdtype),
    }


def apply_mlp(p: Dict[str, Any], x: jax.Array, cfg: ArchConfig) -> jax.Array:
    sp = cfg.opt_seq_parallel and x.ndim == 3
    if sp:
        # FSDP-style: x stays seq-sharded; gather the weights (hoistable,
        # loop-invariant) so the matmuls are fully shard-local
        p = gathered(p)
    h_spec = (BATCH_AXES, MODEL, None) if sp else (BATCH_AXES, None, MODEL)
    if cfg.mlp == "swiglu":
        h = jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])
        h = constrain(h, *h_spec) if x.ndim == 3 else h
        out = h @ p["w_down"]
    else:
        h = x @ p["w_up"] + p["b_up"]
        h = jax.nn.gelu(h) if cfg.mlp == "gelu" else jnp.square(jax.nn.relu(h))
        h = constrain(h, *h_spec) if x.ndim == 3 else h
        out = h @ p["w_down"] + p["b_down"]
    if sp:
        out = constrain(out, BATCH_AXES, MODEL, None)
    return out


# ---------------------------------------------------------------------------
# Embeddings / logits
# ---------------------------------------------------------------------------

def init_embed(key, cfg: ArchConfig) -> Dict[str, Any]:
    kg = KeyGen(key)
    p = {"embedding": embed_init(kg("embedding"), (cfg.vocab, cfg.d_model), cfg.pdtype)}
    if not cfg.tie_embeddings:
        p["unembed"] = dense_init(kg("unembed"), (cfg.d_model, cfg.vocab), cfg.pdtype)
    return p


def embed_tokens(p, tokens: jax.Array, cfg: ArchConfig) -> jax.Array:
    return jnp.take(p["embedding"], tokens, axis=0).astype(cfg.adtype)


def logits_from_hidden(p, x: jax.Array, cfg: ArchConfig) -> jax.Array:
    if cfg.tie_embeddings:
        return (x @ p["embedding"].T.astype(cfg.adtype)).astype(jnp.float32)
    return (x @ p["unembed"]).astype(jnp.float32)


def cross_entropy(logits: jax.Array, labels: jax.Array,
                  mask: Optional[jax.Array] = None) -> jax.Array:
    """Mean token cross-entropy; logits (..., V) f32, labels (...) int32."""
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)
