"""Multi-head Latent Attention (DeepSeek-V2) — train path + absorbed decode.

Train/prefill: the compressed kv latent c_kv (rank=512) and the shared rope
key are expanded to per-head keys/values (direct form).  Decode: the cache
stores ONLY (c_kv, k_rope) per token — the whole point of MLA: cache bytes
per token = rank + rope_dim instead of 2*H*dh — and the up-projections are
*absorbed* into the query/output paths so scores are computed in latent
space (q W_uk^T) . c_kv without materialising per-head keys.

With ``cfg.rope_scaling`` (DeepSeek-V2's YaRN) the rope part of q and k
takes YaRN's frequencies and the softmax scale is multiplied by
``mscale(factor, mscale_all_dim) ** 2``, in both forms alike.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from .common import ArchConfig, KeyGen, dense_init, constrain, MODEL, BATCH_AXES
from .layers import apply_rope, init_norm, apply_norm, yarn_mscale


def init_mla(key, cfg: ArchConfig) -> Dict[str, Any]:
    kg = KeyGen(key)
    d, h = cfg.d_model, cfg.n_heads
    dn, dr, dv, rank = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim, cfg.kv_lora_rank
    return {
        "w_q": dense_init(kg("w_q"), (d, h * (dn + dr)), cfg.pdtype),
        "w_dkv": dense_init(kg("w_dkv"), (d, rank), cfg.pdtype),
        "w_kr": dense_init(kg("w_kr"), (d, dr), cfg.pdtype),
        "kv_norm": init_norm(cfg, rank),
        "w_uk": dense_init(kg("w_uk"), (rank, h, dn), cfg.pdtype),
        "w_uv": dense_init(kg("w_uv"), (rank, h, dv), cfg.pdtype),
        "w_o": dense_init(kg("w_o"), (h * dv, d), cfg.pdtype),
    }


def _q_proj(p, x, cfg: ArchConfig, positions):
    b, s, _ = x.shape
    h, dn, dr = cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim
    q = (x @ p["w_q"]).reshape(b, s, h, dn + dr).transpose(0, 2, 1, 3)
    q_nope, q_pe = q[..., :dn], q[..., dn:]
    q_pe = apply_rope(q_pe, positions, cfg.rope_theta, scaling=cfg.rope_scaling)
    return q_nope, q_pe


def _latents(p, x, cfg: ArchConfig, positions):
    c_kv = apply_norm(p["kv_norm"], x @ p["w_dkv"], cfg)          # (B,S,rank)
    k_pe = (x @ p["w_kr"])[:, None, :, :]                          # (B,1,S,dr)
    k_pe = apply_rope(k_pe, positions, cfg.rope_theta,
                      scaling=cfg.rope_scaling)[:, 0]              # (B,S,dr)
    return c_kv, k_pe


def softmax_scale(cfg: ArchConfig) -> float:
    """``(qk_nope + qk_rope) ** -0.5``, times YaRN's ``mscale ** 2``."""
    scale = (cfg.qk_nope_dim + cfg.qk_rope_dim) ** -0.5
    ys = cfg.rope_scaling
    if ys is not None and ys.mscale_all_dim:
        scale *= yarn_mscale(ys.factor, ys.mscale_all_dim) ** 2
    return scale


#: q-chunking bound, mirroring kernels.ref.attention (and following its
#: unchunked_attention override for cost-analysis compiles)
from repro.kernels import ref as _kref

MLA_CHUNK = 1024


def _chunk_threshold() -> int:
    return _kref.ATTN_CHUNK_THRESHOLD


def _mla_attend_block(q_nope, q_pe, k_nope, k_pe, v, q_off, s_kv, scale):
    """One q-block: q_* (B,H,Cq,*); keys/values full length."""
    cq = q_nope.shape[2]
    logits = (jnp.einsum("bhsd,bhtd->bhst", q_nope, k_nope)
              + jnp.einsum("bhsd,btd->bhst", q_pe, k_pe)) * scale
    q_pos = q_off + jnp.arange(cq)[:, None]
    k_pos = jnp.arange(s_kv)[None, :]
    logits = jnp.where((k_pos <= q_pos)[None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhst,bhtd->bhsd", probs, v)


def mla_full(p, x, cfg: ArchConfig, positions) -> jax.Array:
    """Full-sequence MLA (train / prefill), direct expansion form; long
    sequences scan over q-chunks (bounded logits buffer)."""
    with jax.named_scope("mla"):
        return _mla_full(p, x, cfg, positions)


def _mla_full(p, x, cfg: ArchConfig, positions) -> jax.Array:
    b, s, _ = x.shape
    h, dn, dr, dv = cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    q_nope, q_pe = _q_proj(p, x, cfg, positions)
    c_kv, k_pe = _latents(p, x, cfg, positions)
    k_nope = jnp.einsum("bsr,rhd->bhsd", c_kv, p["w_uk"])
    v = jnp.einsum("bsr,rhd->bhsd", c_kv, p["w_uv"])
    k_nope = constrain(k_nope, BATCH_AXES, MODEL, None, None)
    v = constrain(v, BATCH_AXES, MODEL, None, None)

    scale = softmax_scale(cfg)
    qn = q_nope.astype(jnp.float32)
    qp = q_pe.astype(jnp.float32)
    kn = k_nope.astype(jnp.float32)
    kp = k_pe.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    if s < _chunk_threshold() or s % MLA_CHUNK != 0:
        o = _mla_attend_block(qn, qp, kn, kp, vf, 0, s, scale)
    else:
        nq = s // MLA_CHUNK
        qn_c = jnp.moveaxis(qn.reshape(b, h, nq, MLA_CHUNK, dn), 2, 0)
        qp_c = jnp.moveaxis(qp.reshape(b, h, nq, MLA_CHUNK, dr), 2, 0)

        def body(_, inp):
            qi, qnc, qpc = inp
            return (), _mla_attend_block(qnc, qpc, kn, kp, vf,
                                         qi * MLA_CHUNK, s, scale)

        _, outs = jax.lax.scan(body, (), (jnp.arange(nq), qn_c, qp_c))
        o = jnp.moveaxis(outs, 0, 2).reshape(b, h, s, dv)
    o = o.astype(x.dtype).transpose(0, 2, 1, 3).reshape(b, s, h * dv)
    return o @ p["w_o"]


def init_mla_cache(cfg: ArchConfig, n_layers: int, batch: int, max_len: int, dtype):
    return {
        "c_kv": jnp.zeros((n_layers, batch, max_len, cfg.kv_lora_rank), dtype),
        "k_pe": jnp.zeros((n_layers, batch, max_len, cfg.qk_rope_dim), dtype),
        "kpos": jnp.full((n_layers, batch, max_len), -1, jnp.int32),
    }


def mla_prefill(p, x, cfg: ArchConfig, positions, layer_cache):
    out = mla_full(p, x, cfg, positions)
    c_kv, k_pe = _latents(p, x, cfg, positions)
    cache = {
        "c_kv": jax.lax.dynamic_update_slice(
            layer_cache["c_kv"], c_kv.astype(layer_cache["c_kv"].dtype), (0, 0, 0)),
        "k_pe": jax.lax.dynamic_update_slice(
            layer_cache["k_pe"], k_pe.astype(layer_cache["k_pe"].dtype), (0, 0, 0)),
        "kpos": jax.lax.dynamic_update_slice(
            layer_cache["kpos"], positions.astype(jnp.int32), (0, 0)),
    }
    return out, cache


def mla_decode(p, x, cfg: ArchConfig, pos, layer_cache):
    """Absorbed one-token decode.  Scores live in latent space:
    (q_nope @ W_uk) . c_kv; context is combined in latent space and expanded
    once through W_uv."""
    with jax.named_scope("mla"):
        return _mla_decode(p, x, cfg, pos, layer_cache)


def _mla_decode(p, x, cfg: ArchConfig, pos, layer_cache):
    b = x.shape[0]
    h, dn, dr, dv = cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    positions = jnp.broadcast_to(pos, (b, 1)).astype(jnp.int32)
    q_nope, q_pe = _q_proj(p, x, cfg, positions)          # (B,H,1,dn/dr)
    c_new, kpe_new = _latents(p, x, cfg, positions)       # (B,1,rank),(B,1,dr)

    c_kv = jax.lax.dynamic_update_slice(
        layer_cache["c_kv"], c_new.astype(layer_cache["c_kv"].dtype), (0, pos, 0))
    k_pe = jax.lax.dynamic_update_slice(
        layer_cache["k_pe"], kpe_new.astype(layer_cache["k_pe"].dtype), (0, pos, 0))
    kpos = jax.lax.dynamic_update_slice(
        layer_cache["kpos"], jnp.broadcast_to(pos, (b, 1)).astype(jnp.int32), (0, pos))

    q_lat = jnp.einsum("bhsd,rhd->bhsr", q_nope.astype(jnp.float32),
                       p["w_uk"].astype(jnp.float32))      # (B,H,1,rank)
    scale = softmax_scale(cfg)
    logits = (jnp.einsum("bhsr,btr->bhst", q_lat, c_kv.astype(jnp.float32))
              + jnp.einsum("bhsd,btd->bhst", q_pe.astype(jnp.float32),
                           k_pe.astype(jnp.float32))) * scale
    mask = (kpos[:, None, None, :] >= 0) & (kpos[:, None, None, :] <= pos)
    logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    ctx_lat = jnp.einsum("bhst,btr->bhsr", probs, c_kv.astype(jnp.float32))
    o = jnp.einsum("bhsr,rhd->bhsd", ctx_lat, p["w_uv"].astype(jnp.float32))
    o = o.astype(x.dtype).transpose(0, 2, 1, 3).reshape(b, 1, h * dv)
    return o @ p["w_o"], {"c_kv": c_kv, "k_pe": k_pe, "kpos": kpos}
