"""Plain float32 reference of a DeepSeek-V2 decoder (latent attention and
a share of the routed experts), and its seeded weights.

The architecture (DeepSeek-V2, arXiv:2405.04434; the configuration's keys
are those of the published ``config.json``): token embedding; per layer,
pre-RMSNorm multi-head latent attention, then pre-RMSNorm feed-forward,
each added to the residual; a final RMSNorm and an untied head.

- Attention, with no query compression (``q_lora_rank`` null):
  ``q = x W_q`` split per head into ``q_nope`` (``qk_nope_head_dim``) and
  ``q_pe`` (``qk_rope_head_dim``); ``c_kv = RMSNorm(x W_dkv)`` of width
  ``kv_lora_rank``; ``k_pe = RoPE(x W_kr)``, one rope key shared by every
  head; ``k_nope = c_kv W_uk`` and ``v = c_kv W_uv`` per head; causal
  softmax of ``(q_nope . k_nope + RoPE(q_pe) . k_pe) * scale``, with
  ``scale = (qk_nope + qk_rope) ** -0.5 * mscale(factor,
  mscale_all_dim) ** 2``; the heads' outputs through ``W_o``.
- RoPE with YaRN (``rope_scaling``): frequencies ``f_i = theta ** (-2i /
  d)``; ``low``/``high`` are the floor/ceil of ``d ln(L0 / (2 pi beta)) /
  (2 ln theta)`` for ``beta_fast``/``beta_slow``; ``m_i = 1 - clamp((i -
  low) / (high - low), 0, 1)``; YaRN's frequency is ``f_i / factor * (1 -
  m_i) + f_i m_i``; cos and sin are scaled by ``mscale(factor, mscale) /
  mscale(factor, mscale_all_dim)``, ``mscale(s, m) = 0.1 m ln s + 1``.
- Layer 0 (``first_k_dense_replace`` 1): a SwiGLU of width
  ``intermediate_size``.  The others: a softmax router over all
  ``n_routed_experts``, the greedy top ``num_experts_per_tok``, the gates
  renormalised only if ``norm_topk_prob``, times
  ``routed_scaling_factor``; each routed expert a SwiGLU of width
  ``moe_intermediate_size``; plus the shared experts, one SwiGLU of width
  ``n_shared_experts * moe_intermediate_size``.

The chip's share: the file's ``n_experts`` routed experts from
``expert_offset`` are held here, and only they add their part, as on a
device of an expert-parallel deployment; the router keeps its width.

Departure from the published code: RoPE rotates halves (pairs ``(i, i +
d/2)``), where the published model rotates interleaved pairs ``(2i, 2i +
1)``.  With seeded random weights the two are the same model up to a
fixed permutation of the rope columns of ``W_q`` and ``W_kr``.

Written from that description in straightforward ``jax.numpy``; it
imports nothing of the program.  Matrix products run at
``default_matmul_precision("highest")``.  The model is computed one layer
at a time, every routed expert held here over every token (no dispatch).

``make_weights`` makes the weights from a seed on the device in one
jitted call, in the tree and dtypes the program takes (the router in
float32).  ``control_weights`` rounds every weight to float8 (e4m3, one
scale per tensor, per layer and expert where stacked): the precision just
below the bfloat16 the configuration states.
"""
from __future__ import annotations

import json
import math
import zlib
from typing import Any, Dict, Mapping

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np

Params = Dict[str, Any]


def _attn_shapes(cfg, n=()) -> Params:
    d, h, r = cfg["hidden_size"], cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    return {"w_q": n + (d, h * (dn + dr)), "w_dkv": n + (d, r),
            "w_kr": n + (d, dr), "kv_norm": {"scale": n + (r,)},
            "w_uk": n + (r, h, dn), "w_uv": n + (r, h, dv),
            "w_o": n + (h * dv, d)}


def _mlp_shapes(d, f, n=()) -> Params:
    return {"w_gate": n + (d, f), "w_up": n + (d, f), "w_down": n + (f, d)}


def _shapes(cfg: Mapping[str, Any]) -> Params:
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    f, e_held = cfg["moe_intermediate_size"], cfg["n_experts"]
    n = (cfg["num_hidden_layers"] - 1,)
    return {
        "embed": {"embedding": (v, d), "unembed": (d, v)},
        "final_norm": {"scale": (d,)},
        "layer0": {"attn": _attn_shapes(cfg), "ln_attn": {"scale": (d,)},
                   "ln_mlp": {"scale": (d,)},
                   "mlp": _mlp_shapes(d, cfg["intermediate_size"])},
        "layers": {
            "attn": _attn_shapes(cfg, n),
            "ln_attn": {"scale": n + (d,)},
            "ln_mlp": {"scale": n + (d,)},
            "moe": {"router": n + (d, cfg["n_routed_experts"]),
                    **_mlp_shapes(d, f, n + (e_held,)),
                    "shared": _mlp_shapes(d, cfg["n_shared_experts"] * f, n)},
        },
    }


def _leaf(key, path, shape, dtype):
    k = jax.random.fold_in(
        key, zlib.crc32(jax.tree_util.keystr(path).encode()))
    z = jax.random.normal(k, shape, jnp.float32)
    name = path[-1].key
    if name == "scale":
        return (1.0 + 0.1 * z).astype(dtype)    # norm gains near one
    if name == "embedding":
        return (0.02 * z).astype(dtype)
    if name == "router":
        return z * shape[-2] ** -0.5            # float32, as the program
    if name in ("w_uk", "w_uv"):                # (.., rank, heads, dim)
        return (z * shape[-3] ** -0.5).astype(dtype)
    return (z * shape[-2] ** -0.5).astype(dtype)   # (.., fan_in, fan_out)


def make_weights(cfg: Mapping[str, Any], seed: int, device=None) -> Params:
    """Every weight from ``seed``, made on ``device`` in one jitted call."""
    dtype = jnp.dtype(cfg["param_dtype"])
    shapes = _shapes(cfg)
    is_shape = lambda s: isinstance(s, tuple)
    paths = jax.tree_util.tree_flatten_with_path(shapes, is_leaf=is_shape)[0]
    treedef = jax.tree_util.tree_structure(shapes, is_leaf=is_shape)

    def gen(key):
        return jax.tree_util.tree_unflatten(
            treedef, [_leaf(key, p, s, dtype) for p, s in paths])

    key = jax.random.fold_in(jax.random.key(seed % 2 ** 32), seed >> 32)
    out = None if device is None else jax.sharding.SingleDeviceSharding(
        device)
    return jax.jit(gen, out_shardings=out)(key)


def _fp8(w: np.ndarray) -> np.ndarray:
    """Round to float8 e4m3 with one scale, in numpy: a compiler that may
    keep excess precision cannot skip the rounding."""
    w = w.astype(np.float32)
    scale = np.float32(np.abs(w).max() / 448.0)
    return (w / scale).astype(ml_dtypes.float8_e4m3fn).astype(np.float32) \
        * scale


def _fp8_stacked(w: np.ndarray, lead: int) -> np.ndarray:
    """One scale per tensor of the ``lead`` stacking axes."""
    if lead == 0:
        return _fp8(w)
    return np.stack([_fp8_stacked(x, lead - 1) for x in w])


def control_weights(weights: Params) -> Params:
    """The weights rounded to float8 e4m3 with one scale per tensor (per
    layer, and per expert, of the stacked layer weights), placed where
    the weights are."""
    def one(path, w):
        keys = [p.key for p in path]
        lead = (keys[0] == "layers") + (keys[-2] == "moe"
                                        and keys[-1] != "router")
        h = np.asarray(w)
        q = _fp8_stacked(h, lead)
        return jax.device_put(q.astype(h.dtype), w.sharding)
    return jax.tree_util.tree_map_with_path(one, weights)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale


def yarn_mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def yarn_low_high(cfg: Mapping[str, Any]):
    """The rotary pairs between which YaRN ramps its frequencies."""
    ys, d = cfg["rope_scaling"], cfg["qk_rope_head_dim"]
    theta, l0 = cfg["rope_theta"], ys["original_max_position_embeddings"]
    dim = lambda beta: d * math.log(l0 / (2 * math.pi * beta)) \
        / (2 * math.log(theta))
    return (max(math.floor(dim(ys["beta_fast"])), 0),
            min(math.ceil(dim(ys["beta_slow"])), d - 1))


def rope_tables(cfg: Mapping[str, Any], s: int):
    """cos and sin (S, d/2) of positions 0..S-1, YaRN where configured."""
    d, theta = cfg["qk_rope_head_dim"], cfg["rope_theta"]
    freq = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
    amp = 1.0
    ys = cfg.get("rope_scaling")
    if ys:
        low, high = yarn_low_high(cfg)
        ramp = np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3),
                       0, 1)
        m = 1.0 - ramp
        freq = freq / ys["factor"] * (1 - m) + freq * m
        amp = yarn_mscale(ys["factor"], ys["mscale"]) \
            / yarn_mscale(ys["factor"], ys["mscale_all_dim"])
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] \
        * jnp.asarray(freq, jnp.float32)
    return jnp.cos(ang) * amp, jnp.sin(ang) * amp


def rope(x, cos, sin):
    """Rotate-half RoPE.  x: (B, S, H, D); cos, sin: (S, D/2)."""
    d = x.shape[-1]
    cos, sin = cos[None, :, None], sin[None, :, None]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def softmax_scale(cfg: Mapping[str, Any]) -> float:
    scale = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    ys = cfg.get("rope_scaling")
    if ys and ys.get("mscale_all_dim"):
        scale *= yarn_mscale(ys["factor"], ys["mscale_all_dim"]) ** 2
    return scale


def swiglu(m: Params, x):
    f32 = lambda a: a.astype(jnp.float32)
    return (jax.nn.silu(x @ f32(m["w_gate"])) * (x @ f32(m["w_up"]))) \
        @ f32(m["w_down"])


def attention(cfg: Mapping[str, Any], a: Params, x):
    """Causal latent attention over the whole sequence.  x: (B, S, D)."""
    b, s, _ = x.shape
    h, dn = cfg["num_attention_heads"], cfg["qk_nope_head_dim"]
    f32 = lambda t: t.astype(jnp.float32)
    cos, sin = rope_tables(cfg, s)
    q = (x @ f32(a["w_q"])).reshape(b, s, h, -1)
    q_nope, q_pe = q[..., :dn], rope(q[..., dn:], cos, sin)
    c = rms_norm(x @ f32(a["w_dkv"]), f32(a["kv_norm"]["scale"]),
                 cfg["rms_norm_eps"])
    k_pe = rope((x @ f32(a["w_kr"]))[:, :, None, :], cos, sin)[:, :, 0]
    k_nope = jnp.einsum("bsr,rhd->bshd", c, f32(a["w_uk"]))
    v = jnp.einsum("bsr,rhd->bshd", c, f32(a["w_uv"]))
    scores = (jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope)
              + jnp.einsum("bqhd,bkd->bhqk", q_pe, k_pe)) \
        * softmax_scale(cfg)
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    scores = jnp.where(causal, scores, -jnp.inf)
    out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
    return out.reshape(b, s, -1) @ f32(a["w_o"])


def experts(cfg: Mapping[str, Any], m: Params, x):
    """The routed experts held here (every one over every token, weighted
    by its gate where the router chose it) plus the shared experts."""
    probs = jax.nn.softmax(x @ m["router"].astype(jnp.float32), -1)
    gates, ids = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        gates = gates / gates.sum(-1, keepdims=True)
    gates = gates * cfg["routed_scaling_factor"]
    y = swiglu(m["shared"], x)
    for i in range(cfg["n_experts"]):
        e = cfg["expert_offset"] + i
        gate = jnp.sum(jnp.where(ids == e, gates, 0.0), -1, keepdims=True)
        y = y + gate * swiglu({k: m[k][i] for k in ("w_gate", "w_up",
                                                     "w_down")}, x)
    return y


def layer(cfg: Mapping[str, Any], w: Params, x, dense: bool):
    """One decoder layer over the whole sequence.  x: (B, S, D) float32."""
    eps = cfg["rms_norm_eps"]
    f32 = lambda a: a.astype(jnp.float32)
    x = x + attention(cfg, w["attn"],
                      rms_norm(x, f32(w["ln_attn"]["scale"]), eps))
    hn = rms_norm(x, f32(w["ln_mlp"]["scale"]), eps)
    return x + (swiglu(w["mlp"], hn) if dense else experts(cfg, w["moe"], hn))


_JITS: Dict[str, Any] = {}


def _jitted(cfg: Mapping[str, Any]):
    """The layer and head programs of one configuration, jitted once."""
    key = json.dumps(dict(cfg), sort_keys=True, default=str)
    if key not in _JITS:
        f32 = lambda a: a.astype(jnp.float32)
        _JITS[key] = (
            jax.jit(lambda w, x: layer(cfg, w, x, dense=True)),
            jax.jit(lambda w, x: layer(cfg, w, x, dense=False)),
            jax.jit(lambda w, x: rms_norm(
                x, f32(w["final_norm"]["scale"]), cfg["rms_norm_eps"])
                @ f32(w["embed"]["unembed"])))
    return _JITS[key]


def logits(cfg: Mapping[str, Any], weights: Params, tokens) -> jax.Array:
    """Float32 logits (B, S, V) of a teacher-forced forward, one layer at
    a time."""
    dense, moe, head = _jitted(cfg)
    with jax.default_matmul_precision("highest"):
        x = weights["embed"]["embedding"][jnp.asarray(tokens)].astype(
            jnp.float32)
        x = dense(weights["layer0"], x)
        for i in range(cfg["num_hidden_layers"] - 1):
            x = moe(jax.tree.map(lambda a: a[i], weights["layers"]), x)
        return head(weights, x)


def served_gaps(cfg: Mapping[str, Any], weights: Params, prompt,
                served) -> np.ndarray:
    """For one request: at each position that produced a served token,
    how far that token's reference logit lies below the reference's best
    (0 where the server chose the reference's greedy token)."""
    seq = np.concatenate([np.asarray(prompt), np.asarray(served)[:-1]])
    lg = logits(cfg, weights, seq[None].astype(np.int32))[0]
    at = lg[len(prompt) - 1:]                      # one row per served token
    got = jnp.take_along_axis(at, jnp.asarray(served)[:, None], -1)[:, 0]
    return np.asarray(jnp.max(at, -1) - got)


def control_gaps(cfg: Mapping[str, Any], weights: Params, ctrl: Params,
                 prompt, served) -> np.ndarray:
    """The control read at the same prompt and served tokens: at each
    position, how far the token that the float8 weights put first lies
    below the float32 reference's best."""
    seq = np.concatenate([np.asarray(prompt), np.asarray(served)[:-1]])
    toks = seq[None].astype(np.int32)
    ref = logits(cfg, weights, toks)[0][len(prompt) - 1:]
    low = logits(cfg, ctrl, toks)[0][len(prompt) - 1:]
    pick = jnp.argmax(low, -1)
    got = jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]
    return np.asarray(jnp.max(ref, -1) - got)
