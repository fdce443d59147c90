"""Plain float32 reference of a dense decoder LM, and its seeded weights.

The architecture (h2o-danube-1.8b, arXiv:2401.16818; Llama/Mistral
style): token embedding; per layer, pre-RMSNorm (the configuration's
``norm_eps``, learned scale)
grouped-query attention with rotate-half RoPE over the whole head and a
causal sliding window, then pre-RMSNorm SwiGLU MLP, each added to the
residual; a final RMSNorm and an untied unembedding.  Written from the
published description in straightforward ``jax.numpy``; it imports
nothing of the program.  Matrix products run at
``default_matmul_precision("highest")``, so float32 means float32 on a
TPU too.  The model is computed one layer at a time.

``make_weights`` makes the weights from a seed on the device in one
jitted call, in the dtype the configuration serves them in.  The benchmark
hands the same weights to the program and to this reference.

``control_weights`` rounds every weight to float8 (e4m3, one scale per
tensor): the precision just below the bfloat16 the configuration states.
"""
from __future__ import annotations

import json
import zlib
from typing import Any, Dict, Mapping

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np

Params = Dict[str, Any]


def _shapes(cfg: Mapping[str, Any]) -> Params:
    d, h, hkv = cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"]
    dh, ff, v, n = cfg["d_head"], cfg["d_ff"], cfg["vocab"], cfg["n_layers"]
    return {
        "embed": {"embedding": (v, d), "unembed": (d, v)},
        "final_norm": {"scale": (d,)},
        "layers": {
            "attn": {"w_q": (n, d, h * dh), "w_k": (n, d, hkv * dh),
                     "w_v": (n, d, hkv * dh), "w_o": (n, h * dh, d)},
            "ln_attn": {"scale": (n, d)},
            "ln_mlp": {"scale": (n, d)},
            "mlp": {"w_gate": (n, d, ff), "w_up": (n, d, ff),
                    "w_down": (n, ff, d)},
        },
    }


def _leaf(key, path, shape, dtype):
    k = jax.random.fold_in(
        key, zlib.crc32(jax.tree_util.keystr(path).encode()))
    z = jax.random.normal(k, shape, jnp.float32)
    name = path[-1].key
    if name == "scale":
        w = 1.0 + 0.1 * z                       # norm gains near one
    elif name == "embedding":
        w = 0.02 * z
    else:                                       # (.., fan_in, fan_out)
        w = z * shape[-2] ** -0.5
    return w.astype(dtype)


def make_weights(cfg: Mapping[str, Any], seed: int, device=None) -> Params:
    """Every weight from ``seed``, made on ``device`` in one jitted call."""
    dtype = jnp.dtype(cfg["param_dtype"])
    shapes = _shapes(cfg)
    paths = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda s: isinstance(s, tuple))[0]

    def gen(key):
        leaves = [_leaf(key, p, s, dtype) for p, s in paths]
        return jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(
                shapes, is_leaf=lambda s: isinstance(s, tuple)), leaves)

    key = jax.random.fold_in(jax.random.key(seed % 2 ** 32), seed >> 32)
    out = None if device is None else jax.sharding.SingleDeviceSharding(
        device)
    return jax.jit(gen, out_shardings=out)(key)


def _fp8(w: np.ndarray) -> np.ndarray:
    """Round to float8 e4m3 with one scale per tensor, in numpy: a
    compiler that may keep excess precision cannot skip the rounding."""
    w = w.astype(np.float32)
    scale = np.float32(np.abs(w).max() / 448.0)
    return (w / scale).astype(ml_dtypes.float8_e4m3fn).astype(np.float32) \
        * scale


def control_weights(weights: Params) -> Params:
    """The weights rounded to float8 e4m3 with one scale per tensor (per
    layer for the stacked layer weights), placed where the weights are."""
    def one(path, w):
        h = np.asarray(w)
        if path[0].key == "layers":             # stacked: a scale per layer
            q = np.stack([_fp8(x) for x in h])
        else:
            q = _fp8(h)
        return jax.device_put(q.astype(h.dtype), w.sharding)
    return jax.tree_util.tree_map_with_path(one, weights)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale


def rope(x, theta: float):
    """Rotate-half RoPE.  x: (B, S, H, D) float32, positions 0..S-1."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def layer(cfg: Mapping[str, Any], w: Params, x: jax.Array) -> jax.Array:
    """One decoder layer over the whole sequence.  x: (B, S, D) float32."""
    b, s, _ = x.shape
    h, hkv, dh = cfg["n_heads"], cfg["n_kv_heads"], cfg["d_head"]
    eps = cfg["norm_eps"]
    f32 = lambda a: a.astype(jnp.float32)
    a = w["attn"]
    hn = rms_norm(x, f32(w["ln_attn"]["scale"]), eps)
    q = rope((hn @ f32(a["w_q"])).reshape(b, s, h, dh), cfg["rope_theta"])
    k = rope((hn @ f32(a["w_k"])).reshape(b, s, hkv, dh), cfg["rope_theta"])
    v = (hn @ f32(a["w_v"])).reshape(b, s, hkv, dh)
    k = jnp.repeat(k, h // hkv, axis=2)
    v = jnp.repeat(v, h // hkv, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * dh ** -0.5
    qi = jnp.arange(s)[:, None]
    ki = jnp.arange(s)[None, :]
    allowed = (ki <= qi) & (ki > qi - cfg["window"])
    scores = jnp.where(allowed, scores, -jnp.inf)
    att = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
    x = x + att.reshape(b, s, h * dh) @ f32(a["w_o"])
    m = w["mlp"]
    hn = rms_norm(x, f32(w["ln_mlp"]["scale"]), eps)
    x = x + (jax.nn.silu(hn @ f32(m["w_gate"])) * (hn @ f32(m["w_up"]))) \
        @ f32(m["w_down"])
    return x


_JITS: Dict[str, Any] = {}


def _jitted(cfg: Mapping[str, Any]):
    """The layer and head programs of one configuration, jitted once."""
    key = json.dumps(dict(cfg), sort_keys=True, default=str)
    if key not in _JITS:
        f32 = lambda a: a.astype(jnp.float32)
        _JITS[key] = (
            jax.jit(lambda w, x: layer(cfg, w, x)),
            jax.jit(lambda w, x: rms_norm(
                x, f32(w["final_norm"]["scale"]), cfg["norm_eps"])
                @ f32(w["embed"]["unembed"])))
    return _JITS[key]


def logits(cfg: Mapping[str, Any], weights: Params, tokens) -> jax.Array:
    """Float32 logits (B, S, V) of a teacher-forced forward, one layer at
    a time."""
    lay, head = _jitted(cfg)
    with jax.default_matmul_precision("highest"):
        x = weights["embed"]["embedding"][jnp.asarray(tokens)].astype(
            jnp.float32)
        for i in range(cfg["n_layers"]):
            x = lay(jax.tree.map(lambda a: a[i], weights["layers"]), x)
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
