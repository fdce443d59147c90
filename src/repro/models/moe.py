"""Mixture-of-Experts layer: a dropless share of the routed experts.

The router keeps its full width: a float32 softmax over all ``n_experts``
and a greedy top-k, the gates renormalised over the k only where
``cfg.norm_topk_prob`` says so.  This device holds the routed experts
``expert_offset .. expert_offset + n_held - 1`` (expert parallelism: the
others live on other devices).  The (token, expert) pairs that fall on held
experts are sorted by expert and go through one grouped matmul per
projection over the held experts' stacks (``jax.lax.ragged_dot``; on a TPU
a Mosaic grouped-matmul kernel whose ops are named ``ragged-dot-*``), are
put back in token order and combined with their gates.  Pairs routed to
experts held elsewhere add nothing here, and no token is ever dropped:
every held pair is computed, whatever the routing.  The shared experts
(one SwiGLU of width ``n_shared_experts * d_ff``) are added once.  The
same code runs at prefill and at decode.

Besides the output, :func:`apply_moe` returns the Switch load-balance loss
(training) and per-row routing counts (serving; see :data:`COUNTS`).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .common import ArchConfig, KeyGen, dense_init
from .layers import init_mlp, apply_mlp

#: the routing counts :func:`apply_moe` returns per batch row, in order:
#: (token, expert) pairs routed over all experts, the pairs computed on
#: this device, token rows through the layer
COUNTS = ("assignments", "held_assignments", "rows")


def init_moe(key, cfg: ArchConfig, d_ff: Optional[int] = None) -> Dict[str, Any]:
    kg = KeyGen(key)
    d, f, e = cfg.d_model, d_ff or cfg.d_ff, cfg.n_held
    p = {
        "router": dense_init(kg("router"), (d, cfg.n_experts), jnp.float32),
        "w_gate": dense_init(kg("w_gate"), (e, d, f), cfg.pdtype),
        "w_up": dense_init(kg("w_up"), (e, d, f), cfg.pdtype),
        "w_down": dense_init(kg("w_down"), (e, f, d), cfg.pdtype),
    }
    if cfg.n_shared_experts:
        p["shared"] = init_mlp(kg("shared"), cfg, d_ff=f * cfg.n_shared_experts)
    return p


def route(router: jax.Array, x: jax.Array, cfg: ArchConfig):
    """x: (T, D) -> softmax probabilities (T, E), gates (T, K) f32 and
    expert ids (T, K)."""
    probs = jax.nn.softmax(x.astype(jnp.float32) @ router, axis=-1)
    gates, eids = jax.lax.top_k(probs, cfg.top_k)
    if cfg.norm_topk_prob:
        gates = gates / jnp.maximum(jnp.sum(gates, -1, keepdims=True), 1e-20)
    return probs, gates, eids


def expert_share(p: Dict[str, Any], x: jax.Array, gates: jax.Array,
                 eids: jax.Array, cfg: ArchConfig) -> Tuple[jax.Array, jax.Array]:
    """The held experts' part of the routed output.  x: (T, D); gates and
    eids (T, K).  Returns (T, D) in float32 and the held mask (T, K)."""
    t, d = x.shape
    k, e = cfg.top_k, cfg.n_held
    local = eids.reshape(t * k) - cfg.expert_offset
    held = (local >= 0) & (local < e)
    group = jnp.where(held, local, e)              # absent experts sort last
    order = jnp.argsort(group, stable=True)        # pairs in expert order
    sizes = jnp.bincount(group, length=e + 1)[:e].astype(jnp.int32)
    rows = jnp.take(x, order // k, axis=0)         # (T*K, D)
    h = jax.nn.silu(jax.lax.ragged_dot(rows, p["w_gate"], sizes)) \
        * jax.lax.ragged_dot(rows, p["w_up"], sizes)
    out = jax.lax.ragged_dot(h, p["w_down"], sizes)   # (T*K, D)
    back = jnp.zeros((t * k,), jnp.int32).at[order].set(
        jnp.arange(t * k, dtype=jnp.int32))        # the inverse permutation
    out = jnp.take(out, back, axis=0).astype(jnp.float32)
    # rows past the held groups are no expert's: select, never multiply
    w = jnp.where(held, gates.reshape(t * k), 0.0)[:, None]
    out = jnp.where(held[:, None], out, 0.0) * w
    return out.reshape(t, k, d).sum(axis=1), held.reshape(t, k)


def apply_moe(p: Dict[str, Any], x: jax.Array, cfg: ArchConfig
              ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """x: (B, S, D) -> (B, S, D), aux: ``moe_aux_loss`` (the Switch
    load-balance loss, over all experts) and ``moe_counts`` (B, 3) int32,
    :data:`COUNTS` for each batch row."""
    b, s, d = x.shape
    x2 = x.reshape(b * s, d)
    with jax.named_scope("moe.route"):
        probs, gates, eids = route(p["router"], x2, cfg)
    with jax.named_scope("moe.experts"):
        y, held = expert_share(p, x2.astype(cfg.adtype), gates, eids, cfg)
    y = y.astype(cfg.adtype).reshape(b, s, d)
    if cfg.n_shared_experts:
        with jax.named_scope("moe.shared"):
            y = y + apply_mlp(p["shared"], x, cfg)

    e = cfg.n_experts
    frac_tokens = jnp.mean(jax.nn.one_hot(eids[:, 0], e, dtype=jnp.float32), 0)
    frac_probs = jnp.mean(probs, axis=0)
    aux_loss = e * jnp.sum(frac_tokens * frac_probs) * cfg.router_aux_weight
    held_rows = jnp.sum(held.reshape(b, s * cfg.top_k), axis=1, dtype=jnp.int32)
    counts = jnp.stack([jnp.full((b,), s * cfg.top_k, jnp.int32), held_rows,
                        jnp.full((b,), s, jnp.int32)], axis=1)
    return y, {"moe_aux_loss": aux_loss, "moe_counts": counts}
