"""Operations and bytes of a DeepSeek-V2 decoder (latent attention, a
share of the routed experts), from shapes and the routing counters.

The configuration's keys are the published ``config.json``'s; the file's
``n_experts`` routed experts are held on the chip, ``n_routed_experts``
is the router's width.  Layer 0 is dense, the others hold experts.  Like
:mod:`chipbench.costs` these count the algorithm, never a compiled
program: how many (token, expert) pairs a row sends to the held experts
(``held_per_row``, per expert layer) comes from the program's counters,
not from ``cost_analysis``.
"""
from __future__ import annotations

from typing import Any, Mapping

from chipbench.costs import Work

ROUTER_BYTES = 4                    # the router is float32, as the program's


def _dims(c: Mapping[str, Any]):
    return (c["hidden_size"], c["num_attention_heads"], c["kv_lora_rank"],
            c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"])


def attn_matmul_params(c: Mapping[str, Any]) -> int:
    """W_q, W_dkv, W_kr, W_uk, W_uv and W_o of one layer."""
    d, h, r, dn, dr, dv = _dims(c)
    return d * h * (dn + dr) + d * r + d * dr + r * h * (dn + dv) + h * dv * d


def expert_params(c: Mapping[str, Any]) -> int:
    """One routed expert's SwiGLU: 3 d f."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def moe_layers(c: Mapping[str, Any]) -> int:
    return c["num_hidden_layers"] - 1


def held_expert_params(c: Mapping[str, Any]) -> int:
    """Every routed expert held here, over all expert layers."""
    return moe_layers(c) * c["n_experts"] * expert_params(c)


def total_params(c: Mapping[str, Any]) -> int:
    """Every weight held here: embedding and head, the final norm, per
    layer attention, the latent norm and two norms, layer 0's SwiGLU, and
    per expert layer the router, the held experts and the shared experts."""
    d, v = c["hidden_size"], c["vocab_size"]
    per_layer = attn_matmul_params(c) + c["kv_lora_rank"] + 2 * d
    moe = (d * c["n_routed_experts"] + c["n_experts"] * expert_params(c)
           + c["n_shared_experts"] * expert_params(c))
    return (c["num_hidden_layers"] * per_layer
            + 3 * d * c["intermediate_size"] + moe_layers(c) * moe
            + 2 * v * d + d)


def weight_bytes(c: Mapping[str, Any]) -> int:
    """Bytes of every weight but the embedding table (a row lookup), the
    router in float32."""
    d, v, pb = c["hidden_size"], c["vocab_size"], c["param_bytes"]
    router = moe_layers(c) * d * c["n_routed_experts"]
    return (total_params(c) - v * d - router) * pb + router * ROUTER_BYTES


def active_params(c: Mapping[str, Any], held_per_row: float) -> float:
    """N of the 2 N FLOPs a token costs here: the matmul weights it
    multiplies (attention, layer 0's SwiGLU, the router, the shared
    experts, ``held_per_row`` held experts a expert layer, the head)."""
    d = c["hidden_size"]
    per_moe = (d * c["n_routed_experts"]
               + (c["n_shared_experts"] + held_per_row) * expert_params(c))
    return (c["num_hidden_layers"] * attn_matmul_params(c)
            + 3 * d * c["intermediate_size"] + moe_layers(c) * per_moe
            + d * c["vocab_size"])


def cache_bytes_per_token(c: Mapping[str, Any]) -> int:
    """The latent cache of one position in every layer: ``c_kv`` and the
    rope key in the cache dtype, and its int32 position."""
    return c["num_hidden_layers"] * (
        (c["kv_lora_rank"] + c["qk_rope_head_dim"]) * c["cache_bytes"] + 4)


def decode_step(c: Mapping[str, Any], batch: int, pos: int,
                held_per_row: float) -> Work:
    """One decode step of ``batch`` rows that all sit at position ``pos``
    (the token written this step): every weight held here read once, one
    embedding row per row, each row's live latent cache (positions
    0..pos-1) read and its new entry written, float32 logits out; 2 N
    FLOPs per row plus the absorbed attention over pos + 1 keys (scores
    against ``c_kv`` and the rope key, the context in latent space)."""
    d, h, r, _, dr, _ = _dims(c)
    nbytes = (weight_bytes(c) + batch * d * c["param_bytes"]
              + batch * (pos + 1) * cache_bytes_per_token(c)
              + batch * c["vocab_size"] * 4)
    flops = (batch * 2.0 * active_params(c, held_per_row)
             + batch * c["num_hidden_layers"] * 2.0 * h * (2 * r + dr)
             * (pos + 1))
    return Work(flops, nbytes)


def token_flops(c: Mapping[str, Any], held_per_row: float) -> float:
    """Model FLOPs of one processed token, prefill or decode: 2 N."""
    return 2.0 * active_params(c, held_per_row)


def held_experts_step(c: Mapping[str, Any], held_assignments: float) -> Work:
    """The held experts' grouped matmuls over one call: their weights read
    once, each held (token, expert) pair's row in and out, and 6 d f
    FLOPs a pair (three matrices of d f, two FLOPs a multiply-add)."""
    d, pb = c["hidden_size"], c["param_bytes"]
    return Work(2.0 * expert_params(c) * held_assignments,
                held_expert_params(c) * pb + held_assignments * 2 * d * pb)


def held_per_row(counters: Mapping[str, Any]):
    """Held (token, expert) pairs a token row sends per expert layer, from
    the window's routing counters; None where none were counted."""
    rows = counters.get("moe_rows", 0)
    if not rows:
        return None
    return counters["moe_held_assignments"] / rows
