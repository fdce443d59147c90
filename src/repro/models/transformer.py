"""Decoder-only LM covering the dense, MoE, MLA and VLM-prefix families.

One scan-over-layers body (stacked parameters, remat-wrapped) serves
qwen3 / minitron / h2o-danube / qwen2 (dense), granite (MoE),
deepseek-v2-lite (MLA + MoE + dense layer 0) and internvl2 (patch-prefix).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from . import layers as L
from . import mla as MLA
from . import moe as MOE
from .common import ArchConfig, KeyGen, MODEL, BATCH_AXES, Rules, constrain, scan_layers


def _stacked(rules: Rules) -> Rules:
    """Prepend the layer-stack dim (replicated) to each spec."""
    return [(pat, P(None, *spec)) for pat, spec in rules]


class DecoderLM:
    """Functional model object: params are plain pytrees, methods are pure."""

    def __init__(self, cfg: ArchConfig):
        self.cfg = cfg

    # ------------------------------------------------------------- params
    def _init_layer(self, key):
        cfg = self.cfg
        kg = KeyGen(key)
        p: Dict[str, Any] = {"ln_attn": L.init_norm(cfg), "ln_mlp": L.init_norm(cfg)}
        if cfg.mla:
            p["attn"] = MLA.init_mla(kg("attn"), cfg)
        else:
            p["attn"] = L.init_attention(kg("attn"), cfg)
        if cfg.n_experts:
            p["moe"] = MOE.init_moe(kg("moe"), cfg)
        else:
            p["mlp"] = L.init_mlp(kg("mlp"), cfg)
        return p

    def init_params(self, rng) -> Dict[str, Any]:
        cfg = self.cfg
        kg = KeyGen(rng)
        n_scan = cfg.n_layers - (1 if cfg.first_dense_ff else 0)
        keys = jax.random.split(kg("layers"), n_scan)
        params: Dict[str, Any] = {
            "embed": L.init_embed(kg("embed"), cfg),
            "layers": jax.vmap(self._init_layer)(keys),
            "final_norm": L.init_norm(cfg),
        }
        if cfg.first_dense_ff:
            # deepseek: layer 0 is a dense-FFN layer outside the scan
            dense_cfg = cfg.scaled(n_experts=0)
            kg0 = KeyGen(kg("layer0"))
            params["layer0"] = {
                "ln_attn": L.init_norm(cfg), "ln_mlp": L.init_norm(cfg),
                "attn": MLA.init_mla(kg0("attn"), cfg) if cfg.mla
                        else L.init_attention(kg0("attn"), cfg),
                "mlp": L.init_mlp(kg0("mlp"), dense_cfg, d_ff=cfg.first_dense_ff),
            }
        return params

    # ------------------------------------------------------------ forward
    def _layer_fwd(self, p, x, positions, *, use_moe: bool):
        cfg = self.cfg
        h = L.apply_norm(p["ln_attn"], x, cfg)
        if cfg.mla:
            attn = MLA.mla_full(p["attn"], h, cfg, positions)
        else:
            attn = L.attention_full(p["attn"], h, cfg, positions, causal=cfg.causal)
        x = x + attn
        h = L.apply_norm(p["ln_mlp"], x, cfg)
        aux = {}
        if use_moe:
            y, aux = MOE.apply_moe(p["moe"], h, cfg)
        else:
            y = L.apply_mlp(p["mlp"], h, cfg)
        x = x + y
        if cfg.opt_seq_parallel:
            x = constrain(x, BATCH_AXES, MODEL, None)
        else:
            x = constrain(x, BATCH_AXES, None, None)
        return x, aux

    def hidden_states(self, params, tokens: jax.Array,
                      prefix_embeds: Optional[jax.Array] = None) -> Tuple[jax.Array, Dict]:
        """Full-sequence forward to final hidden states.
        tokens: (B, S_text); prefix_embeds: (B, P, D) for VLM."""
        cfg = self.cfg
        x = L.embed_tokens(params["embed"], tokens, cfg)
        if prefix_embeds is not None:
            x = jnp.concatenate([prefix_embeds.astype(cfg.adtype), x], axis=1)
        b, s, _ = x.shape
        if cfg.opt_seq_parallel:
            x = constrain(x, BATCH_AXES, MODEL, None)
        else:
            x = constrain(x, BATCH_AXES, None, None)
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))

        aux_sums = {"moe_aux_loss": jnp.zeros((), jnp.float32)}
        if cfg.first_dense_ff:
            x, _ = self._layer_fwd(params["layer0"], x, positions, use_moe=False)

        use_moe = bool(cfg.n_experts)

        def body(carry, layer_params):
            xc, acc = carry
            xo, aux = self._layer_fwd(layer_params, xc, positions, use_moe=use_moe)
            if use_moe:
                acc = {k: acc[k] + aux[k] for k in acc}
            return (xo, acc), ()

        body_fn = jax.checkpoint(body) if cfg.remat else body
        (x, aux_sums), _ = scan_layers(body_fn, (x, aux_sums), params["layers"],
                                       unroll=cfg.unroll_layers)
        x = L.apply_norm(params["final_norm"], x, cfg)
        n_moe = max(1, cfg.n_layers - (1 if cfg.first_dense_ff else 0))
        aux = {k: v / n_moe for k, v in aux_sums.items()} if use_moe else {}
        return x, aux

    def logits(self, params, tokens, prefix_embeds=None):
        x, aux = self.hidden_states(params, tokens, prefix_embeds)
        return L.logits_from_hidden(params["embed"], x, self.cfg), aux

    # ------------------------------------------------------------- train
    def loss_fn(self, params, batch: Dict[str, jax.Array]):
        """batch: tokens (B,S), labels (B,S) [, patch_embeds (B,P,D)]."""
        cfg = self.cfg
        prefix = batch.get("patch_embeds")
        logits, aux = self.logits(params, batch["tokens"], prefix)
        labels = batch["labels"]
        if prefix is not None:
            logits = logits[:, prefix.shape[1]:]  # loss over text positions only
        loss = L.cross_entropy(logits, labels, batch.get("loss_mask"))
        total = loss + aux.get("moe_aux_loss", 0.0)
        metrics = {"loss": loss, **aux}
        return total, metrics

    # ------------------------------------------------------------- serve
    def init_cache(self, batch: int, max_len: int) -> Dict[str, Any]:
        """KV (or MLA latent) cache per layer; MoE models add
        ``moe_counts`` (batch, 3) int32: each row's routing counts
        (:data:`repro.models.moe.COUNTS`) summed over the expert layers
        since its prefill, kept with the cache so that they accumulate on
        the device step after step."""
        cfg = self.cfg
        n_scan = cfg.n_layers - (1 if cfg.first_dense_ff else 0)
        mk = (MLA.init_mla_cache if cfg.mla else L.init_kv_cache)
        cache = {"scan": mk(cfg, n_scan, batch, max_len, cfg.adtype)}
        if cfg.first_dense_ff:
            cache["layer0"] = jax.tree.map(lambda a: a[0], mk(cfg, 1, batch, max_len, cfg.adtype))
        if cfg.n_experts:
            cache["moe_counts"] = jnp.zeros((batch, len(MOE.COUNTS)), jnp.int32)
        return cache

    def _layer_decode(self, p, x, pos, lcache, *, use_moe: bool):
        cfg = self.cfg
        h = L.apply_norm(p["ln_attn"], x, cfg)
        if cfg.mla:
            attn, lcache = MLA.mla_decode(p["attn"], h, cfg, pos, lcache)
        else:
            attn, lcache = L.attention_decode(p["attn"], h, cfg, pos, lcache)
        x = x + attn
        h = L.apply_norm(p["ln_mlp"], x, cfg)
        if use_moe:
            y, aux = MOE.apply_moe(p["moe"], h, cfg)
            return x + y, lcache, aux["moe_counts"]
        y = L.apply_mlp(p["mlp"], h, cfg)
        return x + y, lcache, None

    def decode_step(self, params, token: jax.Array, pos, cache):
        """token: (B, 1) int32; pos: scalar int32 (position of this token).
        Returns (logits (B,1,V) f32, updated cache)."""
        cfg = self.cfg
        x = L.embed_tokens(params["embed"], token, cfg)
        use_moe = bool(cfg.n_experts)
        if cfg.first_dense_ff:
            x, l0, _ = self._layer_decode(params["layer0"], x, pos,
                                          cache["layer0"], use_moe=False)
        else:
            l0 = cache.get("layer0")

        def body(xc, xs):
            layer_params, lcache = xs
            xo, lcache, counts = self._layer_decode(layer_params, xc, pos, lcache,
                                                    use_moe=use_moe)
            return xo, (lcache, counts)

        x, ys = scan_layers(body, x, (params["layers"], cache["scan"]),
                            unroll=cfg.unroll_layers)
        x = L.apply_norm(params["final_norm"], x, cfg)
        logits = L.logits_from_hidden(params["embed"], x, cfg)
        return logits, self._cache_out(cache, ys, l0)

    @staticmethod
    def _cache_out(cache, ys, l0):
        """The cache a prefill or decode step hands back from the scan's
        ``(per-layer cache, routing counts or None)``: for MoE models the
        call's counts (summed over layers) added to the rows' running
        counts."""
        new_scan, counts = ys
        new_cache = {"scan": new_scan}
        if l0 is not None:
            new_cache["layer0"] = l0
        if counts is not None:
            new_cache["moe_counts"] = cache["moe_counts"] + jnp.sum(counts, axis=0)
        return new_cache

    def prefill(self, params, tokens: jax.Array, cache,
                prefix_embeds: Optional[jax.Array] = None):
        """Fill the cache with a full prompt; returns (last-token logits, cache)."""
        cfg = self.cfg
        x = L.embed_tokens(params["embed"], tokens, cfg)
        if prefix_embeds is not None:
            x = jnp.concatenate([prefix_embeds.astype(cfg.adtype), x], axis=1)
        b, s, _ = x.shape
        if cfg.opt_seq_parallel:
            x = constrain(x, BATCH_AXES, MODEL, None)
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        use_moe = bool(cfg.n_experts)
        if cfg.first_dense_ff:
            p0 = params["layer0"]
            h = L.apply_norm(p0["ln_attn"], x, cfg)
            fn = MLA.mla_prefill if cfg.mla else L.prefill_kv
            attn, l0 = fn(p0["attn"], h, cfg, positions, cache["layer0"])
            x = x + attn
            h = L.apply_norm(p0["ln_mlp"], x, cfg)
            x = x + L.apply_mlp(p0["mlp"], h, cfg)
        else:
            l0 = cache.get("layer0")

        def body(xc, xs):
            layer_params, lcache = xs
            h = L.apply_norm(layer_params["ln_attn"], xc, cfg)
            fn = MLA.mla_prefill if cfg.mla else L.prefill_kv
            attn, lcache = fn(layer_params["attn"], h, cfg, positions, lcache)
            xc = xc + attn
            h = L.apply_norm(layer_params["ln_mlp"], xc, cfg)
            if use_moe:
                y, aux = MOE.apply_moe(layer_params["moe"], h, cfg)
                return xc + y, (lcache, aux["moe_counts"])
            y = L.apply_mlp(layer_params["mlp"], h, cfg)
            return xc + y, (lcache, None)

        body_fn = jax.checkpoint(body) if cfg.remat else body
        x, ys = scan_layers(body_fn, x, (params["layers"], cache["scan"]),
                            unroll=cfg.unroll_layers)
        x = L.apply_norm(params["final_norm"], x[:, -1:], cfg)
        logits = L.logits_from_hidden(params["embed"], x, cfg)
        return logits, self._cache_out(cache, ys, l0)

    # ---------------------------------------------------------- sharding
    def partition_rules(self) -> Rules:
        base: Rules = [
            (r"embed.*embedding", P(MODEL, None)),
            (r"embed.*unembed", P(None, MODEL)),
        ]
        layer: Rules = [
            # MLA
            (r"attn.*w_uk|attn.*w_uv", P(None, MODEL, None)),
            (r"attn.*w_dkv|attn.*w_kr", P()),
            # GQA + MLA share w_q/w_o shapes
            (r"attn.*w_q|attn.*w_k|attn.*w_v", P(None, MODEL)),
            (r"attn.*b_q|attn.*b_k|attn.*b_v", P(MODEL)),
            (r"attn.*w_o", P(MODEL, None)),
            # MoE: experts over model (EP)
            (r"moe.*router", P()),
            (r"moe.*w_gate|moe.*w_up|moe.*w_down", P(MODEL, None, None)),
            (r"moe.*shared.*w_gate|moe.*shared.*w_up", P(None, MODEL)),
            (r"moe.*shared.*w_down", P(MODEL, None)),
            # dense MLP
            (r"mlp.*w_gate|mlp.*w_up", P(None, MODEL)),
            (r"mlp.*w_down", P(MODEL, None)),
            (r"mlp.*b_up", P(MODEL)),
        ]
        # shared-expert rules must win over the generic expert rules
        layer.sort(key=lambda r: 0 if "shared" in r[0] else 1)
        rules = base + [(rf"layers.*(?:{pat})", P(None, *spec)) for pat, spec in layer]
        rules += [(rf"layer0.*(?:{pat})", spec) for pat, spec in layer]
        return rules

    def cache_partition_rules(self) -> Rules:
        # NOTE: first match wins; kpos must precede the bare k/v patterns.
        if self.cfg.mla:
            return [
                (r"scan.*kpos", P(None, BATCH_AXES, MODEL)),
                (r"scan.*c_kv|scan.*k_pe", P(None, BATCH_AXES, MODEL, None)),
                (r"layer0.*kpos", P(BATCH_AXES, MODEL)),
                (r"layer0.*c_kv|layer0.*k_pe", P(BATCH_AXES, MODEL, None)),
            ]
        return [
            # seq-dim sharding over `model` (flash-decoding partition): always
            # divisible, unlike kv-head counts (8 or 4 vs 16 shards)
            (r"scan.*kpos", P(None, BATCH_AXES, MODEL)),
            (r"scan.*'k'|scan.*'v'", P(None, BATCH_AXES, None, MODEL, None)),
        ]
