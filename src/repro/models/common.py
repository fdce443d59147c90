"""Shared model infrastructure: configs, init, partition rules, dtype policy.

Sharding philosophy (DESIGN.md §5): a single ``(pod, data, model)`` mesh.
Parameters follow Megatron-style tensor parallelism over ``model``; the
batch shards over ``pod`` x ``data``; optimizer state additionally shards
over ``data`` (ZeRO-1).  Rules are expressed as (path-regex -> PartitionSpec)
tables so every architecture reuses one engine.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

# Mesh axis names (fixed by the assignment).
POD, DATA, MODEL = "pod", "data", "model"
#: batch shards over every data-parallel axis present in the mesh
BATCH_AXES = (POD, DATA)


@dataclasses.dataclass(frozen=True)
class YaRN:
    """YaRN RoPE scaling (arXiv:2309.00071), as DeepSeek-V2 configures it:
    frequencies past ``original_max_position_embeddings`` ramp from their
    own value to ``1/factor`` of it between the correction dimensions of
    ``beta_fast`` and ``beta_slow`` rotations; cos and sin take
    ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)`` and an MLA
    softmax scale ``mscale(factor, mscale_all_dim) ** 2``."""

    factor: float
    original_max_position_embeddings: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    """One architecture.  Field presence is governed by ``family``."""

    name: str
    family: str                    # dense | moe | hybrid | ssm | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    d_head: Optional[int] = None   # default d_model // n_heads
    # attention flags
    qk_norm: bool = False
    qkv_bias: bool = False
    window: Optional[int] = None   # sliding-window attention (h2o-danube)
    rope_theta: float = 10000.0
    rope_scaling: Optional[YaRN] = None    # deepseek-v2: YaRN
    use_rope: bool = True          # whisper uses absolute positions instead
    rotary_pct: float = 1.0        # minitron/nemotron: partial rotary
    causal: bool = True
    norm: str = "rmsnorm"          # rmsnorm | layernorm
    mlp: str = "swiglu"            # swiglu | gelu | relu2
    tie_embeddings: bool = False
    # MoE
    n_experts: int = 0
    top_k: int = 0
    n_shared_experts: int = 0
    first_dense_ff: Optional[int] = None   # deepseek: layer 0 is dense
    norm_topk_prob: bool = True            # renormalise the top-k gates
    #: the routed experts this device holds (expert parallelism): experts
    #: ``expert_offset .. expert_offset + experts_held - 1`` of the router's
    #: ``n_experts``; None holds all of them
    experts_held: Optional[int] = None
    expert_offset: int = 0
    router_aux_weight: float = 0.01
    # MLA (deepseek)
    mla: bool = False
    kv_lora_rank: int = 0
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128
    # SSM (mamba2 / zamba2)
    ssm_state: int = 0
    ssm_conv: int = 4
    ssm_expand: int = 2
    ssm_head_dim: int = 64
    ssm_chunk: int = 256
    # hybrid (zamba2): a SHARED attention block applied every k ssm layers
    attn_every: int = 0
    # rwkv6
    rwkv_head_dim: int = 64
    # enc-dec (whisper)
    enc_layers: int = 0
    dec_layers: int = 0
    enc_seq: int = 0               # encoder frames for serve shapes
    # vlm (internvl)
    n_patches: int = 0
    # numerics / execution
    param_dtype: str = "bfloat16"
    dtype: str = "bfloat16"        # activation dtype
    use_pallas: bool = False       # Pallas kernels (tests); jnp refs otherwise
    remat: bool = True
    logit_softcap: Optional[float] = None
    # analysis mode: python-unrolled layer loop instead of lax.scan.  XLA's
    # cost_analysis counts a while body ONCE (trip count ignored), so the
    # dry-run's cost compiles unroll a 1-layer and 2-layer variant and
    # reconstruct total = base + L * (c2 - c1).
    unroll_layers: bool = False
    # ---- §Perf hillclimb levers (default off = paper-faithful baseline) ----
    #: decode caches: one-hot masked write instead of dynamic_update_slice on
    #: the (seq-sharded) cache dim — shard-local, no gather/re-scatter
    opt_local_cache_update: bool = False
    #: explicit head-sharding constraints on recurrent-stream activations
    #: (rwkv6 time-mix r/k/v/w/g), preventing per-op resharding
    opt_shard_heads: bool = False
    #: Megatron-style sequence parallelism: residual-stream activations kept
    #: seq-sharded over `model` between layers (memory + collective shape)
    opt_seq_parallel: bool = False
    #: shard-local decomposition of the Mamba2 SSD multi-operand einsums
    opt_ssd_local: bool = False

    @property
    def head_dim(self) -> int:
        return self.d_head if self.d_head is not None else self.d_model // self.n_heads

    @property
    def n_held(self) -> int:
        """Routed experts whose weights this device holds."""
        return self.n_experts if self.experts_held is None else self.experts_held

    @property
    def adtype(self):
        return jnp.dtype(self.dtype)

    @property
    def pdtype(self):
        return jnp.dtype(self.param_dtype)

    def scaled(self, **overrides) -> "ArchConfig":
        """A reduced copy for smoke tests."""
        return dataclasses.replace(self, **overrides)


# ---------------------------------------------------------------------------
# Parameter init
# ---------------------------------------------------------------------------

def dense_init(key, shape, dtype, in_axis: int = 0) -> jax.Array:
    fan_in = shape[in_axis]
    std = fan_in ** -0.5
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def embed_init(key, shape, dtype) -> jax.Array:
    return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)


class KeyGen:
    """Deterministic rng splitter: one base key, named folds."""

    def __init__(self, key):
        self.key = key

    def __call__(self, name: str):
        return jax.random.fold_in(self.key, abs(hash(name)) % (2 ** 31))


# ---------------------------------------------------------------------------
# Partition rules
# ---------------------------------------------------------------------------
# Conventions for parameter names (leaf paths in the params dict):
#   embed            (V, D)        -> P(MODEL, None)
#   *w_q/w_kv/...    see per-family tables
# A rule table is a list of (regex, PartitionSpec); first match wins.
Rules = List[Tuple[str, P]]


def spec_for(path: str, rules: Rules) -> P:
    for pat, spec in rules:
        if re.search(pat, path):
            return spec
    return P()  # replicate by default (norms, biases, small tables)


def tree_paths(tree) -> Dict[str, Any]:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(p): v for p, v in flat}


def partition_tree(tree, rules: Rules):
    """PartitionSpec pytree matching ``tree`` via the rule table."""

    def _spec(path, leaf):
        name = jax.tree_util.keystr(path)
        spec = spec_for(name, rules)
        # guard: spec rank must not exceed leaf rank
        if len(spec) > np.ndim(leaf):
            raise ValueError(f"{name}: spec {spec} too long for shape {np.shape(leaf)}")
        return spec

    return jax.tree_util.tree_map_with_path(_spec, tree)


def zero1_spec(spec: P, shape: Tuple[int, ...], data_axis: str = DATA) -> P:
    """Add ZeRO-1 sharding over ``data`` to an optimizer-state leaf: extend
    the param's spec by sharding the first unsharded, divisible dim."""
    entries = list(spec) + [None] * (len(shape) - len(spec))
    for i, (e, s) in enumerate(zip(entries, shape)):
        if e is None and s % 16 == 0:  # divisibility by the data axis size
            entries[i] = data_axis
            return P(*entries)
    return P(*entries)


def logical_batch_spec(*trailing) -> P:
    return P(BATCH_AXES, *trailing)


# -- active-mesh axis resolution --------------------------------------------
# Model code writes logical specs mentioning ("pod", "data", "model"); the
# launcher declares which axes the actual mesh has.  Absent axes resolve to
# replication, so one model definition serves the host mesh (1 device), the
# single-pod 16x16 and the multi-pod 2x16x16 without edits.
_ACTIVE_AXES: Tuple[str, ...] = ()
_ACTIVE_SIZES: Dict[str, int] = {}


class mesh_axes:
    """Context manager: declare the mesh whose axes specs resolve against."""

    def __init__(self, mesh):
        self.names = tuple(mesh.axis_names) if mesh is not None else ()
        self.sizes = dict(mesh.shape) if mesh is not None else {}

    def __enter__(self):
        global _ACTIVE_AXES, _ACTIVE_SIZES
        self._old = (_ACTIVE_AXES, _ACTIVE_SIZES)
        _ACTIVE_AXES = self.names
        _ACTIVE_SIZES = self.sizes
        return self

    def __exit__(self, *exc):
        global _ACTIVE_AXES, _ACTIVE_SIZES
        _ACTIVE_AXES, _ACTIVE_SIZES = self._old
        return False


def resolve_spec(spec: P) -> P:
    """Drop axes not present in the active mesh (absent -> replicated)."""
    entries = []
    for e in spec:
        if e is None:
            entries.append(None)
        elif isinstance(e, (tuple, list)):
            keep = tuple(a for a in e if a in _ACTIVE_AXES)
            entries.append(keep if keep else None)
        else:
            entries.append(e if e in _ACTIVE_AXES else None)
    while entries and entries[-1] is None:
        entries.pop()
    return P(*entries)


def resolve_tree(spec_tree):
    return jax.tree.map(
        lambda s: resolve_spec(s) if isinstance(s, P) else s,
        spec_tree, is_leaf=lambda x: isinstance(x, P) or x is None)


def scan_layers(body, carry, xs, *, unroll: bool = False):
    """lax.scan over stacked layer params, or a python unroll (analysis
    mode — see ArchConfig.unroll_layers).  body: (carry, x) -> (carry, y)."""
    if not unroll:
        return jax.lax.scan(body, carry, xs)
    length = jax.tree_util.tree_leaves(xs)[0].shape[0]
    ys = []
    for i in range(length):
        xi = jax.tree_util.tree_map(lambda a: a[i], xs)
        carry, y = body(carry, xi)
        ys.append(y)
    if not ys or not jax.tree_util.tree_leaves(ys[0]):
        return carry, ()
    stacked = jax.tree_util.tree_map(lambda *zs: jnp.stack(zs), *ys)
    return carry, stacked


def constrain(x, *spec_entries):
    """with_sharding_constraint against the active mesh; identity if none.
    Axis entries whose dim size is not divisible by the axis are dropped —
    forcing e.g. 8 kv heads onto 16 'model' shards makes GSPMD pad and
    reshard ("involuntary full rematerialization"); replication + operand
    propagation is strictly better."""
    if not _ACTIVE_AXES:
        return x
    spec = resolve_spec(P(*spec_entries))
    entries = []
    for dim, e in zip(x.shape, tuple(spec) + (None,) * (x.ndim - len(spec))):
        if e is None:
            entries.append(None)
            continue
        axes = e if isinstance(e, (tuple, list)) else (e,)
        size = 1
        for a in axes:
            size *= _ACTIVE_SIZES.get(a, 1)
        entries.append(e if size and dim % size == 0 else None)
    return jax.lax.with_sharding_constraint(x, P(*entries))
