"""deepseek-v2-lite-16b: 27L d=2048 16H MLA(kv_lora=512, no q-LoRA,
qk_nope 128 + qk_rope 64, v 128) vocab=102400; layer 0 dense ff=10944,
layers 1-26 MoE: 64 routed experts of ff=1408, top-6 by softmax, gates not
renormalised (``norm_topk_prob: false``) nor scaled
(``routed_scaling_factor: 1.0``), 2 shared experts; YaRN RoPE
(factor 40 over 4,096 original positions, mscale 0.707).
[arXiv:2405.04434; huggingface.co/deepseek-ai/DeepSeek-V2-Lite config.json]
The config's rms_norm_eps (1e-6) is the program's fixed RMSNorm eps."""
from repro.models.common import ArchConfig, YaRN

CONFIG = ArchConfig(
    name="deepseek-v2-lite-16b", family="moe",
    n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16,
    d_ff=1408, vocab=102400, n_experts=64, top_k=6, n_shared_experts=2,
    first_dense_ff=10944, norm_topk_prob=False,
    mla=True, kv_lora_rank=512, qk_nope_dim=128, qk_rope_dim=64, v_head_dim=128,
    rope_theta=10000.0,
    rope_scaling=YaRN(factor=40.0, original_max_position_embeddings=4096,
                      beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                      mscale_all_dim=0.707),
)

SMOKE = CONFIG.scaled(
    n_layers=3, d_model=64, n_heads=4, n_kv_heads=4, d_ff=48, vocab=128,
    n_experts=4, top_k=2, n_shared_experts=1, first_dense_ff=96,
    kv_lora_rank=32, qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16,
    param_dtype="float32", dtype="float32",
)
