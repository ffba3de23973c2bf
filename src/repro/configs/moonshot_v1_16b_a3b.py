"""moonshot-v1-16b-a3b [moe] — Moonlight-16B-A3B, a DeepSeek-V3 model:
latent attention (MLA), one dense layer then 26 expert layers of 64
routed experts top-6 (sigmoid scores with a correction bias) plus 2
shared experts [hf:moonshotai/Moonlight-16B-A3B config.json]."""
import dataclasses

from repro.models.common import ModelConfig

CONFIG = ModelConfig(
    name="moonshot-v1-16b-a3b", family="moe",
    n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16,
    d_ff=11264, vocab=163840, rope_theta=50_000.0, norm_eps=1e-5,
    kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
    v_head_dim=128,
    n_experts=64, top_k=6, moe_d_ff=1408, n_shared_experts=2,
    first_k_dense=1, scoring_func="sigmoid", routed_scaling=2.446,
)

SMOKE = dataclasses.replace(
    CONFIG, name="moonshot-v1-16b-a3b-smoke",
    n_layers=3, d_model=64, n_heads=4, n_kv_heads=4, d_ff=128,
    vocab=256, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16, n_experts=8, top_k=2, moe_d_ff=32,
)
