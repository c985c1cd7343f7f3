"""DeepSeek-V2-Lite (16B) — MLA (kv_lora=512, no q-LoRA) with YaRN
rotary, 64 routed experts top-6 (softmax scores, greedy top-k, gates not
renormalized) + 2 shared experts, expert d_ff=1408, first layer dense
[arXiv:2405.04434; hf deepseek-ai/DeepSeek-V2-Lite config.json].

27L d_model=2048 16H vocab=102400, RMSNorm eps 1e-6.

Departure: rotary pairs the 64 rope dimensions rotate-half (i with
i + 32) where the published weights are stored interleaved (2i with
2i + 1); that is a fixed permutation of the rope columns of wq and
wkv_a, which seeded random weights cannot tell apart.
"""
from repro.models.common import ModelConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite",
    family="moe",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    head_dim=192,       # qk_nope 128 + qk_rope 64
    d_ff=10944,         # dense first-layer FFN
    vocab_size=102400,
    use_mla=True,
    kv_lora_rank=512,
    qk_nope_dim=128,
    qk_rope_dim=64,
    v_head_dim=128,
    num_experts=64,
    num_shared_experts=2,
    top_k=6,
    moe_d_ff=1408,
    first_dense_layers=1,
    norm_topk_prob=False,
    rope_theta=10000.0,
    yarn_factor=40.0,
    yarn_original_max_pos=4096,
    yarn_beta_fast=32.0,
    yarn_beta_slow=1.0,
    yarn_mscale=0.707,
    yarn_mscale_all_dim=0.707,
    norm_eps=1e-6,
    vq_C=2,
)

SMOKE = ModelConfig(
    name="deepseek-v2-lite-smoke",
    family="moe",
    num_layers=3,
    d_model=128,
    num_heads=4,
    num_kv_heads=4,
    head_dim=48,
    d_ff=512,
    vocab_size=512,
    use_mla=True,
    kv_lora_rank=64,
    qk_nope_dim=32,
    qk_rope_dim=16,
    v_head_dim=32,
    num_experts=8,
    num_shared_experts=2,
    top_k=2,
    moe_d_ff=256,
    first_dense_layers=1,
    norm_topk_prob=False,
    yarn_factor=40.0,
    yarn_original_max_pos=64,
    yarn_beta_fast=32.0,
    yarn_beta_slow=1.0,
    yarn_mscale=0.707,
    yarn_mscale_all_dim=0.707,
    norm_eps=1e-6,
    vq_C=2,
)
