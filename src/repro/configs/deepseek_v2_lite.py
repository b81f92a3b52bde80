"""deepseek-v2-lite [moe]: 27L d_model=2048 16H latent attention (MLA:
kv_lora_rank 512, qk 128 nope + 64 rope, v 128, YaRN x40), layer 0 dense
(d_ff 10944), layers 1-26 MoE with 64 routed experts top-6 (width 1408,
softmax router, no top-k renormalization) and 2 shared, vocab 102400
[hf:deepseek-ai/DeepSeek-V2-Lite config.json; arXiv:2405.04434].
"""

from repro.configs.base import ArchConfig

CONFIG = ArchConfig(
    name="deepseek-v2-lite",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=10944,
    vocab_size=102400,
    n_experts=64,
    experts_per_token=6,
    moe_d_ff=1408,
    n_shared_experts=2,
    first_dense_layers=1,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    rope_theta=1e4,
    yarn_factor=40.0,
    yarn_original_len=4096,
    yarn_beta_fast=32.0,
    yarn_beta_slow=1.0,
    yarn_mscale=0.707,
    yarn_mscale_all_dim=0.707,
    norm_eps=1e-6,
    tie_embeddings=False,
    source="hf:deepseek-ai/DeepSeek-V2-Lite",
)
