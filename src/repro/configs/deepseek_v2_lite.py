"""deepseek-v2-lite — multi-head latent attention (MLA, no q compression) in
every layer, first layer dense, then 2 shared + 64 routed top-6 experts
[arXiv:2405.04434; hf deepseek-ai/DeepSeek-V2-Lite].

``CONFIG`` is one chip's share of the deployment that serves it by expert
parallelism over 8 chips: the router scores all 64 experts, this chip holds
experts 0-7 of each MoE layer; attention, the dense layer, the shared
experts, the embedding and the head are replicated on every chip."""
from .base import ModelConfig, MoEConfig, YarnConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=0,
    vocab_size=102400,
    rope_theta=10000.0,
    tie_embeddings=False,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    yarn=YarnConfig(factor=40.0, original_max_position=4096, beta_fast=32.0,
                    beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707),
    moe=MoEConfig(num_experts=64, top_k=6, num_shared=2, d_ff_expert=1408,
                  first_k_dense=1, d_ff_dense=10944, experts_held=8,
                  experts_offset=0, norm_topk_prob=False,
                  routed_scaling_factor=1.0),
    source="arXiv:2405.04434",
)

# 4 of 8 experts held, so a test sees tokens routed off this chip
REDUCED = CONFIG.replace(
    n_layers=3, d_model=64, n_heads=4, n_kv_heads=4, vocab_size=256,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    moe=MoEConfig(num_experts=8, top_k=2, num_shared=2, d_ff_expert=32,
                  first_k_dense=1, d_ff_dense=128, experts_held=4,
                  norm_topk_prob=False))
