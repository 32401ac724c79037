"""A tiny ``hybrid_moe`` configuration, for driving the benchmark's
reference and harness on the CPU beside ``bench_tiny``'s cells.

It is the registry's reduced granite-4.0-h-small, in the published keys the
``hybrid_moe`` reference reads: two periods of (Mamba2, attention), GQA 4:1,
3 of 6 experts held, top-2.
"""
HYBRID_MOE = {
    "name": "tiny-hybrid-moe", "arch": "granite-4.0-h-small",
    "preset": "reduced", "family": "hybrid_moe", "num_hidden_layers": 4,
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 1,
    "vocab_size": 256, "layer_types": ["mamba", "attention"] * 2,
    "attention_multiplier": 0.125, "embedding_multiplier": 12,
    "residual_multiplier": 0.22, "logits_scaling": 16, "rms_norm_eps": 1e-5,
    "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_d_state": 16,
    "mamba_expand": 2, "mamba_d_conv": 4, "mamba_n_groups": 1,
    "mamba_chunk_size": 16, "intermediate_size": 32,
    "shared_intermediate_size": 64, "num_local_experts": 3,
    "published": {"num_local_experts": 6}, "experts_offset": 0,
    "num_experts_per_tok": 2, "tie_word_embeddings": True,
    "token_ids_below": 256}
