"""granite-4.0-h-small — IBM Granite 4.0-H Small (32B-A9B): Mamba2 mixers
and one NoPE GQA mixer in every period of ten layers, each mixer followed by
72 routed top-10 experts and one shared SwiGLU; muP scalars on the
embedding, the residual branches and the logits
[hf ibm-granite/granite-4.0-h-small config.json; transformers
``granitemoehybrid``].

``CONFIG`` is one chip's share of a deployment on 18 chips: two pipeline
stages of 20 layers, each stage's expert layers split over 9 chips by
expert parallelism, 8 experts a chip. This chip holds the first stage's
20 layers (two whole periods: 18 Mamba2 and 2 attention layers) with
experts 0-7 of each expert layer; the router scores all 72. Mixers, the
shared experts and the tied embedding / head are replicated within a
stage."""
from .base import ModelConfig, MoEConfig, SSMConfig

_PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4

CONFIG = ModelConfig(
    name="granite-4.0-h-small",
    family="hybrid_moe",
    n_layers=20,
    d_model=4096,
    n_heads=32,
    n_kv_heads=8,
    d_ff=0,
    vocab_size=100352,
    rope_pct=0.0,                 # position_embedding_type "nope"
    attn_scale=0.0078125,         # attention_multiplier
    tie_embeddings=True,
    layer_types=_PERIOD * 2,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    logits_scaling=16.0,
    norm_eps=1e-5,
    moe=MoEConfig(num_experts=72, top_k=10, num_shared=2, d_ff_expert=768,
                  experts_held=8, experts_offset=0, norm_topk_prob=True),
    ssm=SSMConfig(d_state=128, head_dim=64, expand=2, conv_width=4,
                  n_groups=1, chunk_size=256),
    source="hf ibm-granite/granite-4.0-h-small",
)

# two periods of (Mamba2, attention) at GQA 4:1, 3 of 6 experts held
REDUCED = CONFIG.replace(
    n_layers=4, d_model=64, n_heads=4, n_kv_heads=1, vocab_size=256,
    attn_scale=0.125, layer_types=("mamba", "attention") * 2,
    moe=MoEConfig(num_experts=6, top_k=2, num_shared=2, d_ff_expert=32,
                  experts_held=3, norm_topk_prob=True),
    ssm=SSMConfig(d_state=16, head_dim=16, expand=2, n_groups=1,
                  chunk_size=16))
