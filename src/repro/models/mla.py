"""Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434), without
query compression.

Per token: q = h W_q, split per head into ``qk_nope_head_dim`` and
``qk_rope_head_dim`` dims; [c_kv, k_pe] = h W_kv_a, where c_kv (width
``kv_lora_rank``) passes through its own RMSNorm and k_pe is one rope key
shared by every head. Each head's keys and values come out of the latent:
k_nope = c_kv W_uk, v = c_kv W_uv. The rope dims rotate under YaRN. The
cache holds only c_kv and k_pe.

- prefill / train: K and V are expanded and the flash kernel runs at q/k
  width nope + rope and v width ``v_head_dim``.
- decode ("absorbed"): q_nope W_uk^T puts each head's query in the latent
  space, so the scores read the cached c_kv and k_pe directly (one key of
  width kv_lora_rank + rope shared by all heads); the latent output goes
  out through W_uv.

The softmax scale is (nope + rope)^-0.5 times YaRN's mscale(mscale_all_dim)
squared, as the published code sets it.
"""
from __future__ import annotations

import jax.numpy as jnp

from ..kernels import ops
from .common import ParamSpec, apply_rope, rms_norm, yarn_mscale

KV_NORM_EPS = 1e-6


def mla_specs(cfg) -> dict:
    d, H, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    nope, rope, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    return {
        "wq": ParamSpec((d, H, nope + rope), ("embed", "heads", "head_dim")),
        "wkv_a": ParamSpec((d, r + rope), ("embed", "kv_lora")),
        "kv_norm": ParamSpec((r,), ("kv_lora",), init="ones"),
        "w_uk": ParamSpec((r, H, nope), ("kv_lora", "heads", "head_dim")),
        "w_uv": ParamSpec((r, H, dv), ("kv_lora", "heads", "head_dim")),
        "wo": ParamSpec((H, dv, d), ("heads", "head_dim", "embed")),
    }


def softmax_scale(cfg) -> float:
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    y = cfg.yarn
    if y.factor and y.mscale_all_dim:
        scale *= yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    return scale


def apply_mla(p, x, *, cfg, positions, cache=None, cache_index=None,
              mode="train"):
    """x: (B, S, d). Returns (out, rows): rows is None without a cache, else
    this call's {"c_kv": (B, S, R), "k_pe": (B, S, P)} in the cache dtype,
    which ``forward`` writes at ``cache_index`` (nothing is written here).
    decode attends over ``cache`` (this layer's c_kv/k_pe, read only) below
    ``cache_index`` plus the new tokens."""
    r, nope = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    rope = lambda t: apply_rope(t, positions, theta=cfg.rope_theta,
                                yarn=cfg.yarn)
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    q_nope, q_pe = q[..., :nope], rope(q[..., nope:])
    kv = jnp.einsum("bsd,dc->bsc", x, p["wkv_a"])
    c_kv = rms_norm(kv[..., :r], p["kv_norm"], KV_NORM_EPS)
    k_pe = rope(kv[:, :, None, r:])[:, :, 0]
    scale = softmax_scale(cfg)

    rows = None
    if cache is not None:
        rows = {"c_kv": c_kv.astype(cache["c_kv"].dtype),
                "k_pe": k_pe.astype(cache["k_pe"].dtype)}
    if mode == "decode":
        q_lat = jnp.einsum("bshn,rhn->bshr", q_nope, p["w_uk"])
        o_lat = ops.mla_decode_attention(
            q_lat, q_pe, cache["c_kv"], cache["k_pe"], rows["c_kv"],
            rows["k_pe"], cache_index=cache_index, scale=scale)
        o = jnp.einsum("bshr,rhv->bshv", o_lat.astype(x.dtype), p["w_uv"])
    else:
        H = q.shape[2]
        k_nope = jnp.einsum("bsr,rhn->bshn", c_kv, p["w_uk"])
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_pe[:, :, None], k_nope.shape[:2]
                                      + (H, k_pe.shape[-1]))], axis=-1)
        v = jnp.einsum("bsr,rhv->bshv", c_kv, p["w_uv"])
        o = ops.flash_attention(jnp.concatenate([q_nope, q_pe], axis=-1), k,
                                v, causal=True, scale=scale)
    return jnp.einsum("bshv,hvd->bsd", o, p["wo"]), rows
