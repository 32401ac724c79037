"""GQA attention block (full / sliding-window / softcap) with KV cache."""
from __future__ import annotations


import jax
import jax.numpy as jnp

from ..kernels import ops
from .common import ParamSpec, apply_rope, rms_norm


def attention_specs(cfg, d_model: int | None = None) -> dict:
    d = d_model or cfg.d_model
    hd = cfg.head_dim_
    spec = {
        "wq": ParamSpec((d, cfg.n_heads, hd), ("embed", "heads", "head_dim")),
        "wk": ParamSpec((d, cfg.n_kv_heads, hd), ("embed", "kv_heads", "head_dim")),
        "wv": ParamSpec((d, cfg.n_kv_heads, hd), ("embed", "kv_heads", "head_dim")),
        "wo": ParamSpec((cfg.n_heads, hd, d), ("heads", "head_dim", "embed")),
    }
    if cfg.use_bias:
        spec["bq"] = ParamSpec((cfg.n_heads, hd), ("heads", "head_dim"), init="zeros")
        spec["bk"] = ParamSpec((cfg.n_kv_heads, hd), ("kv_heads", "head_dim"), init="zeros")
        spec["bv"] = ParamSpec((cfg.n_kv_heads, hd), ("kv_heads", "head_dim"), init="zeros")
        spec["bo"] = ParamSpec((d,), ("embed",), init="zeros")
    if cfg.qk_norm:
        spec["q_norm"] = ParamSpec((hd,), ("head_dim",), init="ones")
        spec["k_norm"] = ParamSpec((hd,), ("head_dim",), init="ones")
    return spec


def _project_qkv(p, x, cfg):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cfg.use_bias:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"])
        k = rms_norm(k, p["k_norm"])
    return q, k, v


def apply_attention(p, x, *, cfg, window: int = 0, positions=None,
                    cache: dict | None = None, cache_index=None,
                    cross_kv: tuple | None = None, causal: bool = True,
                    mode: str = "train"):
    """x: (B, S, d). Returns (out, kv_rows).

    - train: no cache, flash attention over x.
    - prefill: flash attention over x.
    - decode: attention over ``cache`` (this layer's (B, T, KVH, hd) slice,
      read only) at positions below ``cache_index``, plus the new tokens.
    - cross-attention: cross_kv = (k, v) precomputed from encoder/vision
      states; causal is ignored (full visibility).

    kv_rows: None without a cache, else this call's {"k", "v"} of (B, S,
    KVH, hd) in the cache dtype. Nothing is written here: ``forward`` writes
    every layer's rows into the stacked cache at ``cache_index`` after the
    layer scan.
    """
    B, S, _ = x.shape
    scale = cfg.attn_scale or cfg.head_dim_ ** -0.5

    if cross_kv is not None:
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
        if cfg.use_bias:
            q = q + p["bq"]
        if cfg.qk_norm:
            q = rms_norm(q, p["q_norm"])
        k, v = cross_kv
        o = ops.flash_attention(q, k, v, causal=False, scale=scale,
                                logit_softcap=cfg.attn_logit_softcap)
        out = jnp.einsum("bshk,hkd->bsd", o, p["wo"])
        if cfg.use_bias:
            out = out + p["bo"]
        return out, None

    if positions is None:
        positions = jnp.arange(S)[None, :]
    q, k, v = _project_qkv(p, x, cfg)
    q = apply_rope(q, positions, rope_pct=cfg.rope_pct, theta=cfg.rope_theta)
    k = apply_rope(k, positions, rope_pct=cfg.rope_pct, theta=cfg.rope_theta)

    kv_rows = None
    if cache is not None:
        kv_rows = {"k": k.astype(cache["k"].dtype),
                   "v": v.astype(cache["v"].dtype)}
    if mode == "decode":
        o = ops.decode_attention(q, cache["k"], cache["v"], kv_rows["k"],
                                 kv_rows["v"], cache_index=cache_index,
                                 window=window, scale=scale,
                                 logit_softcap=cfg.attn_logit_softcap)
    else:
        o = ops.flash_attention(q, k, v, causal=causal, window=window,
                                logit_softcap=cfg.attn_logit_softcap,
                                scale=scale)

    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"])
    if cfg.use_bias:
        out = out + p["bo"]
    return out, kv_rows


def cross_kv_specs(cfg, d_src: int) -> dict:
    """K/V projections from a source modality (encoder states / patches)."""
    hd = cfg.head_dim_
    return {
        "wk": ParamSpec((d_src, cfg.n_kv_heads, hd), ("src_embed", "kv_heads", "head_dim")),
        "wv": ParamSpec((d_src, cfg.n_kv_heads, hd), ("src_embed", "kv_heads", "head_dim")),
    }


def compute_cross_kv(p, src):
    k = jnp.einsum("bsd,dhk->bshk", src, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", src, p["wv"])
    return k, v
