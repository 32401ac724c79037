"""Jit'd kernel wrappers with backend dispatch.

On the TPU backend the Pallas kernels always run compiled; on every other
backend (CPU tests, dry-run lowering) we execute the chunked pure-jnp twins
from ``ref.py`` — identical math, scan-based so the lowered HLO keeps
O(block) intermediates. Kernel tests call the Pallas kernels themselves with
``interpret=True``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import ref
from .flash_attention import flash_attention_pallas
from .moe_gmm import expert_ffn_pallas
from .ssd_scan import ssd_scan_pallas


def _use_pallas() -> bool:
    return jax.default_backend() == "tpu"


def flash_attention(q, k, v, *, causal=True, window=0, logit_softcap=0.0,
                    scale=None, q_offset=0, kv_len=None, block_k=1024):
    """Multi-head GQA attention; see ``ref.mha_naive`` for semantics.

    kv_len: None, python int, or (B,) array of valid cache lengths.

    The ``pk_`` named scope marks the Pallas-kernel boundary: the dry-run
    cost model (launch/hlo_cost.py) excludes pk_-tagged instructions (the
    CPU stand-in materializes what the kernel keeps in VMEM) and accounts
    the kernel's true HBM IO analytically (launch/dryrun.py).
    """
    with jax.named_scope("pk_flash_attention"):
        if _use_pallas() and not isinstance(kv_len, jax.Array):
            return flash_attention_pallas(
                q, k, v, causal=causal, window=window, softcap=logit_softcap,
                scale=scale, q_offset=q_offset,
                kv_valid=kv_len)
        kv = kv_len
        if isinstance(kv, int):
            kv = jnp.full((q.shape[0],), kv, jnp.int32)
        return ref.mha_chunked(q, k, v, causal=causal, window=window,
                               logit_softcap=logit_softcap, scale=scale,
                               q_offset=q_offset, kv_len=kv, block_k=block_k)


def decode_attention(q, k_cache, v_cache, k_new, v_new, *, cache_index,
                     window=0, logit_softcap=0.0, scale=None):
    """Attention of S new tokens over a cache they are not yet written into;
    plain jnp GEMV path.

    q: (B, S, H, D) at positions ``cache_index + i``; k_cache, v_cache:
    (B, T, KVH, D); k_new, v_new: (B, S, KVH, D), the new tokens' own K/V in
    the cache dtype. The new tokens see the cache's positions below
    ``cache_index`` and each other causally, under ``window`` and
    ``logit_softcap``: the same as attention over the cache with the new rows
    written at ``cache_index``, without that write. cache_index may be traced.

    K/V are contracted in their stored dtype with fp32 accumulation
    (``preferred_element_type``) instead of upcast: an ``astype(f32)`` here
    makes XLA hoist a full-cache fp32 copy out of the decode loop (2x HBM for
    the cache + 2x read traffic). One fp32 softmax spans the cache's columns
    and the new ones; P is fed to both PV products in bf16 (exactly the MXU
    mixed-precision scheme the Pallas flash kernel uses).
    """
    B, S, H, D = q.shape
    T, KVH = k_cache.shape[1], k_cache.shape[2]
    scale = scale if scale is not None else D ** -0.5
    with jax.named_scope("pk_decode_attention"):
        qg = q.reshape(B, S, KVH, H // KVH, D)

        def scores(k, mask):
            s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k,
                           preferred_element_type=jnp.float32) * scale
            if logit_softcap:
                s = logit_softcap * jnp.tanh(s / logit_softcap)
            return jnp.where(mask, s, ref.NEG_INF)

        i, t = jnp.arange(S)[:, None], jnp.arange(T)[None, :]
        m_cache = t < cache_index
        m_new = i.T <= i
        if window:
            m_cache &= cache_index + i - t < window
            m_new &= i - i.T < window
        s_cache, s_new = scores(k_cache, m_cache), scores(k_new, m_new)
        top = jnp.maximum(s_cache.max(-1), s_new.max(-1))[..., None]
        p_cache, p_new = jnp.exp(s_cache - top), jnp.exp(s_new - top)
        total = p_cache.sum(-1, keepdims=True) + p_new.sum(-1, keepdims=True)

        def pv(p, v):
            return jnp.einsum("bhgqk,bkhd->bqhgd", (p / total).astype(v.dtype),
                              v, preferred_element_type=jnp.float32)

        o = pv(p_cache, v_cache) + pv(p_new, v_new)
        return o.reshape(B, S, H, D).astype(q.dtype)


def mla_decode_attention(q_lat, q_pe, c_cache, pe_cache, c_new, pe_new, *,
                         cache_index, scale):
    """Absorbed latent attention (DeepSeek-V2) of S new tokens over a latent
    cache they are not yet written into.

    q_lat: (B, S, H, R), each head's no-rope query taken into the latent
    space through W_uk; q_pe: (B, S, H, P) roped; c_cache: (B, T, R), the
    normed latents; pe_cache: (B, T, P), the roped shared keys; c_new,
    pe_new: (B, S, R), (B, S, P), the new tokens' own rows in the cache
    dtype. A score is q_lat . c + q_pe . k_pe: one key of width R + P shared
    by every head. Returns the latent output P . c, (B, S, H, R), fp32,
    which W_uv takes out to each head's value width.

    As ``decode_attention``: the cache's positions below ``cache_index`` and
    the new tokens causally, one fp32 softmax over both, the cache
    contracted in its stored dtype with fp32 accumulation.
    """
    S, T = q_lat.shape[1], c_cache.shape[1]
    if _use_pallas():
        mixed = functools.partial(jnp.einsum,
                                  preferred_element_type=jnp.float32)
    else:  # the CPU's batched dot takes no bf16 x bf16 -> f32
        mixed = lambda spec, a, b: jnp.einsum(
            spec, a.astype(jnp.float32), b.astype(jnp.float32))
    with jax.named_scope("pk_mla_decode_attention"):
        q_lat = q_lat.astype(c_cache.dtype)
        q_pe = q_pe.astype(pe_cache.dtype)

        def scores(c, pe, mask):
            s = (mixed("bqhr,bkr->bhqk", q_lat, c)
                 + mixed("bqhp,bkp->bhqk", q_pe, pe)) * scale
            return jnp.where(mask, s, ref.NEG_INF)

        i, t = jnp.arange(S)[:, None], jnp.arange(T)[None, :]
        s_cache = scores(c_cache, pe_cache, t < cache_index)
        s_new = scores(c_new, pe_new, i.T <= i)
        top = jnp.maximum(s_cache.max(-1), s_new.max(-1))[..., None]
        p_cache, p_new = jnp.exp(s_cache - top), jnp.exp(s_new - top)
        total = p_cache.sum(-1, keepdims=True) + p_new.sum(-1, keepdims=True)

        def pv(p, c):
            return mixed("bhqk,bkr->bqhr", (p / total).astype(c.dtype), c)

        return pv(p_cache, c_cache) + pv(p_new, c_new)


def ssd_scan(x, dt, a_log, b, c, d_skip, *, chunk=128):
    with jax.named_scope("pk_ssd_scan"):
        if _use_pallas():
            return ssd_scan_pallas(x, dt, a_log, b, c, d_skip, chunk=chunk)
        return ref.ssd_chunked(x, dt, a_log, b, c, d_skip, chunk_size=chunk)


def ssd_decode_step(state, x_t, dt_t, a_log, b_t, c_t, d_skip):
    return ref.ssd_decode_step(state, x_t, dt_t, a_log, b_t, c_t, d_skip)


def expert_ffn(x, w_gate, w_up, w_down, tile_group, active_tiles, group_rows,
               *, block_m):
    """SwiGLU of rows sorted by expert in whole tiles of ``block_m`` rows
    (``moe_gmm.expert_ffn_pallas``); rows of unused tiles are undefined.
    ``group_rows`` (G,) gives each expert's rows for the jnp twin."""
    with jax.named_scope("pk_expert_ffn"):
        if _use_pallas():
            return expert_ffn_pallas(x, w_gate, w_up, w_down, tile_group,
                                     active_tiles, block_m=block_m)
        return ref.expert_ffn_ragged(x, w_gate, w_up, w_down, group_rows)
