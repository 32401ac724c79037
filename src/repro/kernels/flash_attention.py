"""Pallas TPU flash attention (causal / sliding-window / softcap, GQA).

Blockwise online-softmax attention tiled for VMEM/MXU:

- grid = (batch, q_heads, Sq/block_q, Sk/block_k); the kv dim is innermost and
  ``arbitrary`` so fp32 scratch (acc, running max, running sum) carries across
  kv iterations.
- BlockSpecs stage (block_q, head_dim) of Q and (block_k, head_dim) of K/V
  into VMEM per step; blocks are 128-aligned for the MXU.
- GQA is expressed in the K/V index_map (q head -> kv head), so no KV
  repetition ever hits HBM.
- V may be narrower than Q/K (DeepSeek-V2's latent attention: q/k 192, v
  128); the accumulator and the output take V's width.

The oracle is ``ref.mha_naive``; ``ops.flash_attention`` dispatches here on
TPU and to ``ref.mha_chunked`` on CPU (same math, jnp scan).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                 scale: float, causal: bool, window: int, softcap: float,
                 block_q: int, block_k: int, q_offset: int, kv_valid: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32) * scale         # (bq, d)
    k = k_ref[0, 0].astype(jnp.float32)                 # (bk, d)
    v = v_ref[0, 0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (bq, bk)
    if softcap:
        s = softcap * jnp.tanh(s / softcap)

    q_pos = q_offset + iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = ik * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    mask = k_pos < kv_valid
    if causal:
        mask &= k_pos <= q_pos
    if window:
        mask &= q_pos - k_pos < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + p.sum(axis=-1)
    acc_scr[...] = acc_scr[...] * corr[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        o_ref[0, 0] = (acc_scr[...] /
                       jnp.maximum(l_scr[...], 1e-30)[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "softcap", "scale", "q_offset",
                     "kv_valid", "block_q", "block_k", "interpret"))
def flash_attention_pallas(q, k, v, *, causal=True, window=0, softcap=0.0,
                           scale=None, q_offset=0, kv_valid=None,
                           block_q=128, block_k=128, interpret=False):
    """q, k: (B, S, H or KVH, D); v: (B, Sk, KVH, Dv) -> (B, Sq, H, Dv)."""
    B, Sq, H, D = q.shape
    _, Sk, KVH, _ = k.shape
    Dv = v.shape[-1]
    assert H % KVH == 0
    group = H // KVH
    scale = scale if scale is not None else D ** -0.5
    kv_valid = Sk if kv_valid is None else kv_valid

    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    pq = (-Sq) % block_q
    pk = (-Sk) % block_k
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    Sq_p, Sk_p = q.shape[1], k.shape[1]
    # (B, S, H, D) -> (B, H, S, D) blocks
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    grid = (B, H, Sq_p // block_q, Sk_p // block_k)
    kern = functools.partial(
        _attn_kernel, scale=scale, causal=causal, window=window,
        softcap=softcap, block_q=block_q, block_k=block_k,
        q_offset=q_offset, kv_valid=min(kv_valid, Sk))

    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, iq, ik, g=group: (b, h // g, ik, 0)),
            pl.BlockSpec((1, 1, block_k, Dv),
                         lambda b, h, iq, ik, g=group: (b, h // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, Dv),
                               lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq_p, Dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, Dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(qt, kt, vt)
    out = out.transpose(0, 2, 1, 3)
    if pq:
        out = out[:, :Sq]
    return out


def vmem_bytes(block_q: int, block_k: int, d: int, dtype_bytes: int = 2) -> int:
    """Working-set estimate used by block-size selection (ops.py)."""
    io = (block_q + 2 * block_k) * d * dtype_bytes + block_q * d * dtype_bytes
    scratch = 4 * (block_q * d + 2 * block_q)
    scores = 4 * block_q * block_k
    return io + scratch + scores
