"""Jit'd kernel wrappers with backend dispatch.

On the TPU backend the Pallas kernels always run compiled; on every other
backend (CPU tests, dry-run lowering) we execute the chunked pure-jnp twins
from ``ref.py`` — identical math, scan-based so the lowered HLO keeps
O(block) intermediates. Kernel tests call the Pallas kernels themselves with
``interpret=True``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import ref
from .flash_attention import flash_attention_pallas
from .moe_gmm import gmm_pallas
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


def decode_attention(q, k, v, *, window=0, logit_softcap=0.0, scale=None,
                     q_offset, kv_len, bf16_kv: bool = True):
    """Single-token (Sq small) attention over a cache; plain jnp GEMV path.

    q_offset/kv_len may be traced arrays (dynamic decode position).

    bf16_kv: contract K/V in their stored dtype with fp32 accumulation
    (``preferred_element_type``) instead of upcasting — an ``astype(f32)``
    here makes XLA hoist a full-cache fp32 copy out of the decode loop (2x
    HBM for the cache + 2x read traffic).
    The softmax stays fp32; P is fed to the PV product in bf16 (exactly the
    MXU mixed-precision scheme the Pallas flash kernel uses).
    """
    B, Sq, H, D = q.shape
    _, Sk, KVH, _ = k.shape
    g = H // KVH
    scale = scale if scale is not None else D ** -0.5
    ns = jax.named_scope("pk_decode_attention")
    ns.__enter__()
    if bf16_kv:
        qf = q.reshape(B, Sq, KVH, g, D)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qf, k,
                       preferred_element_type=jnp.float32) * scale
    else:
        qf = (q.astype(jnp.float32) * scale).reshape(B, Sq, KVH, g, D)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qf, k.astype(jnp.float32))
    if logit_softcap:
        s = logit_softcap * jnp.tanh(s / logit_softcap)
    q_pos = jnp.asarray(q_offset)[..., None] + jnp.arange(Sq)  # (B?,Sq)
    q_pos = jnp.broadcast_to(q_pos, (B, Sq))
    k_pos = jnp.arange(Sk)
    m = k_pos[None, None, :] <= q_pos[..., None]
    kv = jnp.broadcast_to(jnp.asarray(kv_len), (B,))
    m &= k_pos[None, None, :] < kv[:, None, None]
    if window:
        m &= q_pos[..., None] - k_pos[None, None, :] < window
    s = jnp.where(m[:, None, None], s, ref.NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    if bf16_kv:
        o = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v.dtype), v,
                       preferred_element_type=jnp.float32)
    else:
        o = jnp.einsum("bhgqk,bkhd->bqhgd", p, v.astype(jnp.float32))
    out = o.reshape(B, Sq, H, D).astype(q.dtype)
    ns.__exit__(None, None, None)
    return out


def ssd_scan(x, dt, a_log, b, c, d_skip, *, chunk=128):
    with jax.named_scope("pk_ssd_scan"):
        if _use_pallas():
            return ssd_scan_pallas(x, dt, a_log, b, c, d_skip, chunk=chunk)
        return ref.ssd_chunked(x, dt, a_log, b, c, d_skip, chunk_size=chunk)


def ssd_decode_step(state, x_t, dt_t, a_log, b_t, c_t, d_skip):
    return ref.ssd_decode_step(state, x_t, dt_t, a_log, b_t, c_t, d_skip)


def gmm(x, w):
    """Grouped per-expert matmul: (E, C, d) @ (E, d, f) -> (E, C, f)."""
    with jax.named_scope("pk_gmm"):
        if _use_pallas():
            return gmm_pallas(x, w)
        return ref.gmm_naive(x, w)
