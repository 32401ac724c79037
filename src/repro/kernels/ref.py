"""Pure-jnp reference oracles for every Pallas kernel.

Two tiers per op:

- ``*_naive``   : materializes the full intermediate (scores / states). The
                  ground-truth oracle for kernel tests.
- ``*_chunked`` : flash-style chunked jnp implementation (scan over blocks,
                  online softmax / recurrent state). Numerically equal to the
                  naive tier but with O(block) intermediates — this is the
                  CPU / dry-run execution path, and the mathematical twin of
                  the Pallas kernels.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _softcap(x, cap: float):
    if not cap:
        return x
    return cap * jnp.tanh(x / cap)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------


def _mask(q_pos, k_pos, *, causal: bool, window: int, kv_len=None):
    """Boolean mask (..., q, k): True = attend."""
    m = jnp.ones(q_pos.shape[:-1] + (q_pos.shape[-1], k_pos.shape[-1]), bool)
    qp = q_pos[..., :, None]
    kp = k_pos[..., None, :]
    if causal:
        m &= kp <= qp
    if window:
        m &= qp - kp < window
    if kv_len is not None:
        m &= kp < kv_len[..., None, None]
    return m


def mha_naive(q, k, v, *, causal=True, window=0, logit_softcap=0.0,
              scale=None, q_offset=0, kv_len=None):
    """Full-scores attention.

    q: (B, Sq, H, D); k: (B, Sk, KVH, D); v: (B, Sk, KVH, Dv). GQA via head
    grouping. q_offset: absolute position of q[0] (for decode).
    kv_len: optional (B,) valid kv lengths (for cache decode).
    Returns (B, Sq, H, Dv).
    """
    B, Sq, H, D = q.shape
    _, Sk, KVH, _ = k.shape
    Dv = v.shape[-1]
    g = H // KVH
    scale = scale if scale is not None else D ** -0.5
    qf = q.astype(jnp.float32) * scale
    qf = qf.reshape(B, Sq, KVH, g, D)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qf, k.astype(jnp.float32))
    s = _softcap(s, logit_softcap)
    q_pos = jnp.arange(Sq) + q_offset
    k_pos = jnp.arange(Sk)
    m = _mask(q_pos[None], k_pos[None], causal=causal, window=window,
              kv_len=kv_len)  # (B or 1, q, k)
    s = jnp.where(m[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p, v.astype(jnp.float32))
    return o.reshape(B, Sq, H, Dv).astype(q.dtype)


def mha_chunked(q, k, v, *, causal=True, window=0, logit_softcap=0.0,
                scale=None, q_offset=0, kv_len=None, block_k=1024):
    """Flash-style online-softmax attention, scanning over kv blocks.

    Same signature/semantics as :func:`mha_naive`; intermediates are
    O(Sq * block_k) instead of O(Sq * Sk).
    """
    B, Sq, H, D = q.shape
    _, Sk, KVH, _ = k.shape
    Dv = v.shape[-1]
    g = H // KVH
    scale = scale if scale is not None else D ** -0.5
    block_k = min(block_k, Sk)
    pad = (-Sk) % block_k
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nblk = k.shape[1] // block_k

    qf = (q.astype(jnp.float32) * scale).reshape(B, Sq, KVH, g, D)
    q_pos = jnp.arange(Sq) + q_offset
    kb = k.reshape(B, nblk, block_k, KVH, D).astype(jnp.float32)
    vb = v.reshape(B, nblk, block_k, KVH, Dv).astype(jnp.float32)

    def step(carry, blk):
        m_run, l_run, acc = carry
        kc, vc, start = blk
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qf, kc)
        s = _softcap(s, logit_softcap)
        k_pos = start + jnp.arange(block_k)
        msk = _mask(q_pos[None], k_pos[None], causal=causal, window=window,
                    kv_len=kv_len)  # (B or 1, q, k)
        valid = k_pos < Sk
        msk = msk & valid[None, None, :]
        s = jnp.where(msk[:, None, None], s, NEG_INF)
        m_new = jnp.maximum(m_run, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m_run - m_new)
        l_new = l_run * corr + p.sum(axis=-1)
        acc = acc * corr[..., None] + jnp.einsum("bhgqk,bkhd->bhgqd", p, vc)
        return (m_new, l_new, acc), None

    m0 = jnp.full((B, KVH, g, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, KVH, g, Sq), jnp.float32)
    a0 = jnp.zeros((B, KVH, g, Sq, Dv), jnp.float32)
    starts = jnp.arange(nblk) * block_k
    (m_f, l_f, acc), _ = jax.lax.scan(
        step, (m0, l0, a0),
        (kb.transpose(1, 0, 2, 3, 4), vb.transpose(1, 0, 2, 3, 4), starts))
    o = acc / jnp.maximum(l_f, 1e-30)[..., None]
    o = o.transpose(0, 3, 1, 2, 4).reshape(B, Sq, H, Dv)
    return o.astype(q.dtype)


# ---------------------------------------------------------------------------
# Mamba2 / SSD
# ---------------------------------------------------------------------------


def ssd_naive(x, dt, a_log, b, c, d_skip, *, chunk_size=None):
    """Quadratic-time SSD reference.

    x:  (B, L, H, P) inputs        dt: (B, L, H) softplus'd step sizes
    a_log: (H,) (A = -exp(a_log))  b, c: (B, L, G, N) input/output projections
    d_skip: (H,) skip connection.  Heads map to groups h -> h // (H // G).
    y_t = sum_{s<=t} exp(sum_{r=s+1..t} dt_r*A) (C_t.B_s) dt_s x_s + D x_t
    Returns y (B, L, H, P) and final state (B, H, P, N).
    """
    B, L, H, P = x.shape
    G, N = b.shape[2], b.shape[3]
    rep = H // G
    A = -jnp.exp(a_log.astype(jnp.float32))                       # (H,)
    dtf = dt.astype(jnp.float32)
    log_a = dtf * A                                               # (B,L,H)
    cum = jnp.cumsum(log_a, axis=1)                               # (B,L,H)
    bh = jnp.repeat(b, rep, axis=2).astype(jnp.float32)           # (B,L,H,N)
    ch = jnp.repeat(c, rep, axis=2).astype(jnp.float32)
    u = x.astype(jnp.float32) * dtf[..., None]                    # (B,L,H,P)

    cb = jnp.einsum("bthn,bshn->bhts", ch, bh)                    # (B,H,L,L)
    decay = jnp.exp(cum.transpose(0, 2, 1)[:, :, :, None]
                    - cum.transpose(0, 2, 1)[:, :, None, :])      # (B,H,t,s)
    causal = jnp.tril(jnp.ones((L, L), bool))
    w = jnp.where(causal, cb * decay, 0.0)
    y = jnp.einsum("bhts,bshp->bthp", w, u)
    y = y + x.astype(jnp.float32) * d_skip.astype(jnp.float32)[None, None, :, None]

    # final state: S = sum_s exp(cum_L - cum_s) u_s b_s^T
    w_end = jnp.exp(cum[:, -1][:, None] - cum).transpose(0, 2, 1)  # (B,H,L)
    state = jnp.einsum("bhs,bshp,bshn->bhpn", w_end, u, bh)
    return y.astype(x.dtype), state


def ssd_chunked(x, dt, a_log, b, c, d_skip, *, chunk_size=128):
    """Chunked SSD: dense intra-chunk + sequential inter-chunk recurrence.

    Mathematical twin of the Pallas ``ssd_scan`` kernel. Same returns as
    :func:`ssd_naive`.
    """
    B, L, H, P = x.shape
    G, N = b.shape[2], b.shape[3]
    rep = H // G
    Q = min(chunk_size, L)
    assert L % Q == 0, f"L={L} must divide chunk {Q}"
    nc = L // Q
    A = -jnp.exp(a_log.astype(jnp.float32))
    dtf = dt.astype(jnp.float32)
    u = x.astype(jnp.float32) * dtf[..., None]
    bh = jnp.repeat(b, rep, axis=2).astype(jnp.float32)
    ch = jnp.repeat(c, rep, axis=2).astype(jnp.float32)

    uc = u.reshape(B, nc, Q, H, P).transpose(1, 0, 2, 3, 4)
    bc = bh.reshape(B, nc, Q, H, N).transpose(1, 0, 2, 3, 4)
    cc = ch.reshape(B, nc, Q, H, N).transpose(1, 0, 2, 3, 4)
    la = (dtf * A[None, None]).reshape(B, nc, Q, H).transpose(1, 0, 2, 3)

    causal = jnp.tril(jnp.ones((Q, Q), bool))

    def chunk_step(state, blk):
        u_, b_, c_, la_ = blk                     # (B,Q,H,P/N), (B,Q,H)
        cum = jnp.cumsum(la_, axis=1)             # (B,Q,H)
        cum_t = cum.transpose(0, 2, 1)            # (B,H,Q)
        cb = jnp.einsum("bthn,bshn->bhts", c_, b_)
        decay = jnp.exp(cum_t[:, :, :, None] - cum_t[:, :, None, :])
        w = jnp.where(causal, cb * decay, 0.0)
        y = jnp.einsum("bhts,bshp->bthp", w, u_)
        # contribution from carried state
        y = y + jnp.einsum("bthn,bhpn->bthp", c_, state) * jnp.exp(cum)[..., None]
        # state update
        tot = cum_t[:, :, -1]                                     # (B,H)
        w_end = jnp.exp(tot[:, :, None] - cum_t)                  # (B,H,Q)
        s_loc = jnp.einsum("bhs,bshp,bshn->bhpn", w_end, u_, b_)
        state = state * jnp.exp(tot)[..., None, None] + s_loc
        return state, y

    s0 = jnp.zeros((B, H, P, N), jnp.float32)
    state, ys = jax.lax.scan(chunk_step, s0, (uc, bc, cc, la))
    y = ys.transpose(1, 0, 2, 3, 4).reshape(B, L, H, P)
    y = y + x.astype(jnp.float32) * d_skip.astype(jnp.float32)[None, None, :, None]
    return y.astype(x.dtype), state


def ssd_decode_step(state, x_t, dt_t, a_log, b_t, c_t, d_skip):
    """Single-token recurrent update.

    state: (B,H,P,N); x_t: (B,H,P); dt_t: (B,H); b_t, c_t: (B,G,N).
    Returns y_t (B,H,P), new state.
    """
    H = x_t.shape[1]
    G = b_t.shape[1]
    rep = H // G
    A = -jnp.exp(a_log.astype(jnp.float32))
    a = jnp.exp(dt_t.astype(jnp.float32) * A[None])               # (B,H)
    u = x_t.astype(jnp.float32) * dt_t.astype(jnp.float32)[..., None]
    bh = jnp.repeat(b_t, rep, axis=1).astype(jnp.float32)          # (B,H,N)
    ch = jnp.repeat(c_t, rep, axis=1).astype(jnp.float32)
    state = state * a[..., None, None] + u[..., None] * bh[..., None, :]
    y = jnp.einsum("bhpn,bhn->bhp", state, ch)
    y = y + x_t.astype(jnp.float32) * d_skip.astype(jnp.float32)[None, :, None]
    return y.astype(x_t.dtype), state


# ---------------------------------------------------------------------------
# Grouped expert SwiGLU
# ---------------------------------------------------------------------------


def expert_ffn_ragged(x, w_gate, w_up, w_down, layer, group_rows):
    """SwiGLU of each row under its group's weights in ``layer`` of the
    stacks (L, G, ...): x (M, d) rows sorted by group, ``group_rows`` (G,)
    rows of each group in order; rows past them give 0. Twin of
    ``moe_gmm.expert_ffn_pallas``."""
    def mm(a, w):  # fp32 operands: the CPU's ragged dot takes no bf16 -> f32
        return jax.lax.ragged_dot(a.astype(jnp.float32),
                                  w[layer].astype(jnp.float32), group_rows)
    h = jax.nn.silu(mm(x, w_gate)) * mm(x, w_up)
    return mm(h.astype(x.dtype), w_down).astype(x.dtype)
