"""Pallas TPU flash attention (causal / sliding-window / softcap, GQA).

Blockwise online-softmax attention tiled for VMEM/MXU:

- grid = (batch, q_heads, Sq/block_q, Sk/block_k); the kv dim is innermost and
  ``arbitrary`` so fp32 scratch (acc, running max, running sum) carries across
  kv iterations.
- BlockSpecs stage (block_q, head_dim) of Q and (block_k, head_dim) of K/V
  into VMEM per step; blocks are 128-aligned for the MXU.
- Both products take their operands in the input dtype (bf16 on the serving
  path) with fp32 accumulation; the scores, the running max and sum and the
  accumulator are fp32. The probabilities enter the PV product in V's dtype.
- Blocks that the causal mask, the window or ``kv_valid`` mask entirely are
  skipped: their step computes nothing, and the K/V index_map clamps to the
  q block's needed range, so the pipeline does not fetch them either. Only
  blocks that straddle a mask boundary build the iota mask.
- Block sizes come from the shape (``flash_plan``): the largest tiles that
  divide the length padded to 128 and fit a VMEM budget (``vmem_bytes``).
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
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128
MAX_BLOCK = 1024
# Under the 16 MiB of VMEM a TPU v5e kernel may use by default, with room
# for the compiler's own temporaries.
VMEM_BUDGET = 12 * 2**20


def _k_blocks(iq, *, block_q, block_k, nk, causal, window, q_offset,
              kv_valid, xp=jnp):
    """(first, last) K/V block, inclusive, that q block ``iq`` needs: the
    blocks outside hold no key that any of its rows may attend. ``iq`` is a
    traced scalar (``xp=jnp``) or a numpy array (``xp=np``)."""
    q_lo = q_offset + iq * block_q
    last = xp.minimum(nk - 1, max(kv_valid - 1, 0) // block_k)
    if causal:
        last = xp.minimum(last, (q_lo + block_q - 1) // block_k)
    first = xp.zeros_like(last)
    if window:
        first = xp.maximum(q_lo - window + 1, 0) // block_k
    return first, last


def _lanes(x, n):
    """A lane-replicated (rows, 128) statistic as (rows, n)."""
    if n % LANES == 0:
        return jnp.tile(x, (1, n // LANES))
    return x[:, :1]


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                 scale: float, causal: bool, window: int, softcap: float,
                 block_q: int, block_k: int, nk: int, q_offset: int,
                 kv_valid: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_lo = q_offset + iq * block_q
    k_lo = ik * block_k

    def step(masked: bool):
        v = v_ref[0, 0]
        s = jax.lax.dot_general(q_ref[0, 0], k_ref[0, 0],
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        if masked:
            q_pos = q_lo + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = k_lo + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            mask = k_pos < kv_valid
            if causal:
                mask &= k_pos <= q_pos
            if window:
                mask &= q_pos - k_pos < window
            s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]                                  # (bq, 128)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - _lanes(m_new, block_k))
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = corr * l_scr[...] + p.sum(axis=1, keepdims=True)
        m_scr[...] = m_new
        acc_scr[...] = acc_scr[...] * _lanes(corr, acc_scr.shape[1]) + (
            jax.lax.dot_general(p.astype(v.dtype), v,
                                (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32))

    first, last = _k_blocks(iq, block_q=block_q, block_k=block_k, nk=nk,
                            causal=causal, window=window, q_offset=q_offset,
                            kv_valid=kv_valid)
    run = (first <= ik) & (ik <= last)
    # No element of a whole block is masked.
    whole = k_lo + block_k <= kv_valid
    if causal:
        whole &= k_lo + block_k - 1 <= q_lo
    if window:
        whole &= q_lo + block_q - 1 - k_lo < window

    pl.when(run & whole)(lambda: step(masked=False))
    pl.when(run & jnp.logical_not(whole))(lambda: step(masked=True))

    @pl.when(ik == nk - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] /
                       _lanes(l, acc_scr.shape[1])).astype(o_ref.dtype)


def _tiles(n: int, cap: int) -> list[int]:
    """Block sizes for a length ``n``: the whole length up to one lane
    width, else the multiples of 128 up to ``cap`` that divide ``n`` padded
    to 128 (so a length is never padded past the next multiple of 128)."""
    if n <= LANES:
        return [n]
    n_p = -(-n // LANES) * LANES
    return [b for b in range(LANES, min(cap, n_p) + 1, LANES) if n_p % b == 0]


def flash_blocks(sq: int, sk: int, block_q: int, block_k: int, *,
                 causal: bool = True, window: int = 0, q_offset: int = 0,
                 kv_valid: int | None = None) -> tuple[int, int]:
    """(blocks computed, grid blocks) of one (batch, head) slice: the grid
    steps whose K/V block some row of the q block may attend, of all."""
    nq, nk = -(-sq // block_q), -(-sk // block_k)
    first, last = _k_blocks(np.arange(nq), block_q=block_q, block_k=block_k,
                            nk=nk, causal=causal, window=window,
                            q_offset=q_offset,
                            kv_valid=min(sk if kv_valid is None else kv_valid,
                                         sk), xp=np)
    run = np.broadcast_to(np.maximum(last - first + 1, 0), (nq,))
    return int(run.sum()), nq * nk


def flash_plan(sq: int, sk: int, d: int, dv: int, dtype, *,
               causal: bool = True, window: int = 0, q_offset: int = 0,
               kv_valid: int | None = None) -> tuple[int, int]:
    """(block_q, block_k) for one call at this shape: of the tiles that fit
    ``VMEM_BUDGET``, the pair of the largest area, which pays the fewest
    grid steps' overhead; ties go to fewer computed score elements, then to
    the taller q tile."""
    itemsize = jnp.dtype(dtype).itemsize

    def rank(tiles):
        bq, bk = tiles
        run, _ = flash_blocks(sq, sk, bq, bk, causal=causal, window=window,
                              q_offset=q_offset, kv_valid=kv_valid)
        return bq * bk, -run * bq * bk, bq

    return max(((bq, bk) for bq in _tiles(sq, MAX_BLOCK)
                for bk in _tiles(sk, MAX_BLOCK)
                if vmem_bytes(bq, bk, d, dv, itemsize) <= VMEM_BUDGET),
               key=rank)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "softcap", "scale", "q_offset",
                     "kv_valid", "block_q", "block_k", "interpret"))
def flash_attention_pallas(q, k, v, *, causal=True, window=0, softcap=0.0,
                           scale=None, q_offset=0, kv_valid=None,
                           block_q=None, block_k=None, interpret=False):
    """q, k: (B, S, H or KVH, D); v: (B, Sk, KVH, Dv) -> (B, Sq, H, Dv).

    block_q, block_k: None (the default) takes ``flash_plan``'s tiles."""
    B, Sq, H, D = q.shape
    _, Sk, KVH, _ = k.shape
    Dv = v.shape[-1]
    assert H % KVH == 0
    group = H // KVH
    scale = scale if scale is not None else D ** -0.5
    kv_valid = Sk if kv_valid is None else min(kv_valid, Sk)

    if block_q is None or block_k is None:
        plan = flash_plan(Sq, Sk, D, Dv, q.dtype, causal=causal, window=window,
                          q_offset=q_offset, kv_valid=kv_valid)
        block_q, block_k = block_q or plan[0], block_k or plan[1]
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

    nk = Sk_p // block_k
    grid = (B, H, Sq_p // block_q, nk)
    blocks = functools.partial(
        _k_blocks, block_q=block_q, block_k=block_k, nk=nk, causal=causal,
        window=window, q_offset=q_offset, kv_valid=kv_valid)

    def kv_map(b, h, iq, ik):
        first, last = blocks(iq)
        return b, h // group, jnp.minimum(jnp.maximum(ik, first), last), 0

    kern = functools.partial(
        _attn_kernel, scale=scale, causal=causal, window=window,
        softcap=softcap, block_q=block_q, block_k=block_k, nk=nk,
        q_offset=q_offset, kv_valid=kv_valid)

    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_k, D), kv_map),
            pl.BlockSpec((1, 1, block_k, Dv), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, Dv),
                               lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq_p, Dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
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


def vmem_bytes(block_q: int, block_k: int, d: int, dv: int,
               dtype_bytes: int = 2) -> int:
    """VMEM the kernel holds at these tiles, which ``flash_plan`` keeps
    under ``VMEM_BUDGET``: double-buffered q, k, v and o tiles, the fp32
    score and probability tiles, the fp32 accumulator and the two
    lane-replicated fp32 row statistics."""
    io = 2 * dtype_bytes * (block_q * d + block_k * d + block_k * dv
                            + block_q * dv)
    scores = 2 * 4 * block_q * block_k
    scratch = 4 * (block_q * dv + 2 * block_q * LANES)
    return io + scores + scratch
