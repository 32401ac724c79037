"""Pallas TPU kernel for the Mamba2 SSD (state-space duality) scan.

TPU adaptation of the chunked SSD algorithm (arXiv:2405.21060): the sequence
is tiled into chunks of Q tokens; within a chunk the recurrence is expanded
into a dense (Q x Q) decay-masked matmul (MXU work), while the cross-chunk
recurrence is carried in an fp32 VMEM scratch state of shape (P, N) across the
innermost (``arbitrary``) grid dimension. This replaces the GPU
warp-level-scan formulation with a systolic-friendly block recurrence.

grid = (B, H, L/Q). Inputs are laid out head-major so each program instance
streams (Q, P) / (Q, N) tiles through VMEM.

Oracle: ``ref.ssd_naive`` / ``ref.ssd_chunked``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(u_ref, cum_col_ref, cum_row_ref, b_ref, c_ref, y_ref,
                state_ref, s_scr, *, chunk: int):
    ic = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ic == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    u = u_ref[0, 0]                            # (Q, P) fp32
    cum_c = cum_col_ref[0, 0]                  # (Q, 1) in-chunk cumsum of dt*A
    cum_r = cum_row_ref[0, 0, 0]               # (1, Q) the same, as a row
    b = b_ref[0, 0].astype(jnp.float32)        # (Q, N)
    c = c_ref[0, 0].astype(jnp.float32)        # (Q, N)

    cb = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (Q, Q)
    decay = jnp.exp(cum_c - cum_r)
    ti = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    si = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    w = jnp.where(si <= ti, cb * decay, 0.0)
    y = jax.lax.dot_general(w, u, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (Q, P)
    # carried-state contribution: y_t += exp(cum_t) * (c_t . S_prev)
    y_state = jax.lax.dot_general(c, s_scr[...], (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    y = y + y_state * jnp.exp(cum_c)
    y_ref[0, 0] = y.astype(y_ref.dtype)

    # state update: S = exp(tot) * S_prev + sum_s exp(tot - cum_s) u_s b_s^T
    # last element of the row, read by a masked lane reduction (a static
    # slice at lane Q-1 leaves a layout the TPU cannot broadcast)
    last = jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1) == chunk - 1
    tot = jnp.sum(jnp.where(last, cum_r, 0.0), axis=1, keepdims=True)  # (1, 1)
    w_end = jnp.exp(tot - cum_c)               # (Q, 1)
    s_loc = jax.lax.dot_general(u * w_end, b, (((0,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (P, N)
    s_scr[...] = s_scr[...] * jnp.exp(tot) + s_loc

    @pl.when(ic == nc - 1)
    def _emit_state():
        state_ref[0, 0] = s_scr[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan_pallas(x, dt, a_log, b, c, d_skip, *, chunk=128,
                    interpret=False):
    """Same contract as ``ref.ssd_chunked``.

    x: (B, L, H, P); dt: (B, L, H); a_log, d_skip: (H,);
    b, c: (B, L, G, N). Returns y (B, L, H, P), state (B, H, P, N) fp32.
    """
    B, L, H, P = x.shape
    G, N = b.shape[2], b.shape[3]
    rep = H // G
    chunk = min(chunk, L)
    assert L % chunk == 0, f"L={L} % chunk={chunk} != 0"
    nc = L // chunk

    A = -jnp.exp(a_log.astype(jnp.float32))
    dtf = dt.astype(jnp.float32)
    la = (dtf * A[None, None]).transpose(0, 2, 1)           # (B, H, L)
    # In-chunk cumulative decay, handed to the kernel in two layouts (a
    # column and a row per chunk) so every block's last two dims satisfy
    # the TPU's (8, 128) tiling rule and the kernel needs no cumsum or
    # vector transpose of its own.
    cum = jnp.cumsum(la.reshape(B, H, nc, chunk), axis=-1)
    cum_col = cum.reshape(B, H, L, 1)
    cum_row = cum.reshape(B, H, nc, 1, chunk)
    u = (x.astype(jnp.float32) * dtf[..., None]).transpose(0, 2, 1, 3)
    bt = b.transpose(0, 2, 1, 3)                            # (B, G, L, N)
    ct = c.transpose(0, 2, 1, 3)

    grid = (B, H, nc)
    y, state = pl.pallas_call(
        functools.partial(_ssd_kernel, chunk=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda ib, ih, ic: (ib, ih, ic, 0)),
            pl.BlockSpec((1, 1, chunk, 1), lambda ib, ih, ic: (ib, ih, ic, 0)),
            pl.BlockSpec((1, 1, 1, 1, chunk),
                         lambda ib, ih, ic: (ib, ih, ic, 0, 0)),
            pl.BlockSpec((1, 1, chunk, N),
                         lambda ib, ih, ic, r=rep: (ib, ih // r, ic, 0)),
            pl.BlockSpec((1, 1, chunk, N),
                         lambda ib, ih, ic, r=rep: (ib, ih // r, ic, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda ib, ih, ic: (ib, ih, ic, 0)),
            pl.BlockSpec((1, 1, P, N), lambda ib, ih, ic: (ib, ih, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, L, P), x.dtype),
            jax.ShapeDtypeStruct((B, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(u, cum_col, cum_row, bt, ct)

    y = y.transpose(0, 2, 1, 3)
    y = y + x.astype(jnp.float32).astype(y.dtype) * \
        d_skip.astype(y.dtype)[None, None, :, None]
    return y, state
