"""Pallas TPU grouped (per-expert) matmul for MoE expert FFNs.

MegaBlocks-style grouped GEMM adapted to the TPU: tokens are pre-gathered into
a dense (E, C, d) capacity buffer (sort-based dispatch lives in
``repro.models.moe``), so the kernel is a bank of E independent GEMMs tiled
for the MXU:

grid = (E, C/bc, f/bf, d/bd); the contraction dim is innermost/``arbitrary``
with an fp32 (bc, bf) VMEM accumulator. Block sizes default to 128 (MXU
native) and are clamped to the problem size.

Oracle: ``ref.gmm_naive``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gmm_kernel(x_ref, w_ref, o_ref, acc_scr):
    kd = pl.program_id(3)
    nd = pl.num_programs(3)

    @pl.when(kd == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    x = x_ref[0]                                # (bc, bd)
    w = w_ref[0]                                # (bd, bf)
    acc_scr[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(kd == nd - 1)
    def _emit():
        o_ref[0] = acc_scr[...].astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("block_c", "block_f", "block_d",
                                    "interpret"))
def gmm_pallas(x, w, *, block_c=128, block_f=128, block_d=512,
               interpret=False):
    """x: (E, C, d), w: (E, d, f) -> (E, C, f)."""
    E, C, d = x.shape
    _, _, f = w.shape
    block_c = min(block_c, C)
    block_f = min(block_f, f)
    block_d = min(block_d, d)

    def _pad_to(a, axis, mult):
        pad = (-a.shape[axis]) % mult
        if pad:
            widths = [(0, 0)] * a.ndim
            widths[axis] = (0, pad)
            a = jnp.pad(a, widths)
        return a

    x = _pad_to(_pad_to(x, 1, block_c), 2, block_d)
    w = _pad_to(_pad_to(w, 1, block_d), 2, block_f)
    Cp, dp, fp = x.shape[1], x.shape[2], w.shape[2]

    grid = (E, Cp // block_c, fp // block_f, dp // block_d)
    out = pl.pallas_call(
        _gmm_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_c, block_d),
                         lambda e, ic, jf, kd: (e, ic, kd)),
            pl.BlockSpec((1, block_d, block_f),
                         lambda e, ic, jf, kd: (e, kd, jf)),
        ],
        out_specs=pl.BlockSpec((1, block_c, block_f),
                               lambda e, ic, jf, kd: (e, ic, jf)),
        out_shape=jax.ShapeDtypeStruct((E, Cp, fp), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_c, block_f), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(x, w)
    return out[:, :C, :f]


# ---------------------------------------------------------------------------
# Grouped expert SwiGLU over routed rows (dropless, one device's experts)
# ---------------------------------------------------------------------------


def _expert_ffn_kernel(tile_group_ref, active_ref, x_ref, wg_ref, wu_ref,
                       wd_ref, o_ref):
    @pl.when(pl.program_id(0) < active_ref[0])
    def _tile():
        x = x_ref[...]                                   # (bm, d)
        dims = (((1,), (0,)), ((), ()))
        g = jax.lax.dot_general(x, wg_ref[0], dims,
                                preferred_element_type=jnp.float32)
        u = jax.lax.dot_general(x, wu_ref[0], dims,
                                preferred_element_type=jnp.float32)
        h = (g / (1.0 + jnp.exp(-g)) * u).astype(x.dtype)  # silu(g) * u
        o_ref[...] = jax.lax.dot_general(
            h, wd_ref[0], dims,
            preferred_element_type=jnp.float32).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_m", "interpret"))
def expert_ffn_pallas(x, w_gate, w_up, w_down, tile_group, active_tiles, *,
                      block_m, interpret=False):
    """SwiGLU of routed rows, each under its expert's weights.

    x: (M, d), the rows of each expert in whole tiles of ``block_m`` rows,
    tiles in use first; w_gate, w_up: (G, d, f); w_down: (G, f, d);
    tile_group: (M // block_m,) int32 expert of each tile; active_tiles: (1,)
    int32 tiles in use. -> (M, d); the rows of tiles past ``active_tiles``
    are left unwritten.

    grid = (tiles,), one expert's three whole matrices a step (34.6 MB of
    VMEM, double-buffered, at DeepSeek-V2-Lite's 2048 x 1408).
    Consecutive tiles of one expert keep the same weight blocks, so each
    expert's weights are read once a call. A tile past the ones in use maps
    every block to the last tile in use, so it fetches and writes nothing,
    and its step is skipped.
    """
    M, d = x.shape
    f = w_gate.shape[2]

    def row_map(i, tg, active):
        return jnp.where(i < active[0], i, jnp.maximum(active[0] - 1, 0)), 0

    def w_map(i, tg, active):
        return tg[row_map(i, tg, active)[0]], 0, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(M // block_m,),
        in_specs=[pl.BlockSpec((block_m, d), row_map),
                  pl.BlockSpec((1, d, f), w_map),
                  pl.BlockSpec((1, d, f), w_map),
                  pl.BlockSpec((1, f, d), w_map)],
        out_specs=pl.BlockSpec((block_m, d), row_map))
    return pl.pallas_call(
        _expert_ffn_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=96 << 20),
        interpret=interpret,
    )(tile_group, active_tiles, x, w_gate, w_up, w_down)
