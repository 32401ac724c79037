"""Pallas TPU grouped expert SwiGLU for the MoE layer's routed rows.

The rows of each held expert arrive sorted by expert in whole tiles of
``block_m`` rows (the dropless dispatch lives in ``repro.models.moe``), the
tiles in use first. One grid step runs one tile through its expert's gate,
up and down projections, with the hidden kept in VMEM and fp32
accumulation; consecutive tiles of one expert keep its weight blocks, so
each expert's weights are read once a call.

Oracle: ``ref.expert_ffn_ragged``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


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
