"""Fine-grained mixture-of-experts (DeepSeekMoE / DeepSeek-V2 / Kimi-K2).

Token-choice top-k routing: softmax over every expert's router score in
fp32, greedy top-k, the weights renormalised over the k (``norm_topk_prob``)
or not, times ``routed_scaling_factor``; the shared experts (one SwiGLU of
``num_shared`` experts' width) are added to every token.

One device holds experts ``[experts_offset, experts_offset + experts_held)``
of the ``num_experts`` the router scores: its share under expert
parallelism. It computes only its experts' part of the routed result, and
drops nothing: the assignments that fall on its experts are sorted by expert
into whole row tiles, and one grouped kernel (``kernels.ops.expert_ffn``,
SwiGLU only) runs each expert over its rows. Its buffers are sized from the
token count (all of a token's k choices could fall here), never from experts
x tokens, and long inputs go through in chunks of ``CHUNK_TOKENS``. What the
absent experts add lies on other devices; nothing here stands in for them.

On a mesh the same layer runs under ``jax.shard_map``, once per shard: the
tokens are split over the data axes and replicated over the model axis, and
the held experts are split over the model axis, so each shard holds a share
of the share, at an offset that follows from its index on that axis. Each
shard computes its experts' part for its data shard's tokens, and a ``psum``
over the model axis adds the parts up.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..kernels import ops
from ..runtime.sharding import batch_axes
from .common import ParamSpec


@dataclass(frozen=True)
class DistContext:
    """How apply-fns should distribute themselves (None mesh = local)."""

    mesh: object = None
    data_axes: tuple = ("data",)     # batch axes (may include 'pod')
    model_axis: str = "model"


LOCAL = DistContext()


def make_dist(mesh) -> DistContext:
    """The context of a mesh with a ``model`` axis and batch axes ``pod``
    and/or ``data``; LOCAL for no mesh."""
    if mesh is None:
        return LOCAL
    return DistContext(mesh=mesh, data_axes=batch_axes(mesh))


def moe_specs(cfg) -> dict:
    d, m = cfg.d_model, cfg.moe
    spec = {
        "router": ParamSpec((d, m.num_experts), ("router_in", "experts_in"),
                            dtype=jnp.float32),
        "w_gate": ParamSpec((m.held, d, m.d_ff_expert),
                            ("experts", "embed", "expert_mlp")),
        "w_up": ParamSpec((m.held, d, m.d_ff_expert),
                          ("experts", "embed", "expert_mlp")),
        "w_down": ParamSpec((m.held, m.d_ff_expert, d),
                            ("experts", "expert_mlp", "embed")),
    }
    if m.num_shared:
        f_sh = m.num_shared * m.d_ff_expert
        spec["shared"] = {
            "w_gate": ParamSpec((d, f_sh), ("embed", "mlp")),
            "w_up": ParamSpec((d, f_sh), ("embed", "mlp")),
            "w_down": ParamSpec((f_sh, d), ("mlp", "embed")),
        }
    return spec


def _route(x2d, router_w, cfg):
    """Top-k routing over all ``num_experts``. x2d: (T, d). Returns
    topk_idx (T,k), fp32 weights (T,k), aux."""
    m = cfg.moe
    logits = jnp.einsum("td,de->te", x2d.astype(jnp.float32),
                        router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    topk_p, topk_idx = jax.lax.top_k(probs, m.top_k)
    topk_w = topk_p
    if m.norm_topk_prob:
        topk_w = topk_p / jnp.maximum(topk_p.sum(-1, keepdims=True), 1e-9)
    topk_w = topk_w * m.routed_scaling_factor
    # load-balance aux (Switch-style): E * sum_e f_e * p_e
    E = m.num_experts
    f_e = jnp.zeros((E,), jnp.float32).at[topk_idx.reshape(-1)].add(
        1.0 / (topk_idx.size))
    p_e = probs.mean(0)
    aux = E * jnp.sum(f_e * p_e) * m.router_aux_coef
    return topk_idx, topk_w, aux


CHUNK_TOKENS = 8192  # tokens per pass of the one-device expert layer


def row_tile(n_tokens: int, cfg) -> int:
    """Rows of one tile of the grouped kernel: 128 (the MXU's side) where an
    expert expects a tile's worth of rows under uniform routing, else 16
    (decode), so that an expert with a few rows pads to 16 and not 128."""
    m = cfg.moe
    return 128 if n_tokens * m.top_k >= 128 * m.num_experts else 16


def dispatch_rows(n_tokens: int, top_k: int, held: int, bm: int) -> int:
    """Rows of the dispatch buffer: every choice of every token fits, and
    each held expert's last tile may be part padding."""
    return (-(-n_tokens * min(top_k, held) // bm) + held) * bm


def _dispatch_held(topk_idx, n: int, offset, bm: int):
    """The assignments that fall on experts ``[offset, offset + n)``, sorted
    by expert into whole tiles of ``bm`` rows, the tiles in use first.

    Returns slot (T*k,) int32 (buffer row of each assignment, M where its
    expert is not held here), row_token (M,) int32 (token of each buffer
    row, T for padding), tile_group (M/bm,) int32, active (1,) int32 tiles
    in use, group_rows (n,) int32 rows (padding included) of each expert,
    and the number of held assignments. M = ``dispatch_rows``."""
    T, k = topk_idx.shape
    M = dispatch_rows(T, k, n, bm)
    n_tiles = M // bm
    e = topk_idx.reshape(-1) - offset
    grp = jnp.where((e >= 0) & (e < n), e, n)            # n: held elsewhere
    counts = jnp.zeros((n + 1,), jnp.int32).at[grp].add(1)
    tiles = -(-counts[:n] // bm)
    tile_end = jnp.cumsum(tiles)
    first_row = (tile_end - tiles) * bm
    order = jnp.argsort(grp, stable=True).astype(jnp.int32)
    g_sorted = grp[order]
    rank = jnp.arange(T * k, dtype=jnp.int32) - (
        jnp.cumsum(counts) - counts)[g_sorted]
    dest = jnp.where(g_sorted < n,
                     first_row[jnp.minimum(g_sorted, n - 1)] + rank, M)
    slot = jnp.zeros((T * k,), jnp.int32).at[order].set(dest)
    row_token = jnp.full((M,), T, jnp.int32).at[dest].set(order // k,
                                                         mode="drop")
    tile_group = jnp.minimum(
        jnp.searchsorted(tile_end, jnp.arange(n_tiles), side="right"),
        n - 1).astype(jnp.int32)
    return (slot, row_token, tile_group, tile_end[-1:], tiles * bm,
            counts[:n].sum())


def _moe_held_chunk(x2d, topk_idx, topk_w, p, cfg, offset):
    """The held experts' part of the routed result for one chunk of tokens:
    the experts of ``p``'s weights, from ``offset`` on. Returns (out (T, d),
    [held assignments, rows computed, held experts with a row]): the kernel
    reads each such expert's weights once, and no other's."""
    T, d = x2d.shape
    bm = row_tile(T, cfg)
    slot, row_token, tile_group, active, group_rows, routed = _dispatch_held(
        topk_idx, p["w_gate"].shape[0], offset, bm)
    x_pad = jnp.concatenate([x2d, jnp.zeros((1, d), x2d.dtype)], 0)
    y = ops.expert_ffn(x_pad[row_token], p["w_gate"], p["w_up"],
                       p["w_down"], tile_group, active, group_rows,
                       block_m=bm)
    y_pad = jnp.concatenate([y, jnp.zeros((1, d), y.dtype)], 0)
    y_tok = y_pad[slot].reshape(T, topk_idx.shape[1], d)  # elsewhere -> 0
    out = jnp.einsum("tkd,tk->td", y_tok.astype(jnp.float32),
                     topk_w).astype(x2d.dtype)
    hit = (group_rows > 0).sum(dtype=jnp.int32)
    return out, jnp.stack([routed, active[0] * bm, hit])


def _moe_local(x2d, p, cfg, offset):
    """One device's MoE: route over all experts, then the held experts (from
    ``offset`` on) over their rows, dropless, in chunks of ``CHUNK_TOKENS``
    tokens. Returns (out, aux, rows): rows = int32 [assignments that fell on
    the held experts, rows the grouped kernel computed, held experts it read
    the weights of], summed over the chunks."""
    T, d = x2d.shape
    topk_idx, topk_w, aux = _route(x2d, p["router"], cfg)
    if T <= CHUNK_TOKENS:
        out, rows = _moe_held_chunk(x2d, topk_idx, topk_w, p, cfg, offset)
        return out, aux, rows
    n, pad = -(-T // CHUNK_TOKENS), -T % CHUNK_TOKENS
    chunks = lambda a, fill: jnp.pad(
        a, ((0, pad),) + ((0, 0),) * (a.ndim - 1), constant_values=fill
    ).reshape(n, CHUNK_TOKENS, *a.shape[1:])
    # padding tokens choose an expert no device holds
    out, rows = jax.lax.map(
        lambda c: _moe_held_chunk(*c, p, cfg, offset),
        (chunks(x2d, 0), chunks(topk_idx, -1), chunks(topk_w, 0)))
    return out.reshape(n * CHUNK_TOKENS, d)[:T], aux, rows.sum(0)


def _moe_shard(x2d, router, w_gate, w_up, w_down, *, cfg, dist):
    """``shard_map`` body: one data shard's tokens through this model
    shard's experts, the parts added up over the model axis."""
    ax = dist.model_axis
    offset = cfg.moe.experts_offset + jax.lax.axis_index(ax) * w_gate.shape[0]
    p = {"router": router, "w_gate": w_gate, "w_up": w_up, "w_down": w_down}
    out, aux, rows = _moe_local(x2d, p, cfg, offset)
    return (jax.lax.psum(out, ax), jax.lax.pmean(aux, dist.data_axes),
            jax.lax.psum(rows, dist.data_axes + (ax,)))


def apply_moe(p, x, *, cfg, dist: DistContext = LOCAL):
    """x: (B, S, d) -> (out (B, S, d), aux_loss scalar, rows int32[3]):
    rows counts the assignments that fell on the held experts, the rows the
    grouped kernel computed and the experts whose weights it read, summed
    over the shards on a mesh. On a mesh the expert weights are split over
    its model axis at the ``shard_map`` boundary, and any other sharding of
    theirs is gathered there."""
    B, S, d = x.shape
    x2d = x.reshape(B * S, d)
    m = cfg.moe
    if dist.mesh is None:
        out, aux, rows = _moe_local(x2d, p, cfg, m.experts_offset)
    else:
        experts = P(dist.model_axis)
        # check_vma off: the Pallas kernel's output shape names no mesh axes
        out, aux, rows = jax.shard_map(
            partial(_moe_shard, cfg=cfg, dist=dist), mesh=dist.mesh,
            in_specs=(P(dist.data_axes), P(), experts, experts, experts),
            out_specs=(P(dist.data_axes), P(), P()), check_vma=False,
        )(x2d, p["router"], p["w_gate"], p["w_up"], p["w_down"])
    if m.num_shared:
        from .ffn import apply_ffn
        out = out + apply_ffn(p["shared"], x, cfg=cfg).reshape(B * S, d)
    return out.reshape(B, S, d), aux, rows
