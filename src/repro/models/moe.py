"""Fine-grained mixture-of-experts (DeepSeekMoE / DeepSeek-V2 / Kimi-K2).

Token-choice top-k routing: softmax over every expert's router score in
fp32, greedy top-k, the weights renormalised over the k (``norm_topk_prob``)
or not, times ``routed_scaling_factor``; the shared experts (one SwiGLU of
``num_shared`` experts' width) are added to every token.

One device (``dist.mesh is None``) holds experts ``[experts_offset,
experts_offset + experts_held)`` of the ``num_experts`` the router scores:
its share under expert parallelism. It computes only its experts' part of
the routed result, and drops nothing: the assignments that fall on its
experts are sorted by expert into whole row tiles, and one grouped kernel
(``kernels.ops.expert_ffn``, SwiGLU only) runs each expert over its rows. Its
buffers are sized from the token count (all of a token's k choices could
fall here), never from experts x tokens, and long inputs go through in
chunks of ``CHUNK_TOKENS``. What the absent experts add lies on other
devices; nothing here stands in for them or their exchange.

Distribution (TPU-native EP, every expert sharded over the model axis): the
expert interior runs under ``jax.shard_map`` — each data shard routes its
local tokens, builds an (E, C_local, d) capacity buffer (assignments past a
capacity are dropped), and a **tiled all-to-all over the model axis**
exchanges it for an (E_local, C_local * ep, d) buffer (the DeepSeek-EP
dispatch pattern mapped to ``jax.lax.all_to_all``). Expert weights live
sharded on the model axis; optionally they are additionally FSDP-sharded
over the data axis and all-gathered just-in-time inside the shard_map body.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..kernels import ops
from .common import ParamSpec


@dataclass(frozen=True)
class DistContext:
    """How apply-fns should distribute themselves (None mesh = local)."""

    mesh: object = None
    data_axes: tuple = ("data",)     # batch axes (may include 'pod')
    model_axis: str = "model"
    fsdp_experts: bool = False       # expert weights FSDP'd over data axis
    ep: bool = True                  # expert-parallel all-to-all on
    # serving (§Perf B1): expert weights stored 2D — EP over the model axis,
    # f (expert_mlp) TP over the data axes. gate/up produce f-sharded
    # hidden locally; the down projection contracts f and psums over data.
    expert_tp: bool = False
    # serving (§Perf B2): cap per-expert capacity at decode time. With a
    # handful of tokens per shard, the default floor (8) pads the dispatch
    # buffers and the EP all-to-all ~8x. 0 = default capacity rule.
    capacity_cap: int = 0


LOCAL = DistContext()


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


def _capacity(n_tokens: int, cfg, cap: int = 0) -> int:
    m = cfg.moe
    c = int(n_tokens * m.top_k * m.capacity_factor / m.num_experts) + 1
    c = max(8, -(-c // 8) * 8)  # round up to 8
    if cap:
        c = min(c, max(cap, 1))
    return c


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


def _dispatch_indices(topk_idx, E: int, C: int):
    """Sort-based dispatch metadata.

    Returns gather_idx (E, C) int32 (token index per slot; T = dropped slot)
    and, aligned with the flattened (T*k,) assignment order:
    es (expert id), pos (slot), keep (bool).
    """
    T, k = topk_idx.shape
    e_flat = topk_idx.reshape(-1)                       # (T*k,)
    order = jnp.argsort(e_flat, stable=True)
    es = e_flat[order]
    ts = (jnp.arange(T * k) // k)[order]
    counts = jnp.zeros((E,), jnp.int32).at[e_flat].add(1)
    starts = jnp.cumsum(counts) - counts                # exclusive cumsum
    pos = jnp.arange(T * k, dtype=jnp.int32) - starts[es]
    keep = pos < C
    gather_idx = jnp.full((E, C), T, jnp.int32)
    gather_idx = gather_idx.at[
        jnp.where(keep, es, E - 1),
        jnp.where(keep, pos, C - 1)].set(jnp.where(keep, ts, T),
                                         mode="drop")
    # inverse map for combine: slot of assignment (t, j)
    inv = jnp.zeros((T * k,), jnp.int32)
    inv = inv.at[order].set(jnp.where(keep, es * C + pos, E * C))
    return gather_idx, inv


def _expert_ffn(x_e, wg, wu, wd, cfg):
    """x_e: (E?, C?, d) grouped tokens -> grouped outputs, via Pallas gmm."""
    act = jax.nn.silu if cfg.mlp_act == "silu" else jax.nn.gelu
    g = ops.gmm(x_e, wg)
    u = ops.gmm(x_e, wu)
    return ops.gmm((act(g.astype(jnp.float32)) * u.astype(jnp.float32)
                    ).astype(x_e.dtype), wd)


CHUNK_TOKENS = 8192  # tokens per pass of the one-device expert layer


def _row_tile(n_tokens: int, cfg) -> int:
    """Rows of one tile of the grouped kernel: 128 (the MXU's side) where an
    expert expects a tile's worth of rows under uniform routing, else 16
    (decode), so that an expert with a few rows pads to 16 and not 128."""
    m = cfg.moe
    return 128 if n_tokens * m.top_k >= 128 * m.num_experts else 16


def _dispatch_held(topk_idx, cfg, bm: int):
    """The assignments that fall on this device's experts, sorted by expert
    into whole tiles of ``bm`` rows, the tiles in use first.

    Returns slot (T*k,) int32 (buffer row of each assignment, M where its
    expert is not held here), row_token (M,) int32 (token of each buffer
    row, T for padding), tile_group (M/bm,) int32, active (1,) int32 tiles
    in use, group_rows (held,) int32 rows (padding included) of each
    expert, and the number of held assignments. M = (ceil(T*kk/bm) + held)
    * bm with kk = min(k, held): every choice of every token fits."""
    m = cfg.moe
    T, k = topk_idx.shape
    n = m.held
    n_tiles = -(-T * min(k, n) // bm) + n
    M = n_tiles * bm
    e = topk_idx.reshape(-1) - m.experts_offset
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


def _moe_held_chunk(x2d, topk_idx, topk_w, p, cfg):
    """This device's experts' part of the routed result for one chunk of
    tokens. Returns (out (T, d), [held assignments, rows computed, held
    experts with a row]): the kernel reads each such expert's weights
    once, and no other's."""
    T, d = x2d.shape
    bm = _row_tile(T, cfg)
    slot, row_token, tile_group, active, group_rows, routed = _dispatch_held(
        topk_idx, cfg, bm)
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


def _moe_local(x2d, p, cfg):
    """One device's MoE: route over all experts, then this device's experts
    over their rows, dropless, in chunks of ``CHUNK_TOKENS`` tokens. Returns
    (out, aux, rows): rows = int32 [assignments that fell on the held
    experts, rows the grouped kernel computed, held experts it read the
    weights of], summed over the chunks."""
    T, d = x2d.shape
    topk_idx, topk_w, aux = _route(x2d, p["router"], cfg)
    if T <= CHUNK_TOKENS:
        out, rows = _moe_held_chunk(x2d, topk_idx, topk_w, p, cfg)
        return out, aux, rows
    n, pad = -(-T // CHUNK_TOKENS), -T % CHUNK_TOKENS
    chunks = lambda a, fill: jnp.pad(
        a, ((0, pad),) + ((0, 0),) * (a.ndim - 1), constant_values=fill
    ).reshape(n, CHUNK_TOKENS, *a.shape[1:])
    # padding tokens choose an expert no device holds
    out, rows = jax.lax.map(
        lambda c: _moe_held_chunk(*c, p, cfg),
        (chunks(x2d, 0), chunks(topk_idx, -1), chunks(topk_w, 0)))
    return out.reshape(n * CHUNK_TOKENS, d)[:T], aux, rows.sum(0)


def _moe_ep_body(x_local, router_w, wg, wu, wd, *, cfg, dist: DistContext):
    """shard_map body: x_local (T_loc, d); expert weights local (E_loc,...)."""
    m = cfg.moe
    T, d = x_local.shape
    C = _capacity(T, cfg, dist.capacity_cap)
    ax = dist.model_axis
    if dist.fsdp_experts and not dist.expert_tp:
        wg = jax.lax.all_gather(wg, dist.data_axes, axis=1, tiled=True)
        wu = jax.lax.all_gather(wu, dist.data_axes, axis=1, tiled=True)
        wd = jax.lax.all_gather(wd, dist.data_axes, axis=2, tiled=True)

    topk_idx, topk_w, aux = _route(x_local, router_w, cfg)
    gather_idx, inv = _dispatch_indices(topk_idx, m.num_experts, C)
    x_pad = jnp.concatenate([x_local, jnp.zeros((1, d), x_local.dtype)], 0)
    x_e = x_pad[gather_idx]                              # (E, C, d)
    # dispatch: split experts across shards, concat capacity
    x_e = jax.lax.all_to_all(x_e, ax, split_axis=0, concat_axis=1,
                             tiled=True)                 # (E_loc, C*ep, d)
    if dist.expert_tp:
        # weights (E_loc, d, f_loc)/(E_loc, f_loc, d): gate/up emit an
        # f-sharded hidden locally; down contracts f -> psum over data.
        act = jax.nn.silu if cfg.mlp_act == "silu" else jax.nn.gelu
        g = ops.gmm(x_e, wg)
        u = ops.gmm(x_e, wu)
        h = (act(g.astype(jnp.float32)) * u.astype(jnp.float32)
             ).astype(x_e.dtype)
        y_e = jax.lax.psum(ops.gmm(h, wd), dist.data_axes)
    else:
        y_e = _expert_ffn(x_e, wg, wu, wd, cfg)
    # combine: reverse exchange
    y_e = jax.lax.all_to_all(y_e, ax, split_axis=1, concat_axis=0,
                             tiled=True)                 # (E, C, d)
    E = m.num_experts
    y_flat = jnp.concatenate(
        [y_e.reshape(E * C, d), jnp.zeros((1, d), y_e.dtype)], 0)
    y_tok = y_flat[inv].reshape(T, m.top_k, d)
    out = jnp.einsum("tkd,tk->td", y_tok.astype(jnp.float32),
                     topk_w.astype(jnp.float32)).astype(x_local.dtype)
    aux = jax.lax.pmean(aux, dist.data_axes)
    aux = jax.lax.pmean(aux, ax)
    return out, aux


def apply_moe(p, x, *, cfg, dist: DistContext = LOCAL):
    """x: (B, S, d) -> (out (B, S, d), aux_loss scalar, rows int32[3]):
    rows counts, on one device, the assignments that fell on its experts,
    the rows its grouped kernel computed and the experts whose weights the
    kernel read (zeros on the mesh path)."""
    B, S, d = x.shape
    x2d = x.reshape(B * S, d)
    m = cfg.moe

    rows = jnp.zeros((3,), jnp.int32)
    if dist.mesh is None or not dist.ep:
        out, aux, rows = _moe_local(x2d, p, cfg)
    else:
        if m.held != m.num_experts:
            raise NotImplementedError(
                "the expert-parallel path shards all num_experts over the "
                "model axis; a config holding a share runs on one device")
        if dist.expert_tp:     # 2D: EP over model, f TP'd over data
            ep_w_spec = P(dist.model_axis, None, dist.data_axes)
            ep_wd_spec = P(dist.model_axis, dist.data_axes, None)
        elif dist.fsdp_experts:
            ep_w_spec = P(dist.model_axis, dist.data_axes, None)
            ep_wd_spec = P(dist.model_axis, None, dist.data_axes)
        else:
            ep_w_spec = ep_wd_spec = P(dist.model_axis, None, None)
        out, aux = jax.shard_map(
            lambda xl, rw, wg, wu, wd: _moe_ep_body(
                xl, rw, wg, wu, wd, cfg=cfg, dist=dist),
            mesh=dist.mesh,
            in_specs=(P(dist.data_axes, None), P(None, None),
                      ep_w_spec, ep_w_spec, ep_wd_spec),
            out_specs=(P(dist.data_axes, None), P()),
            check_vma=False,
        )(x2d, p["router"], p["w_gate"], p["w_up"], p["w_down"])

    if m.num_shared:
        from .ffn import apply_ffn
        out = out + apply_ffn(p["shared"], x, cfg=cfg).reshape(B * S, d)
    return out.reshape(B, S, d), aux, rows
