"""Architecture-generic decoder stack: layer plans, scan-over-layers, caches.

Every assigned architecture is expressed as a *layer plan* — a tuple of
``GroupDesc`` entries; each group is scanned ``repeat`` times over stacked
per-layer parameters (compile-time O(1) in depth). Heterogeneous depth
patterns (gemma2 local/global alternation, DeepSeek first-k-dense, Llama-3.2
cross-attn interleave, Zamba2 shared block) become multi-block groups.

Modes: ``train`` (no cache), ``prefill`` (flash attention over the prompt),
``decode`` (single-token step over the cache / SSM state). With a cache, the
layer scan reads it and emits only what each layer computed: the attention
blocks' new K/V rows (latent attention's c_kv / k_pe rows), the SSM blocks'
new state, the cross-attention K/V of a prefill. ``forward`` then writes the
rows into the stacked cache at ``cache_index`` and replaces the rest whole;
the serving steps donate the cache, so the write happens in place. A model
with expert layers also carries ``moe_rows`` in its cache: its experts'
routed rows, computed rows and weight reads, summed over layers and steps,
one row for prefill and one for decode. Prefill
evaluates the head at the last position only.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from .attention import (apply_attention, attention_specs, compute_cross_kv,
                        cross_kv_specs)
from .common import (ParamSpec, apply_norm, norm_spec, softcap)
from .ffn import apply_ffn, ffn_specs
from .mla import apply_mla, mla_specs
from .moe import DistContext, LOCAL, apply_moe, moe_specs
from .ssm import (apply_ssm, apply_ssm_decode, init_ssm_state,
                  ssm_specs)


@dataclass(frozen=True)
class BlockDesc:
    kind: str            # attn | mla | ffn | moe | ssm | cross_attn | parallel | shared_attn
    window: int = 0
    d_ff: int = 0        # ffn width override (0 -> cfg.d_ff)
    causal: bool = True


@dataclass(frozen=True)
class GroupDesc:
    repeat: int
    blocks: tuple[BlockDesc, ...]


A, F, S = BlockDesc("attn"), BlockDesc("ffn"), BlockDesc("ssm")
ATTN_KINDS = ("attn", "mla", "parallel", "shared_attn")  # blocks with a KV cache


def layer_plan(cfg) -> tuple[GroupDesc, ...]:
    if cfg.family == "ssm":
        return (GroupDesc(cfg.n_layers, (S,)),)
    if cfg.family == "hybrid":
        per, n = cfg.shared_attn_every, cfg.n_layers
        full, rest = divmod(n, per)
        groups = [GroupDesc(full, tuple([S] * per) + (BlockDesc("shared_attn"),))]
        if rest:
            groups.append(GroupDesc(rest, (S,)))
        return tuple(groups)
    if cfg.family == "vlm":
        ce = cfg.vision.cross_every
        assert cfg.n_layers % ce == 0
        blocks = tuple([A, F] * (ce - 1)) + (BlockDesc("cross_attn"), F)
        return (GroupDesc(cfg.n_layers // ce, blocks),)
    if cfg.family == "encdec":
        return (GroupDesc(cfg.n_layers, (A, BlockDesc("cross_attn"), F)),)
    if cfg.parallel_block:
        return (GroupDesc(cfg.n_layers, (BlockDesc("parallel"),)),)
    if cfg.alt_local_global:
        assert cfg.n_layers % 2 == 0
        return (GroupDesc(cfg.n_layers // 2,
                          (BlockDesc("attn", window=cfg.sliding_window), F,
                           A, F)),)
    if cfg.family == "moe":
        m = cfg.moe
        attn = BlockDesc("mla") if cfg.kv_lora_rank else A
        groups = []
        if m.first_k_dense:
            groups.append(GroupDesc(
                m.first_k_dense, (attn, BlockDesc("ffn", d_ff=m.d_ff_dense))))
        groups.append(GroupDesc(cfg.n_layers - m.first_k_dense,
                                (attn, BlockDesc("moe"))))
        return tuple(groups)
    # plain dense decoder
    w = cfg.sliding_window
    attn = BlockDesc("attn", window=w) if w else A
    return (GroupDesc(cfg.n_layers, (attn, F)),)


def encoder_plan(cfg) -> tuple[GroupDesc, ...]:
    return (GroupDesc(cfg.n_encoder_layers,
                      (BlockDesc("attn", causal=False), F)),)


# ---------------------------------------------------------------------------
# Specs
# ---------------------------------------------------------------------------


def _block_specs(cfg, b: BlockDesc) -> dict:
    if b.kind == "shared_attn":
        return {}  # parameters live at the top level (tied across repeats)
    spec: dict = {"norm": norm_spec(cfg)}
    if cfg.post_block_norm:
        spec["post_norm"] = norm_spec(cfg)
    if b.kind == "attn":
        spec["attn"] = attention_specs(cfg)
    elif b.kind == "mla":
        spec["attn"] = mla_specs(cfg)
    elif b.kind == "ffn":
        spec["ffn"] = ffn_specs(cfg, d_ff=b.d_ff or cfg.d_ff)
    elif b.kind == "moe":
        spec["moe"] = moe_specs(cfg)
    elif b.kind == "ssm":
        spec["ssm"] = ssm_specs(cfg)
    elif b.kind == "cross_attn":
        spec["attn"] = attention_specs(cfg)
        spec["cross_kv"] = cross_kv_specs(cfg, cfg.d_model)
    elif b.kind == "parallel":
        spec["attn"] = attention_specs(cfg)
        spec["ffn"] = ffn_specs(cfg)
    else:
        raise ValueError(b.kind)
    return spec


def _group_specs(cfg, gd: GroupDesc) -> dict:
    from .common import stack_specs
    blocks = {f"b{i}": _block_specs(cfg, b) for i, b in enumerate(gd.blocks)}
    return stack_specs(blocks, gd.repeat)


def lm_specs(cfg) -> dict:
    spec: dict = {
        "embed": ParamSpec((cfg.vocab_size, cfg.d_model), ("vocab", "embed"),
                           init="embed", scale=0.02),
        "final_norm": norm_spec(cfg),
        "groups": {f"g{i}": _group_specs(cfg, gd)
                   for i, gd in enumerate(layer_plan(cfg))},
    }
    if not cfg.tie_embeddings:
        spec["lm_head"] = ParamSpec((cfg.d_model, cfg.vocab_size),
                                    ("embed", "vocab"))
    if cfg.family == "vlm":
        spec["vision_proj"] = ParamSpec((cfg.vision.d_vision, cfg.d_model),
                                        ("vision_embed", "embed"))
    if cfg.family == "hybrid":
        spec["shared"] = {
            "norm": norm_spec(cfg),
            "attn": attention_specs(cfg),
            "ffn": ffn_specs(cfg),
            "ffn_norm": norm_spec(cfg),
        }
    if cfg.family == "encdec":
        spec["encoder"] = {
            "in_proj": ParamSpec((cfg.d_model, cfg.d_model),
                                 ("src_embed", "embed")),
            "final_norm": norm_spec(cfg),
            "groups": {f"g{i}": _group_specs(cfg, gd)
                       for i, gd in enumerate(encoder_plan(cfg))},
        }
    return spec


# ---------------------------------------------------------------------------
# Caches
# ---------------------------------------------------------------------------


def init_cache(cfg, batch: int, max_len: int, *, enc_len: int = 0,
               kv_dtype=jnp.bfloat16) -> dict:
    """Decode cache pytree mirroring the layer plan."""
    hd = cfg.head_dim_
    kvh = cfg.n_kv_heads

    def attn_cache(repeat):
        shape = (repeat, batch, max_len, kvh, hd)
        return {"k": jnp.zeros(shape, kv_dtype), "v": jnp.zeros(shape, kv_dtype)}

    def mla_cache(repeat):
        shape = (repeat, batch, max_len)
        return {"c_kv": jnp.zeros(shape + (cfg.kv_lora_rank,), kv_dtype),
                "k_pe": jnp.zeros(shape + (cfg.qk_rope_head_dim,), kv_dtype)}

    def cross_cache(repeat):
        shape = (repeat, batch, enc_len, kvh, hd)
        return {"ck": jnp.zeros(shape, kv_dtype), "cv": jnp.zeros(shape, kv_dtype)}

    def ssm_cache(repeat):
        st = init_ssm_state(cfg, batch, repeat)
        return st

    groups = {}
    for i, gd in enumerate(layer_plan(cfg)):
        blocks = {}
        for j, b in enumerate(gd.blocks):
            if b.kind == "mla":
                blocks[f"b{j}"] = mla_cache(gd.repeat)
            elif b.kind in ATTN_KINDS:
                blocks[f"b{j}"] = attn_cache(gd.repeat)
            elif b.kind == "cross_attn":
                blocks[f"b{j}"] = cross_cache(gd.repeat)
            elif b.kind == "ssm":
                blocks[f"b{j}"] = ssm_cache(gd.repeat)
        groups[f"g{i}"] = blocks
    cache = {"groups": groups}
    if _has_moe(cfg):
        cache["moe_rows"] = jnp.zeros((2, 3), jnp.int32)  # prefill, decode
    return cache


def _has_moe(cfg) -> bool:
    return any(b.kind == "moe" for gd in layer_plan(cfg) for b in gd.blocks)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _apply_block(bp, x, b: BlockDesc, *, cfg, dist, mode, cache, cache_index,
                 cross_states, shared_params, positions):
    """One residual block. Returns (x, cache_out|None, aux, rows|None):
    cache_out is what ``_write_cache`` puts into this block's cache, rows an
    expert layer's [routed rows, computed rows, experts read] counts."""
    aux = jnp.zeros((), jnp.float32)
    new_cache = rows = None

    def maybe_post(out, p):
        return apply_norm(p["post_norm"], out, cfg) if cfg.post_block_norm else out

    if b.kind in ("attn", "shared_attn"):
        p = shared_params if b.kind == "shared_attn" else bp
        h = apply_norm(p["norm"], x, cfg)
        out, new_cache = apply_attention(
            p["attn"], h, cfg=cfg, window=b.window, positions=positions,
            cache=cache, cache_index=cache_index, causal=b.causal, mode=mode)
        x = x + maybe_post(out, p)
        if b.kind == "shared_attn":  # zamba2 shared block = attn + mlp
            h = apply_norm(p["ffn_norm"], x, cfg)
            x = x + apply_ffn(p["ffn"], h, cfg=cfg)
    elif b.kind == "mla":
        h = apply_norm(bp["norm"], x, cfg)
        out, new_cache = apply_mla(bp["attn"], h, cfg=cfg, positions=positions,
                                   cache=cache, cache_index=cache_index,
                                   mode=mode)
        x = x + maybe_post(out, bp)
    elif b.kind == "parallel":  # command-r: one norm, attn || ffn
        h = apply_norm(bp["norm"], x, cfg)
        out_a, new_cache = apply_attention(
            bp["attn"], h, cfg=cfg, window=b.window, positions=positions,
            cache=cache, cache_index=cache_index, mode=mode)
        out_f = apply_ffn(bp["ffn"], h, cfg=cfg)
        x = x + out_a + out_f
    elif b.kind == "ffn":
        h = apply_norm(bp["norm"], x, cfg)
        x = x + maybe_post(apply_ffn(bp["ffn"], h, cfg=cfg), bp)
    elif b.kind == "moe":
        h = apply_norm(bp["norm"], x, cfg)
        out, aux, rows = apply_moe(bp["moe"], h, cfg=cfg, dist=dist)
        x = x + maybe_post(out, bp)
    elif b.kind == "ssm":
        h = apply_norm(bp["norm"], x, cfg)
        if mode == "decode":
            out, new_cache = apply_ssm_decode(bp["ssm"], h, cache, cfg=cfg)
        else:
            out, new_cache = apply_ssm(bp["ssm"], h, cfg=cfg, state=cache)
        x = x + maybe_post(out, bp)
    elif b.kind == "cross_attn":
        h = apply_norm(bp["norm"], x, cfg)
        if mode == "decode":
            kv = (cache["ck"], cache["cv"])
        else:
            k, v = compute_cross_kv(bp["cross_kv"], cross_states)
            kv = (k, v)
            if cache is not None:
                new_cache = {"ck": k.astype(cache["ck"].dtype),
                             "cv": v.astype(cache["cv"].dtype)}
        out, _ = apply_attention(bp["attn"], h, cfg=cfg, cross_kv=kv,
                                 positions=positions, mode=mode)
        x = x + maybe_post(out, bp)
    else:
        raise ValueError(b.kind)
    return x, new_cache, aux, rows


def _maybe_remat(body, remat_policy: str | None, mode: str):
    """remat_policy: None (no remat) | 'full' | 'dots' | 'minimal'."""
    if remat_policy is None or mode != "train":
        return body
    if remat_policy == "full":
        return jax.checkpoint(body)
    if remat_policy == "dots":
        return jax.checkpoint(
            body, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    if remat_policy == "minimal":
        return jax.checkpoint(
            body, policy=jax.checkpoint_policies.everything_saveable)
    raise ValueError(remat_policy)


def _apply_group(gp, x, gd: GroupDesc, *, cfg, dist, mode, cache, cache_index,
                 cross_states, shared_params, positions, remat_policy=None,
                 unroll: int = 1):
    """Scan the group body over its ``repeat`` stacked layers. Returns (x,
    aux, emitted cache, expert rows summed over the layers, or None where
    the group has no expert layer)."""

    def body(carry, xs):
        h, aux, rows = carry
        bp_all, bc_all = xs
        new_caches = {}
        for j, b in enumerate(gd.blocks):
            key = f"b{j}"
            bc = None if bc_all is None else bc_all.get(key)
            h, nc, aux_j, rows_j = _apply_block(
                bp_all[key], h, b, cfg=cfg, dist=dist, mode=mode, cache=bc,
                cache_index=cache_index, cross_states=cross_states,
                shared_params=shared_params, positions=positions)
            if nc is not None:
                new_caches[key] = nc
            aux = aux + aux_j
            if rows_j is not None:
                rows = rows + rows_j
        return (h, aux, rows), (new_caches if new_caches else None)

    body = _maybe_remat(body, remat_policy, mode)

    rows0 = (jnp.zeros((3,), jnp.int32)
             if any(b.kind == "moe" for b in gd.blocks) else None)
    (x, aux, rows), new_cache = jax.lax.scan(
        body, (x, jnp.zeros((), jnp.float32), rows0), (gp, cache),
        unroll=min(unroll, gd.repeat) or 1)
    return x, aux, new_cache, rows


def _write_cache(gcache, emitted, gd: GroupDesc, cache_index):
    """A group's cache after a step: the attention blocks' emitted rows
    (repeat, B, S, ...) written at ``cache_index``, any other emitted
    state (SSM, a prefill's cross K/V) replacing the block's cache whole, and
    blocks that emitted nothing (cross-attention in decode) unchanged."""
    new = dict(gcache)
    for j, b in enumerate(gd.blocks):
        key = f"b{j}"
        if emitted is None or key not in emitted:
            continue
        out = emitted[key]
        if b.kind in ATTN_KINDS:
            out = jax.tree.map(
                lambda c, rows: jax.lax.dynamic_update_slice(
                    c, rows, (0, 0, cache_index) + (0,) * (rows.ndim - 3)),
                gcache[key], out)
        new[key] = out
    return new


def forward(params, inputs, *, cfg, dist: DistContext = LOCAL, mode="train",
            cache=None, cache_index=None, remat_policy=None,
            scan_unroll: int = 1):
    """Run the model.

    inputs: {'tokens': (B, S) int32, optional 'frames': (B, S_enc, d_model)
    (encdec stub frontend), optional 'patches': (B, P, d_vision) (vlm stub)}.
    Returns (logits, new_cache|None, aux_loss); in ``prefill`` mode the
    logits of the last position only, (B, 1, vocab).
    """
    tokens = inputs["tokens"]
    B, Sq = tokens.shape
    x = jnp.take(params["embed"], tokens, axis=0).astype(
        jnp.dtype(cfg.activ_dtype))
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)

    if cache_index is None:
        positions = jnp.arange(Sq)[None, :]
        cache_index = 0 if cache is not None else None
    else:
        positions = cache_index + jnp.arange(Sq)[None, :]

    cross_states = None
    if cfg.family == "vlm" and mode != "decode":
        patches = inputs["patches"].astype(x.dtype)
        cross_states = jnp.einsum("bpv,vd->bpd", patches,
                                  params["vision_proj"].astype(x.dtype))
    if cfg.family == "encdec" and mode != "decode":
        enc = params["encoder"]
        h = jnp.einsum("bse,ed->bsd", inputs["frames"].astype(x.dtype),
                       enc["in_proj"].astype(x.dtype))
        for i, gd in enumerate(encoder_plan(cfg)):
            h, _, _, _ = _apply_group(
                enc["groups"][f"g{i}"], h, gd, cfg=cfg, dist=dist,
                mode="train", cache=None, cache_index=None,
                cross_states=None, shared_params=None,
                positions=jnp.arange(h.shape[1])[None, :],
                remat_policy=remat_policy, unroll=scan_unroll)
        cross_states = apply_norm(enc["final_norm"], h, cfg)

    shared_params = params.get("shared")
    aux = jnp.zeros((), jnp.float32)
    new_groups, rows = {}, None
    for i, gd in enumerate(layer_plan(cfg)):
        gcache = None if cache is None else cache["groups"].get(f"g{i}")
        x, aux_g, ncache, rows_g = _apply_group(
            params["groups"][f"g{i}"], x, gd, cfg=cfg, dist=dist, mode=mode,
            cache=gcache, cache_index=cache_index, cross_states=cross_states,
            shared_params=shared_params, positions=positions,
            remat_policy=remat_policy, unroll=scan_unroll)
        aux = aux + aux_g
        if rows_g is not None:
            rows = rows_g if rows is None else rows + rows_g
        if gcache is not None:
            new_groups[f"g{i}"] = _write_cache(gcache, ncache, gd,
                                               cache_index)

    if mode == "prefill":
        x = x[:, -1:]
    x = apply_norm(params["final_norm"], x, cfg)
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", x, params["embed"])
    else:
        logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"])
    logits = softcap(logits.astype(jnp.float32), cfg.final_logit_softcap)
    new_cache = None
    if cache is not None:
        new_cache = {"groups": new_groups}
        if "moe_rows" in cache:
            new_cache["moe_rows"] = cache["moe_rows"].at[
                int(mode == "decode")].add(rows)
    return logits, new_cache, aux
