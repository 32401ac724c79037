"""Mamba2 block (SSD) — projections, causal depthwise conv, gated norm.

Train/prefill runs the chunked SSD scan (Pallas kernel on TPU); decode is the
O(1)-per-token recurrent update carried in (conv_buffer, ssm_state).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..kernels import ops
from .common import ParamSpec, rms_norm


def ssm_dims(cfg, d_model: int | None = None):
    s = cfg.ssm
    d = d_model or cfg.d_model
    d_inner = s.expand * d
    n_heads = d_inner // s.head_dim
    conv_dim = d_inner + 2 * s.n_groups * s.d_state
    return d, d_inner, n_heads, conv_dim


def ssm_specs(cfg, d_model: int | None = None) -> dict:
    s = cfg.ssm
    d, d_inner, nh, conv_dim = ssm_dims(cfg, d_model)
    proj_out = 2 * d_inner + 2 * s.n_groups * s.d_state + nh
    return {
        "in_proj": ParamSpec((d, proj_out), ("embed", "ssm_in")),
        "conv_w": ParamSpec((s.conv_width, conv_dim), ("conv", "ssm_conv"),
                            scale=0.5),
        "conv_b": ParamSpec((conv_dim,), ("ssm_conv",), init="zeros"),
        "dt_bias": ParamSpec((nh,), ("ssm_heads",), init="zeros"),
        "a_log": ParamSpec((nh,), ("ssm_heads",), init="ones"),
        "d_skip": ParamSpec((nh,), ("ssm_heads",), init="ones"),
        "norm": ParamSpec((d_inner,), ("ssm_inner",), init="ones"),
        "out_proj": ParamSpec((d_inner, d), ("ssm_inner", "embed")),
    }


def init_ssm_state(cfg, batch: int, n_layers: int, d_model: int | None = None,
                   lead: tuple[int, ...] = ()):
    s = cfg.ssm
    _, d_inner, nh, conv_dim = ssm_dims(cfg, d_model)
    return {
        "conv": jnp.zeros((n_layers, *lead, batch, s.conv_width - 1, conv_dim),
                          jnp.float32),
        "ssm": jnp.zeros((n_layers, *lead, batch, nh, s.head_dim, s.d_state),
                         jnp.float32),
    }


def _split_proj(proj, cfg, d_model=None):
    s = cfg.ssm
    _, d_inner, nh, _ = ssm_dims(cfg, d_model)
    gn = s.n_groups * s.d_state
    z, xs, b, c, dt = jnp.split(
        proj, [d_inner, 2 * d_inner, 2 * d_inner + gn, 2 * d_inner + 2 * gn],
        axis=-1)
    return z, xs, b, c, dt


def _causal_conv(x, w, b):
    """x: (B, L, C); w: (W, C) depthwise causal conv via shifted adds."""
    W = w.shape[0]
    out = x * w[W - 1]
    for i in range(1, W):
        shifted = jnp.pad(x, ((0, 0), (i, 0), (0, 0)))[:, :-i or None][:, :x.shape[1]]
        out = out + shifted * w[W - 1 - i]
    return out + b


def apply_ssm(p, x, *, cfg, d_model=None, state=None):
    """x: (B, L, d). Returns (out, new_state|None).

    state (decode handoff): dict(conv=(B, W-1, conv_dim), ssm=(B,H,P,N));
    when provided for prefill, the returned state reflects the sequence end.
    """
    s = cfg.ssm
    B, L, _ = x.shape
    _, d_inner, nh, conv_dim = ssm_dims(cfg, d_model)
    proj = jnp.einsum("bld,dk->blk", x, p["in_proj"])
    z, xs, bm, cm, dt = _split_proj(proj, cfg, d_model)
    conv_in = jnp.concatenate([xs, bm, cm], axis=-1)
    conv_out = jax.nn.silu(_causal_conv(conv_in, p["conv_w"], p["conv_b"]))
    xs, bm, cm = jnp.split(conv_out, [d_inner, d_inner + s.n_groups * s.d_state],
                           axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
    xh = xs.reshape(B, L, nh, s.head_dim)
    bh = bm.reshape(B, L, s.n_groups, s.d_state)
    ch = cm.reshape(B, L, s.n_groups, s.d_state)
    # pad L to a chunk multiple; dt=0 at pad positions makes the recurrence
    # an exact identity there (decay exp(0)=1, input u=0) so y and the final
    # state are unaffected.
    chunk = min(s.chunk_size, L)
    pad = (-L) % chunk
    if pad:
        xh = jnp.pad(xh, ((0, 0), (0, pad), (0, 0), (0, 0)))
        bh = jnp.pad(bh, ((0, 0), (0, pad), (0, 0), (0, 0)))
        ch = jnp.pad(ch, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
    y, ssm_state = ops.ssd_scan(xh, dt, p["a_log"], bh, ch, p["d_skip"],
                                chunk=chunk)
    y = y[:, :L].reshape(B, L, d_inner)
    y = rms_norm(y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype),
                 p["norm"], cfg.norm_eps)
    out = jnp.einsum("blk,kd->bld", y, p["out_proj"])
    new_state = None
    if state is not None:
        conv_buf = conv_in[:, -(s.conv_width - 1):].astype(jnp.float32)
        new_state = {"conv": conv_buf, "ssm": ssm_state}
    return out, new_state


def apply_ssm_decode(p, x_t, state, *, cfg, d_model=None):
    """Single-token step. x_t: (B, 1, d); state from init/prefill."""
    s = cfg.ssm
    B = x_t.shape[0]
    _, d_inner, nh, conv_dim = ssm_dims(cfg, d_model)
    proj = jnp.einsum("bld,dk->blk", x_t, p["in_proj"])[:, 0]     # (B, k)
    z, xs, bm, cm, dt = _split_proj(proj, cfg, d_model)
    conv_in = jnp.concatenate([xs, bm, cm], axis=-1)              # (B, conv_dim)
    window = jnp.concatenate(
        [state["conv"], conv_in[:, None].astype(jnp.float32)], axis=1)
    w = p["conv_w"].astype(jnp.float32)                           # (W, C)
    conv_out = jnp.einsum("bwc,wc->bc", window, w) + p["conv_b"].astype(jnp.float32)
    conv_out = jax.nn.silu(conv_out).astype(x_t.dtype)
    xs, bm, cm = jnp.split(conv_out, [d_inner, d_inner + s.n_groups * s.d_state],
                           axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
    y, ssm_state = ops.ssd_decode_step(
        state["ssm"], xs.reshape(B, nh, s.head_dim), dt, p["a_log"],
        bm.reshape(B, s.n_groups, s.d_state), cm.reshape(B, s.n_groups, s.d_state),
        p["d_skip"])
    y = y.reshape(B, d_inner)
    y = rms_norm(y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype),
                 p["norm"], cfg.norm_eps)
    out = jnp.einsum("bk,kd->bd", y, p["out_proj"])[:, None]      # (B, 1, d)
    new_state = {"conv": window[:, 1:], "ssm": ssm_state}
    return out, new_state
