"""Parameter-spec system, norms, RoPE and init helpers.

Models are (spec, apply) pairs over plain dict pytrees. A ``ParamSpec`` tree is
the single source of truth from which we derive:

- ``init_params``      concrete arrays (for smoke tests / real execution)
- ``abstract_params``  ShapeDtypeStructs (for the 512-device dry-run — never
                       allocates)
- ``logical_axes``     per-leaf logical axis names, mapped to mesh axes by
                       ``repro.runtime.sharding``.
"""
from __future__ import annotations

import functools
import math
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp


class ParamSpec(NamedTuple):
    shape: tuple[int, ...]
    axes: tuple[str | None, ...]   # logical axis name per dim
    init: str = "normal"           # normal | zeros | ones | embed
    scale: float = 0.0             # stddev override; 0 -> fan-in scaled
    dtype: Any = None              # None -> model param dtype


def _is_spec(x: Any) -> bool:
    return isinstance(x, ParamSpec)


def tree_map_specs(fn, spec_tree):
    return jax.tree.map(fn, spec_tree, is_leaf=_is_spec)


def stack_specs(spec_tree, n: int, axis_name: str = "layers"):
    """Prepend a stacking dim (for scan-over-layers parameter stacks)."""

    def _stack(s: ParamSpec) -> ParamSpec:
        return s._replace(shape=(n, *s.shape), axes=(axis_name, *s.axes))

    return tree_map_specs(_stack, spec_tree)


def _fan_in(shape: tuple[int, ...], axes: tuple[str | None, ...]) -> int:
    # Fan-in = product of all dims except the last "output-ish" dim; for
    # stacked layer params, skip the leading 'layers'/stack dims.
    dims = [d for d, a in zip(shape, axes) if a not in ("layers", "group")]
    if len(dims) <= 1:
        return max(dims[0] if dims else 1, 1)
    return max(math.prod(dims[:-1]), 1)


@functools.partial(jax.jit, static_argnames=("shape", "dtype"))
def _normal_leaf(key, std, shape, dtype):
    """One leaf drawn on the device in its own dtype. Under jit the fp32
    draw, the scaling and the cast fuse, so no fp32 array the size of the
    leaf is ever resident (a 7B model's stacked FFN leaf would need 5 GiB).

    The no-op ``reduce_precision`` keeps XLA from folding ``std`` into
    ``normal``'s own sqrt(2) factor, which would move the last bit of many
    values away from what the unjitted draw gives for the same key."""
    x = jax.lax.reduce_precision(jax.random.normal(key, shape, jnp.float32),
                                 exponent_bits=8, mantissa_bits=23)
    return (x * std).astype(dtype)


def init_params(spec_tree, key, default_dtype=jnp.bfloat16):
    leaves, treedef = jax.tree.flatten(spec_tree, is_leaf=_is_spec)
    keys = jax.random.split(key, len(leaves))

    def _init(s: ParamSpec, k):
        dt = s.dtype or default_dtype
        if s.init == "zeros":
            return jnp.zeros(s.shape, dt)
        if s.init == "ones":
            return jnp.ones(s.shape, dt)
        if s.init == "embed":
            std = s.scale or 1.0
        else:
            std = s.scale or 1.0 / math.sqrt(_fan_in(s.shape, s.axes))
        return _normal_leaf(k, std, s.shape, dt)

    return jax.tree.unflatten(treedef, [_init(s, k) for s, k in zip(leaves, keys)])


def abstract_params(spec_tree, default_dtype=jnp.bfloat16):
    def _abs(s: ParamSpec):
        return jax.ShapeDtypeStruct(s.shape, s.dtype or default_dtype)

    return tree_map_specs(_abs, spec_tree)


def logical_axes(spec_tree):
    return tree_map_specs(lambda s: s.axes, spec_tree)


def param_count(spec_tree) -> int:
    leaves = jax.tree.leaves(spec_tree, is_leaf=_is_spec)
    return sum(int(math.prod(s.shape)) for s in leaves)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rms_norm(x, scale, eps: float = 1e-6, zero_centered: bool = False):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    s = scale.astype(jnp.float32)
    if zero_centered:  # gemma-style (1 + scale)
        s = 1.0 + s
    return (y * s).astype(dt)


def layer_norm(x, scale, bias=None, eps: float = 1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y.astype(dt)


def norm_spec(cfg, d: int | None = None) -> dict:
    d = d or cfg.d_model
    spec = {"scale": ParamSpec((d,), ("embed_norm",),
                               init="zeros" if _zero_centered(cfg) else "ones")}
    if cfg.use_layernorm and cfg.use_bias:
        spec["bias"] = ParamSpec((d,), ("embed_norm",), init="zeros")
    return spec


def _zero_centered(cfg) -> bool:
    return cfg.name.startswith("gemma")


def apply_norm(p: dict, x, cfg):
    if cfg.use_layernorm:
        return layer_norm(x, p["scale"], p.get("bias"))
    return rms_norm(x, p["scale"], zero_centered=_zero_centered(cfg))


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, rope_pct: float, theta: float):
    rot_dim = int(head_dim * rope_pct)
    rot_dim -= rot_dim % 2
    inv = 1.0 / (theta ** (jnp.arange(0, rot_dim, 2, dtype=jnp.float32) / rot_dim))
    return inv, rot_dim


def yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(dim: int, theta: float, yarn):
    """YaRN's frequencies for ``dim`` rotated dims, as DeepSeek-V2's
    ``DeepseekV2YarnRotaryEmbedding`` computes them: the plain frequencies
    (extrapolated) below the correction range found from ``beta_fast`` and
    ``beta_slow`` at the original context, divided by ``factor``
    (interpolated) above it, and a linear ramp between."""
    extra = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    interp = extra / yarn.factor

    def corr(rotations):
        return dim * math.log(yarn.original_max_position
                              / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(corr(yarn.beta_fast)), 0)
    high = min(math.ceil(corr(yarn.beta_slow)), dim - 1)
    high = high + 0.001 if high == low else high
    i = jnp.arange(dim // 2, dtype=jnp.float32)
    mask = 1.0 - jnp.clip((i - low) / (high - low), 0.0, 1.0)
    return interp * (1.0 - mask) + extra * mask


def apply_rope(x, positions, *, rope_pct: float = 1.0, theta: float = 10000.0,
               yarn=None):
    """x: (..., seq, heads, head_dim); positions: (..., seq) int32. With a
    ``yarn`` config of nonzero factor, its frequencies (every dim rotated)
    and its cos/sin scale mscale / mscale_all_dim."""
    head_dim = x.shape[-1]
    if yarn is not None and yarn.factor:
        inv, rot_dim = yarn_inv_freq(head_dim, theta, yarn), head_dim
        amp = (yarn_mscale(yarn.factor, yarn.mscale)
               / yarn_mscale(yarn.factor, yarn.mscale_all_dim))
    else:
        inv, rot_dim = rope_freqs(head_dim, rope_pct, theta)
        amp = 1.0
    if rot_dim == 0:
        return x
    ang = positions[..., :, None].astype(jnp.float32) * inv  # (..., seq, rot/2)
    cos = jnp.cos(ang)[..., :, None, :]  # broadcast over heads
    sin = jnp.sin(ang)[..., :, None, :]
    if amp != 1.0:
        cos, sin = cos * amp, sin * amp
    x_rot, x_pass = x[..., :rot_dim], x[..., rot_dim:]
    x1, x2 = x_rot[..., 0::2], x_rot[..., 1::2]
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    y = jnp.stack([y1, y2], axis=-1).reshape(x_rot.shape)
    return jnp.concatenate([y.astype(x.dtype), x_pass], axis=-1)


def softcap(x, cap: float):
    if not cap:
        return x
    return cap * jnp.tanh(x / cap)


def dtype_of(name: str):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
            "float16": jnp.float16}[name]
