"""Plain float32 reference of a llama-style dense decoder (deepseek-llm-7b).

Pre-norm blocks: RMSNorm, multi-head attention with rotary positions and a
causal mask, residual; RMSNorm, SwiGLU feed-forward, residual. A final
RMSNorm and an untied (or tied) output head. Every product is float32 at
``Precision.HIGHEST``; the residual stream stays float32.

One departure from the published model, shared with the program: rotary
embedding rotates interleaved pairs (0::2, 1::2) where the published code
rotates halves. For random weights the two differ only by a fixed
permutation of each head's query and key columns.

This module imports nothing of the program. It also makes the weights, in
one jitted call from a key and in bfloat16 (the type they are served in),
and lays them out as the program's parameter tree (``program_params``).
The reference reads the same bfloat16 values, upcast.

``fp8=True`` is the lower-precision control: every linear layer rounds its
input (per row) and its weight (per output column) to float8_e4m3fn with a
max-abs scale before the float32 product.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0

# the program's config fields, by the published key that sets each
PROGRAM_KEYS = {
    "num_hidden_layers": "n_layers", "hidden_size": "d_model",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "d_ff", "vocab_size": "vocab_size",
    "rope_theta": "rope_theta", "tie_word_embeddings": "tie_embeddings",
}


def _dims(spec):
    d, h = spec["hidden_size"], spec["num_attention_heads"]
    return (spec["num_hidden_layers"], d, h, spec["num_key_value_heads"],
            d // h, spec["intermediate_size"], spec["vocab_size"],
            bool(spec["tie_word_embeddings"]))


def shapes(spec):
    """Weight name -> shape; leading axis of per-layer weights is the layer."""
    L, d, H, KVH, hd, F, V, tie = _dims(spec)
    out = {"embed": (V, d), "attn_norm": (L, d), "wq": (L, d, H, hd),
           "wk": (L, d, KVH, hd), "wv": (L, d, KVH, hd), "wo": (L, H, hd, d),
           "mlp_norm": (L, d), "w_gate": (L, d, F), "w_up": (L, d, F),
           "w_down": (L, F, d), "final_norm": (d,)}
    if not tie:
        out["lm_head"] = (d, V)
    return out


def _std(name, shape):
    if name == "embed":
        return 1.0
    if name == "wo":
        return 1.0 / math.sqrt(shape[1] * shape[2])
    return 1.0 / math.sqrt(shape[0] if name == "lm_head" else shape[1])


@functools.partial(jax.jit, static_argnums=0)
def _init(shape_items, key):
    out = {}
    for i, (name, shape) in enumerate(shape_items):
        if name.endswith("norm"):
            out[name] = jnp.ones(shape, jnp.bfloat16)
            continue
        x = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        out[name] = (x * _std(name, shape)).astype(jnp.bfloat16)
    return out


def init_weights(spec, key):
    """All weights, bfloat16, on the default device, in one jitted call."""
    return _init(tuple(sorted(shapes(spec).items())), key)


def program_params(w):
    """The program's parameter tree over the same arrays (no copies)."""
    tree = {"embed": w["embed"], "final_norm": {"scale": w["final_norm"]},
            "groups": {"g0": {
                "b0": {"norm": {"scale": w["attn_norm"]},
                       "attn": {k: w[k] for k in ("wq", "wk", "wv", "wo")}},
                "b1": {"norm": {"scale": w["mlp_norm"]},
                       "ffn": {k: w[k] for k in ("w_gate", "w_up", "w_down")}},
            }}}
    if "lm_head" in w:
        tree["lm_head"] = w["lm_head"]
    return tree


def _q8(x, axis):
    """Round to float8_e4m3fn with a max-abs scale along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / FP8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _linear(x, w, fp8):
    """x (..., k) float32 @ w (k, n) -> (..., n) float32."""
    w = w.astype(jnp.float32)
    if fp8:
        x, w = _q8(x, -1), _q8(w, 0)
    return jnp.matmul(x, w, precision=HI)


def _rms(x, scale, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * scale.astype(jnp.float32)


def _rope(x, theta):
    """x: (T, heads, hd), positions 0..T-1, interleaved pairs."""
    T, _, hd = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def _block(x, w, spec, fp8):
    """One decoder layer over one sequence x: (T, d) float32."""
    L, d, H, KVH, hd, F, V, tie = _dims(spec)
    eps, theta = spec["rms_norm_eps"], spec["rope_theta"]
    T = x.shape[0]
    h = _rms(x, w["attn_norm"], eps)
    q = _linear(h, w["wq"].reshape(d, H * hd), fp8).reshape(T, H, hd)
    k = _linear(h, w["wk"].reshape(d, KVH * hd), fp8).reshape(T, KVH, hd)
    v = _linear(h, w["wv"].reshape(d, KVH * hd), fp8).reshape(T, KVH, hd)
    q, k = _rope(q, theta), _rope(k, theta)
    k, v = jnp.repeat(k, H // KVH, axis=1), jnp.repeat(v, H // KVH, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((T, T), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p, v, precision=HI).reshape(T, H * hd)
    x = x + _linear(o, w["wo"].reshape(H * hd, d), fp8)
    h = _rms(x, w["mlp_norm"], eps)
    g = _linear(h, w["w_gate"], fp8)
    u = _linear(h, w["w_up"], fp8)
    return x + _linear(jax.nn.silu(g) * u, w["w_down"], fp8)


_LAYER = ("attn_norm", "wq", "wk", "wv", "wo", "mlp_norm", "w_gate", "w_up",
          "w_down")


@functools.partial(jax.jit, static_argnums=(2, 4))
def _layer(x, w, spec_items, layer, fp8):
    spec = dict(spec_items)
    wl = {k: jax.lax.dynamic_index_in_dim(w[k], layer, keepdims=False)
          for k in _LAYER}
    return jax.lax.map(lambda row: _block(row, wl, spec, fp8), x)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _head(x, w, spec_items, start, fp8):
    spec = dict(spec_items)
    h = _rms(x[:, start:], w["final_norm"], spec["rms_norm_eps"])
    head = w["lm_head"] if "lm_head" in w else w["embed"].T
    return jax.lax.map(lambda row: _linear(row, head, fp8), h)


def _hashable(spec):
    return tuple(sorted((k, v) for k, v in spec.items()
                        if isinstance(v, (int, float, str, bool))))


def logits(w, spec, tokens, start: int, fp8: bool = False):
    """tokens: (R, T) int32. Float32 logits (R, T - start, vocab) at
    positions start..T-1, layer by layer so that one layer's float32
    weights are resident at a time."""
    items = _hashable(spec)
    x = jnp.take(w["embed"], jnp.asarray(tokens), axis=0).astype(jnp.float32)
    for layer in range(spec["num_hidden_layers"]):
        x = _layer(x, w, items, jnp.int32(layer), fp8)
    return _head(x, w, items, start, fp8)
