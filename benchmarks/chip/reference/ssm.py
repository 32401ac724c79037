"""Plain float32 reference of a Mamba2 language model (mamba2-370m).

Each layer: x + out_proj(gated_rmsnorm(SSD(conv(in_proj(rmsnorm(x)))))),
as in arXiv:2405.21060 and mamba_ssm's Mamba2 layer. in_proj yields
(z, x, B, C, dt); x, B and C pass a depthwise causal convolution and SiLU;
dt = softplus(dt + dt_bias), A = -exp(a_log); the state-space scan is
h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T, y_t = h_t C_t + D x_t,
computed by the chunked SSD algorithm (the paper's minimal listing) with
64-token chunks and the chunk-to-chunk recurrence as a scan; the output is
RMSNorm(y * silu(z)) and out_proj. A final RMSNorm and the tied head.
Every product is float32 at ``Precision.HIGHEST``.

This module imports nothing of the program. It also makes the weights, in
one jitted call from a key and in bfloat16, and lays them out as the
program's parameter tree (``program_params``).

``fp8=True`` is the lower-precision control: in_proj, out_proj and the head
round their input (per row) and weight (per output column) to
float8_e4m3fn with a max-abs scale before the float32 product.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0
CHUNK = 64

# the program's config fields, by the published key that sets each
PROGRAM_KEYS = {
    "n_layer": "n_layers", "d_model": "d_model", "vocab_size": "vocab_size",
    "tie_embeddings": "tie_embeddings", "ssm_cfg.d_state": "ssm.d_state",
    "ssm_cfg.d_conv": "ssm.conv_width", "ssm_cfg.expand": "ssm.expand",
    "ssm_cfg.headdim": "ssm.head_dim", "ssm_cfg.ngroups": "ssm.n_groups",
    "ssm_cfg.chunk_size": "ssm.chunk_size",
}


def _dims(spec):
    s = spec["ssm_cfg"]
    d = spec["d_model"]
    di = s["expand"] * d
    return dict(L=spec["n_layer"], d=d, di=di, H=di // s["headdim"],
                P=s["headdim"], N=s["d_state"], G=s["ngroups"],
                W=s["d_conv"], V=spec["vocab_size"])


def shapes(spec):
    """Weight name -> shape; leading axis of per-layer weights is the layer."""
    m = _dims(spec)
    L, d, di, H = m["L"], m["d"], m["di"], m["H"]
    gn = m["G"] * m["N"]
    return {"embed": (m["V"], d), "norm": (L, d),
            "in_proj": (L, d, 2 * di + 2 * gn + H),
            "conv_w": (L, m["W"], di + 2 * gn), "conv_b": (L, di + 2 * gn),
            "dt_bias": (L, H), "a_log": (L, H), "d_skip": (L, H),
            "gate_norm": (L, di), "out_proj": (L, di, d), "final_norm": (d,)}


@functools.partial(jax.jit, static_argnums=0)
def _init(shape_items, key):
    out = {}
    for i, (name, shape) in enumerate(shape_items):
        k = jax.random.fold_in(key, i)
        if name in ("norm", "gate_norm", "final_norm", "d_skip"):
            x = jnp.ones(shape, jnp.float32)
        elif name == "conv_b":
            x = jnp.zeros(shape, jnp.float32)
        elif name == "dt_bias":  # dt log-uniform in [1e-3, 1e-1]
            dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32,
                                            math.log(1e-3), math.log(1e-1)))
            x = dt + jnp.log(-jnp.expm1(-dt))
        elif name == "a_log":    # A uniform in [1, 16]
            x = jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))
        else:
            std = {"embed": 1.0 / math.sqrt(shape[1]), "conv_w": 0.5}.get(
                name, 1.0 / math.sqrt(shape[1]))
            x = jax.random.normal(k, shape, jnp.float32) * std
        out[name] = x.astype(jnp.bfloat16)
    return out


def init_weights(spec, key):
    """All weights, bfloat16, on the default device, in one jitted call."""
    return _init(tuple(sorted(shapes(spec).items())), key)


def program_params(w):
    """The program's parameter tree over the same arrays (no copies)."""
    ssm = {"in_proj": w["in_proj"], "conv_w": w["conv_w"],
           "conv_b": w["conv_b"], "dt_bias": w["dt_bias"],
           "a_log": w["a_log"], "d_skip": w["d_skip"],
           "norm": w["gate_norm"], "out_proj": w["out_proj"]}
    return {"embed": w["embed"], "final_norm": {"scale": w["final_norm"]},
            "groups": {"g0": {"b0": {"norm": {"scale": w["norm"]},
                                     "ssm": ssm}}}}


def _q8(x, axis):
    """Round to float8_e4m3fn with a max-abs scale along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / FP8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _linear(x, w, fp8):
    w = w.astype(jnp.float32)
    if fp8:
        x, w = _q8(x, -1), _q8(w, 0)
    return jnp.matmul(x, w, precision=HI)


def _rms(x, scale, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * scale.astype(jnp.float32)


def _conv(x, w, b):
    """Depthwise causal conv. x: (R, T, C); w: (W, C); out_t = b +
    sum_k w[k] x_{t-W+1+k}."""
    W, T = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (W - 1, 0), (0, 0)))
    return b + sum(xp[:, k:k + T] * w[k] for k in range(W))


def _ssd(x, a, b, c):
    """Chunked SSD. x: (R, T, H, P) (already times dt); a: (R, T, H) = dt*A;
    b, c: (R, T, H, N). Returns y: (R, T, H, P) without the D term."""
    R, T, H, P = x.shape
    N = b.shape[-1]
    pad = (-T) % CHUNK
    if pad:  # a = 0 and x = 0 past the end leave every earlier y unchanged
        x, a, b, c = (jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
                      for t in (x, a, b, c))
    n = x.shape[1] // CHUNK
    x, b, c = (t.reshape(R, n, CHUNK, H, -1) for t in (x, b, c))
    a = a.reshape(R, n, CHUNK, H).transpose(0, 3, 1, 2)       # (R, H, n, l)
    cum = jnp.cumsum(a, axis=-1)
    seg = cum[..., :, None] - cum[..., None, :]               # (R,H,n,l,s)
    causal = jnp.tril(jnp.ones((CHUNK, CHUNK), bool))
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    scores = jnp.einsum("rclhn,rcshn->rhcls", c, b, precision=HI)
    y = jnp.einsum("rhcls,rcshp->rclhp", scores * decay, x, precision=HI)
    # state at each chunk's end from its own inputs, then the recurrence
    w_end = jnp.exp(cum[..., -1:] - cum)                      # (R, H, n, l)
    local = jnp.einsum("rclhn,rhcl,rclhp->rchpn", b, w_end, x, precision=HI)
    chunk_decay = jnp.exp(cum[..., -1]).transpose(2, 0, 1)    # (n, R, H)

    def step(h, inp):
        dec, loc = inp
        return h * dec[..., None, None] + loc, h              # carry-in out

    _, h_in = jax.lax.scan(step, jnp.zeros((R, H, P, N), jnp.float32),
                           (chunk_decay, local.transpose(1, 0, 2, 3, 4)))
    h_in = h_in.transpose(1, 0, 2, 3, 4)                      # (R, n, H, P, N)
    y = y + jnp.einsum("rclhn,rchpn,rhcl->rclhp", c, h_in, jnp.exp(cum),
                       precision=HI)
    return y.reshape(R, n * CHUNK, H, P)[:, :T]


def _block(x, w, spec, fp8):
    """One Mamba2 layer over sequences x: (R, T, d) float32."""
    m = _dims(spec)
    di, H, P, N, G = m["di"], m["H"], m["P"], m["N"], m["G"]
    eps = spec["norm_epsilon"]
    R, T, _ = x.shape
    h = _rms(x, w["norm"], eps)
    proj = _linear(h, w["in_proj"], fp8)
    z, xbc, dt = jnp.split(proj, [di, 2 * di + 2 * G * N], axis=-1)
    xbc = jax.nn.silu(_conv(xbc, w["conv_w"].astype(jnp.float32),
                            w["conv_b"].astype(jnp.float32)))
    xs, b, c = jnp.split(xbc, [di, di + G * N], axis=-1)
    dt = jax.nn.softplus(dt + w["dt_bias"].astype(jnp.float32))  # (R, T, H)
    A = -jnp.exp(w["a_log"].astype(jnp.float32))
    xs = xs.reshape(R, T, H, P)
    rep = H // G
    b = jnp.repeat(b.reshape(R, T, G, N), rep, axis=2)
    c = jnp.repeat(c.reshape(R, T, G, N), rep, axis=2)
    y = _ssd(xs * dt[..., None], dt * A, b, c)
    y = y + xs * w["d_skip"].astype(jnp.float32)[:, None]
    y = _rms(y.reshape(R, T, di) * jax.nn.silu(z), w["gate_norm"], eps)
    return x + _linear(y, w["out_proj"], fp8)


_LAYER = ("norm", "in_proj", "conv_w", "conv_b", "dt_bias", "a_log",
          "d_skip", "gate_norm", "out_proj")


@functools.partial(jax.jit, static_argnums=(2, 4))
def _layer(x, w, spec_items, layer, fp8):
    spec = _unhash(spec_items)
    wl = {k: jax.lax.dynamic_index_in_dim(w[k], layer, keepdims=False)
          for k in _LAYER}
    return jax.lax.map(lambda row: _block(row[None], wl, spec, fp8)[0], x)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _head(x, w, spec_items, start, fp8):
    spec = _unhash(spec_items)
    h = _rms(x[:, start:], w["final_norm"], spec["norm_epsilon"])
    return jax.lax.map(lambda row: _linear(row, w["embed"].T, fp8), h)


def _unhash(items):
    return {k: dict(v) if isinstance(v, tuple) else v for k, v in items}


def _hashable(spec):
    flat = {}
    for k, v in spec.items():
        if isinstance(v, dict):
            flat[k] = tuple(sorted(v.items()))
        elif isinstance(v, (int, float, str, bool)):
            flat[k] = v
    return tuple(sorted(flat.items()))


def logits(w, spec, tokens, start: int, fp8: bool = False):
    """tokens: (R, T) int32. Float32 logits (R, T - start, vocab) at
    positions start..T-1, layer by layer."""
    items = _hashable(spec)
    x = jnp.take(w["embed"], jnp.asarray(tokens), axis=0).astype(jnp.float32)
    for layer in range(spec["n_layer"]):
        x = _layer(x, w, items, jnp.int32(layer), fp8)
    return _head(x, w, items, start, fp8)
