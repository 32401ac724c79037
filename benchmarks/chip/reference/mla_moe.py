"""Plain float32 reference of DeepSeek-V2 (arXiv:2405.04434): latent
attention and a first dense layer, then routed and shared experts, at one
chip's share of the routed experts.

Pre-norm blocks, RMSNorm (eps from the file) and residuals. Attention (MLA,
no query compression): q = h W_q per head, split into nope and rope dims;
[c, k_pe] = h W_kv_a, c through its own RMSNorm (eps 1e-6); each head's
k = [c W_uk, k_pe] and v = c W_uv; rotary positions on the rope dims only,
under YaRN's frequencies; causal softmax at scale (nope + rope)^-0.5 times
YaRN's mscale(mscale_all_dim) squared; out through W_o. Feed-forward:
SwiGLU of width ``intermediate_size`` in the first ``first_k_dense_replace``
layers; then an expert layer: softmax over the router's
``published.n_routed_experts`` scores, greedy top-k, the top-k scores as
weights (renormalised only if ``norm_topk_prob``) times
``routed_scaling_factor``, the SwiGLU of each chosen expert that this chip
holds (experts ``experts_offset`` .. + ``n_routed_experts``) weighted and
summed, plus the shared experts as one SwiGLU of ``n_shared_experts`` times
the expert width. What the experts held on other chips add is left out, as
in the program. A final RMSNorm and an untied head. Every product is
float32 at ``Precision.HIGHEST``; the residual stream stays float32.

Each layer computes every token through each held expert and weighs the
outputs by the routing (zero where not chosen): no sort, no capacity.
Attention runs in blocks of ``QUERY_BLOCK`` queries against all keys, so
that a row of 8k tokens needs one block's scores at a time.

One departure from the published code, shared with the program: rotary
embedding rotates interleaved pairs (0::2, 1::2). The published code first
permutes q_pe and k_pe from interleaved to halves and then rotates halves,
which is the same rotation, so nothing departs here.

This module imports nothing of the program. It makes the weights in one
jitted call from a key, in bfloat16 (the router's bfloat16 draw held in
float32, the type the program scores it in), and lays them out as the
program's parameter tree (``program_params``) without copies: the dense
layers' and the expert layers' weights are two stacks, and ``_layer``
picks one with ``lax.cond`` on the traced layer index.

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
KV_NORM_EPS = 1e-6
QUERY_BLOCK = 256

# the program's config fields, by the published key that sets each
PROGRAM_KEYS = {
    "num_hidden_layers": "n_layers", "hidden_size": "d_model",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "vocab_size": "vocab_size", "rope_theta": "rope_theta",
    "tie_word_embeddings": "tie_embeddings",
    "kv_lora_rank": "kv_lora_rank", "qk_nope_head_dim": "qk_nope_head_dim",
    "qk_rope_head_dim": "qk_rope_head_dim", "v_head_dim": "v_head_dim",
    "rope_scaling.factor": "yarn.factor",
    "rope_scaling.original_max_position_embeddings":
        "yarn.original_max_position",
    "rope_scaling.beta_fast": "yarn.beta_fast",
    "rope_scaling.beta_slow": "yarn.beta_slow",
    "rope_scaling.mscale": "yarn.mscale",
    "rope_scaling.mscale_all_dim": "yarn.mscale_all_dim",
    "first_k_dense_replace": "moe.first_k_dense",
    "intermediate_size": "moe.d_ff_dense",
    "moe_intermediate_size": "moe.d_ff_expert",
    "published.n_routed_experts": "moe.num_experts",
    "n_routed_experts": "moe.experts_held",
    "experts_offset": "moe.experts_offset",
    "num_experts_per_tok": "moe.top_k",
    "n_shared_experts": "moe.num_shared",
    "norm_topk_prob": "moe.norm_topk_prob",
    "routed_scaling_factor": "moe.routed_scaling_factor",
}

_ATTN = ("attn_norm", "wq", "wkv_a", "kv_norm", "w_uk", "w_uv", "wo",
         "mlp_norm")
_FFN = ("w_gate", "w_up", "w_down")
_MOE = ("router", "w_gate", "w_up", "w_down", "sh_gate", "sh_up", "sh_down")


def _dims(spec):
    return dict(
        L=spec["num_hidden_layers"], d=spec["hidden_size"],
        H=spec["num_attention_heads"], r=spec["kv_lora_rank"],
        nope=spec["qk_nope_head_dim"], rope=spec["qk_rope_head_dim"],
        dv=spec["v_head_dim"], F=spec["intermediate_size"],
        f=spec["moe_intermediate_size"],
        E=spec["published"]["n_routed_experts"],
        n=spec["n_routed_experts"], k=spec["num_experts_per_tok"],
        sh=spec["n_shared_experts"], K=spec["first_k_dense_replace"],
        V=spec["vocab_size"])


def shapes(spec):
    """Weight name -> shape. ``dense.*`` weights stack the first K layers,
    ``moe.*`` the rest; the leading axis is the layer within the stack."""
    D = _dims(spec)
    d, H, r, qk = D["d"], D["H"], D["r"], D["nope"] + D["rope"]
    out = {"embed": (D["V"], d), "final_norm": (d,), "lm_head": (d, D["V"])}
    for stack, L in (("dense", D["K"]), ("moe", D["L"] - D["K"])):
        out.update({
            f"{stack}.attn_norm": (L, d), f"{stack}.wq": (L, d, H, qk),
            f"{stack}.wkv_a": (L, d, r + D["rope"]),
            f"{stack}.kv_norm": (L, r),
            f"{stack}.w_uk": (L, r, H, D["nope"]),
            f"{stack}.w_uv": (L, r, H, D["dv"]),
            f"{stack}.wo": (L, H, D["dv"], d), f"{stack}.mlp_norm": (L, d)})
    K, M, fs = D["K"], D["L"] - D["K"], D["sh"] * D["f"]
    out.update({
        "dense.w_gate": (K, d, D["F"]), "dense.w_up": (K, d, D["F"]),
        "dense.w_down": (K, D["F"], d),
        "moe.router": (M, d, D["E"]),
        "moe.w_gate": (M, D["n"], d, D["f"]), "moe.w_up": (M, D["n"], d, D["f"]),
        "moe.w_down": (M, D["n"], D["f"], d),
        "moe.sh_gate": (M, d, fs), "moe.sh_up": (M, d, fs),
        "moe.sh_down": (M, fs, d)})
    return out


def _fan_in(name, shape):
    base = name.split(".")[-1]
    if base == "lm_head":
        return shape[0]
    if base == "wo":
        return shape[1] * shape[2]
    if base in ("w_gate", "w_up", "w_down"):
        return shape[-2]
    return shape[1]


@functools.partial(jax.jit, static_argnums=0)
def _init(shape_items, key):
    out = {}
    for i, (name, shape) in enumerate(shape_items):
        if name.endswith("norm"):
            out[name] = jnp.ones(shape, jnp.bfloat16)
            continue
        std = 1.0 if name == "embed" else 1.0 / math.sqrt(_fan_in(name, shape))
        x = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        x = (x * std).astype(jnp.bfloat16)
        out[name] = x.astype(jnp.float32) if name.endswith("router") else x
    return out


def init_weights(spec, key):
    """All weights, bfloat16 (the router float32), on the default device,
    in one jitted call."""
    return _init(tuple(sorted(shapes(spec).items())), key)


def program_params(w):
    """The program's parameter tree over the same arrays (no copies)."""
    def attn(s):
        return {"norm": {"scale": w[f"{s}.attn_norm"]},
                "attn": {k: w[f"{s}.{k}"]
                         for k in ("wq", "wkv_a", "kv_norm", "w_uk", "w_uv",
                                   "wo")}}

    def ffn(s):
        return {k: w[f"{s}.{k}"] for k in _FFN}

    moe = {"router": w["moe.router"], **ffn("moe"),
           "shared": {"w_gate": w["moe.sh_gate"], "w_up": w["moe.sh_up"],
                      "w_down": w["moe.sh_down"]}}
    return {"embed": w["embed"], "final_norm": {"scale": w["final_norm"]},
            "lm_head": w["lm_head"],
            "groups": {
                "g0": {"b0": attn("dense"),
                       "b1": {"norm": {"scale": w["dense.mlp_norm"]},
                              "ffn": ffn("dense")}},
                "g1": {"b0": attn("moe"),
                       "b1": {"norm": {"scale": w["moe.mlp_norm"]},
                              "moe": moe}}}}


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


def _yarn_mscale(factor, mscale):
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def _inv_freq(spec):
    """DeepseekV2YarnRotaryEmbedding: the plain frequencies below the
    correction range, those divided by ``factor`` above it, a linear ramp
    between (the range from beta_fast / beta_slow rotations at the
    original context)."""
    dim, base = spec["qk_rope_head_dim"], spec["rope_theta"]
    y = spec["rope_scaling"]
    extra = 1.0 / base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    interp = 1.0 / (y["factor"] * base ** (
        jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))

    def corr(rot):
        return (dim * math.log(y["original_max_position_embeddings"]
                               / (rot * 2 * math.pi))) / (2 * math.log(base))

    low = max(math.floor(corr(y["beta_fast"])), 0)
    high = min(math.ceil(corr(y["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    mask = 1.0 - ramp
    return interp * (1.0 - mask) + extra * mask


def _rope(x, spec):
    """x: (T, heads, rope), positions 0..T-1, interleaved pairs, cos and sin
    scaled by mscale / mscale_all_dim."""
    y = spec["rope_scaling"]
    amp = (_yarn_mscale(y["factor"], y["mscale"])
           / _yarn_mscale(y["factor"], y["mscale_all_dim"]))
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * _inv_freq(spec)
    cos = jnp.cos(ang)[:, None, :] * amp
    sin = jnp.sin(ang)[:, None, :] * amp
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def _attend(q, k, v, scale):
    """Causal attention of q, k (T, H, qk) and v (T, H, dv), in blocks of
    ``QUERY_BLOCK`` queries over every key."""
    T, H, D = q.shape
    nb = -(-T // QUERY_BLOCK)
    qb = jnp.pad(q, ((0, nb * QUERY_BLOCK - T), (0, 0), (0, 0))).reshape(
        nb, QUERY_BLOCK, H, D)

    def block(args):
        i, qi = args
        s = jnp.einsum("qhd,khd->hqk", qi, k, precision=HI) * scale
        pos = i * QUERY_BLOCK + jnp.arange(QUERY_BLOCK)
        causal = jnp.arange(T)[None, :] <= pos[:, None]
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HI)

    o = jax.lax.map(block, (jnp.arange(nb), qb))
    return o.reshape(nb * QUERY_BLOCK, H, v.shape[-1])[:T]


def _mla(x, w, spec, fp8):
    """Attention sublayer of one sequence x: (T, d) float32."""
    D = _dims(spec)
    T, d, H, r, nope = x.shape[0], D["d"], D["H"], D["r"], D["nope"]
    h = _rms(x, w["attn_norm"], spec["rms_norm_eps"])
    q = _linear(h, w["wq"].reshape(d, -1), fp8).reshape(T, H, -1)
    kv = _linear(h, w["wkv_a"], fp8)
    c = _rms(kv[:, :r], w["kv_norm"], KV_NORM_EPS)
    k_pe = _rope(kv[:, None, r:], spec)
    k_nope = _linear(c, w["w_uk"].reshape(r, -1), fp8).reshape(T, H, nope)
    v = _linear(c, w["w_uv"].reshape(r, -1), fp8).reshape(T, H, D["dv"])
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], spec)], -1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe, (T, H, D["rope"]))], -1)
    y = spec["rope_scaling"]
    scale = ((nope + D["rope"]) ** -0.5
             * _yarn_mscale(y["factor"], y["mscale_all_dim"]) ** 2)
    o = _attend(q, k, v, scale).reshape(T, -1)
    return x + _linear(o, w["wo"].reshape(-1, d), fp8)


def _swiglu(h, wg, wu, wd, fp8):
    return _linear(jax.nn.silu(_linear(h, wg, fp8)) * _linear(h, wu, fp8),
                   wd, fp8)


def _experts(h, w, spec, fp8):
    """The routed experts held here, weighted by the routing, plus the
    shared experts."""
    D = _dims(spec)
    probs = jax.nn.softmax(_linear(h, w["router"], fp8), axis=-1)
    top_p, top_i = jax.lax.top_k(probs, D["k"])
    if spec["norm_topk_prob"]:
        top_p = top_p / top_p.sum(-1, keepdims=True)
    top_p = top_p * spec["routed_scaling_factor"]
    held = spec.get("experts_offset", 0) + jnp.arange(D["n"])
    gate = jnp.einsum("tk,tke->te", top_p,
                      (top_i[:, :, None] == held).astype(jnp.float32))

    def one(acc, e):
        y = _swiglu(h, w["w_gate"][e], w["w_up"][e], w["w_down"][e], fp8)
        return acc + gate[:, e, None] * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(h), jnp.arange(D["n"]))
    return out + _swiglu(h, w["sh_gate"], w["sh_up"], w["sh_down"], fp8)


def _block(x, w, spec, fp8, moe: bool):
    x = _mla(x, w, spec, fp8)
    h = _rms(x, w["mlp_norm"], spec["rms_norm_eps"])
    if moe:
        return x + _experts(h, w, spec, fp8)
    return x + _swiglu(h, w["w_gate"], w["w_up"], w["w_down"], fp8)


@functools.partial(jax.jit, static_argnums=(2, 4))
def _layer(x, w, spec_items, layer, fp8):
    spec = _nested(spec_items)
    K = spec["first_k_dense_replace"]

    def run(stack, index, moe):
        names = _ATTN + (_MOE if moe else _FFN)
        wl = {k: jax.lax.dynamic_index_in_dim(w[f"{stack}.{k}"], index,
                                              keepdims=False) for k in names}
        return jax.lax.map(lambda row: _block(row, wl, spec, fp8, moe), x)

    return jax.lax.cond(layer < K,
                        lambda: run("dense", jnp.minimum(layer, K - 1), False),
                        lambda: run("moe", jnp.maximum(layer - K, 0), True))


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _head(x, w, spec_items, start, fp8):
    spec = _nested(spec_items)
    h = _rms(x[:, start:], w["final_norm"], spec["rms_norm_eps"])
    return jax.lax.map(lambda row: _linear(row, w["lm_head"], fp8), h)


def _hashable(spec):
    """The file's scalars, with those of nested objects (``rope_scaling``,
    ``published``) under dotted names."""
    flat = {}
    for k, v in spec.items():
        for kk, vv in (v.items() if isinstance(v, dict) else [(None, v)]):
            if isinstance(vv, (int, float, str, bool)):
                flat[k if kk is None else f"{k}.{kk}"] = vv
    return tuple(sorted(flat.items()))


def _nested(items):
    spec = {}
    for k, v in items:
        outer, _, inner = k.partition(".")
        if inner:
            spec.setdefault(outer, {})[inner] = v
        else:
            spec[k] = v
    return spec


def logits(w, spec, tokens, start: int, fp8: bool = False):
    """tokens: (R, T) int32. Float32 logits (R, T - start, vocab) at
    positions start..T-1, layer by layer so that one layer's float32
    weights are resident at a time."""
    items = _hashable(spec)
    x = jnp.take(w["embed"], jnp.asarray(tokens), axis=0).astype(jnp.float32)
    for layer in range(spec["num_hidden_layers"]):
        x = _layer(x, w, items, jnp.int32(layer), fp8)
    return _head(x, w, items, start, fp8)
