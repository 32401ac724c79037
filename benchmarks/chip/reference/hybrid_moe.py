"""Plain float32 reference of IBM Granite 4.0-H (``granitemoehybrid``):
Mamba2 and attention mixers, each followed by an expert layer, at one chip's
share of the routed experts.

Each layer, as ``GraniteMoeHybridDecoderLayer`` of the transformers library:
x = x + m * mixer(rms(x)), then x = x + m * (experts(rms(x)) + shared(rms(x))),
with m = ``residual_multiplier`` and RMSNorm at ``rms_norm_eps``. The mixer is
``layer_types[i]``:

- ``mamba``: in_proj gives (z, x, B, C, dt); x, B and C pass a depthwise
  causal convolution with its bias and SiLU; dt = softplus(dt + dt_bias),
  A = -exp(A_log); the scan h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T,
  y_t = h_t C_t + D x_t, computed chunk by chunk (``CHUNK`` tokens, the
  state carried from chunk to chunk); then RMSNorm(y * silu(z)) over the
  whole inner width (``GraniteMoeHybridRMSNormGated``) and out_proj.
- ``attention``: grouped-query attention (``num_key_value_heads`` K/V heads,
  head width hidden / heads) with no positional encoding
  (``position_embedding_type`` "nope"), causal softmax at the score scale
  ``attention_multiplier``, in blocks of ``QUERY_BLOCK`` queries.

The expert layer: the router's ``published.num_local_experts`` logits, the
top-k of them, softmax over those k (``GraniteMoeHybridTopKGating``: the same
weights as a softmax over all renormalised over the top k), the SwiGLU of
each chosen expert that this chip holds (experts ``experts_offset`` .. +
``num_local_experts``) weighted and summed; what the experts held on other
chips add is left out, as in the program. The shared SwiGLU of width
``shared_intermediate_size`` is added for every token. Embeddings times
``embedding_multiplier``; a final RMSNorm, the tied head, the logits divided
by ``logits_scaling``. Every product is float32 at ``Precision.HIGHEST``; the
residual stream stays float32.

Each layer computes every token through each held expert and weighs the
outputs by the routing (zero where not chosen): no sort, no capacity.

Departures from the published code: none in the mathematics. The experts'
fused ``input_linear`` (gate rows, then up rows) is held as two matrices,
and the state-space scan is blocked differently from the library's chunked
form (the same recurrence, another order of float32 sums).

This module imports nothing of the program. It makes the weights in one
jitted call from a key, in bfloat16 (the router's bfloat16 draw held in
float32, the type the program scores it in), and lays them out as the
program's parameter tree (``program_params``) without copies: one stack per
block of the layer period (``b<j>``: the period's mixers at even j, their
expert layers at odd j), over the period's repeats. ``_layer`` takes a
traced layer index and picks that layer's block weights with ``lax.switch``.

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
CHUNK = 128
QUERY_BLOCK = 256
VOCAB_BLOCK = 8192

# the program's config fields, by the published key that sets each
# (``layer_types``, a list here and a tuple there, is compared by the tests)
PROGRAM_KEYS = {
    "num_hidden_layers": "n_layers", "hidden_size": "d_model",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "vocab_size": "vocab_size", "tie_word_embeddings": "tie_embeddings",
    "attention_multiplier": "attn_scale",
    "embedding_multiplier": "embedding_multiplier",
    "residual_multiplier": "residual_multiplier",
    "logits_scaling": "logits_scaling", "rms_norm_eps": "norm_eps",
    "mamba_d_state": "ssm.d_state", "mamba_d_head": "ssm.head_dim",
    "mamba_expand": "ssm.expand", "mamba_d_conv": "ssm.conv_width",
    "mamba_n_groups": "ssm.n_groups", "mamba_chunk_size": "ssm.chunk_size",
    "intermediate_size": "moe.d_ff_expert",
    "published.num_local_experts": "moe.num_experts",
    "num_local_experts": "moe.experts_held",
    "experts_offset": "moe.experts_offset",
    "num_experts_per_tok": "moe.top_k",
}

_MAMBA = ("norm", "in_proj", "conv_w", "conv_b", "dt_bias", "a_log",
          "d_skip", "gate_norm", "out_proj")
_ATTN = ("norm", "wq", "wk", "wv", "wo")
_MOE = ("norm", "router", "w_gate", "w_up", "w_down", "sh_gate", "sh_up",
        "sh_down")


def period(types) -> int:
    """The shortest period of the layer pattern."""
    return next(p for p in range(1, len(types) + 1)
                if list(types) == list(types[:p]) * (len(types) // p))


def _dims(spec):
    d, types = spec["hidden_size"], spec["layer_types"]
    H, di = spec["num_attention_heads"], spec["mamba_expand"] * d
    P = period(types)
    return dict(
        L=spec["num_hidden_layers"], d=d, H=H,
        KVH=spec["num_key_value_heads"], hd=d // H, V=spec["vocab_size"],
        di=di, mh=spec["mamba_n_heads"], hp=spec["mamba_d_head"],
        N=spec["mamba_d_state"], G=spec["mamba_n_groups"],
        W=spec["mamba_d_conv"], f=spec["intermediate_size"],
        fs=spec["shared_intermediate_size"],
        E=spec["published"]["num_local_experts"],
        n=spec["num_local_experts"], k=spec["num_experts_per_tok"],
        P=P, R=len(types) // P, kinds=tuple(types[:P]))


def shapes(spec):
    """Weight name -> shape: ``b<j>.<weight>`` with a leading axis over the
    period's repeats (the layer i = repeat x period + j // 2)."""
    D = _dims(spec)
    d, R, gn = D["d"], D["R"], D["G"] * D["N"]
    conv = D["di"] + 2 * gn
    out = {"embed": (D["V"], d), "final_norm": (d,)}
    for p, kind in enumerate(D["kinds"]):
        m, e = f"b{2 * p}", f"b{2 * p + 1}"
        if kind == "mamba":
            mh = D["mh"]
            out.update({
                f"{m}.norm": (R, d),
                f"{m}.in_proj": (R, d, 2 * D["di"] + 2 * gn + mh),
                f"{m}.conv_w": (R, D["W"], conv), f"{m}.conv_b": (R, conv),
                f"{m}.dt_bias": (R, mh), f"{m}.a_log": (R, mh),
                f"{m}.d_skip": (R, mh), f"{m}.gate_norm": (R, D["di"]),
                f"{m}.out_proj": (R, D["di"], d)})
        else:
            H, KVH, hd = D["H"], D["KVH"], D["hd"]
            out.update({
                f"{m}.norm": (R, d), f"{m}.wq": (R, d, H, hd),
                f"{m}.wk": (R, d, KVH, hd), f"{m}.wv": (R, d, KVH, hd),
                f"{m}.wo": (R, H, hd, d)})
        out.update({
            f"{e}.norm": (R, d), f"{e}.router": (R, d, D["E"]),
            f"{e}.w_gate": (R, D["n"], d, D["f"]),
            f"{e}.w_up": (R, D["n"], d, D["f"]),
            f"{e}.w_down": (R, D["n"], D["f"], d),
            f"{e}.sh_gate": (R, d, D["fs"]), f"{e}.sh_up": (R, d, D["fs"]),
            f"{e}.sh_down": (R, D["fs"], d)})
    return out


def _fan_in(base, shape):
    if base == "wo":
        return shape[1] * shape[2]
    if base in ("w_gate", "w_up", "w_down"):
        return shape[-2]
    return shape[1]


@functools.partial(jax.jit, static_argnums=(0, 2))
def _init(shape_items, key, embedding_multiplier=1.0):
    out = {}
    for i, (name, shape) in enumerate(shape_items):
        k = jax.random.fold_in(key, i)
        base = name.split(".")[-1]
        if base in ("norm", "gate_norm", "final_norm", "d_skip"):
            x = jnp.ones(shape, jnp.float32)
        elif base == "dt_bias":  # dt log-uniform in [1e-3, 1e-1]
            dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32,
                                            math.log(1e-3), math.log(1e-1)))
            x = dt + jnp.log(-jnp.expm1(-dt))
        elif base == "a_log":    # A uniform in [1, 16]
            x = jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))
        else:
            std = {"embed": 1.0 / (math.sqrt(shape[1])
                                   * embedding_multiplier),
                   "conv_w": 0.5, "conv_b": 0.1}.get(base)
            std = std or 1.0 / math.sqrt(_fan_in(base, shape))
            x = jax.random.normal(k, shape, jnp.float32) * std
        x = x.astype(jnp.bfloat16)
        out[name] = x.astype(jnp.float32) if base == "router" else x
    return out


def init_weights(spec, key):
    """All weights, bfloat16 (the router float32), on the default device,
    in one jitted call. The embedding's std is 1 / (sqrt(hidden) x
    ``embedding_multiplier``), so that the scaled embedding enters the
    residual stream at std 1 / sqrt(hidden): any larger, and the tied head
    finds each position's own input token far above every other logit."""
    return _init(tuple(sorted(shapes(spec).items())), key,
                 float(spec["embedding_multiplier"]))


def program_params(w):
    """The program's parameter tree over the same arrays (no copies)."""
    blocks = {}
    for name in w:
        blk, _, base = name.partition(".")
        if base:
            blocks.setdefault(blk, {})[base] = w[name]
    tree = {}
    for blk, b in blocks.items():
        if "in_proj" in b:
            tree[blk] = {"norm": {"scale": b["norm"]}, "ssm": {
                "in_proj": b["in_proj"], "conv_w": b["conv_w"],
                "conv_b": b["conv_b"], "dt_bias": b["dt_bias"],
                "a_log": b["a_log"], "d_skip": b["d_skip"],
                "norm": b["gate_norm"], "out_proj": b["out_proj"]}}
        elif "wq" in b:
            tree[blk] = {"norm": {"scale": b["norm"]},
                         "attn": {k: b[k] for k in ("wq", "wk", "wv", "wo")}}
        else:
            tree[blk] = {"norm": {"scale": b["norm"]}, "moe": {
                "router": b["router"], "w_gate": b["w_gate"],
                "w_up": b["w_up"], "w_down": b["w_down"],
                "shared": {"w_gate": b["sh_gate"], "w_up": b["sh_up"],
                           "w_down": b["sh_down"]}}}
    return {"embed": w["embed"], "final_norm": {"scale": w["final_norm"]},
            "groups": {"g0": tree}}


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


def _conv(x, w, b):
    """Depthwise causal conv of one sequence. x: (T, C); w: (W, C); out_t =
    b + sum_k w[k] x_{t-W+1+k}."""
    W, T = w.shape[0], x.shape[0]
    xp = jnp.pad(x, ((W - 1, 0), (0, 0)))
    return b + sum(xp[k:k + T] * w[k] for k in range(W))


def _ssd(x, dt, A, b, c, group):
    """The scan of one sequence, ``CHUNK`` tokens at a time. x: (T, H, P);
    dt: (T, H); A: (H,); b, c: (T, G, N), head h reading group
    ``group[h]``. Returns y: (T, H, P) without the D term. Within a chunk y_l = sum_{s<=l} (c_l . b_s) exp(cum_l - cum_s)
    dt_s x_s + exp(cum_l) c_l h, with cum the running sum of dt A and h the
    state carried in; the state carried out is exp(cum_last) h + sum_s
    exp(cum_last - cum_s) dt_s x_s b_s^T."""
    T, H, P = x.shape
    N = b.shape[-1]
    pad = (-T) % CHUNK  # dt = 0 and x = 0 past the end change nothing before
    x, dt, b, c = (jnp.pad(t, [(0, pad)] + [(0, 0)] * (t.ndim - 1))
                   for t in (x, dt, b, c))
    n = x.shape[0] // CHUNK
    x, dt, b, c = (t.reshape(n, CHUNK, *t.shape[1:]) for t in (x, dt, b, c))
    causal = jnp.tril(jnp.ones((CHUNK, CHUNK), bool))[..., None]

    def chunk(h, inp):
        xc, dtc, bc, cc = inp
        bc, cc = bc[:, group], cc[:, group]                     # (l, H, N)
        cum = jnp.cumsum(dtc * A, axis=0)                       # (l, H)
        decay = jnp.exp(jnp.where(causal, cum[:, None] - cum[None], -jnp.inf))
        scores = jnp.einsum("lhn,shn->lsh", cc, bc, precision=HI) * decay
        y = jnp.einsum("lsh,shp->lhp", scores, xc * dtc[..., None],
                       precision=HI)
        y = y + jnp.exp(cum)[..., None] * jnp.einsum(
            "lhn,hpn->lhp", cc, h, precision=HI)
        w_end = jnp.exp(cum[-1] - cum) * dtc                    # (s, H)
        h = jnp.exp(cum[-1])[:, None, None] * h + jnp.einsum(
            "sh,shp,shn->hpn", w_end, xc, bc, precision=HI)
        return h, y

    _, y = jax.lax.scan(chunk, jnp.zeros((H, P, N), jnp.float32),
                        (x, dt, b, c))
    return y.reshape(n * CHUNK, H, P)[:T]


def _mamba(x, w, D, eps, fp8):
    """The Mamba2 mixer of one sequence x: (T, d) float32."""
    T = x.shape[0]
    di, mh, hp, N, G = D["di"], D["mh"], D["hp"], D["N"], D["G"]
    proj = _linear(_rms(x, w["norm"], eps), w["in_proj"], fp8)
    z, xbc, dt = jnp.split(proj, [di, 2 * di + 2 * G * N], axis=-1)
    xbc = jax.nn.silu(_conv(xbc, w["conv_w"].astype(jnp.float32),
                            w["conv_b"].astype(jnp.float32)))
    xs, b, c = jnp.split(xbc, [di, di + G * N], axis=-1)
    dt = jax.nn.softplus(dt + w["dt_bias"].astype(jnp.float32))    # (T, mh)
    A = -jnp.exp(w["a_log"].astype(jnp.float32))
    xs = xs.reshape(T, mh, hp)
    group = jnp.arange(mh) // (mh // G)                  # each head's group
    y = _ssd(xs, dt, A, b.reshape(T, G, N), c.reshape(T, G, N), group)
    y = y + xs * w["d_skip"].astype(jnp.float32)[:, None]
    y = _rms(y.reshape(T, di) * jax.nn.silu(z), w["gate_norm"], eps)
    return _linear(y, w["out_proj"], fp8)


def _attention(x, w, D, scale, eps, fp8):
    """The NoPE grouped-query attention mixer of one sequence x: (T, d)."""
    T, d, H, KVH, hd = x.shape[0], D["d"], D["H"], D["KVH"], D["hd"]
    h = _rms(x, w["norm"], eps)
    q = _linear(h, w["wq"].reshape(d, -1), fp8).reshape(T, KVH, H // KVH, hd)
    k = _linear(h, w["wk"].reshape(d, -1), fp8).reshape(T, KVH, hd)
    v = _linear(h, w["wv"].reshape(d, -1), fp8).reshape(T, KVH, hd)
    nb = -(-T // QUERY_BLOCK)
    qb = jnp.pad(q, ((0, nb * QUERY_BLOCK - T), (0, 0), (0, 0), (0, 0)))
    qb = qb.reshape(nb, QUERY_BLOCK, KVH, H // KVH, hd)

    def block(args):
        i, qi = args
        s = jnp.einsum("qhgd,khd->hgqk", qi, k, precision=HI) * scale
        pos = i * QUERY_BLOCK + jnp.arange(QUERY_BLOCK)
        causal = jnp.arange(T)[None, :] <= pos[:, None]
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return jnp.einsum("hgqk,khd->qhgd", p, v, precision=HI)

    o = jax.lax.map(block, (jnp.arange(nb), qb))
    o = o.reshape(nb * QUERY_BLOCK, H * hd)[:T]
    return _linear(o, w["wo"].reshape(-1, d), fp8)


def _swiglu(h, wg, wu, wd, fp8):
    return _linear(jax.nn.silu(_linear(h, wg, fp8)) * _linear(h, wu, fp8),
                   wd, fp8)


def _experts(x, w, D, offset, eps, fp8):
    """The held routed experts, weighted by the routing, plus the shared
    expert, of one sequence x: (T, d)."""
    h = _rms(x, w["norm"], eps)
    probs = jax.nn.softmax(_linear(h, w["router"], fp8), axis=-1)
    top_p, top_i = jax.lax.top_k(probs, D["k"])
    top_p = top_p / top_p.sum(-1, keepdims=True)
    held = offset + jnp.arange(D["n"])
    gate = jnp.einsum("tk,tke->te", top_p,
                      (top_i[:, :, None] == held).astype(jnp.float32))

    def one(acc, e):
        y = _swiglu(h, w["w_gate"][e], w["w_up"][e], w["w_down"][e], fp8)
        return acc + gate[:, e, None] * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(h), jnp.arange(D["n"]))
    return out + _swiglu(h, w["sh_gate"], w["sh_up"], w["sh_down"], fp8)


@functools.partial(jax.jit, static_argnums=(2, 4))
def _layer(x, w, spec_items, layer, fp8):
    """Layer ``layer`` (traced) of rows x: (R, T, d) float32, one row at a
    time."""
    spec = _nested(spec_items)
    D = _dims(spec)
    eps, m = spec["rms_norm_eps"], spec["residual_multiplier"]
    scale = spec["attention_multiplier"]
    offset = spec.get("experts_offset", 0)
    pos, rep = layer % D["P"], layer // D["P"]

    def weights(block, names):
        return {k: jax.lax.dynamic_index_in_dim(w[f"b{block}.{k}"], rep,
                                                keepdims=False)
                for k in names}

    def mixer(kind):
        """The mixer of kind ``kind`` at the period's position ``pos``,
        its weights picked among the positions of that kind."""
        where = [p for p, kd in enumerate(D["kinds"]) if kd == kind]
        index = jnp.asarray([where.index(p) if p in where else 0
                             for p in range(D["P"])])[pos]
        names = _MAMBA if kind == "mamba" else _ATTN
        wl = jax.lax.switch(index, [functools.partial(weights, 2 * p, names)
                                    for p in where])
        if kind == "mamba":
            return jax.lax.map(lambda r: _mamba(r, wl, D, eps, fp8), x)
        return jax.lax.map(lambda r: _attention(r, wl, D, scale, eps, fp8), x)

    kinds = sorted(set(D["kinds"]))
    which = jnp.asarray([kinds.index(kd) for kd in D["kinds"]])[pos]
    x = x + m * jax.lax.switch(which, [functools.partial(mixer, kd)
                                       for kd in kinds])
    we = jax.lax.switch(pos, [functools.partial(weights, 2 * p + 1, _MOE)
                              for p in range(D["P"])])
    return x + m * jax.lax.map(lambda r: _experts(r, we, D, offset, eps,
                                                  fp8), x)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _head(x, w, spec_items, start, fp8):
    """Logits at positions start.. of rows x: (R, T, d), against the tied
    embedding a block of ``VOCAB_BLOCK`` rows at a time."""
    spec = _nested(spec_items)
    h = _rms(x[:, start:], w["final_norm"], spec["rms_norm_eps"])
    V, d = w["embed"].shape
    nb = -(-V // VOCAB_BLOCK)
    emb = jnp.pad(w["embed"], ((0, nb * VOCAB_BLOCK - V), (0, 0)))

    def row(r):
        z = jax.lax.map(lambda e: _linear(r, e.T, fp8),
                        emb.reshape(nb, VOCAB_BLOCK, d))
        return jnp.moveaxis(z, 0, -2).reshape(r.shape[0], -1)[:, :V]

    return jax.lax.map(row, h) / spec["logits_scaling"]


def _hashable(spec):
    """The file's scalars, with those of nested objects (``published``)
    under dotted names, and ``layer_types`` as a tuple."""
    flat = {"layer_types": tuple(spec["layer_types"])}
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
    x = x * spec["embedding_multiplier"]
    for layer in range(spec["num_hidden_layers"]):
        x = _layer(x, w, items, jnp.int32(layer), fp8)
    return _head(x, w, items, start, fp8)
