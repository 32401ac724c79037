"""DeepSeek-V2's mechanisms on the CPU at reduced widths: latent attention
(absorbed decode against expanded attention), YaRN frequencies, the flash
kernel with a narrower V, and the one-device expert layer that holds a
share of the experts (shares add up, no token dropped, row counters); the
latent cache's size at published widths and the counters in the trace."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import YarnConfig
from repro.configs.registry import get_config
from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.moe_gmm import expert_ffn_pallas
from repro.models import mla, moe
from repro.models import transformer
from repro.models.common import apply_norm, init_params, yarn_inv_freq
from repro.models.ffn import apply_ffn
from repro.models.model_zoo import build_model
from repro.runtime import tracing
from repro.runtime.serve import ServeSession, abstract_cache

CFG = get_config("deepseek-v2-lite", reduced=True)


def _f32(specs, seed):
    return init_params(specs, jax.random.PRNGKey(seed), jnp.float32)


def test_absorbed_decode_matches_expanded_attention():
    """One MLA layer in float32: prefill over S - 1 tokens fills the latent
    cache, then one absorbed decode step reads it. Its output equals the
    expanded (train) attention's at the last position. Both are float32
    products of the same weights, regrouped (q W_uk^T c against q (c
    W_uk)), so they agree to float32 rounding: 1e-5 relative."""
    p = _f32(mla.mla_specs(CFG), 0)
    B, S = 2, 12
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, CFG.d_model))
    pos = jnp.arange(S)[None, :]
    want, _ = mla.apply_mla(p, x, cfg=CFG, positions=pos)
    cache = {"c_kv": jnp.zeros((B, S + 3, CFG.kv_lora_rank)),
             "k_pe": jnp.zeros((B, S + 3, CFG.qk_rope_head_dim))}
    _, rows = mla.apply_mla(p, x[:, :-1], cfg=CFG, positions=pos[:, :-1],
                            cache=cache, cache_index=0, mode="prefill")
    cache = {k: v.at[:, :S - 1].set(rows[k]) for k, v in cache.items()}
    got, new = mla.apply_mla(p, x[:, -1:], cfg=CFG, positions=pos[:, -1:],
                             cache=cache, cache_index=S - 1, mode="decode")
    np.testing.assert_allclose(np.asarray(got[:, 0]),
                               np.asarray(want[:, -1]), rtol=1e-5, atol=1e-5)
    assert set(new) == {"c_kv", "k_pe"} and new["c_kv"].shape == (
        B, 1, CFG.kv_lora_rank)


@pytest.mark.parametrize("i", [0, 10, 16, 23, 31])
def test_yarn_inv_freq(i):
    """DeepSeek-V2-Lite's rope dims (64, theta 1e4, factor 40, original
    4096, beta 32 / 1): the correction range is [10, 23]; the frequencies
    are the plain ones at and below 10, divided by 40 at and above 23, a
    linear ramp between."""
    y = CFG.yarn
    full = YarnConfig(factor=40.0, original_max_position=4096,
                      beta_fast=32.0, beta_slow=1.0, mscale=y.mscale,
                      mscale_all_dim=y.mscale_all_dim)
    inv = np.asarray(yarn_inv_freq(64, 10000.0, full))
    extra = 10000.0 ** (-2 * i / 64)
    mask = 1.0 - min(max((i - 10) / 13, 0.0), 1.0)
    want = extra / 40 * (1 - mask) + extra * mask
    assert inv[i] == pytest.approx(want, rel=1e-6)


def test_softmax_scale_takes_yarn_mscale_squared():
    full = get_config("deepseek-v2-lite")
    m = 0.1 * 0.707 * math.log(40) + 1
    assert mla.softmax_scale(full) == pytest.approx(192 ** -0.5 * m * m)
    assert round(m, 5) == 1.26080


def test_flash_attention_with_a_narrower_v():
    """q/k width 192 and v width 128, as DeepSeek-V2's prefill runs it, in
    interpret mode against the full-scores oracle (both float32)."""
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, 256, 2, 192))
    k = jax.random.normal(ks[1], (1, 256, 2, 192))
    v = jax.random.normal(ks[2], (1, 256, 2, 128))
    got = flash_attention_pallas(q, k, v, causal=True, scale=0.07,
                                 interpret=True)
    want = ref.mha_naive(q, k, v, causal=True, scale=0.07)
    assert got.shape == (1, 256, 2, 128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("rows, d, f, layers, layer", [
    pytest.param((20, 0, 9), 64, 32, 1, 0, id="3g-64-32"),
    pytest.param((16, 11), 32, 64, 1, 0, id="2g-32-64"),
    pytest.param((64, 59, 54, 49, 44, 39, 34, 0), 128, 64, 1, 0,
                 id="8g-128-64"),
    pytest.param((8, 3, 6, 0), 256, 128, 1, 0, id="4g-256-128"),
    pytest.param((20, 0, 9), 64, 32, 3, 0, id="3g-64-32-layer0of3"),
    pytest.param((20, 0, 9), 64, 32, 3, 1, id="3g-64-32-layer1of3"),
    pytest.param((20, 0, 9), 64, 32, 3, 2, id="3g-64-32-layer2of3"),
])
def test_expert_ffn_kernel_matches_its_twin(rows, d, f, layers, layer, dtype):
    """The grouped kernel in interpret mode against the ragged jnp twin:
    experts of uneven row counts (some empty) in whole 16-row tiles, then
    three unused tiles, whose rows the kernel leaves unwritten and nothing
    reads. The kernel reads ``layer`` of a stack of ``layers`` layers whose
    weights all differ; the twin gets that layer's slice alone, as a stack
    of one. Both cast the hidden and the output to ``dtype`` and accumulate
    in float32, so they agree to float32 rounding, or to a bfloat16 step
    where a rounding of the hidden falls the other way."""
    G, bm = len(rows), 16
    ks = jax.random.split(jax.random.PRNGKey(8), 4)
    wg, wu = (jax.random.normal(k, (layers, G, d, f)) / d ** 0.5
              for k in ks[:2])
    wd = jax.random.normal(ks[2], (layers, G, f, d)) / f ** 0.5
    rows = np.array(rows)
    tiles = -(-rows // bm)
    group_rows = jnp.asarray(tiles * bm, jnp.int32)
    n = int(tiles.sum()) * bm
    M = n + 3 * bm
    live = np.zeros(M, bool)                              # padding rows: 0
    for start, r in zip(np.cumsum(tiles * bm) - tiles * bm, rows):
        live[start:start + r] = True
    x = jnp.where(live[:, None], jax.random.normal(ks[3], (M, d)), 0.0)
    tile_group = jnp.asarray(np.minimum(
        np.searchsorted(np.cumsum(tiles), np.arange(M // bm), side="right"),
        G - 1), jnp.int32)
    x, wg, wu, wd = (a.astype(dtype) for a in (x, wg, wu, wd))
    got = expert_ffn_pallas(x, wg, wu, wd, jnp.asarray([layer], jnp.int32),
                            tile_group, jnp.asarray([tiles.sum()], jnp.int32),
                            block_m=bm, interpret=True)
    want = ref.expert_ffn_ragged(x, wg[layer:layer + 1], wu[layer:layer + 1],
                                 wd[layer:layer + 1], 0, group_rows)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got[:n], np.float32),
                               np.asarray(want[:n], np.float32),
                               rtol=tol, atol=tol)


def _uncut(x, p, cfg):
    """Every token's top-k experts of all ``num_experts`` and the shared
    experts, one token at a time, in float32."""
    probs = np.asarray(jax.nn.softmax(x @ p["router"], axis=-1))
    silu = lambda a: a / (1 + np.exp(-a))
    out = []
    for t in range(x.shape[0]):
        top = np.argsort(-probs[t])[:cfg.moe.top_k]
        acc = np.zeros(x.shape[1], np.float32)
        for e in top:
            h = silu(x[t] @ p["w_gate"][e]) * (x[t] @ p["w_up"][e])
            acc += probs[t, e] * (h @ p["w_down"][e])
        sh = p["shared"]
        acc += (silu(x[t] @ sh["w_gate"]) * (x[t] @ sh["w_up"])) @ sh["w_down"]
        out.append(acc)
    return np.stack(out)


def _share(p, cfg, offset):
    held = cfg.moe.held
    cut = {k: v[offset:offset + held] if k in ("w_gate", "w_up", "w_down")
           else v for k, v in p.items()}
    return cut, cfg.replace(moe=dataclasses.replace(cfg.moe,
                                                    experts_offset=offset))


def test_expert_shares_add_up_to_the_uncut_layer():
    """Each offset's share computes its own experts' part of the routed
    result; the parts of all shares, plus the shared experts once, are the
    uncut layer. Float32 throughout, so 1e-4 relative covers the order of
    the sums."""
    uncut_cfg = CFG.replace(moe=dataclasses.replace(CFG.moe, experts_held=0))
    p = jax.tree.map(np.asarray, _f32(moe.moe_specs(uncut_cfg), 2))
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(3), (1, 40, 64)))
    want = _uncut(x[0], p, CFG)
    total = np.zeros_like(want)
    n = CFG.moe.num_experts // CFG.moe.held
    assert n >= 2
    for i in range(n):
        part, cfg = _share(p, CFG, i * CFG.moe.held)
        out, _, _ = moe.apply_moe(part, jnp.asarray(x), cfg=cfg)
        total += np.asarray(out[0])
    sh = p["shared"]
    silu = lambda a: a / (1 + np.exp(-a))
    shared = (silu(x[0] @ sh["w_gate"]) * (x[0] @ sh["w_up"])) @ sh["w_down"]
    total -= (n - 1) * shared          # each share adds the shared experts
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-4)


def test_no_token_is_dropped_when_every_token_picks_one_held_expert():
    """A router that sends every token to expert 0 (held) and expert 1
    (held): 2 T rows on two experts, far past any capacity of
    T k / E x 1.25; every row is computed and the output is the uncut
    layer's. The counters give the routed rows, whole 16-row tiles and
    the two experts whose weights the kernel read."""
    T = 64
    p = _f32(moe.moe_specs(CFG), 4)
    router = np.zeros((64, CFG.moe.num_experts), np.float32)
    router[0, 0], router[0, 1] = 100.0, 50.0
    p["router"] = jnp.asarray(router)
    x = np.array(jax.random.normal(jax.random.PRNGKey(5), (T, 64)))
    x[:, 0] = 1.0 + np.abs(x[:, 0])
    out, _, rows = moe.apply_moe(p, jnp.asarray(x)[None], cfg=CFG)
    np.testing.assert_allclose(np.asarray(out[0]),
                               _uncut(x, jax.tree.map(np.asarray, p), CFG),
                               rtol=1e-4, atol=1e-4)
    routed, computed, read = (int(r) for r in rows)
    assert routed == 2 * T and computed == 2 * T   # 64 rows = 4 tiles each
    assert read == 2


def test_routing_elsewhere_computes_nothing():
    """Tokens that all choose experts this device does not hold give an
    all-zero routed part, no rows and no expert's weights read."""
    p = _f32(moe.moe_specs(CFG), 6)
    router = np.zeros((64, CFG.moe.num_experts), np.float32)
    router[0, 6], router[0, 7] = 100.0, 99.0
    p["router"] = jnp.asarray(router)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(7), (1, 20, 64))) + 1
    out, _, rows = moe.apply_moe(p, x, cfg=CFG)
    shared = apply_ffn(p["shared"], x, cfg=CFG)
    np.testing.assert_allclose(np.asarray(out), np.asarray(shared))
    assert [int(r) for r in rows] == [0, 0, 0]


def _layer_by_layer(params, tokens, cfg, cache, cache_index, mode):
    """``forward`` as a Python loop over the layers, each handed its own
    slice of every stacked parameter and cache leaf (an expert layer gets
    one layer's weights). Returns (logits of the last position, each
    attention block's emitted rows by (group, block, layer), the expert
    row counters summed)."""
    x = jnp.take(params["embed"], tokens, axis=0)
    positions = cache_index + jnp.arange(tokens.shape[1])[None, :]
    emitted, rows = {}, jnp.zeros((3,), jnp.int32)
    for i, gd in enumerate(transformer.layer_plan(cfg)):
        gp, gc = params["groups"][f"g{i}"], cache["groups"][f"g{i}"]
        for layer in range(gd.repeat):
            for j, b in enumerate(gd.blocks):
                at = lambda tree: jax.tree.map(lambda a: a[layer], tree)
                bc = at(gc[f"b{j}"]) if f"b{j}" in gc else None
                x, nc, _, r = transformer._apply_block(
                    at(gp[f"b{j}"]), x, b, cfg=cfg, dist=moe.LOCAL,
                    mode=mode, cache=bc, cache_index=cache_index,
                    cross_states=None, shared_params=None,
                    positions=positions)
                if nc is not None:
                    emitted[(i, j, layer)] = nc
                if r is not None:
                    rows = rows + r
    x = apply_norm(params["final_norm"], x[:, -1:], cfg)
    return x @ params["lm_head"], emitted, rows


def test_scan_reads_each_layers_experts_like_a_loop_over_layers():
    """A float32 model with three expert layers whose weights all differ:
    prefill then one decode step through ``forward``, whose scan hands the
    expert layer the whole stacks and the layer's index, against a loop
    over the layers handing each its own slice. Logits, the latent cache
    rows and the expert row counters agree to float32 rounding: 1e-4
    relative, as the other expert-layer tests."""
    cfg = CFG.replace(n_layers=4, param_dtype="float32",
                      activ_dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(11))
    w = params["groups"]["g1"]["b1"]["moe"]["w_gate"]
    assert w.shape[0] == 3 and not np.allclose(w[0], w[1])
    B, S = 2, 24
    tokens = jax.random.randint(jax.random.PRNGKey(12), (B, S + 1), 0,
                                cfg.vocab_size, dtype=jnp.int32)
    cache = model.init_cache(B, S + 4)
    logits, cache, _ = model.apply(params, {"tokens": tokens[:, :S]},
                                   mode="prefill", cache=cache,
                                   cache_index=0)
    want, emitted, rows = _layer_by_layer(params, tokens[:, :S], cfg,
                                          model.init_cache(B, S + 4), 0,
                                          "prefill")
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    for (i, j, layer), nc in emitted.items():
        for k, v in nc.items():
            got = cache["groups"][f"g{i}"][f"b{j}"][k][layer, :, :S]
            np.testing.assert_allclose(np.asarray(got), np.asarray(v),
                                       rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(np.asarray(cache["moe_rows"][0]),
                                  np.asarray(rows))
    assert int(rows[0]) > 0
    logits, new, _ = model.apply(params, {"tokens": tokens[:, S:]},
                                 mode="decode", cache=cache, cache_index=S)
    want, _, rows = _layer_by_layer(params, tokens[:, S:], cfg, cache, S,
                                    "decode")
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(np.asarray(new["moe_rows"][1]),
                                  np.asarray(rows))


def test_latent_cache_bytes_at_published_widths():
    """27 layers x (512 + 64) x 2 bytes = 31,104 bytes a token, no
    per-head K/V; the counters, one row for prefill and one for decode,
    ride beside the layers' leaves."""
    model = build_model(get_config("deepseek-v2-lite"))
    B, T = 8, 8192 + 64
    cache = abstract_cache(model, B, T)
    nbytes = sum(x.size * x.dtype.itemsize
                 for x in jax.tree.leaves(cache["groups"]))
    assert nbytes == B * T * 31_104
    assert cache["moe_rows"].shape == (2, 3)


def test_generate_records_expert_counters_only_in_a_session(tmp_path):
    model = build_model(CFG)
    sess = ServeSession(model, model.init(jax.random.PRNGKey(0)))
    prompts = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 256,
                                 dtype=jnp.int32)
    tracing.clear()
    off = np.asarray(sess.generate(prompts, 4))
    assert tracing.spans() == []
    with jax.profiler.trace(str(tmp_path)):
        on = np.asarray(sess.generate(prompts, 4))
    top = [r for r in tracing.spans() if r.name == "serve.generate"][0]
    tracing.clear()
    np.testing.assert_array_equal(on, off)
    a = top.attrs
    assert a["experts_held"] == CFG.moe.held
    assert a["cache_bytes"] == 2 * 20 * 3 * (32 + 8) * 2
    # 2 expert layers x (prefill 32 tokens + 3 decode steps of 2) x k 2
    assert 0 < a["moe_rows_routed"] <= 2 * (32 + 3 * 2) * 2
    assert a["moe_rows_computed"] % 16 == 0
    assert a["moe_rows_computed"] >= a["moe_rows_routed"]
    # at most every held expert once a kernel call: 2 layers x 4 calls
    assert 0 < a["moe_experts_read"] <= 2 * 4 * CFG.moe.held
    assert 0 <= a["moe_decode_rows_routed"] <= 2 * 3 * 2 * 2
    assert a["moe_decode_rows_routed"] < a["moe_rows_routed"]
    assert 0 <= a["moe_decode_experts_read"] <= 2 * 3 * CFG.moe.held
    assert a["moe_decode_experts_read"] < a["moe_experts_read"]
