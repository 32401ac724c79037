"""Granite-4.0-H's mechanisms on the CPU at reduced widths: the layer plan
of Mamba2 and NoPE GQA mixers each followed by an expert layer, the served
path (prefill, then decode through one cache of K/V rows and SSM state)
against the float32 reference, the reference against the transformers
library's ``GraniteMoeHybridForCausalLM``, the held expert shares adding up
to the uncut layer, and the cache's split in the trace."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.bench_tiny_hybrid_moe import HYBRID_MOE as TINY
from benchmarks.chip import harness
from repro.configs.registry import get_config
from repro.models import moe, transformer
from repro.models.common import rms_norm
from repro.models.model_zoo import build_model
from repro.runtime import tracing
from repro.runtime.serve import (ServeOptions, ServeSession,
                                 build_decode_step, build_prefill_step)

CFG = get_config("granite-4.0-h-small", reduced=True)
REF = harness.load_module(harness.ROOT, "reference", "hybrid_moe")


def test_the_plan_groups_one_period_of_mixers_each_with_its_experts():
    full = transformer.layer_plan(get_config("granite-4.0-h-small"))
    assert len(full) == 1 and full[0].repeat == 2
    kinds = [b.kind for b in full[0].blocks]
    assert kinds[1::2] == ["moe"] * 10
    assert kinds[0::2] == ["ssm"] * 5 + ["attn"] + ["ssm"] * 4
    (small,) = transformer.layer_plan(CFG)
    assert small.repeat == 2
    assert [b.kind for b in small.blocks] == ["ssm", "moe", "attn", "moe"]


def _served_logits(model, params, tokens, S):
    """Prefill over the first S tokens, then decode the rest one at a time
    through the cache; the logits at positions S-1 .. T-2."""
    B, T = tokens.shape
    cache = model.init_cache(B, T)
    last, cache = jax.jit(build_prefill_step(model, ServeOptions()))(
        params, {"tokens": tokens[:, :S]}, cache)
    out = [last]
    decode = jax.jit(build_decode_step(model, ServeOptions()))
    for i in range(S, T - 1):
        _, last, cache = decode(params, cache, tokens[:, i:i + 1],
                                jnp.asarray(i, jnp.int32))
        out.append(last)
    return np.stack([np.asarray(x, np.float32) for x in out], 1)


def _rel(got, want):
    return np.linalg.norm(got - want) / np.linalg.norm(want)


@pytest.mark.parametrize("seed", [0, 2**33 + 1])
def test_prefill_then_decode_match_the_reference(seed):
    """B 2: a 32-token prefill, then 9 decode steps through the cache of
    K/V rows and SSM state, against the reference's full forward. The
    program runs in bf16 and the reference in float32: the relative L2 gap
    of the logits is 1.2-1.5e-2 at this size on these seeds (the float8
    control's 0.14-0.16), so 3e-2 leaves room for rounding and none for a
    wrong equation."""
    model = build_model(harness.program_config(TINY, REF))
    w = REF.init_weights(TINY, harness.weights_key(seed))
    tokens = np.random.default_rng(seed % 97).integers(
        0, 256, (2, 42)).astype(np.int32)
    got = _served_logits(model, REF.program_params(w), jnp.asarray(tokens),
                         32)
    want = np.asarray(REF.logits(w, TINY, tokens[:, :-1], 31))
    assert got.shape == want.shape == (2, 10, 256)
    assert _rel(got, want) < 3e-2


def test_float32_program_equals_the_reference():
    """The whole forward of the program with float32 weights and
    activations against the float32 reference: the same equations in
    another order of float32 sums, 1e-5 relative (measured ~2e-7)."""
    cfg = harness.program_config(TINY, REF).replace(
        param_dtype="float32", activ_dtype="float32")
    w = REF.init_weights(TINY, harness.weights_key(4))
    params = jax.tree.map(lambda a: a.astype(jnp.float32),
                          REF.program_params(w))
    tokens = np.random.default_rng(4).integers(0, 256, (2, 40)).astype(
        np.int32)
    got, _, _ = build_model(cfg).apply(params, {"tokens": tokens})
    want = np.asarray(REF.logits(w, TINY, tokens, 0))
    assert _rel(np.asarray(got), want) < 1e-5


def test_float8_control_departs_further():
    w = REF.init_weights(TINY, harness.weights_key(3))
    model = build_model(harness.program_config(TINY, REF))
    tokens = np.random.default_rng(3).integers(0, 256, (2, 48)).astype(
        np.int32)
    want = np.asarray(REF.logits(w, TINY, tokens, 0))
    got, _, _ = model.apply(REF.program_params(w), {"tokens": tokens})
    ctl = np.asarray(REF.logits(w, TINY, tokens, 0, fp8=True))
    assert _rel(ctl, want) > 3 * _rel(np.asarray(got, np.float32), want)


def _to_transformers(w, spec):
    """The reference's weights as the state dict of the library's
    ``GraniteMoeHybridForCausalLM`` (every expert held)."""
    import torch
    T = lambda a: torch.tensor(np.ascontiguousarray(a, np.float32))
    d, P = spec["hidden_size"], REF.period(spec["layer_types"])
    sd = {"model.embed_tokens.weight": T(w["embed"]),
          "lm_head.weight": T(w["embed"]),
          "model.norm.weight": T(w["final_norm"])}
    for i, kind in enumerate(spec["layer_types"]):
        r, p = divmod(i, P)
        m = {k.split(".")[1]: v[r] for k, v in w.items()
             if k.startswith(f"b{2 * p}.")}
        e = {k.split(".")[1]: v[r] for k, v in w.items()
             if k.startswith(f"b{2 * p + 1}.")}
        at = f"model.layers.{i}."
        sd[at + "input_layernorm.weight"] = T(m["norm"])
        if kind == "mamba":
            sd.update({
                at + "mamba.in_proj.weight": T(m["in_proj"].T),
                at + "mamba.conv1d.weight": T(m["conv_w"].T[:, None, :]),
                at + "mamba.conv1d.bias": T(m["conv_b"]),
                at + "mamba.dt_bias": T(m["dt_bias"]),
                at + "mamba.A_log": T(m["a_log"]),
                at + "mamba.D": T(m["d_skip"]),
                at + "mamba.norm.weight": T(m["gate_norm"]),
                at + "mamba.out_proj.weight": T(m["out_proj"].T)})
        else:
            for x in "qkv":
                sd[at + f"self_attn.{x}_proj.weight"] = T(
                    m[f"w{x}"].reshape(d, -1).T)
            sd[at + "self_attn.o_proj.weight"] = T(m["wo"].reshape(-1, d).T)
        moe_at = at + "block_sparse_moe."
        sd.update({
            at + "post_attention_layernorm.weight": T(e["norm"]),
            moe_at + "router.layer.weight": T(e["router"].T),
            moe_at + "input_linear.weight": T(np.concatenate(
                [e["w_gate"].transpose(0, 2, 1),
                 e["w_up"].transpose(0, 2, 1)], 1)),
            moe_at + "output_linear.weight": T(
                e["w_down"].transpose(0, 2, 1)),
            at + "shared_mlp.input_linear.weight": T(np.concatenate(
                [e["sh_gate"].T, e["sh_up"].T], 0)),
            at + "shared_mlp.output_linear.weight": T(e["sh_down"].T)})
    return sd


def test_the_reference_matches_the_transformers_library():
    """d 64, 4 layers (one attention, at position 2), 6 experts all held,
    top-2, a shared expert, NoPE, the muP scalars, RMSNorm at 1e-5: the
    library's float32 forward (its naive Mamba2 path) with the reference's
    weights against the reference. Both float32, so 1e-5 relative
    (measured ~2e-7)."""
    torch = pytest.importorskip("torch")
    tf = pytest.importorskip("transformers")
    spec = dict(TINY, layer_types=["mamba", "mamba", "attention", "mamba"],
                num_local_experts=6)
    w = jax.tree.map(lambda a: np.asarray(a, np.float32),
                     REF.init_weights(spec, jax.random.PRNGKey(0)))
    cfg = tf.GraniteMoeHybridConfig(
        vocab_size=256, hidden_size=64, intermediate_size=32,
        num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=1,
        rms_norm_eps=1e-5, tie_word_embeddings=True,
        embedding_multiplier=12.0, logits_scaling=16.0,
        residual_multiplier=0.22, attention_multiplier=0.125,
        num_local_experts=6, num_experts_per_tok=2,
        shared_intermediate_size=64, position_embedding_type="nope",
        layer_types=spec["layer_types"], mamba_n_heads=8, mamba_n_groups=1,
        mamba_d_state=16, mamba_d_head=16, mamba_d_conv=4, mamba_expand=2,
        mamba_chunk_size=16, mamba_conv_bias=True, mamba_proj_bias=False,
        attn_implementation="eager")
    lib = tf.GraniteMoeHybridForCausalLM(cfg).float().eval()
    missing, unexpected = lib.load_state_dict(_to_transformers(w, spec),
                                              strict=False)
    assert not missing and not unexpected
    tokens = np.random.default_rng(1).integers(0, 256, (2, 40))
    with torch.no_grad():
        got = lib(torch.tensor(tokens)).logits.numpy()
    want = np.asarray(REF.logits(w, spec, tokens, 0))
    assert _rel(got, want) < 1e-5


@pytest.mark.parametrize("side", ["program", "reference"])
def test_expert_shares_add_up_to_the_uncut_layer(side):
    """6 experts, 2 held, 3 shares at offsets 0, 2, 4: each share's part of
    the routed result, with the shared expert counted once, adds up to the
    reference's uncut layer (all 6 held). Float32 throughout, so 1e-4
    relative covers the order of the sums."""
    spec = dict(TINY, num_local_experts=6)
    D = REF._dims(spec)
    w = REF.init_weights(spec, harness.weights_key(7))
    e = {k.split(".")[1]: jnp.asarray(v[0], jnp.float32)
         for k, v in w.items() if k.startswith("b1.")}
    x = jax.random.normal(jax.random.PRNGKey(8), (40, 64))
    want = np.asarray(REF._experts(x, e, D, 0, 1e-5, False))
    h = rms_norm(x, e["norm"], 1e-5)
    cfg = CFG.replace(param_dtype="float32", activ_dtype="float32")
    total = np.zeros_like(want)
    for offset in (0, 2, 4):
        part = {k: v[offset:offset + 2] if k in moe.EXPERT_WEIGHTS else v
                for k, v in e.items()}
        if side == "program":
            share = cfg.replace(moe=dataclasses.replace(
                cfg.moe, num_experts=6, experts_held=2, experts_offset=offset))
            p = {"router": part["router"], "w_gate": part["w_gate"],
                 "w_up": part["w_up"], "w_down": part["w_down"],
                 "shared": {"w_gate": part["sh_gate"],
                            "w_up": part["sh_up"],
                            "w_down": part["sh_down"]}}
            out, _, _ = moe.apply_moe(p, h[None], cfg=share)
            total += np.asarray(out[0])
        else:
            D2 = dict(D, n=2)
            total += np.asarray(REF._experts(x, part, D2, offset, 1e-5,
                                             False))
    shared = np.asarray(REF._swiglu(h, e["sh_gate"], e["sh_up"],
                                    e["sh_down"], False))
    total -= 2 * shared               # each share adds the shared expert
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-4)


def test_generate_records_both_kinds_of_cache(tmp_path):
    """``serve.generate`` gives the cache it allocated split into K/V rows
    of the attention layers and conv and scan state of the Mamba2 layers;
    ``cache_bytes`` is their sum."""
    model = build_model(CFG)
    sess = ServeSession(model, model.init(jax.random.PRNGKey(0)))
    prompts = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 256,
                                 dtype=jnp.int32)
    tracing.clear()
    with jax.profiler.trace(str(tmp_path)):
        np.asarray(sess.generate(prompts, 4))
    (top,) = [r for r in tracing.spans() if r.name == "serve.generate"]
    tracing.clear()
    a = top.attrs
    # 2 attention layers x K and V x (16 + 4) positions x 1 head x 16 x bf16
    assert a["kv_cache_bytes"] == 2 * 2 * 2 * 20 * 16 * 2
    # 2 Mamba2 layers x f32 (3 x conv width 160 + 8 heads x 16 x 16)
    assert a["ssm_state_bytes"] == 2 * 2 * 4 * (3 * 160 + 8 * 16 * 16)
    assert a["cache_bytes"] == a["kv_cache_bytes"] + a["ssm_state_bytes"]
    assert a["experts_held"] == 3 and a["moe_rows_routed"] > 0


@pytest.mark.parametrize("arch", ["granite-4.0-h-small", "deepseek-v2-lite",
                                  "deepseek-moe-16b"])
def test_active_params_count_the_routed_experts_a_token_passes(arch):
    """Every expert layer's held experts give way to the top-k a token
    passes through: granite's 20 expert layers hold 8 and a token passes
    10, so its active count exceeds what this chip holds."""
    cfg = get_config(arch)
    model = build_model(cfg)
    m = cfg.moe
    layers = sum(gd.repeat for gd in transformer.layer_plan(cfg)
                 for b in gd.blocks if b.kind == "moe")
    per_expert = 3 * cfg.d_model * m.d_ff_expert
    assert layers == cfg.n_layers - m.first_k_dense
    assert model.active_param_count() == (
        model.param_count() - (m.held - m.top_k) * per_expert * layers)
    if arch == "granite-4.0-h-small":
        assert model.active_param_count() == 4_607_083_776
