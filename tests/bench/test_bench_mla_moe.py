"""The ``mla_moe`` family on the benchmark's side: its configuration file
against the registry, its float32 reference against the program (prefill,
then decode through the latent cache), the float8 control, the operation
and byte counts, the new metrics' readers, and one tiny cell driven end to
end through the harness on the CPU."""
import copy
import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_tiny
from benchmarks.chip import harness, work_mla_moe
from repro.models.model_zoo import build_model
from repro.runtime.serve import build_decode_step, build_prefill_step, \
    ServeOptions

ROOT = harness.ROOT
SPEC = json.load(open(os.path.join(
    ROOT, "benchmarks", "chip", "configs", "deepseek-v2-lite.json")))
# the registry's reduced preset: 4 of 8 experts held, top-2
TINY = {"name": "tiny-mla-moe", "arch": "deepseek-v2-lite",
        "preset": "reduced", "family": "mla_moe", "num_hidden_layers": 3,
        "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 4,
        "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
        "v_head_dim": 16, "intermediate_size": 128,
        "moe_intermediate_size": 32, "n_routed_experts": 4,
        "experts_offset": 0, "published": {"n_routed_experts": 8},
        "num_experts_per_tok": 2, "n_shared_experts": 2,
        "first_k_dense_replace": 1, "norm_topk_prob": False,
        "routed_scaling_factor": 1.0,
        "rope_scaling": SPEC["rope_scaling"], "vocab_size": 256,
        "rope_theta": 10000.0, "rms_norm_eps": 1e-6,
        "tie_word_embeddings": False, "token_ids_below": 256}
# bf16 program against the float32 reference: relative L2 gap of the
# logits ~1.5e-2 at this size (as the dense family's, test_bench_reference)
TOL = 5e-2


def _setup(spec, seed):
    ref = harness.load_module(ROOT, "reference", spec["family"])
    model = build_model(harness.program_config(spec, ref))
    return ref, model, ref.init_weights(spec, harness.weights_key(seed))


def _served_logits(model, params, tokens, S):
    """Prefill over the first S tokens, then decode the rest one at a time
    through the latent cache; the logits at positions S-1 .. T-2."""
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


@pytest.mark.parametrize("seed", [0, 2**33 + 1])
def test_prefill_then_decode_match_the_reference(seed):
    ref, model, w = _setup(TINY, seed)
    tokens = np.random.default_rng(seed % 97).integers(
        0, 256, (2, 40)).astype(np.int32)
    got = _served_logits(model, ref.program_params(w), jnp.asarray(tokens),
                         32)
    want = np.asarray(ref.logits(w, TINY, tokens[:, :-1], 31))
    assert got.shape == want.shape
    rel = np.linalg.norm(got - want) / np.linalg.norm(want)
    assert rel < TOL, rel


def test_float8_control_departs_further():
    ref, model, w = _setup(TINY, 3)
    tokens = np.random.default_rng(3).integers(0, 256, (2, 48)).astype(
        np.int32)
    want = np.asarray(ref.logits(w, TINY, tokens, 0))
    got, _, _ = model.apply(ref.program_params(w), {"tokens": tokens})
    ctl = np.asarray(ref.logits(w, TINY, tokens, 0, fp8=True))
    rel = lambda x: np.linalg.norm(x - want) / np.linalg.norm(want)
    assert rel(ctl) > 3 * rel(np.asarray(got, np.float32))


def test_the_reference_leaves_out_the_experts_held_elsewhere():
    """Another share of the experts (offset 4) gives other logits: the
    reference computes only the share it is given, as the program does."""
    ref, model, w = _setup(TINY, 4)
    tokens = np.random.default_rng(4).integers(0, 256, (1, 24)).astype(
        np.int32)
    here = np.asarray(ref.logits(w, TINY, tokens, 0))
    there = np.asarray(ref.logits(w, dict(TINY, experts_offset=4), tokens, 0))
    assert np.linalg.norm(here - there) > 0.05 * np.linalg.norm(here)


def test_configuration_file_matches_the_registry():
    ref = harness.load_module(ROOT, "reference", "mla_moe")
    cfg = harness.program_config(SPEC, ref)
    assert cfg.name == "deepseek-v2-lite"
    assert (cfg.moe.num_experts, cfg.moe.held, cfg.moe.top_k) == (64, 8, 6)
    assert SPEC["reduced"] == ["n_routed_experts"]
    for key, value in (("n_routed_experts", 64), ("kv_lora_rank", 256)):
        with pytest.raises(ValueError):
            harness.program_config(dict(SPEC, **{key: value}), ref)
    wrong = copy.deepcopy(SPEC)
    wrong["rope_scaling"]["factor"] = 4
    with pytest.raises(ValueError):
        harness.program_config(wrong, ref)


def test_reference_weights_fill_the_programs_tree():
    """At the published sizes, by shape only: 3.108e9 parameters of this
    chip's share, laid out as the program's tree, leaf for leaf."""
    ref = harness.load_module(ROOT, "reference", "mla_moe")
    model = build_model(harness.program_config(SPEC, ref))
    w = jax.eval_shape(lambda: ref.init_weights(SPEC, harness.weights_key(0)))
    got, want = ref.program_params(w), model.abstract()
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)
    n = sum(x.size for x in jax.tree.leaves(w))
    assert 3.10e9 < n < 3.115e9


def test_work_counts_by_hand():
    s = SPEC
    assert work_mla_moe.routed_rows(s, 64) == 64 * 6 * 8 / 64
    f, b = work_mla_moe.mla_flash_work(s, 8, 8192)
    assert f == 27 * 8 * 16 * 2.0 * 320 * (8192 * 8193 // 2)
    assert b == 27 * 8 * 8192 * 16 * 2.0 * (192 * 2 + 128 * 2)
    f, b = work_mla_moe.expert_ffn_calls(s, 6, 4)
    assert f == 6 * 2048 * 1408 * 6.0
    assert b == 4 * 3 * 2048 * 1408 * 2 + 6 * 2048 * 4
    # 2.12 GFLOP a token through the layers, head excluded
    per_token = work_mla_moe._per_token_params(s) * 2
    assert 2.05e9 < per_token < 2.2e9
    one = work_mla_moe.task_flops(s, 1, 4096, 2)
    assert one == pytest.approx(
        per_token * 4097 + 2 * 2048 * 102400 * 2
        + 27 * 16 * 2 * 320 * (4096 * 4097 // 2)
        + 27 * 16 * 2 * (2 * 512 + 64) * 4097)


def _fake_run(records=(), op_seconds=1.0):
    done = [types.SimpleNamespace(task=types.SimpleNamespace(prompt_len=4096),
                                  tokens=np.zeros((8, 64), np.int32))]
    # a second of each kernel, half of it past the task spans' window
    # [0, 1 s): a device clock shifted onto the host's too far
    half = op_seconds / 2 * 1e9
    ops = [(0, half, "flash_attention_pallas.3", "jit_prefill"),
           (2e9, 2e9 + half, "flash_attention_pallas.3", "jit_prefill"),
           (0, half, "expert_ffn_pallas.7", "jit_prefill"),
           (3e9, 3e9 + half, "expert_ffn_pallas.8", "jit_decode"),
           (0, 7e9, "fusion.1", "jit_prefill")]
    trace = types.SimpleNamespace(devices={"/device:TPU:0": {"ops": ops}},
                                  t0=0, t1=1e9)
    return types.SimpleNamespace(spec=SPEC, done=done, trace=trace,
                                 elapsed=10.0,
                                 peaks={"flops_per_s": 197e12,
                                        "hbm_bytes_per_s": 819e9})


def test_new_readers_by_hand(monkeypatch):
    from benchmarks.chip import program_spans
    rec = lambda a: types.SimpleNamespace(name="serve.generate", attrs=a)
    monkeypatch.setattr(program_spans, "records", lambda: [
        rec({"moe_rows_routed": 90, "moe_rows_computed": 100,
             "moe_experts_read": 300, "moe_decode_rows_routed": 10,
             "moe_decode_experts_read": 250}),
        rec({"moe_rows_routed": 60, "moe_rows_computed": 100,
             "moe_experts_read": 100, "moe_decode_rows_routed": 0,
             "moe_decode_experts_read": 0}),
        rec({"cache_bytes": 1})])
    read = lambda m: harness.load_module(ROOT, "metrics", m).read(_fake_run())
    assert read("moe.pad_share") == pytest.approx(25.0)
    flops = work_mla_moe.task_flops(SPEC, 8, 4096, 64)
    assert read("model.moe_mfu") == pytest.approx(
        100 * flops / (10.0 * 197e12))
    f, b = work_mla_moe.mla_flash_work(SPEC, 8, 4096)
    assert read("kernel.mla_flash_roofline") == pytest.approx(
        100 * max(f / 197e12, b / 819e9))
    # each call's prefill and decode apart: 80 rows and 50 experts read,
    # 10 and 250, then 60 and 100 (a call without decode steps)
    least = 0.0
    for rows, experts in ((80, 50), (10, 250), (60, 100), (0, 0)):
        f, b = work_mla_moe.expert_ffn_calls(SPEC, rows, experts)
        least += max(f / 197e12, b / 819e9)
    assert read("kernel.gmm_roofline") == pytest.approx(100 * least)
    monkeypatch.setattr(program_spans, "records", lambda: None)
    assert read("moe.pad_share") is None
    assert read("kernel.gmm_roofline") is None


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """bench_tiny's root with one tiny mla_moe cell added."""
    root = bench_tiny.make_root(tmp_path_factory.mktemp("bench"))
    here = os.path.join(root, "benchmarks", "chip")
    path = os.path.join("benchmarks", "chip", "configs", "tiny-mla-moe.json")
    json.dump(TINY, open(os.path.join(root, path), "w"))
    cell = "tiny-mla-moe.mix"
    json.dump(bench_tiny.MIX, open(os.path.join(here, "traffic",
                                                cell + ".json"), "w"))
    # between the program's widest gap (0.028 over 8 seeds on the CPU) and
    # the float8 control's narrowest (0.146)
    json.dump({"sample_rows": 8, "logit_gap": 0.07},
              open(os.path.join(here, "checks", cell + ".json"), "w"))
    bench = json.load(open(os.path.join(root, "BENCHMARK.json")))
    bench["configs"].append({"name": "tiny-mla-moe", "file": path})
    bench["workloads"].append({"name": cell, "config": "tiny-mla-moe",
                               "traffic": cell, "chips": 1})
    json.dump(bench, open(os.path.join(root, "BENCHMARK.json"), "w"))
    return root


def test_a_tiny_cell_runs_and_is_correct(root, capsys):
    code = harness.main(["--workload", "tiny-mla-moe.mix", "--seed",
                         str(2**33 + 5), "--seconds", "0.3", "--trace", "0"],
                        root=root, on_chip=False)
    out, _ = capsys.readouterr()
    assert code == 0
    result = json.loads(out.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0
    assert result["compared"]["logit_gap"]["value"] < 0.07
