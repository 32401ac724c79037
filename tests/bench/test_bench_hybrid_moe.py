"""The ``hybrid_moe`` family on the benchmark's side: its configuration file
against the registry and the published config, the operation, byte and
cache counts by hand, the new metrics' readers on synthetic traces, both
new cells' files, and one tiny cell driven end to end through the harness
on the CPU."""
import copy
import json
import os
import types

import jax
import numpy as np
import pytest

import bench_tiny
from bench_tiny_hybrid_moe import HYBRID_MOE as TINY
from benchmarks.chip import harness, work_hybrid_moe
from repro.models.model_zoo import build_model
from repro.runtime.serve import abstract_cache, cache_split_bytes

ROOT = harness.ROOT
SPEC = json.load(open(os.path.join(
    ROOT, "benchmarks", "chip", "configs", "granite-4.0-h-small.json")))
REF = harness.load_module(ROOT, "reference", "hybrid_moe")
CATALOG_LAYER_TYPES = (["mamba"] * 5 + ["attention"] + ["mamba"] * 4) * 4


def test_configuration_file_matches_the_registry():
    cfg = harness.program_config(SPEC, REF)
    assert cfg.name == "granite-4.0-h-small"
    assert list(cfg.layer_types) == SPEC["layer_types"]
    assert SPEC["layer_types"] == CATALOG_LAYER_TYPES[:20]
    assert cfg.moe.num_shared * cfg.moe.d_ff_expert == \
        SPEC["shared_intermediate_size"]
    assert cfg.ssm.expand * cfg.d_model // cfg.ssm.head_dim == \
        SPEC["mamba_n_heads"]
    assert (cfg.moe.num_experts, cfg.moe.held, cfg.moe.top_k) == (72, 8, 10)
    assert cfg.rope_pct == 0 and SPEC["position_embedding_type"] == "nope"
    assert SPEC["reduced"] == ["num_hidden_layers", "layer_types",
                               "num_local_experts"]
    assert SPEC["published"] == {"num_hidden_layers": 40,
                                 "num_local_experts": 72}
    for key, value in (("num_local_experts", 72), ("rms_norm_eps", 1e-6),
                       ("attention_multiplier", 128 ** -0.5),
                       ("residual_multiplier", 1.0)):
        with pytest.raises(ValueError):
            harness.program_config(dict(SPEC, **{key: value}), REF)
    wrong = copy.deepcopy(SPEC)
    wrong["published"]["num_local_experts"] = 64
    with pytest.raises(ValueError):
        harness.program_config(wrong, REF)


def test_reference_weights_fill_the_programs_tree():
    """At the published sizes, by shape only: 4,229,596,416 parameters of
    this chip's share, laid out as the program's tree, leaf for leaf."""
    model = build_model(harness.program_config(SPEC, REF))
    w = jax.eval_shape(lambda: REF.init_weights(SPEC, harness.weights_key(0)))
    got, want = REF.program_params(w), model.abstract()
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)
    n = sum(x.size for x in jax.tree.leaves(w))
    assert n == model.param_count() == work_hybrid_moe.params(SPEC) \
        == 4_229_596_416


def test_work_counts_by_hand():
    s = SPEC
    assert work_hybrid_moe.kv_bytes_per_token(s) == 2 * 2 * 8 * 128 * 2 \
        == 8192
    assert work_hybrid_moe.state_bytes_per_sequence(s) == \
        18 * 4 * (3 * (8192 + 256) + 128 * 64 * 128) == 77_322_240
    assert work_hybrid_moe.routed_rows(s, 72) == 72 * 10 * 8 / 72
    # one token through the layers, head excluded: 18 Mamba2 layers, 2
    # attention layers, 20 expert layers (router, shared, 10 x 8/72 rows)
    mamba = 4096 * (2 * 8192 + 2 * 128 + 128) + 8192 * 4096
    attn = 4096 * (32 + 16) * 128 + 32 * 128 * 4096
    moe = 4096 * 72 + 3 * 4096 * 1536 + 10 * 8 / 72 * 3 * 4096 * 768
    per_token = 18 * mamba + 2 * attn + 20 * moe
    assert work_hybrid_moe._per_token_params(s) == pytest.approx(per_token)
    S, N = 16384, 128
    ssd = (256 + 1) * (128 + 64) + 4 * 128 * 64
    want = (2 * per_token * (S + N - 1) + 2 * 4096 * 100352 * N
            + 18 * 128 * (ssd * S + 5 * 64 * 128 * (N - 1))
            + 2 * 4 * 32 * 128 * sum(range(1, S + N)))
    assert work_hybrid_moe.task_flops(s, 1, S, N) == pytest.approx(want)
    assert 85e12 < want < 95e12     # the ~90 TFLOP of a 16k digest
    f, b = work_hybrid_moe.ssd_scan_work(s, 1, S)
    assert f == 18 * 128 * S * ssd
    assert b == 18 * (S * 128 * (64 * 4 + 4) + S * 2 * 128 * 2
                      + 128 * 64 * 128 * 4)


def test_the_programs_cache_holds_both_kinds_at_published_widths():
    """One sequence of 16,384 + 128 positions: K/V rows of the two
    attention layers, 8,192 bytes a position, beside the 18 Mamba2 layers'
    77,322,240 bytes of state, which do not grow with the length."""
    model = build_model(harness.program_config(SPEC, REF))
    for B, T in ((1, 16384 + 128), (2, 24576 + 128)):
        kv, state = cache_split_bytes(abstract_cache(model, B, T))
        assert kv == B * T * 8192
        assert state == B * 77_322_240


def _fake_run(op_seconds=1.0):
    done = [types.SimpleNamespace(task=types.SimpleNamespace(prompt_len=S),
                                  tokens=np.zeros((1, 128), np.int32))
            for S in (16384, 16384, 24576)]
    # a second of the SSD kernel, half of it past the task spans' window
    # [0, 1 s): a device clock shifted onto the host's too far
    half = op_seconds / 2 * 1e9
    ops = [(0, half, "ssd_scan_pallas.3", "jit_prefill"),
           (2e9, 2e9 + half, "ssd_scan_pallas.3", "jit_prefill"),
           (0, half, "expert_ffn_pallas.7", "jit_prefill"),
           (0, 7e9, "fusion.1", "jit_prefill")]
    trace = types.SimpleNamespace(devices={"/device:TPU:0": {"ops": ops}},
                                  t0=0, t1=1e9)
    return types.SimpleNamespace(spec=SPEC, done=done, trace=trace,
                                 elapsed=10.0,
                                 peaks={"flops_per_s": 197e12,
                                        "hbm_bytes_per_s": 819e9})


def test_new_readers_by_hand():
    read = lambda m, run: harness.load_module(ROOT, "metrics", m).read(run)
    run = _fake_run()
    flops = sum(work_hybrid_moe.task_flops(SPEC, 1, S, 128)
                for S in (16384, 16384, 24576))
    assert read("model.hybrid_mfu", run) == pytest.approx(
        100 * flops / (10.0 * 197e12))
    f = b = 0.0
    for S in (16384, 16384, 24576):
        fs, bs = work_hybrid_moe.ssd_scan_work(SPEC, 1, S)
        f, b = f + fs, b + bs
    assert f / 197e12 > b / 819e9            # compute-bound
    assert read("kernel.hybrid_ssd_roofline", run) == pytest.approx(
        100 * f / 197e12)
    run.trace.devices["/device:TPU:0"]["ops"] = [
        o for o in run.trace.devices["/device:TPU:0"]["ops"]
        if not o[2].startswith("ssd_scan_pallas")]
    assert read("kernel.hybrid_ssd_roofline", run) is None
    run.trace, run.peaks = None, None
    assert read("kernel.hybrid_ssd_roofline", run) is None
    assert read("model.hybrid_mfu", run) is None


@pytest.mark.parametrize("cell, config, batch, lens, new, rows", [
    ("g4hs.docdigest", "granite-4.0-h-small", 1, [16384, 24576], 128, 4),
    ("m2.summarize", "mamba2-370m", 96, [256, 512], 64, 16),
])
def test_the_new_cells_load(cell, config, batch, lens, new, rows):
    c = harness.load_cell(ROOT, cell)
    assert c.cell["config"] == config and c.cell["chips"] == 1
    assert c.mix["batch"] == batch and c.mix["new_tokens"] == new
    assert [b["prompt_len"] for b in c.mix["buckets"]] == lens
    assert [b["weight"] for b in c.mix["buckets"]] == [3, 1]
    assert c.limits["sample_rows"] == rows and c.limits["logit_gap"] > 0
    assert c.spec["family"] == ("hybrid_moe" if config.startswith("granite")
                                else "ssm")
    traced = {m["name"] for m in harness.metrics_of(c.bench, cell, True)}
    assert {"device.idle_share", "device.peak_hbm_share",
            "model.decode_step_ms", "serve.cache_mb_per_task"} <= traced
    if cell == "g4hs.docdigest":
        assert {"model.hybrid_mfu", "kernel.hybrid_ssd_roofline",
                "moe.pad_share"} <= traced
    else:
        assert {"model.mfu", "serve.decode_dispatch_ms"} <= traced


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """bench_tiny's root with one tiny hybrid_moe cell added."""
    root = bench_tiny.make_root(tmp_path_factory.mktemp("bench"))
    here = os.path.join(root, "benchmarks", "chip")
    path = os.path.join("benchmarks", "chip", "configs",
                        "tiny-hybrid-moe.json")
    json.dump(TINY, open(os.path.join(root, path), "w"))
    cell = "tiny-hybrid-moe.mix"
    json.dump(bench_tiny.MIX, open(os.path.join(here, "traffic",
                                                cell + ".json"), "w"))
    # above the program's widest gap over 8 seeds on the CPU (0.0019, at
    # this test's seed) and below the float8 control's at that seed
    # (0.0087); over the 8 seeds the control's narrowest is 0.0009, so at
    # this size the limit parts the two seed by seed only
    json.dump({"sample_rows": 8, "logit_gap": 0.003},
              open(os.path.join(here, "checks", cell + ".json"), "w"))
    bench = json.load(open(os.path.join(root, "BENCHMARK.json")))
    bench["configs"].append({"name": "tiny-hybrid-moe", "file": path})
    bench["workloads"].append({"name": cell, "config": "tiny-hybrid-moe",
                               "traffic": cell, "chips": 1})
    json.dump(bench, open(os.path.join(root, "BENCHMARK.json"), "w"))
    return root


def test_a_tiny_cell_runs_and_is_correct(root, capsys):
    code = harness.main(["--workload", "tiny-hybrid-moe.mix", "--seed",
                         str(2**33 + 5), "--seconds", "0.3", "--trace", "0"],
                        root=root, on_chip=False)
    out, _ = capsys.readouterr()
    assert code == 0
    result = json.loads(out.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0
    assert result["compared"]["logit_gap"]["value"] < 0.003
