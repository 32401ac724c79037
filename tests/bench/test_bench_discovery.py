"""BENCHMARK.json and the files the harness finds by name."""
import json
import os
import re

import jax
import pytest

from benchmarks.chip import harness

ROOT = harness.ROOT
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELLS = [w["name"] for w in BENCH["workloads"]]
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.mark.parametrize("cell", CELLS)
def test_each_cell_finds_its_files(cell):
    c = harness.load_cell(ROOT, cell)
    assert c.cell["chips"] == 1
    assert hasattr(c.driver, "Driver")
    assert hasattr(c.ref, "logits") and hasattr(c.ref, "init_weights")
    assert c.limits["logit_gap"] > 0 and c.limits["sample_rows"] > 0
    chunk = c.spec.get("ssm_cfg", {}).get("chunk_size", 128)
    for b in c.mix["buckets"]:
        # flash attention's blocks and the SSD chunk
        assert b["prompt_len"] % 128 == 0 and b["prompt_len"] % chunk == 0
    for m in harness.metrics_of(c.bench, cell, traced=False):
        assert m["name"] != "task_s_p90" or cell == "m2.longdoc"


@pytest.mark.parametrize("metric", [m["name"] for m in METRICS])
def test_each_metric_has_a_reader(metric):
    assert callable(harness.load_module(ROOT, "metrics", metric).read)


def test_names_units_and_keys_keep_to_the_format():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert len(w["why"]) <= 200
    for m in METRICS:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert set(m["workloads"] if "workloads" in m else []) <= set(CELLS)
    for m in BENCH["per_layer"]:
        assert m["moves"] == "output_tokens_per_s"
    assert all(NAME.match(n) for n in CELLS)
    setup = [m for m in BENCH["end_to_end"] if m["name"] == "setup_s"]
    assert len(setup) == 1 and setup[0]["bound"] <= 0.25
    assert all(0.01 <= m["bound"] <= 0.25 for m in BENCH["end_to_end"])


def test_unknown_names_are_errors():
    with pytest.raises(KeyError):
        harness.load_cell(ROOT, "no.such.cell")
    with pytest.raises(FileNotFoundError):
        harness.load_module(ROOT, "metrics", "no.such.metric")


@pytest.mark.parametrize("name", ["deepseek-7b", "mamba2-370m"])
def test_configuration_files_match_the_registry(name):
    spec = json.load(open(os.path.join(
        ROOT, "benchmarks", "chip", "configs", name + ".json")))
    ref = harness.load_module(ROOT, "reference", spec["family"])
    cfg = harness.program_config(spec, ref)
    assert cfg.name == name
    wrong = dict(spec, vocab_size=spec["vocab_size"] + 1)
    with pytest.raises(ValueError):
        harness.program_config(wrong, ref)


@pytest.mark.parametrize("name", ["deepseek-7b", "mamba2-370m"])
def test_reference_weights_fill_the_programs_tree(name):
    """At the published sizes, by shape only: the weights the reference
    makes lay out as the program's parameters, leaf for leaf."""
    from repro.models.model_zoo import build_model
    spec = json.load(open(os.path.join(
        ROOT, "benchmarks", "chip", "configs", name + ".json")))
    ref = harness.load_module(ROOT, "reference", spec["family"])
    model = build_model(harness.program_config(spec, ref))
    w = jax.eval_shape(lambda: ref.init_weights(spec, harness.weights_key(0)))
    got, want = ref.program_params(w), model.abstract()
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)
