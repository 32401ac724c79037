"""A benchmark root with tiny cells, for driving the harness on the CPU.

``make_root(tmp)`` copies the benchmark's drivers, metrics and references
into ``tmp`` and writes configurations at the registry's reduced sizes,
traffic mixes and checks of their own, and a ``BENCHMARK.json`` naming them.
The harness then finds all of it by name, as it does in the checkout.
"""
from __future__ import annotations

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmarks", "chip")

DENSE = {"name": "tiny-dense", "arch": "deepseek-7b", "preset": "reduced",
         "family": "dense", "num_hidden_layers": 2, "hidden_size": 64,
         "num_attention_heads": 4, "num_key_value_heads": 4,
         "intermediate_size": 128, "vocab_size": 256, "rope_theta": 10000.0,
         "rms_norm_eps": 1e-6, "tie_word_embeddings": False,
         "token_ids_below": 256}
SSM = {"name": "tiny-ssm", "arch": "mamba2-370m", "preset": "reduced",
       "family": "ssm", "d_model": 64, "n_layer": 2, "vocab_size": 256,
       "ssm_cfg": {"d_state": 16, "d_conv": 4, "expand": 2, "headdim": 16,
                   "ngroups": 1, "chunk_size": 16},
       "norm_epsilon": 1e-5, "tie_embeddings": True, "token_ids_below": 256}

# Limits of the tiny cells, set as the benchmark's are (PERF.md): between
# the widest gap of the program's served tokens over seeds (lower reading,
# CPU, bf16 program against the float32 reference: dense 0.0075, Mamba2
# 0.033) and that of the float8 control (upper: dense 0.061, Mamba2 0.16).
LIMITS = {"tiny-dense": 0.03, "tiny-ssm": 0.08}

MIX = {"driver": "generate", "batch": 4,
       "buckets": [{"prompt_len": 32, "weight": 3},
                   {"prompt_len": 64, "weight": 1}],
       "new_tokens": 8}


def make_root(tmp: str) -> str:
    root = str(tmp)
    here = os.path.join(root, "benchmarks", "chip")
    for kind in ("drivers", "metrics", "reference"):
        shutil.copytree(os.path.join(BENCH, kind), os.path.join(here, kind),
                        ignore=shutil.ignore_patterns("__pycache__"))
    for kind in ("configs", "traffic", "checks"):
        os.makedirs(os.path.join(here, kind))
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    bench["configs"], bench["workloads"] = [], []
    for spec in (DENSE, SSM):
        path = os.path.join("benchmarks", "chip", "configs",
                            spec["name"] + ".json")
        json.dump(spec, open(os.path.join(root, path), "w"))
        bench["configs"].append({"name": spec["name"], "file": path})
        cell = spec["name"] + ".mix"
        json.dump(MIX, open(os.path.join(here, "traffic", cell + ".json"), "w"))
        json.dump({"sample_rows": 8, "logit_gap": LIMITS[spec["name"]]},
                  open(os.path.join(here, "checks", cell + ".json"), "w"))
        bench["workloads"].append({"name": cell, "config": spec["name"],
                                   "traffic": cell, "chips": 1})
    for group in ("end_to_end", "per_layer"):
        for m in bench[group]:
            m.pop("workloads", None)
    json.dump(bench, open(os.path.join(root, "BENCHMARK.json"), "w"))
    return root
