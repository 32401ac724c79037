#!/usr/bin/env python3
"""Compile every program of the benchmark's cells for a described TPU v5e,
with no chip attached, and print each one's ``memory_analysis``.

    JAX_PLATFORMS=cpu python3 benchmarks/chip/rehearse.py [cell ...]

Per cell: the weights' maker, the program's prefill and decode steps at
each prompt length of its mix, and the reference's layer and head at the
check's sample shapes. Nothing runs; the TPU compiler refuses what would
not fit or not lower. The program decides between its Pallas kernels and
their jnp twins by the default backend, which is the CPU here, so this
script makes it take the kernels.
"""
from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main(cells: list[str]) -> int:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import repro.kernels.ops as ops
    from benchmarks.chip import check, harness
    from repro.models.model_zoo import build_model
    from repro.runtime.serve import ServeSession, abstract_cache

    ops._use_pallas = lambda: True
    jax.config.update("jax_enable_compilation_cache", False)
    one = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])

    def place(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
            tree)

    def show(what, compiled):
        m = compiled.memory_analysis()
        total = (m.argument_size_in_bytes + m.output_size_in_bytes
                 + m.temp_size_in_bytes - m.alias_size_in_bytes)
        print(f"{what}: argument {m.argument_size_in_bytes} output "
              f"{m.output_size_in_bytes} temp {m.temp_size_in_bytes} alias "
              f"{m.alias_size_in_bytes} total {total}", flush=True)

    bench = harness._json(os.path.join(ROOT, "BENCHMARK.json"))
    for name in cells or [w["name"] for w in bench["workloads"]]:
        c = harness.load_cell(ROOT, name)
        spec, mix, limits, ref = c.spec, c.mix, c.limits, c.ref
        model = build_model(harness.program_config(spec, ref))
        key = place(jax.eval_shape(lambda: harness.weights_key(0)))
        shapes = tuple(sorted(ref.shapes(spec).items()))
        show(f"{name} weights", ref._init.lower(shapes, key).compile())
        w = place(jax.eval_shape(ref._init, shapes, harness.weights_key(0)))
        params = ref.program_params(w)
        sess = ServeSession(model, None)
        B, N = int(mix["batch"]), int(mix["new_tokens"])
        tok = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one)
        picks = check.pick_rows(
            [_Fake(S, B, N) for b in mix["buckets"]
             for S in [int(b["prompt_len"])] for _ in range(64)],
            mix, int(limits["sample_rows"]), 0)
        for S in sorted({int(b["prompt_len"]) for b in mix["buckets"]}):
            cache = place(abstract_cache(model, B, S + N))
            compiled = sess.prefill.lower(params, {"tokens": tok(B, S)},
                                          cache).compile()
            show(f"{name} prefill B={B} S={S}", compiled)
            assert "tpu_custom_call" in compiled.as_text()
            show(f"{name} decode B={B} cache={S + N}", sess.decode.lower(
                params, cache, tok(B, 1), tok()).compile())
            R, T = len(picks.get(S, [])), S + N - 1
            if not R:
                continue
            items = ref._hashable(spec)
            x = jax.ShapeDtypeStruct((R, T, _width(spec)), jnp.float32,
                                     sharding=one)
            show(f"{name} reference layer R={R} T={T}", ref._layer.lower(
                x, w, items, tok(), False).compile())
            show(f"{name} reference head R={R} T={T}", ref._head.lower(
                x, w, items, S - 1, False).compile())
    return 0


class _Fake:
    """A finished task of a given prompt length, for drawing sample shapes."""

    def __init__(self, S, B, N):
        import numpy as np
        self.task = type("T", (), {"prompt_len": S})()
        self.tokens = np.zeros((B, N), np.int32)


def _width(spec):
    return spec.get("hidden_size") or spec["d_model"]


if __name__ == "__main__":
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.exit(main(sys.argv[1:]))
