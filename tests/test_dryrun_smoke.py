"""Dry-run machinery: HLO collective parsing + a subprocess mini dry-run
(8 host devices) exercising lower+compile for dense/moe/ssm archs."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from repro.launch.dryrun import parse_collectives

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


class TestParseCollectives:
    def test_counts_and_bytes(self):
        hlo = textwrap.dedent("""\
            %ag = f32[4,256]{1,0} all-gather(f32[1,256] %x), replica_groups={{0,1,2,3}}, dimensions={0}
            %ar = bf16[1024]{0} all-reduce(bf16[1024] %y), replica_groups=[2,8]<=[16], to_apply=%add
            %d = f32[8]{0} add(f32[8] %a, f32[8] %b)
        """)
        out = parse_collectives(hlo)
        assert out["all-gather"]["count"] == 1
        assert out["all-gather"]["raw_bytes"] == 4 * 256 * 4
        assert out["all-reduce"]["count"] == 1
        assert out["all-reduce"]["raw_bytes"] == 1024 * 2
        assert out["reduce-scatter"]["count"] == 0
        assert out["total_bytes"] > 0

    def test_traffic_factors(self):
        from repro.launch.dryrun import _traffic_factor
        assert _traffic_factor("all-gather", 4) == pytest.approx(0.75)
        assert _traffic_factor("all-reduce", 4) == pytest.approx(1.5)
        assert _traffic_factor("collective-permute", 4) == 1.0
        assert _traffic_factor("all-reduce", 1) == 0.0


@pytest.mark.slow
@pytest.mark.parametrize("arch", ["deepseek-7b", "deepseek-moe-16b",
                                  "mamba2-370m"])
def test_mini_dryrun_subprocess(arch):
    """lower+compile a reduced config on an 8-device host mesh, both the
    train and decode step (the real dry-run entrypoints, small)."""
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import json
        import jax, jax.numpy as jnp
        from repro.configs.registry import get_config
        from repro.models.model_zoo import build_model
        from repro.launch.mesh import make_mesh
        from repro.runtime import train as train_rt, serve as serve_rt

        cfg = get_config({arch!r}, reduced=True)
        model = build_model(cfg)
        mesh = make_mesh((2, 4), ("data", "model"))
        B, S = 8, 32
        opts = train_rt.TrainOptions(remat_policy=None)
        batch_abs = {{"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32),
                     "labels": jax.ShapeDtypeStruct((B, S), jnp.int32),
                     **model.extra_inputs(B, S, abstract=True)}}
        with jax.set_mesh(mesh):
            fn = train_rt.jit_train_step(model, opts, mesh, batch_abs)
            st_abs = train_rt.abstract_train_state(model, opts)
            lowered = fn.lower(st_abs, batch_abs)
            compiled = lowered.compile()
            cost = compiled.cost_analysis()
            if isinstance(cost, (list, tuple)):
                cost = cost[0]
            dfn, (p_abs, c_abs) = serve_rt.jit_decode_step(
                model, serve_rt.ServeOptions(), mesh, B, S,
                enc_len=S if cfg.family == "encdec" else 0)
            dfn.lower(p_abs, c_abs,
                      jax.ShapeDtypeStruct((B, 1), jnp.int32),
                      jax.ShapeDtypeStruct((), jnp.int32)).compile()
        print(json.dumps({{"flops": float(cost.get("flops", 0.0))}}))
    """)
    env = dict(os.environ, PYTHONPATH=SRC)
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-2000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert out["flops"] > 0
