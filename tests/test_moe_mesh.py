"""The expert layer on a mesh is the one-device layer run per shard: on 8
host devices (one subprocess, so the device count can be forced), reduced
MoE configs in float32 give the same output as on one device, and the same
count of routed rows, whatever the mesh's data x model split."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

# (config, held-share override, mesh shape (data, model))
CASES = {
    "moe16b-1x4": ("deepseek-moe-16b", {}, (1, 4)),
    "moe16b-2x4": ("deepseek-moe-16b", {}, (2, 4)),
    "moe16b-2x2": ("deepseek-moe-16b", {}, (2, 2)),
    # 4 of 8 experts from expert 4 on: each model shard holds 2 of them
    "v2lite-share-1x2": ("deepseek-v2-lite", {"experts_offset": 4}, (1, 2)),
}


@pytest.fixture(scope="module")
def results():
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import dataclasses, json
        import jax, jax.numpy as jnp
        import numpy as np
        from repro.configs.registry import get_config
        from repro.launch.mesh import make_mesh
        from repro.models import moe
        from repro.models.common import init_params

        out = {{}}
        for name, (arch, share, shape) in {CASES!r}.items():
            cfg = get_config(arch, reduced=True)
            cfg = cfg.replace(moe=dataclasses.replace(cfg.moe, **share))
            p = init_params(moe.moe_specs(cfg), jax.random.PRNGKey(0),
                            jnp.float32)
            x = jax.random.normal(jax.random.PRNGKey(1), (4, 32, cfg.d_model))
            dist = moe.make_dist(make_mesh(shape, ("data", "model")))
            one, _, rows_one = jax.jit(
                lambda p, x: moe.apply_moe(p, x, cfg=cfg))(p, x)
            mesh, _, rows_mesh = jax.jit(
                lambda p, x: moe.apply_moe(p, x, cfg=cfg, dist=dist))(p, x)
            out[name] = {{"err": float(jnp.abs(mesh - one).max()),
                          "rows_one": np.asarray(rows_one).tolist(),
                          "rows_mesh": np.asarray(rows_mesh).tolist()}}
        print(json.dumps(out))
    """)
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-2000:]
    return json.loads(res.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("case", list(CASES))
def test_mesh_layer_equals_the_one_device_layer(results, case):
    r = results[case]
    assert r["err"] <= 1e-5, r
    # the assignments that fell on the held experts, over all shards
    assert r["rows_mesh"][0] == r["rows_one"][0] > 0, r
