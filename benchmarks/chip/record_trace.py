#!/usr/bin/env python3
"""Record the small TPU trace that the trace-reduction tests read.

    python3 benchmarks/chip/record_trace.py [out.xplane.pb]

Two tasks, each inside the harness's ``bench.task`` span: one program named
``prefill`` that runs the program's flash-attention and SSD-scan kernels
(through ``repro.kernels.ops``, as the models call them), then one program
named ``decode``. The trace is written to ``chiprun_out/tiny.xplane.pb``
unless a path is given; the tests keep a copy at
``tests/bench/data/tiny.xplane.pb``.
"""
import glob
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main(out: str) -> int:
    import jax
    import jax.numpy as jnp
    from benchmarks.chip import harness
    from repro.kernels import ops

    harness.find_chip(1)
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 256, 2, 128),
                          jnp.bfloat16)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 512, 4, 64), jnp.bfloat16)
    dt = jnp.full((1, 512, 4), 0.05, jnp.float32)
    bc = jax.random.normal(jax.random.PRNGKey(2), (1, 512, 1, 128),
                           jnp.bfloat16)
    a = jnp.zeros((4,), jnp.float32)

    def prefill(q, x, dt, bc, a):
        o = ops.flash_attention(q, q, q, causal=True)
        y, s = ops.ssd_scan(x, dt, a, bc, bc, a, chunk=256)
        return o.sum() + y.sum() + s.sum()

    def decode(q):
        return (q * 2).sum()

    prefill, decode = jax.jit(prefill), jax.jit(decode)
    prefill(q, x, dt, bc, a).block_until_ready()
    decode(q).block_until_ready()
    tmp = tempfile.mkdtemp(prefix="chipbench-")
    try:
        jax.profiler.start_trace(tmp)
        for _ in range(2):
            with jax.profiler.TraceAnnotation("bench.task"):
                prefill(q, x, dt, bc, a).block_until_ready()
                decode(q).block_until_ready()
            time.sleep(0.002)
        jax.profiler.stop_trace()
        found = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                          recursive=True)
        os.makedirs(os.path.dirname(out), exist_ok=True)
        shutil.copy(found[0], out)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    print(f"{out}: {os.path.getsize(out)} bytes")
    return 0


if __name__ == "__main__":
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.exit(main(sys.argv[1] if len(sys.argv) > 1 else
                  os.path.join(ROOT, "chiprun_out", "tiny.xplane.pb")))
