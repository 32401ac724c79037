"""Time the Pallas flash-attention kernel over its tile sizes on a TPU.

For each prefill shape of the chip benchmark's attention cells
(deepseek-v2-lite's latent attention at q/k 192, v 128, B 8, S 4096 and
8192; deepseek-7b at 32x128 heads, B 2, S 1024 and 1280), it times the
causal kernel at every (block_q, block_k) pair of multiples of 128 that
divide the length (up to 1024), the pair ``flash_plan`` picks among them.
Each line gives the grid steps and computed score elements of one (batch,
head) slice and the causal FLOPs' share of 197e12 FLOP/s. Before the
sweep, a few tile pairs are checked against the float32 reference on the
chip.

CLI (needs a TPU; prints one JSON line per timing)::

    python -m benchmarks.flash_tiles
"""
from __future__ import annotations

import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import flash_attention as fa
from repro.kernels import ref

SHAPES = [  # (name, B, S, H, D, Dv)
    ("v2lite", 8, 4096, 16, 192, 128),
    ("v2lite", 8, 8192, 16, 192, 128),
    ("ds7b", 2, 1024, 32, 128, 128),
    ("ds7b", 2, 1280, 32, 128, 128),
]
PEAK_FLOPS = 197e12
TOL = 2e-2


def _inputs(B, S, H, D, Dv, seed=0):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(k1, (B, S, H, D), jnp.bfloat16),
            jax.random.normal(k2, (B, S, H, D), jnp.bfloat16),
            jax.random.normal(k3, (B, S, H, Dv), jnp.bfloat16))


def _time(fn, args, reps=5) -> float:
    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(reps):
        t = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t)
    return best


def check() -> bool:
    """The kernel at a few tile pairs against the float32 reference."""
    ok = True
    for (S, D, Dv, window, bq, bk) in [(2048, 192, 128, 0, 1024, 512),
                                       (1280, 128, 128, 0, 640, 256),
                                       (1000, 128, 128, 0, None, None),
                                       (1024, 128, 128, 300, 512, 256)]:
        q, k, v = _inputs(1, S, 2, D, Dv, seed=S)
        got = np.asarray(fa.flash_attention_pallas(
            q, k, v, causal=True, window=window, block_q=bq, block_k=bk),
            np.float32)
        with jax.default_matmul_precision("highest"):
            want = np.asarray(ref.mha_naive(
                q.astype(jnp.float32), k.astype(jnp.float32),
                v.astype(jnp.float32), causal=True, window=window))
        err = float(np.max(np.abs(got - want)))
        excess = float(np.max(np.abs(got - want) - TOL * (1 + np.abs(want))))
        line = {"check": [S, D, Dv, window, bq, bk], "max_abs_err": err,
                "ok": excess <= 0}
        ok &= line["ok"]
        print(json.dumps(line), flush=True)
    return ok


def sweep() -> None:
    """One JSON line per (shape, tiles) timing."""
    for name, B, S, H, D, Dv in SHAPES:
        q, k, v = _inputs(B, S, H, D, Dv)
        flops = B * H * S * (S + 1) / 2 * 2 * (D + Dv)
        plan = fa.flash_plan(S, S, D, Dv, jnp.bfloat16)
        sizes = fa._tiles(S, fa.MAX_BLOCK)
        for bq, bk in [(bq, bk) for bq in sizes for bk in sizes]:
            fn = jax.jit(lambda q, k, v, bq=bq, bk=bk: fa.flash_attention_pallas(
                q, k, v, causal=True, block_q=bq, block_k=bk))
            seconds = _time(fn, (q, k, v))
            run, steps = fa.flash_blocks(S, S, bq, bk)
            print(json.dumps({
                "shape": name, "B": B, "S": S, "H": H, "D": D, "Dv": Dv,
                "tiles": [bq, bk], "planned": (bq, bk) == plan,
                "seconds": seconds, "steps": steps,
                "elements": run * bq * bk,
                "causal_flops_share": flops / PEAK_FLOPS / seconds}),
                flush=True)


def main() -> None:
    assert jax.default_backend() == "tpu", "needs a TPU"
    print(json.dumps({"device": jax.devices()[0].device_kind}), flush=True)
    if not check():
        raise SystemExit("flash kernel off the float32 reference")
    sweep()


if __name__ == "__main__":
    main()
