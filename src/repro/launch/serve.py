"""Serving launcher: ``python -m repro.launch.serve --arch <id> [...]``.

Serves queued generation requests against a zoo model in static batches
(the last one padded), through the same ServeSession path the Murakkab
real executor uses. Reduced configs by default; ``--no-reduced`` serves the
published widths. Reports throughput and per-batch latency.

    PYTHONPATH=src python -m repro.launch.serve --arch mamba2-370m \
        --requests 16 --batch 4 --prompt-len 32 --max-new 16
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.registry import ARCH_IDS, get_config
from ..models.model_zoo import build_model
from ..runtime.serve import ServeOptions, ServeSession
from .compile_cache import enable_compile_cache


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="mamba2-370m")
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch, reduced=args.reduced)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))
    sess = ServeSession(model, params,
                        opts=ServeOptions(temperature=args.temperature))

    rng = np.random.default_rng(args.seed)
    queue = [jnp.asarray(rng.integers(0, cfg.vocab_size,
                                      (args.prompt_len,), dtype=np.int32))
             for _ in range(args.requests)]
    extras = model.extra_inputs(args.batch, args.prompt_len)

    done, lat = 0, []
    t0 = time.time()
    while done < len(queue):
        chunk = queue[done:done + args.batch]
        while len(chunk) < args.batch:     # pad the final batch
            chunk.append(chunk[-1])
        prompts = jnp.stack(chunk)
        ts = time.time()
        out = sess.generate(prompts, max_new_tokens=args.max_new,
                            extras=extras)
        jax.block_until_ready(out)
        lat.append(time.time() - ts)
        done += args.batch
    dt = time.time() - t0
    toks = args.requests * args.max_new
    print(f"[serve] {args.arch}: {args.requests} reqs, "
          f"{toks / dt:.1f} tok/s, p50 batch latency "
          f"{sorted(lat)[len(lat) // 2]:.2f}s")
    return {"tok_per_s": toks / dt, "batches": len(lat)}


if __name__ == "__main__":
    main()
