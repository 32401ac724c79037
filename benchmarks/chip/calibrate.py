#!/usr/bin/env python3
"""Readings that a cell's limit is set from, on the chip, in one process.

    python3 benchmarks/chip/calibrate.py --workload <cell> --seconds <s> \
        --seeds <n> ... [--control-seeds <n> ...]

For every seed: the cell's weights and session, a short window at the
cell's own load, and the widest gap of the program's served tokens under
the float32 reference (the lower reading). For a control seed also the
control's widest gap: the reference computed in float8 put in the
program's place, at the same prompts and positions (the upper reading).
One JSON line per seed. The limit in ``checks/<cell>.json`` is set
between the largest lower reading and the smallest upper one.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def readings(c, seed: int, seconds: float, control: bool) -> dict:
    from benchmarks.chip import check, harness
    weights, drv = harness.start(c, seed)
    done, attempted, failed, elapsed, _, compiles = harness.window(
        drv, seconds, traced=False)
    drv.close()
    del drv
    gc.collect()
    correct, compared, gaps = harness.verdict(c, weights, done, failed, seed)
    gap = compared["logit_gap"][0]
    out = {"seed": seed, "tasks": len(done), "failed": failed,
           "compiles": compiles, "tokens_compared": int(gaps.size),
           "program_gap": gap, "program_gaps_above_0": int((gaps > 0).sum()),
           "correct": correct}
    if control:
        picks = check.pick_rows(done, c.mix, int(c.limits["sample_rows"]),
                                seed)
        ctl = check.control_gaps(c.ref, weights, c.spec, done, picks)
        out["control_gap"] = float(ctl.max())
        out["control_gaps_above_0"] = int((ctl > 0).sum())
    del weights
    gc.collect()
    return out


def main(argv=None, root: str = ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=())
    args = ap.parse_args(argv)
    from benchmarks.chip import harness
    harness.find_chip(1)
    c = harness.load_cell(root, args.workload)
    for seed in args.seeds:
        print(json.dumps(readings(c, seed, args.seconds,
                                  seed in args.control_seeds)), flush=True)
    return 0


if __name__ == "__main__":
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
    sys.exit(main())
