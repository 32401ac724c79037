#!/usr/bin/env python3
"""Run one benchmark cell once on the accelerator this machine holds.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

JAX's persistent compilation cache lives at ``.jax_cache`` in the root of
the checkout, a fixed path, so only a checkout's first run of a cell
compiles. See ``harness.py``.
"""
import time

T0 = time.perf_counter()

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

if __name__ == "__main__":
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    # libtpu would otherwise write its logs under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
    from benchmarks.chip.harness import main
    sys.exit(main(t0=T0))
