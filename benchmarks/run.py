"""Benchmark harness: one module per paper table/figure + system benches.

    PYTHONPATH=src python -m benchmarks.run [--only fig3,table2,...]

Emits ``name,value,note`` CSV to stdout (and results/bench.csv).
"""
from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SUITES = ("fig3", "table2", "table1", "overheads", "multitenant")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=",".join(SUITES))
    ap.add_argument("--quiet", action="store_true")
    args = ap.parse_args()
    picked = [s.strip() for s in args.only.split(",") if s.strip()]

    from . import (fig3_traces, multitenant, overheads, table1_levers,
                   table2_energy)
    mods = {"fig3": fig3_traces, "table2": table2_energy,
            "table1": table1_levers, "overheads": overheads,
            "multitenant": multitenant}

    all_rows: list[tuple[str, float, str]] = []
    failures = []
    for name in picked:
        print(f"\n##### {name} " + "#" * (60 - len(name)))
        t0 = time.perf_counter()
        try:
            rows = mods[name].run(verbose=not args.quiet)
            all_rows += rows
        except Exception as e:  # keep the harness going; report at the end
            import traceback
            traceback.print_exc()
            failures.append((name, repr(e)))
        print(f"[{name}: {time.perf_counter() - t0:.1f}s]")

    print("\n===== CSV =====")
    print("name,value,note")
    for r in all_rows:
        print(",".join(str(x) for x in r))
    os.makedirs("results", exist_ok=True)
    with open("results/bench.csv", "w") as f:
        f.write("name,value,note\n")
        for r in all_rows:
            f.write(",".join(str(x) for x in r) + "\n")
    if failures:
        print(f"\n{len(failures)} suite failures: {failures}")
        raise SystemExit(1)
    print(f"\nall {len(picked)} suites completed; "
          f"{len(all_rows)} metrics -> results/bench.csv")


if __name__ == "__main__":
    main()
