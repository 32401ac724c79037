"""One run of one benchmark cell on the chip.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Everything a cell needs is found by name: the cell in ``BENCHMARK.json``
names a configuration (its file there) and a traffic mix
(``traffic/<name>.json``); the mix names its driver (``drivers/<name>.py``);
the configuration names its family's reference (``reference/<family>.py``);
the check's parameters and limit are ``checks/<cell>.json``; each metric is
read by ``metrics/<metric>.py``. Adding a cell or a metric adds files.

Set-up (counted in ``setup_s`` from the start of the process): the weights,
made from the seed on the device in one jitted call; the program's session;
one task of each prompt length, which compiles every program the window
runs. Then the window: the driver serves tasks for ``--seconds``; nothing
compiles there (counted and printed). With ``--trace 1`` the window runs
under the profiler and the per-layer metrics are read from its trace.

After the window: the device's peak memory is read, the program's session
is dropped, and a sample of the finished requests is compared with the
float32 reference (``check.py``). The last line of standard output is the
result; its last key, ``compared``, holds each number compared beside its
limit, which are also the last lines of standard error.
"""
from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field

import jax
import numpy as np

from . import check, trace, traffic
from .peaks import peaks

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
HERE = os.path.join("benchmarks", "chip")
WEIGHTS = 5  # stream id of the weights under one seed


@dataclass
class Run:
    """What a metric reader may read."""
    cell: dict
    spec: dict            # the configuration file
    mix: dict             # the traffic file
    done: list            # drivers' Done records of the window
    attempted: int
    failed: int
    elapsed: float        # window seconds, host clock
    setup_s: float
    peaks: dict | None    # of the device kind, None off the chip
    memory: dict = field(default_factory=dict)
    trace: object = None  # trace.Trace of the window, with --trace 1


def _json(path):
    with open(path) as f:
        return json.load(f)


def load_module(root: str, kind: str, name: str):
    """The file ``<kind>/<name>.py`` of the benchmark, as a module."""
    path = os.path.join(root, HERE, kind, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind[:-1]} named {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(
        f"chipbench.{kind}.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def metrics_of(bench: dict, cell: str, traced: bool) -> list[dict]:
    group = bench["per_layer"] if traced else bench["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def _get(obj, dotted: str):
    for part in dotted.split("."):
        obj = obj[part] if isinstance(obj, dict) else getattr(obj, part)
    return obj


def program_config(spec: dict, ref):
    """The registry's configuration of ``spec['arch']``; every size the
    configuration file states must be what the registry holds."""
    from repro.configs.registry import get_config
    cfg = get_config(spec["arch"], reduced=spec.get("preset") == "reduced")
    for key, attr in ref.PROGRAM_KEYS.items():
        want, have = _get(spec, key), _get(cfg, attr)
        if want != have:
            raise ValueError(f"{spec['name']}: {key} is {want} in the file "
                             f"and {have} in the registry ({attr})")
    return cfg


def weights_key(seed: int):
    data = traffic.rng(seed, WEIGHTS).integers(0, 2**32, 2, dtype=np.uint32)
    return jax.random.wrap_key_data(data)


class CompileCount:
    """JAX compile events while armed (listener removed on close)."""

    def __init__(self):
        self.n, self.armed = 0, False
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if self.armed and "compile" in event:
            self.n += 1

    def close(self) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on)


def find_chip(chips: int):
    """The devices, or exit non-zero where there is no accelerator or too
    few of them."""
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        raise SystemExit(f"needs {chips} accelerator chip(s); JAX found "
                         f"{len(devices)} {devices[0].platform} device(s)")
    return devices


@dataclass
class Cell:
    """Everything one cell needs, found by name."""
    bench: dict
    cell: dict
    spec: dict
    mix: dict
    limits: dict
    ref: object      # reference/<family>.py
    driver: object   # drivers/<name>.py


def load_cell(root: str, name: str) -> Cell:
    bench = _json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    spec = _json(os.path.join(root, configs[cell["config"]]["file"]))
    mix = _json(os.path.join(root, HERE, "traffic", cell["traffic"] + ".json"))
    limits = _json(os.path.join(root, HERE, "checks", name + ".json"))
    return Cell(bench, cell, spec, mix, limits,
                load_module(root, "reference", spec["family"]),
                load_module(root, "drivers", mix["driver"]))


def start(c: Cell, seed: int):
    """Weights from the seed, the program's session over them, every shape
    of the mix warmed. Returns (weights, driver)."""
    from repro.models.model_zoo import build_model
    model = build_model(program_config(c.spec, c.ref))
    weights = c.ref.init_weights(c.spec, weights_key(seed))
    params = c.ref.program_params(weights)
    want = model.abstract()
    if jax.tree.structure(params) != jax.tree.structure(want) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(want))):
        raise ValueError("the reference's weights do not fit the program's "
                         "parameter tree")
    jax.block_until_ready(weights)
    stream = traffic.TaskStream(c.mix, seed, c.spec["token_ids_below"])
    drv = c.driver.Driver(model, params, stream)
    drv.warm()
    return weights, drv


def window(drv, seconds: float, traced: bool):
    """The measured window. Returns (done, attempted, failed, elapsed,
    trace or None, compiles counted in the window)."""
    compiles = CompileCount()
    trace_dir = tempfile.mkdtemp(prefix="chipbench-") if traced else None
    tr = None
    try:
        if trace_dir:
            jax.profiler.start_trace(trace_dir)
        compiles.armed = True
        done, attempted, failed, elapsed = drv.window(
            seconds, jax.profiler.TraceAnnotation)
        compiles.armed = False
        if trace_dir:
            jax.profiler.stop_trace()
            tr = trace.load(trace_dir)
    finally:
        compiles.close()
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    return done, attempted, failed, elapsed, tr, compiles.n


def verdict(c: Cell, weights, done, failed: int, seed: int):
    """(correct, {name: (number, limit)}, every gap) of a window's finished
    tasks: the widest gap of the sampled served tokens, the tasks that
    failed, and the finished tasks whose tokens have the wrong shape or lie
    outside the vocabulary."""
    picks = check.pick_rows(done, c.mix, int(c.limits["sample_rows"]), seed)
    B, N = int(c.mix["batch"]), int(c.mix["new_tokens"])
    malformed = sum(bool(d.tokens.shape != (B, N) or d.tokens.min() < 0
                         or d.tokens.max() >= c.spec["vocab_size"])
                    for d in done)
    gaps = (check.served_gaps(c.ref, weights, c.spec, done, picks)
            if not malformed else np.zeros(0))
    compared = {
        "logit_gap": (float(gaps.max()) if gaps.size else float("inf"),
                      float(c.limits["logit_gap"])),
        "failed_tasks": (failed, 0),
        "malformed_tasks": (malformed, 0),
    }
    correct = bool(done) and all(v <= lim for v, lim in compared.values())
    return correct, compared, gaps


def main(argv=None, *, root: str = ROOT, t0: float | None = None,
         on_chip: bool = True) -> int:
    t0 = time.perf_counter() if t0 is None else t0
    ap = argparse.ArgumentParser(description="one run of one cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    c = load_cell(root, args.workload)
    wanted = metrics_of(c.bench, args.workload, bool(args.trace))
    readers = {m["name"]: load_module(root, "metrics", m["name"])
               for m in wanted}
    devices = find_chip(c.cell["chips"]) if on_chip else jax.devices()
    dev = devices[0]
    peak = peaks(dev.device_kind) if on_chip else None

    weights, drv = start(c, args.seed)
    setup_s = time.perf_counter() - t0
    done, attempted, failed, elapsed, tr, compiles = window(
        drv, args.seconds, bool(args.trace))
    memory = dev.memory_stats() or {}
    lens = [d.task.prompt_len for d in done]
    print(f"[window] {attempted} tasks attempted, {failed} failed, "
          f"{elapsed:.3f} s; compiles in the window: {compiles}; finished "
          f"by prompt length: { {S: lens.count(S) for S in sorted(set(lens))} }")
    print(f"[memory] peak_bytes_in_use {memory.get('peak_bytes_in_use')} "
          f"bytes_limit {memory.get('bytes_limit')}")
    if tr is not None:
        print(f"[trace] {tr.describe()}")

    drv.close()
    del drv
    gc.collect()
    t_ref = time.perf_counter()
    correct, compared, gaps = verdict(c, weights, done, failed, args.seed)
    print(f"[check] {gaps.size} served tokens compared in "
          f"{time.perf_counter() - t_ref:.1f} s; gaps above 0: "
          f"{int((gaps > 0).sum())}")

    run = Run(c.cell, c.spec, c.mix, done, attempted, failed, elapsed,
              setup_s, peak, memory, tr)
    metrics = {}
    for m in wanted:
        value = readers[m["name"]].read(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": memory.get("peak_bytes_in_use")}
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if tr is not None:
        device["busy_s"], device["window_s"] = tr.busy_s(), tr.window_s()
        result["breakdown"] = tr.breakdown()
    # a gap that could not be read (no task finished) is null in the line
    result["compared"] = {
        k: {"value": v if np.isfinite(v) else None, "limit": lim}
        for k, (v, lim) in compared.items()}
    print(json.dumps(result), flush=True)
    for k, (v, lim) in compared.items():
        print(f"{k} {v!r} limit {lim!r}", file=sys.stderr)
    return 0
