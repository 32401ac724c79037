import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512")
"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The two lines above MUST run before any jax-importing module (jax locks the
device count on first init). The dry-run proves the distribution config is
coherent without hardware:

    with mesh:
        lowered  = jax.jit(step, in_shardings=..., out_shardings=...)\
                      .lower(**input_specs(arch, shape))
        compiled = lowered.compile()
        compiled.memory_analysis()   # fits?
        compiled.cost_analysis()     # FLOPs/bytes for the roofline

Per cell it records a JSON blob (results/dryrun/) with per-device memory,
HLO FLOPs/bytes, and per-collective byte counts parsed from the optimized
HLO — the inputs to the roofline estimate in ``launch/hlo_cost.py``.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch deepseek-7b \
        --shape train_4k [--multi-pod] [--rules k=v ...]
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]
"""
import argparse
import json
import re
import time
import traceback

import jax
import jax.numpy as jnp

from ..configs.base import SHAPE_CELLS, cell_applicable
from ..configs.registry import ARCH_IDS, get_config
from ..models import moe
from ..models.model_zoo import build_model
from ..runtime import sharding as shd
from ..runtime import serve as serve_rt
from ..runtime import train as train_rt
from .hlo_cost import hlo_cost
from .mesh import make_production_mesh

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dryrun")

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1,
                "f8e5m2": 1, "s64": 8, "u64": 8, "s32": 4, "u32": 4,
                "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1, "c64": 8,
                "c128": 16}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

# Bytes each device puts on the links, as a fraction of the RESULT size,
# for a ring/bidirectional implementation over a group of size n.
def _traffic_factor(op: str, n: int) -> float:
    if n <= 1:
        return 0.0
    if op == "all-gather":
        return (n - 1) / n          # result is the gathered (full) buffer
    if op == "all-reduce":
        return 2.0 * (n - 1) / n    # reduce-scatter + all-gather phases
    if op == "reduce-scatter":
        return (n - 1) * 1.0        # result is the scattered (1/n) buffer
    if op == "all-to-all":
        return (n - 1) / n
    if op == "collective-permute":
        return 1.0
    return 1.0


_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{?\{([0-9, ]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    b = _DTYPE_BYTES.get(dtype)
    if b is None:
        return 0
    n = 1
    for d in dims.split(","):
        if d.strip():
            n *= int(d)
    return n * b


def parse_collectives(hlo_text: str) -> dict:
    """Sum per-device collective payload bytes from optimized HLO."""
    out: dict[str, dict] = {op: {"count": 0, "bytes": 0.0, "raw_bytes": 0}
                            for op in _COLLECTIVES}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        m = re.match(r"%?[\w.\-]+\s*=\s*(.*)", stripped)
        if not m:
            continue
        rhs = m.group(1)
        op_found = None
        for op in _COLLECTIVES:
            if re.search(rf"\b{op}(-start)?\(", rhs):
                op_found = op
                break
        if not op_found or f"{op_found}-done" in rhs:
            continue
        # result shapes = everything before the op name
        head = rhs.split(op_found)[0]
        nbytes = sum(_shape_bytes(d, dims)
                     for d, dims in _SHAPE_RE.findall(head))
        gm = _GROUPS_RE.search(rhs)
        if gm:
            group = len([x for x in gm.group(1).split(",") if x.strip()])
        else:
            gi = _GROUPS_IOTA_RE.search(rhs)
            group = int(gi.group(2)) if gi else 2
        rec = out[op_found]
        rec["count"] += 1
        rec["raw_bytes"] += nbytes
        rec["bytes"] += nbytes * _traffic_factor(op_found, group)
    out["total_bytes"] = sum(v["bytes"] for v in out.values()
                             if isinstance(v, dict))
    return out


def input_specs(arch: str, shape: str, cfg=None) -> dict:
    """Abstract (ShapeDtypeStruct) inputs for a cell — never allocates."""
    cfg = cfg or get_config(arch)
    cell = SHAPE_CELLS[shape]
    model = build_model(cfg)
    B, S = cell.global_batch, cell.seq_len
    def tok(b, s):
        return jax.ShapeDtypeStruct((b, s), jnp.int32)
    if cell.kind == "train":
        return {"tokens": tok(B, S), "labels": tok(B, S),
                **model.extra_inputs(B, S, abstract=True)}
    if cell.kind == "prefill":
        return {"tokens": tok(B, S),
                **model.extra_inputs(B, S, abstract=True)}
    # decode: one new token over a cache of length S
    return {"tokens": tok(B, 1)}


def depth_variants(cfg):
    """(base_overrides, [(var_overrides, scale), ...]) for cost extrapolation.

    XLA's cost analysis counts a while-loop body once regardless of trip
    count, so per-layer costs are measured from fully-unrolled shallow
    variants at FULL width/sharding and extrapolated linearly:
        cost_full = cost(base) + sum_k (cost(var_k) - cost(base)) * scale_k
    Exact for FLOPs (group layers are homogeneous); collective/byte counts
    extrapolate the same way.
    """
    L = cfg.n_layers
    if cfg.family == "encdec":
        E = cfg.n_encoder_layers
        return (dict(n_layers=1, n_encoder_layers=1),
                [(dict(n_layers=2, n_encoder_layers=1), L - 1),
                 (dict(n_layers=1, n_encoder_layers=2), E - 1)])
    if cfg.family == "hybrid":
        per = cfg.shared_attn_every
        full, rest = divmod(L, per)
        base = dict(n_layers=per + 1)      # 1 group + 1 tail layer
        var = [(dict(n_layers=2 * per + 1), full - 1)]
        if rest:
            var.append((dict(n_layers=per + 2), rest - 1))
        return base, var
    if cfg.family == "vlm":
        ce = cfg.vision.cross_every
        return dict(n_layers=ce), [(dict(n_layers=2 * ce), L // ce - 1)]
    if cfg.alt_local_global:
        return dict(n_layers=2), [(dict(n_layers=4), L // 2 - 1)]
    if cfg.family == "moe" and cfg.moe.first_k_dense:
        k = cfg.moe.first_k_dense
        return dict(n_layers=k + 1), [(dict(n_layers=k + 2), L - k - 1)]
    return dict(n_layers=1), [(dict(n_layers=2), L - 1)]


def _lower_cell(cfg, cell, mesh, *, rules=None, opts_over=None,
                scan_unroll=1):
    """Build + lower the cell's step function. Returns the Lowered object."""
    model = build_model(cfg)
    B, S = cell.global_batch, cell.seq_len
    opts_over = opts_over or {}
    with jax.set_mesh(mesh):
        if cell.kind == "train":
            opts = train_rt.TrainOptions(**{"remat_policy": "full",
                                            "microbatches": 1,
                                            "scan_unroll": scan_unroll,
                                            **opts_over})
            step = train_rt.build_train_step(model, opts, mesh, rules)
            st_abs = train_rt.abstract_train_state(model, opts)
            st_sh = train_rt.state_shardings(model, mesh, opts, rules)
            batch_abs = input_specs(cfg.name, cell.name, cfg)
            b_sh = train_rt.batch_shardings(batch_abs, mesh)
            jitted = jax.jit(step, in_shardings=(st_sh, b_sh),
                             out_shardings=(st_sh, None),
                             donate_argnums=(0,))
            return jitted.lower(st_abs, batch_abs)
        if cell.kind == "prefill":
            sopts = serve_rt.ServeOptions(**{"scan_unroll": scan_unroll,
                                             **opts_over})
            fn, (p_abs, in_abs, cache_abs) = serve_rt.jit_prefill_step(
                model, sopts, mesh, B, S, rules=rules)
            return fn.lower(p_abs, in_abs, cache_abs)
        sopts = serve_rt.ServeOptions(**{"scan_unroll": scan_unroll,
                                         **opts_over})
        fn, (p_abs, cache_abs) = serve_rt.jit_decode_step(
            model, sopts, mesh, B, S, enc_len=model.enc_len_for(S),
            rules=rules)
        tok_abs = jax.ShapeDtypeStruct((B, 1), jnp.int32)
        idx_abs = jax.ShapeDtypeStruct((), jnp.int32)
        return fn.lower(p_abs, cache_abs, tok_abs, idx_abs)


def kernel_io_per_device(cfg, cell, n_dev: int) -> float:
    """Analytic HBM IO of the Pallas kernels, per device per step.

    The dry-run lowers the CPU stand-ins (chunked jnp scans) whose
    intermediates materialize; on TPU the Pallas kernels keep them in VMEM
    and touch HBM only for their operands/results. This substitutes that
    true IO for the depth>=2 loop traffic hlo_cost excludes.

    flash attention fwd:  (Q + O + K + V) once       [x4.5 for train:
    ssd scan fwd:         (x + y + B + C + states)    fwd + recompute + bwd]
    decode attention:     read the whole KV cache + write one token.
    """
    from ..models.transformer import layer_plan, encoder_plan
    B, S = cell.global_batch, cell.seq_len
    hd = cfg.head_dim_
    train_f = 4.5 if cell.kind == "train" else 1.0
    total = 0.0

    def attn_io(S_q, S_kv, decode=False):
        if decode:
            return 2.0 * (2 * B * S_kv * cfg.n_kv_heads * hd
                          + 2 * B * 1 * cfg.n_kv_heads * hd
                          + 2 * B * 1 * cfg.n_heads * hd)
        return 2.0 * (2 * B * S_q * cfg.n_heads * hd
                      + 2 * B * S_kv * cfg.n_kv_heads * hd)

    def ssm_io():
        from ..models.ssm import ssm_dims
        s = cfg.ssm
        _, d_inner, nh, _ = ssm_dims(cfg)
        chunks = max(S // max(s.chunk_size, 1), 1)
        return (2.0 * 2 * B * S * d_inner
                + 2.0 * 2 * B * S * s.n_groups * s.d_state
                + 4.0 * chunks * B * nh * s.head_dim * s.d_state)

    def moe_io():
        m = cfg.moe
        # the grouped kernel reads its dropless dispatch buffer (T k rows in
        # whole tiles) and writes as many rows, the hidden staying in VMEM,
        # and streams each held expert's weights once a step (the dominant
        # decode term for big MoE)
        T = B * (1 if cell.kind == "decode" else S)
        rows = moe.dispatch_rows(T, m.top_k, m.held, moe.row_tile(T, cfg))
        buf = rows * cfg.d_model * 2.0
        weights = m.held * 3 * cfg.d_model * m.d_ff_expert * 2.0
        return (2 * buf + weights) * train_f

    groups = list(layer_plan(cfg))
    if cfg.family == "encdec":
        groups += list(encoder_plan(cfg))
    dec = cell.kind == "decode"
    for gd in groups:
        for b in gd.blocks:
            if b.kind in ("attn", "parallel", "shared_attn"):
                total += gd.repeat * (attn_io(1, S, decode=True) if dec
                                      else attn_io(S, S) * train_f)
            elif b.kind == "cross_attn":
                enc = (cfg.vision.num_patches if cfg.family == "vlm"
                       else S)
                total += gd.repeat * (attn_io(1, enc, decode=True) if dec
                                      else attn_io(S, enc) * train_f)
            elif b.kind == "ssm" and not dec:
                total += gd.repeat * ssm_io() * train_f
            elif b.kind == "ssm" and dec:
                total += gd.repeat * 2.0 * B * (
                    2 * cfg.ssm.expand * cfg.d_model)
            elif b.kind == "moe":
                total += gd.repeat * moe_io()
    return total / n_dev


def _cost_of(compiled) -> dict:
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    coll = parse_collectives(compiled.as_text())
    return {"flops": float(cost.get("flops", 0.0) or 0.0),
            "bytes": float(cost.get("bytes accessed", 0.0) or 0.0),
            "coll": coll}


def _extrapolate(base: dict, variants: list[tuple[dict, float]]) -> dict:
    out = {"flops": base["flops"], "bytes": base["bytes"],
           "coll": {}, "coll_total": base["coll"]["total_bytes"]}
    for op in _COLLECTIVES:
        out["coll"][op] = dict(base["coll"][op])
    for var, scale in variants:
        out["flops"] += (var["flops"] - base["flops"]) * scale
        out["bytes"] += (var["bytes"] - base["bytes"]) * scale
        out["coll_total"] += (var["coll"]["total_bytes"]
                              - base["coll"]["total_bytes"]) * scale
        for op in _COLLECTIVES:
            for k in ("count", "bytes", "raw_bytes"):
                out["coll"][op][k] += (var["coll"][op][k]
                                       - base["coll"][op][k]) * scale
    return out


def run_cell(arch: str, shape: str, *, multi_pod: bool, rules=None,
             opts_over=None, verbose: bool = True,
             skip_variants: bool = False, serving_rules: bool = False) -> dict:
    if serving_rules:   # §Perf optimized sharding for serve cells
        cell0 = SHAPE_CELLS[shape]
        if cell0.kind != "train":
            rules = dict(shd.SERVING_RULES, **(rules or {}))
    cfg = get_config(arch)
    cell = SHAPE_CELLS[shape]
    model = build_model(cfg)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    rec: dict = {"arch": arch, "shape": shape, "mesh": mesh_name,
                 "kind": cell.kind, "ok": False,
                 "serving_rules": serving_rules}
    if not cell_applicable(cfg, cell):
        rec.update(skipped=True,
                   reason="full-attention arch at 500k ctx (DESIGN.md §4)")
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    B, S = cell.global_batch, cell.seq_len

    # 1) the real artifact: full depth, scan-over-layers -> memory analysis
    t0 = time.time()
    lowered = _lower_cell(cfg, cell, mesh, rules=rules, opts_over=opts_over)
    t_lower = time.time() - t0
    compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower
    mem = compiled.memory_analysis()
    full_cost = _cost_of(compiled)
    # v2: production-artifact accounting (hlo_cost) + Pallas-kernel IO
    v2 = hlo_cost(compiled.as_text())
    n_dev = {"16x16": 256, "2x16x16": 512}[mesh_name]
    kio = kernel_io_per_device(cfg, cell, n_dev)

    # 2) per-layer costs: decode graphs are small -> cost the fully
    # unrolled lowering directly (exact); train/prefill use shallow
    # unrolled variants extrapolated over depth (exact for FLOPs).
    if cell.kind == "decode":
        unrolled = _cost_of(_lower_cell(cfg, cell, mesh, rules=rules,
                                        opts_over=opts_over,
                                        scan_unroll=4096).compile())
        cost = {"flops": unrolled["flops"], "bytes": unrolled["bytes"],
                "coll": {op: unrolled["coll"][op] for op in _COLLECTIVES},
                "coll_total": unrolled["coll"]["total_bytes"]}
    elif skip_variants:
        cost = {"flops": full_cost["flops"], "bytes": full_cost["bytes"],
                "coll": full_cost["coll"],
                "coll_total": full_cost["coll"]["total_bytes"]}
        cost["coll"] = {op: full_cost["coll"][op] for op in _COLLECTIVES}
    else:
        base_over, var_overs = depth_variants(cfg)
        base_cost = _cost_of(_lower_cell(
            cfg.replace(**base_over), cell, mesh, rules=rules,
            opts_over=opts_over, scan_unroll=64).compile())
        var_costs = [
            (_cost_of(_lower_cell(cfg.replace(**vo), cell, mesh, rules=rules,
                                  opts_over=opts_over,
                                  scan_unroll=64).compile()), sc)
            for vo, sc in var_overs]
        cost = _extrapolate(base_cost, var_costs)

    rec.update(
        ok=True,
        lower_s=round(t_lower, 1),
        compile_s=round(t_compile, 1),
        total_s=round(time.time() - t0, 1),
        flops_per_device=cost["flops"],
        hbm_bytes_per_device=cost["bytes"],
        collective_bytes_per_device=cost["coll_total"],
        # v2 (production artifact): see launch/hlo_cost.py
        v2_bytes_per_device=v2["bytes_outer"] + kio,
        v2_bytes_outer=v2["bytes_outer"],
        v2_bytes_alldepth=v2["bytes"],
        v2_kernel_io=kio,
        v2_collective_bytes_per_device=v2["coll_total"],
        v2_collectives={op: v2["coll"][op] for op in _COLLECTIVES},
        collectives={op: cost["coll"][op] for op in _COLLECTIVES},
        scan_cost_raw=full_cost,       # un-extrapolated (body-once) numbers
        memory={
            "argument_bytes": int(getattr(mem, "argument_size_in_bytes", -1)),
            "output_bytes": int(getattr(mem, "output_size_in_bytes", -1)),
            "temp_bytes": int(getattr(mem, "temp_size_in_bytes", -1)),
            "alias_bytes": int(getattr(mem, "alias_size_in_bytes", -1)),
        },
        params_total=model.param_count(),
        params_active=model.active_param_count(),
        global_batch=B, seq_len=S,
    )
    if verbose:
        m = rec["memory"]
        live = m["argument_bytes"] + m["temp_bytes"] - max(m["alias_bytes"], 0)
        print(f"[dryrun] {arch} {shape} {mesh_name}: "
              f"compile={t_compile:.0f}s total={rec['total_s']:.0f}s "
              f"flops/dev={cost['flops']:.3e} "
              f"v2bytes/dev={rec['v2_bytes_per_device']:.3e} "
              f"v2coll/dev={rec['v2_collective_bytes_per_device']:.3e}B "
              f"live/dev={live:.3e}B")
    return rec


def save_record(rec: dict, out_dir: str = RESULTS_DIR):
    os.makedirs(out_dir, exist_ok=True)
    suffix = "_opt" if rec.get("serving_rules") else ""
    name = f"{rec['arch']}_{rec['shape']}_{rec['mesh']}{suffix}.json"
    with open(os.path.join(out_dir, name), "w") as f:
        json.dump(rec, f, indent=1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=tuple(SHAPE_CELLS))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--serving-rules", action="store_true",
                    help="optimized serve-time sharding (SERVING_RULES)")
    ap.add_argument("--out", default=RESULTS_DIR)
    args = ap.parse_args()

    cells = ([(a, s) for a in ARCH_IDS for s in SHAPE_CELLS]
             if args.all else [(args.arch, args.shape)])
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    failures = []
    for arch, shape in cells:
        for mp in meshes:
            try:
                rec = run_cell(arch, shape, multi_pod=mp,
                               serving_rules=args.serving_rules)
            except Exception as e:
                traceback.print_exc()
                rec = {"arch": arch, "shape": shape,
                       "mesh": "2x16x16" if mp else "16x16", "ok": False,
                       "error": f"{type(e).__name__}: {e}"}
                failures.append(rec)
            save_record(rec, args.out)
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for f in failures:
            print(" ", f["arch"], f["shape"], f["mesh"], "->", f["error"])
        raise SystemExit(1)
    print("\nall requested dry-run cells passed")


if __name__ == "__main__":
    main()
