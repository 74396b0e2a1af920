import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# Multi-pod dry-run: prove every (arch × shape × mesh) cell lowers, SPMD-
# partitions, and compiles on the production meshes — and extract the
# roofline inputs (FLOPs, bytes, per-collective bytes) from the compiled
# artifact.
#
# The two lines above run BEFORE any other import: jax locks the device count
# on first init (see the deliverable spec).
#
# Usage::
#
#   PYTHONPATH=src python -m repro.launch.dryrun --arch gemma-2b --shape train_4k
#   PYTHONPATH=src python -m repro.launch.dryrun --arch gemma-2b --shape train_4k --multi-pod
#   PYTHONPATH=src python -m repro.launch.dryrun --paper-cell [--multi-pod]
#   PYTHONPATH=src python -m repro.launch.dryrun --all --out results/

import argparse
import json
import re
import sys
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

import repro.models as M
from repro.configs import SHAPES, applicable, get_config
from repro.configs.shapes import ShapeCell
from repro.launch.mesh import data_axes, make_production_mesh, num_chips
from repro.launch.sharding import batch_struct, cache_struct, named, rules_for
from repro.models.common import ModelConfig
from repro.train import default_lr, default_optimizer, make_train_step
from repro.train.step import make_decode_step, make_prefill_step

_DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "s32": 4,
                "u64": 8, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "c64": 8, "c128": 16}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")


def collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Sum operand bytes of every collective in (optimized) HLO text."""
    out = {k: 0.0 for k in _COLLECTIVES}
    shape_re = re.compile(r"(\w+)\[([\d,]*)\]")
    for line in hlo_text.splitlines():
        line = line.strip()
        m = re.match(r"(?:ROOT\s+)?%?[\w\.\-]+\s*=\s*(.*)$", line)
        if not m:
            continue
        rhs = m.group(1)
        for op in _COLLECTIVES:
            # match the op as instruction name: "<shapes> all-reduce(" or
            # "all-reduce-start("
            if re.search(rf"\)?\s{op}(-start|-done)?\(", " " + rhs):
                if f"{op}-done(" in rhs:
                    continue  # avoid double-count of async pairs
                # result shapes appear before the op token
                head = rhs.split(op)[0]
                nbytes = 0.0
                for dt, dims in shape_re.findall(head):
                    if dt not in _DTYPE_BYTES:
                        continue
                    n = 1
                    for d in dims.split(","):
                        if d:
                            n *= int(d)
                    nbytes += n * _DTYPE_BYTES[dt]
                out[op] += nbytes
                break
    return out


def analyze(compiled, lowered=None) -> Dict[str, Any]:
    import pathlib
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[3]))
    from benchmarks.hlo_cost import analyze_hlo

    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    mem = compiled.memory_analysis()
    hlo = compiled.as_text()
    rep = analyze_hlo(hlo)                 # loop-aware (see benchmarks/hlo_cost)
    coll_raw = collective_bytes(hlo)       # raw single-visit parse (reference)
    return {
        "flops_per_device": float(rep.flops),
        "bytes_per_device": float(rep.bytes),
        "collective_bytes_per_device": dict(rep.collective),
        "collective_total": float(rep.collective_total),
        "xla_flops_single_visit": float(cost.get("flops", -1.0)),
        "xla_bytes_single_visit": float(cost.get("bytes accessed", -1.0)),
        "collective_single_visit": coll_raw,
        "argument_bytes": getattr(mem, "argument_size_in_bytes", -1),
        "output_bytes": getattr(mem, "output_size_in_bytes", -1),
        "temp_bytes": getattr(mem, "temp_size_in_bytes", -1),
        "peak_bytes": (getattr(mem, "argument_size_in_bytes", 0)
                       + getattr(mem, "output_size_in_bytes", 0)
                       + getattr(mem, "temp_size_in_bytes", 0)),
    }


# ---------------------------------------------------------------------------
# cell builders
# ---------------------------------------------------------------------------

def lower_cell(arch: str, shape: str, mesh, *, donate: bool = True,
               remat: Optional[str] = None, shard_map_moe: bool = True,
               accum_steps: int = 1):
    """Build + lower + compile one (arch, shape, mesh) cell.  Returns
    (lowered, compiled, meta)."""
    import dataclasses

    from repro.models.common import set_current_mesh

    cfg = get_config(arch)
    if remat:
        cfg = dataclasses.replace(cfg, remat=remat)
    cell = SHAPES[shape]
    if not applicable(cfg, cell):
        raise SystemExit(f"SKIP {arch}×{shape}: needs sub-quadratic arch")
    set_current_mesh(mesh if shard_map_moe else None)
    rules = rules_for(cfg, cell, mesh)
    pspecs = M.param_specs(cfg, rules)
    pshapes = M.param_shapes(cfg)
    meta = {"arch": arch, "shape": shape, "chips": num_chips(mesh),
            "params": M.count_params(cfg),
            "active_ratio": M.active_param_ratio(cfg)}

    with mesh:
        if cell.kind == "train":
            opt = default_optimizer(cfg)
            ostate_shapes = opt.state_shapes(pshapes)
            ospecs = opt.state_specs(pspecs)
            bshapes, bspecs = batch_struct(cfg, cell, rules)
            step = make_train_step(cfg, rules, opt, default_lr(cfg),
                                   accum_steps=accum_steps)
            in_sh = (named(mesh, pspecs), named(mesh, ospecs),
                     named(mesh, bspecs), NamedSharding(mesh, P()))
            out_sh = (named(mesh, pspecs), named(mesh, ospecs),
                      {"loss": NamedSharding(mesh, P()),
                       "lr": NamedSharding(mesh, P()),
                       "grad_norm": NamedSharding(mesh, P())})
            jitted = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh,
                             donate_argnums=(0, 1) if donate else ())
            lowered = jitted.lower(pshapes, ostate_shapes, bshapes,
                                   jax.ShapeDtypeStruct((), jnp.int32))
        elif cell.kind == "prefill":
            bshapes, bspecs = batch_struct(cfg, cell, rules)
            cshapes, cspecs = cache_struct(cfg, cell, rules)
            step = make_prefill_step(cfg, rules)
            jitted = jax.jit(
                step,
                in_shardings=(named(mesh, pspecs), named(mesh, bspecs),
                              named(mesh, cspecs)),
                donate_argnums=(2,) if donate else ())
            lowered = jitted.lower(pshapes, bshapes, cshapes)
        else:  # decode
            cshapes, cspecs = cache_struct(cfg, cell, rules)
            B = cell.global_batch
            bt = rules.resolve("batch")
            tok_shape = jax.ShapeDtypeStruct((B, 1), jnp.int32)
            tok_spec = NamedSharding(mesh, P(bt, None))
            step = make_decode_step(cfg, rules)
            jitted = jax.jit(
                step,
                in_shardings=(named(mesh, pspecs), tok_spec,
                              NamedSharding(mesh, P()), named(mesh, cspecs)),
                donate_argnums=(3,) if donate else ())
            lowered = jitted.lower(pshapes, tok_shape,
                                   jax.ShapeDtypeStruct((), jnp.int32),
                                   cshapes)
        t0 = time.time()
        compiled = lowered.compile()
        meta["compile_s"] = time.time() - t0
    return lowered, compiled, meta


def lower_paper_cell(mesh, *, n_points: int = 2 ** 30, dim: int = 64,
                     k: int = 128, kprime: int = 2048, batch_b: int = 0,
                     points_bf16: bool = False):
    """The paper's own workload: 2-round MR GMM core-set over the mesh.
    Round 1 = per-device GMM on the local shard (shard_map), round 2 = the
    all-gather 'shuffle'.  ``batch_b > 0`` switches round 1 to the batched
    lookahead-b GMM (EXPERIMENTS.md §Perf hillclimb #1)."""
    from jax import shard_map
    from repro.core.gmm import gmm as _gmm, gmm_batched as _gmm_b

    daxes = data_axes(mesh)
    nshards = num_chips(mesh)
    per = n_points // nshards
    n = per * nshards

    axes_all = tuple(mesh.axis_names)

    def body(shard):
        # bf16 point storage (§Perf iteration 3): the sweep's HBM read
        # halves; distances accumulate in f32 via preferred_element_type
        work = shard
        if batch_b:
            idx, radius, _ = _gmm_b(work, kprime, b=batch_b,
                                    metric="euclidean")
        else:
            res = _gmm(work, kprime, metric="euclidean")
            idx, radius = res.idx, res.radius
        local = shard[idx].astype(jnp.float32)
        g = jax.lax.all_gather(local, axes_all, tiled=True)
        rad = jax.lax.pmax(radius.astype(jnp.float32), axes_all)
        return g, rad

    fn = shard_map(body, mesh=mesh, in_specs=P(axes_all),
                   out_specs=(P(), P()), check_vma=False)
    pts = jax.ShapeDtypeStruct((n, dim),
                               jnp.bfloat16 if points_bf16 else jnp.float32)
    with mesh:
        jitted = jax.jit(fn)
        lowered = jitted.lower(pts)
        t0 = time.time()
        compiled = lowered.compile()
    name = "coreset_mr" if not batch_b else f"coreset_mr_b{batch_b}"
    if points_bf16:
        name += "_bf16"
    meta = {"arch": name, "shape": f"n{n_points}_d{dim}_k{kprime}",
            "chips": nshards, "params": 0, "active_ratio": 1.0,
            "compile_s": time.time() - t0}
    return lowered, compiled, meta


def run_cell(arch: str, shape: str, multi_pod: bool,
             out_path: Optional[str] = None, batch_b: int = 0,
             points_bf16: bool = False, remat: Optional[str] = None,
             shard_map_moe: bool = True, accum_steps: int = 1) -> Dict[str, Any]:
    mesh = make_production_mesh(multi_pod=multi_pod)
    if arch == "coreset_mr":
        lowered, compiled, meta = lower_paper_cell(mesh, batch_b=batch_b,
                                                   points_bf16=points_bf16)
    else:
        lowered, compiled, meta = lower_cell(arch, shape, mesh, remat=remat,
                                             shard_map_moe=shard_map_moe,
                                             accum_steps=accum_steps)
    info = analyze(compiled)
    info.update(meta)
    info["multi_pod"] = multi_pod
    print(f"== {arch} × {shape} ({'2x16x16' if multi_pod else '16x16'}) ==")
    print(f"compile: {meta['compile_s']:.1f}s")
    print(compiled.memory_analysis())
    print(f"loop-aware flops/device: {info['flops_per_device']:.3e}  "
          f"bytes/device: {info['bytes_per_device']:.3e}")
    print("collectives (loop-aware):", info["collective_bytes_per_device"])
    if out_path:
        with open(out_path, "w") as f:
            json.dump(info, f, indent=1)
    return info


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--paper-cell", action="store_true")
    ap.add_argument("--batch-b", type=int, default=0,
                    help="batched-GMM block for the paper cell (§Perf)")
    ap.add_argument("--points-bf16", action="store_true",
                    help="bf16 point storage for the paper cell (§Perf)")
    ap.add_argument("--remat", default=None, choices=("none", "dots", "full"))
    ap.add_argument("--accum", type=int, default=1,
                    help="microbatch gradient-accumulation steps (§Perf)")
    ap.add_argument("--no-shard-map-moe", action="store_true",
                    help="fall back to GSPMD-inferred MoE dispatch (§Perf)")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    if args.paper_cell:
        run_cell("coreset_mr", "paper", args.multi_pod, args.out,
                 batch_b=args.batch_b, points_bf16=args.points_bf16)
        return
    if args.all:
        from repro.configs import ARCH_IDS
        ok, failed = [], []
        for arch in ARCH_IDS:
            cfg = get_config(arch)
            for shape, cell in SHAPES.items():
                if not applicable(cfg, cell):
                    print(f"SKIP {arch}×{shape} (full-attention arch)")
                    continue
                out = (f"{args.out}/{arch}_{shape}"
                       f"{'_mp' if args.multi_pod else ''}.json"
                       if args.out else None)
                try:
                    run_cell(arch, shape, args.multi_pod, out,
                             remat=args.remat,
                             shard_map_moe=not args.no_shard_map_moe)
                    ok.append((arch, shape))
                except Exception as e:
                    traceback.print_exc()
                    failed.append((arch, shape, repr(e)))
        print(f"\n{len(ok)} cells OK, {len(failed)} failed")
        for f in failed:
            print("FAILED:", f)
        sys.exit(1 if failed else 0)
    run_cell(args.arch, args.shape, args.multi_pod, args.out,
             remat=args.remat, shard_map_moe=not args.no_shard_map_moe,
             accum_steps=args.accum)


if __name__ == "__main__":
    main()
