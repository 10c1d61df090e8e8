import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS_EXTRA", "")
)
# NOTE: the two lines above MUST run before any other import (jax locks the
# device count on first init) — see the brief, MULTI-POD DRY-RUN step 0.

"""Multi-pod dry-run: lower + compile every (arch x input-shape) cell on the
production meshes and dump memory/cost/collective analysis.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch phi3-mini-3.8b \
      --shape train_4k [--multi-pod] [--out results.json]
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]
"""
import argparse
import json
import sys
import time
import traceback

import jax

from repro.configs import SHAPES, all_configs, get_config, make_plan
from repro.launch import hlo_analysis as ha
from repro.launch import steps as steps_mod
from repro.launch.mesh import make_production_mesh, mesh_shape_dict


def run_cell(arch: str, shape: str, multi_pod: bool = False,
             overrides: dict | None = None, verbose: bool = True,
             analysis: bool = False, infer_plan: bool = False,
             quant: str | None = None, prequant: bool = False) -> dict:
    cfg = get_config(arch)
    if quant:
        import dataclasses
        from repro.core.quant import PAPER_CONFIGS
        cfg = dataclasses.replace(cfg, quant=PAPER_CONFIGS[quant])
    if analysis:
        # exact loop accounting: unroll layers, closed-form attention,
        # associative recurrences (see hlo_analysis + EXPERIMENTS.md)
        import dataclasses
        cfg = dataclasses.replace(cfg, scan_layers=False,
                                  full_attn_analysis=True, rglru_assoc=True)
    if overrides:
        import dataclasses
        cfg = dataclasses.replace(cfg, **overrides)
    cell = SHAPES[shape]
    mesh = make_production_mesh(multi_pod=multi_pod)
    plan = make_plan(mesh_shape_dict(mesh),
                     inference=infer_plan and cell.kind != "train")
    chips = mesh.devices.size
    t0 = time.time()
    from repro.models.layers import set_static_act_scale
    set_static_act_scale(getattr(cfg, "act_scale", 0.0))
    with jax.set_mesh(mesh):
        built = steps_mod.build_cell(
            cfg, cell, plan, mesh,
            qmode="serve" if (quant and cell.kind != "train") else "train",
            prequant=prequant)
        jitted = jax.jit(
            built["fn"],
            in_shardings=built["in_shardings"],
            out_shardings=built["out_shardings"],
            donate_argnums=built["donate_argnums"],
        )
        lowered = jitted.lower(*built["args"])
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    if os.environ.get("DUMP_HLO"):
        with open(os.environ["DUMP_HLO"], "w") as f:
            f.write(hlo)
    coll = ha.collective_stats(hlo)
    flops = float(cost.get("flops", 0.0))
    byts = float(cost.get("bytes accessed", 0.0))
    rec_corr = ha.recurrence_flops_correction(cfg, cell) / chips
    rl = ha.Roofline(
        hlo_flops=flops + rec_corr, hlo_bytes=byts,
        collective_bytes=float(coll["total_bytes"]), chips=chips,
        model_flops=ha.model_flops_estimate(cfg, cell),
    )
    mem_d = {}
    for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "generated_code_size_in_bytes"):
        v = getattr(mem, attr, None)
        if v is not None:
            mem_d[attr] = int(v)
    res = dict(
        arch=arch, shape=shape, mesh="2x16x16" if multi_pod else "16x16",
        chips=chips, ok=True,
        lower_s=round(t_lower, 1), compile_s=round(t_compile, 1),
        memory=mem_d, collectives=coll, roofline=rl.to_dict(),
        flops=flops, bytes_accessed=byts,
    )
    if verbose:
        print(f"[dryrun] {arch} x {shape} on {res['mesh']}:")
        print(f"  memory_analysis: {mem}")
        print(f"  cost_analysis: flops={flops:.3e} bytes={byts:.3e}")
        print(f"  collectives: {coll['counts']} -> {coll['total_bytes']:.3e} B")
        r = res["roofline"]
        print(f"  roofline: compute={r['compute_s']:.4e}s memory={r['memory_s']:.4e}s "
              f"collective={r['collective_s']:.4e}s dominant={r['dominant']} "
              f"useful={r['useful_flops_frac']:.2%} frac={r['roofline_frac']:.2%}")
    return res


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", type=str, default=None)
    ap.add_argument("--shape", type=str, default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--analysis", action="store_true")
    ap.add_argument("--infer-plan", action="store_true")
    ap.add_argument("--quant", default=None)
    ap.add_argument("--prequant", action="store_true")
    ap.add_argument("--set", default=None,
                    help="comma list of ArchConfig overrides key=val (bool/int)")
    ap.add_argument("--out", type=str, default=None)
    args = ap.parse_args()

    cells = []
    if args.all:
        for arch, cfg in all_configs().items():
            for cell in cfg.shapes():
                cells.append((arch, cell.name))
    else:
        assert args.arch and args.shape, "--arch and --shape (or --all)"
        cells.append((args.arch, args.shape))

    overrides = {}
    if args.set:
        for kv in args.set.split(","):
            k, v = kv.split("=")
            overrides[k] = (v == "1" if v in ("0", "1") else
                            int(v) if v.isdigit() else v)

    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    results = []
    fails = 0
    for arch, shape in cells:
        for mp in meshes:
            try:
                results.append(run_cell(
                    arch, shape, multi_pod=mp, analysis=args.analysis,
                    infer_plan=args.infer_plan, quant=args.quant,
                    prequant=args.prequant, overrides=overrides or None))
            except Exception as e:  # repro-lint: disable=RL003 — a failure here is a bug: structured-recorded below and the run exits nonzero
                fails += 1
                traceback.print_exc()
                results.append(dict(arch=arch, shape=shape,
                                    mesh="2x16x16" if mp else "16x16",
                                    ok=False, error=str(e)[-2000:],
                                    error_type=type(e).__name__,
                                    traceback=traceback.format_exc()[-2000:]))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
    print(f"[dryrun] {len(results) - fails}/{len(results)} cells OK")
    sys.exit(1 if fails else 0)


if __name__ == "__main__":
    main()
