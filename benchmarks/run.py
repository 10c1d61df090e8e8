"""Benchmark aggregator — one entry per paper table/figure + roofline.

Prints ``name,us_per_call,derived`` CSV lines per the repo convention.
"""
from __future__ import annotations

import json
import os
import sys
import time


def _run(name, fn, *args, **kw):
    t0 = time.perf_counter()
    rows = fn(*args, **kw)
    us = (time.perf_counter() - t0) * 1e6
    return name, us, rows


def main() -> None:
    sys.path.insert(0, os.path.dirname(__file__))
    from repro.launch.jit_cache import enable_compile_cache

    enable_compile_cache()
    from paper_tables import (api_claims, fig8_storage, fig9_energy,
                              fig10_performance, intermittency_study,
                              kernel_bench, table1_accuracy,
                              table2_energy_area)

    def serve_fused(fast=False):
        # deferred so a bench_serve import failure stays one failing row
        from bench_serve import serve_rows
        return serve_rows(fast=fast)

    def conv_implicit(fast=False):
        from bench_conv import conv_rows
        return conv_rows(fast=fast)

    def attn_flash(fast=False):
        from bench_attn import attn_rows
        return attn_rows(fast=fast)

    def resilience(fast=False):
        from bench_resilience import resilience_rows
        return resilience_rows(fast=fast)

    def fleet_study(fast=False):
        from bench_fleet import fleet_rows
        return fleet_rows(fast=fast)

    fast = "--fast" in sys.argv
    strict = "--strict" in sys.argv  # exit nonzero if any job errors (CI)
    failed = []
    jobs = [
        ("table1_accuracy", table1_accuracy,
         dict(steps=20 if fast else 60, train=True)),
        ("fig8_storage", fig8_storage, {}),
        ("fig9_energy", fig9_energy, {}),
        ("fig10_performance", fig10_performance, {}),
        ("table2_energy_area", table2_energy_area, {}),
        ("api_claims", api_claims, {}),
        ("intermittency", intermittency_study, {}),
        ("kernels", kernel_bench, {}),
        ("conv_implicit", conv_implicit, dict(fast=fast)),
        ("attn_flash", attn_flash, dict(fast=fast)),
        ("serve_fused", serve_fused, dict(fast=fast)),
        ("resilience", resilience, dict(fast=fast)),
        ("fleet_study", fleet_study, dict(fast=fast)),
    ]
    print("name,us_per_call,derived")
    all_rows = {}
    for name, fn, kw in jobs:
        try:
            name, us, rows = _run(name, fn, **kw)
            all_rows[name] = rows
            derived = json.dumps(rows[:3] if isinstance(rows, list) else rows)
            print(f"{name},{us:.0f},{derived}")
        except Exception as e:  # repro-lint: disable=RL003 — recorded in the failure list; --strict exits nonzero on it
            print(f"{name},0,ERROR:{e}")
            failed.append(f"{name} ({type(e).__name__}: {e})")
    # roofline table (if dry-run results exist)
    try:
        import roofline
        tag = ("16x16-analysis"
               if any("analysis" in f for f in os.listdir(roofline.RESULTS_DIR))
               else "16x16")
        rows = roofline.rows_csv(tag)
        if rows:
            ok = [r for r in rows if r.get("ok")]
            fr = sorted(ok, key=lambda r: -r["frac"])[:3]
            print(f"roofline,{len(rows)},{json.dumps([dict(arch=r['arch'], shape=r['shape'], frac=round(r['frac'], 3)) for r in fr])}")
    except Exception as e:  # repro-lint: disable=RL003 — optional table; the error is printed in the CSV row
        print(f"roofline,0,ERROR:{e}")
    out = "results/bench_rows.json"
    os.makedirs("results", exist_ok=True)
    with open(out, "w") as f:
        json.dump(all_rows, f, indent=1, default=str)
    print(f"# full rows -> {out}", file=sys.stderr)
    if strict and failed:
        sys.exit(f"jobs failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
