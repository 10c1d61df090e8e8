"""Plan-cache smoke gate (CI): compile -> serialize -> FRESH-PROCESS reload
-> assert bit-identical serve output, with requantization forcibly disabled
in the reloading process.

  PYTHONPATH=src python -m repro.launch.plan_smoke [--out results/plan_cache/plan_smoke]

The parent never imports JAX (on a TPU the process that touches JAX holds
the chip, and a child would then fail or hang).  It runs two children, one
after the other: the compile child builds a CNN ModelPlan (with a small
autotune pass), saves it plus the expected logits; the reload child
reloads the plan from disk and serves.  The reload child patches
``repro.core.quant.weight_levels`` to raise — proving the reload path
never requantizes — and asserts the logits match bit-for-bit.  If
``--out`` already holds a valid plan for the same fingerprintable inputs
(the CI plan-artifact cache), compilation is skipped and only the reload
gate runs.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

SEED = 0
IMG = 16
BATCH = 4
CHANNELS = 8


def _setup():
    import jax

    from repro.core.quant import W1A4
    from repro.models.cnn import init_cnn, svhn_cnn_spec

    spec = svhn_cnn_spec(CHANNELS)
    params, _ = init_cnn(jax.random.PRNGKey(SEED), spec)
    x = jax.random.uniform(jax.random.PRNGKey(SEED + 1),
                           (BATCH, IMG, IMG, 3))
    return spec, params, x, W1A4


def check(base: str) -> int:
    """Child: reload the plan, forbid requantization, compare bit-exactly."""
    import jax
    import numpy as np

    import repro.core.quant as quant_mod
    from repro.core.plan import load_plan, plan_forward

    _, _, x, _ = _setup()
    t0 = time.perf_counter()
    plan = load_plan(base)
    load_ms = (time.perf_counter() - t0) * 1e3

    def _forbidden(*a, **kw):
        raise AssertionError(
            "weight_levels called after plan reload — the plan path must "
            "never requantize")

    quant_mod.weight_levels = _forbidden
    # jitted whole, same composition as the parent's expected program
    out = np.asarray(jax.jit(lambda v: plan_forward(plan, v))(x))
    expected = np.load(base + ".expected.npy")
    np.testing.assert_array_equal(out, expected)
    print(f"PLAN SMOKE OK: reload {load_ms:.1f}ms, output bit-identical, "
          f"no requantization (fingerprint {plan.fingerprint()})")
    return 0


def compile_and_save(base: str) -> int:
    """Compile child: compile (or reuse) the plan, save the expected
    logits, and check them against the legacy auto-dispatch forward."""
    import jax
    import numpy as np

    from repro.core.plan import compile_model, load_plan, plan_forward, \
        save_plan

    spec, params, x, quant = _setup()
    reused = False
    recompile_reason = None
    if os.path.exists(base + ".json") and os.path.exists(
            base + ".expected.npy"):
        try:
            plan = load_plan(base)  # cached artifact from a previous CI run
            reused = True
        except Exception as e:  # repro-lint: disable=RL003 — reason recorded in the output JSON; any reload failure means recompile
            recompile_reason = f"{type(e).__name__}: {e}"
            print(f"cached plan unusable ({recompile_reason}); recompiling")
            plan = None
    else:
        plan = None
        recompile_reason = "no cached artifact"
    if plan is None:
        t0 = time.perf_counter()
        plan = compile_model(params, spec, quant, batch_hints=(1, BATCH),
                             img_hw=IMG, autotune=True, model="svhn_smoke")
        compile_ms = (time.perf_counter() - t0) * 1e3
        save_plan(plan, base)
        print(f"compiled plan (+autotune) in {compile_ms:.1f}ms -> "
              f"{base}.json")
    else:
        print(f"reusing cached plan artifact {base}.json "
              f"(fingerprint {plan.fingerprint()})")
    expected = np.asarray(jax.jit(lambda v: plan_forward(plan, v))(x))
    np.save(base + ".expected.npy", expected)
    # bit-identity vs the legacy auto-dispatch forward at the SAME program
    # composition (both jitted whole — jit-vs-eager flips activation
    # quantization levels at ulp boundaries, same as test_engine pins)
    from repro.models.cnn import cnn_forward

    legacy = np.asarray(jax.jit(
        lambda v: cnn_forward(plan.params, v, spec, quant, "serve"))(x))
    np.testing.assert_array_equal(expected, legacy)
    print(json.dumps(dict(
        plan=base + ".json", reused_cached_artifact=reused,
        recompile_reason=recompile_reason,
        fingerprint=plan.fingerprint(),
        engines={lp.name: lp.engine for lp in plan.layers})))
    return 0


def _child(flag: str, base: str) -> subprocess.CompletedProcess:
    env = dict(os.environ)
    env["PYTHONPATH"] = (os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
        + os.pathsep + env.get("PYTHONPATH", ""))
    p = subprocess.run(
        [sys.executable, "-m", "repro.launch.plan_smoke", flag, base],
        env=env, capture_output=True, text=True, timeout=600)
    sys.stdout.write(p.stdout)
    sys.stderr.write(p.stderr)
    return p


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="results/plan_cache/plan_smoke")
    ap.add_argument("--compile", default=None, metavar="BASE",
                    help="internal: run the compile child")
    ap.add_argument("--check", default=None, metavar="BASE",
                    help="internal: run the fresh-process reload gate")
    args = ap.parse_args()
    if args.compile or args.check:
        from repro.launch.jit_cache import enable_compile_cache

        enable_compile_cache()
        if args.compile:
            return compile_and_save(args.compile)
        return check(args.check)

    if _child("--compile", args.out).returncode != 0:
        print("PLAN SMOKE FAILED (compile)", file=sys.stderr)
        return 1
    p = _child("--check", args.out)
    if p.returncode != 0 or "PLAN SMOKE OK" not in p.stdout:
        print("PLAN SMOKE FAILED", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
