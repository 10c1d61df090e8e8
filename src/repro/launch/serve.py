"""LM serving CLI on the public facade (``repro.api``).

  PYTHONPATH=src python -m repro.launch.serve --arch phi3-mini-3.8b \
      --batch 4 --prompt-len 16 --new-tokens 16 [--quant w1a8] [--no-smoke]

The model is compiled once into a ModelPlan (``--plan-cache`` persists and
reloads it) and served by ``compiled.serve()``: the continuous-batching
engine (``launch/engine.ContinuousLMEngine``) with ``--batch`` decode slots
over a paged KV cache.  The default mode serves one batch of prompts twice,
cold (compile included) and warm.  ``--throughput`` sweeps offered load;
``--chaos-mtbf`` serves under a seeded fault schedule with epoch
checkpoints, through ``serve(resilience=...)``.
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import numpy as np

from repro.configs import SINGLE, get_config
from repro.core.quant import PAPER_CONFIGS
from repro.data.synthetic import lm_batch
from repro.models import transformer as T


def _prompts(cfg, args) -> list:
    return [np.random.RandomState(i)
            .randint(0, cfg.vocab, size=(args.prompt_len,))
            .astype(np.int32) for i in range(args.requests)]


def run_throughput(compiled, cfg, qmode: str, args) -> None:
    """Offered-load throughput mode: serve ``--requests`` independent
    prompts with one decode slot (sequential) and with ``--batch`` slots,
    then sweep offered rates around the sequential capacity; prints
    requests/s and p50/p99 latency per setting."""
    import json

    from repro.launch.engine import run_offered_load, warm_engine

    prompts = _prompts(cfg, args)

    def mk(slots):
        return compiled.serve(max_batch=slots, new_tokens=args.new_tokens,
                              qmode=qmode).engine

    seq = run_offered_load(warm_engine(mk(1), prompts), prompts, None)
    eng = warm_engine(mk(args.batch), prompts)
    bat = run_offered_load(eng, prompts, None)
    print(f"arch={cfg.name} requests={args.requests} "
          f"prompt_len={args.prompt_len} new_tokens={args.new_tokens}")
    print(f"sequential: {seq['achieved_rps']:.1f} req/s "
          f"p50={seq['p50_ms']}ms p99={seq['p99_ms']}ms")
    print(f"slots={args.batch}: {bat['achieved_rps']:.1f} req/s "
          f"p50={bat['p50_ms']}ms p99={bat['p99_ms']}ms "
          f"({bat['achieved_rps'] / max(seq['achieved_rps'], 1e-9):.2f}x)")
    for mult in (0.5, 1.0, 2.0, 4.0):
        row = run_offered_load(eng, prompts,
                               rate_rps=mult * seq["achieved_rps"])
        print(f"offered {row['offered_rps']:>8} req/s: {json.dumps(row)}")


def run_chaos(compiled, cfg, qmode: str, args) -> None:
    """Fault-injected serving mode (``--chaos-mtbf``): serve through the
    resilient facade under a seeded exponential fault schedule with
    epoch checkpoints every ``--epoch-steps`` decode steps, compare every
    completed request against a fault-free run, and print the recovery
    statistics.  The benchmark-grade sweep lives in
    ``benchmarks/bench_resilience.py``."""
    import tempfile

    from repro.resilience import FaultPlan, ResilienceConfig

    prompts = _prompts(cfg, args)

    def mk(faults, ckdir):
        return compiled.serve(
            max_batch=args.batch, new_tokens=args.new_tokens, qmode=qmode,
            resilience=ResilienceConfig(fault_plan=faults,
                                        checkpoint_dir=ckdir,
                                        epoch_steps=args.epoch_steps)).engine

    ckroot = args.checkpoint_dir or tempfile.mkdtemp(prefix="chaos_ckpt_")
    ref = {r.rid: r.value for r in mk(None, None).serve(prompts)}
    eng = mk(FaultPlan(args.chaos_mtbf, seed=args.chaos_seed), ckroot)
    t0 = time.perf_counter()
    res = eng.serve(prompts)
    wall = time.perf_counter() - t0
    identical = len(res) == len(prompts) and all(
        np.array_equal(r.value, ref.get(r.rid)) for r in res)
    s = eng.stats
    print(f"arch={cfg.name} chaos: mtbf={args.chaos_mtbf} steps "
          f"(seed {args.chaos_seed}), K={args.epoch_steps}, "
          f"requests={len(prompts)}")
    print(f"completed {len(res)}/{len(prompts)} in {wall:.2f}s, "
          f"bit-identical to fault-free: {identical}")
    print(f"power_losses={s['power_losses']} commits={s['commits']} "
          f"decode_steps={s['steps']} prefill_chunks={s['prefill_chunks']} "
          f"dead={s['dead_lettered']}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="phi3-mini-3.8b")
    # BooleanOptionalAction so --no-smoke can actually disable it
    # (store_true with default=True made the flag impossible to turn off)
    ap.add_argument("--smoke", action=argparse.BooleanOptionalAction,
                    default=True)
    ap.add_argument("--batch", type=int, default=4,
                    help="decode slots of the continuous engine")
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--quant", default=None, choices=list(PAPER_CONFIGS))
    ap.add_argument("--plan-cache", default=None, metavar="PATH",
                    help="compile-once execution plan (repro.core.plan): if "
                         "PATH.json exists, reload it — a restarted node "
                         "skips requantization and autotuning entirely (the "
                         "intermittency-resume fast path); otherwise compile "
                         "the plan (prequant + engine resolution) and save "
                         "it there")
    ap.add_argument("--autotune", action="store_true",
                    help="MEASURE candidate engines per GEMM shape on the "
                         "live backend instead of trusting the heuristic "
                         "cost model")
    ap.add_argument("--throughput", action="store_true",
                    help="request-level offered-load mode: serve "
                         "--requests independent prompts, one slot vs "
                         "--batch slots, plus an offered-rate sweep")
    ap.add_argument("--requests", type=int, default=32,
                    help="--throughput / --chaos-mtbf: number of requests")
    ap.add_argument("--chaos-mtbf", type=float, default=None, metavar="STEPS",
                    help="fault-injected serving: mean work steps between "
                         "faults (exponential schedule, repro.resilience); "
                         "serves through serve(resilience=...) and checks "
                         "outputs against a fault-free run")
    ap.add_argument("--chaos-seed", type=int, default=0,
                    help="--chaos-mtbf: fault schedule seed")
    ap.add_argument("--epoch-steps", type=int, default=4,
                    help="--chaos-mtbf: decode checkpoint period K (the "
                         "paper's NV write period P, in decode steps)")
    ap.add_argument("--checkpoint-dir", default=None, metavar="DIR",
                    help="--chaos-mtbf: decode epoch checkpoint directory "
                         "(default: a fresh temp dir)")
    args = ap.parse_args()
    from repro import api
    from repro.launch.jit_cache import enable_compile_cache

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    if args.quant:
        cfg = dataclasses.replace(cfg, quant=PAPER_CONFIGS[args.quant])
    qmode = "serve" if args.quant and args.quant != "w32a32" else "train"

    params, _ = T.init_lm(jax.random.PRNGKey(0), cfg, SINGLE)
    # a cached plan compiled under a different quant/arch is refused
    # (wrong bit widths would silently decode the stored integer levels
    # into garbage rather than erroring on shapes)
    compiled = api.build(cfg, params=params).compile(
        batch_hints=(args.batch,), prompt_len=args.prompt_len,
        autotune=args.autotune, cache=args.plan_cache)
    if compiled.reloaded:
        print(f"plan: reloaded {args.plan_cache} in "
              f"{compiled.compile_s * 1e3:.1f}ms (requantization "
              f"+ autotune skipped)")
    elif compiled.cache_path:
        print(f"plan: compiled{' +autotune' if args.autotune else ''} in "
              f"{compiled.compile_s * 1e3:.1f}ms -> {compiled.cache_path}")
    if args.chaos_mtbf is not None:
        run_chaos(compiled, cfg, qmode, args)
        return
    if args.throughput:
        run_throughput(compiled, cfg, qmode, args)
        return
    B, S_p, S_d = args.batch, args.prompt_len, args.new_tokens
    prompts = [np.asarray(p) for p in
               lm_batch(0, 0, batch=B, seq=S_p, vocab=cfg.vocab)["tokens"]]
    dep = compiled.serve(max_batch=B, new_tokens=S_d, qmode=qmode)
    t0 = time.perf_counter()
    gen = dep.predict(prompts)
    dt_cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    dep.predict(prompts)
    dt_warm = time.perf_counter() - t0
    print(f"arch={cfg.name} quant={args.quant or 'fp'} engine={qmode}")
    print(f"generated {B}x{S_d} tokens: cold {dt_cold:.2f}s "
          f"({B * S_d / dt_cold:.1f} tok/s incl. compile), "
          f"warm {dt_warm * 1e3:.1f}ms ({B * S_d / dt_warm:.1f} tok/s)")
    for b in range(min(B, 2)):
        print(f"  sample[{b}]: {list(map(int, gen[b][:12]))}")


if __name__ == "__main__":
    main()
