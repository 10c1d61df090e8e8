"""Production serving driver: batched prefill + scanned decode with KV cache.

  PYTHONPATH=src python -m repro.launch.serve --arch phi3-mini-3.8b \
      --batch 4 --prompt-len 16 --new-tokens 16 [--quant w1a8] [--no-smoke]

Decode runs as ONE ``lax.scan``-compiled program over the token axis: a
single trace/dispatch for the whole generation, greedy argmax in-graph (no
host sync per token), and the KV cache donated into the step so XLA updates
it in place instead of copying the full cache every token.  The seed path
re-dispatched a jitted single-token step from Python ``S_d - 1`` times —
each step paid dispatch latency plus a device->host argmax round-trip.
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp

from repro.configs import SINGLE, get_config
from repro.core.quant import PAPER_CONFIGS
from repro.data.synthetic import lm_batch
from repro.models import transformer as T


# The only cache tensors with a sequence axis are the attention KV entries
# (k, v, pos), and their layout is fixed by transformer.init_cache:
# (layers, batch, slots, ...).  Identified by KEY, never by size: recurrent
# state (rec.h is (layers, batch, lru_width), rwkv.s is (layers, batch,
# heads, hd, hd), ...) has no sequence axis, and a width/head count that
# merely *equals* the prompt length must not be padded.
CACHE_SEQ_AXIS = {"k": 2, "v": 2, "pos": 2}


def grow_cache(cache, prompt_len: int, slots: int):
    """Grow a prefill cache to the decode horizon (position-preserving).

    Only attention-style entries (dicts carrying k/v/pos) are grown, along
    their structural sequence axis; every other state tensor passes through
    untouched regardless of any size coincidence with ``prompt_len``.
    New k/v slots are zero-filled and their ``pos`` is -1 (empty).

    This is the *contiguous* cache's growth path (bucket engine, single-
    shot CLI).  The continuous engine
    (``launch/engine.ContinuousLMEngine``) never grows or re-pads a cache:
    KV lives in fixed-size pages and a request's extent is a page-table
    row (``core/kv_pages``).
    """
    out = {}
    for kind, entry in cache.items():
        if not (isinstance(entry, dict) and "pos" in entry):
            out[kind] = entry  # recurrent state: no sequence axis
            continue
        widened = dict(entry)
        for key, axis in CACHE_SEQ_AXIS.items():
            if key not in entry:
                continue
            t = entry[key]
            grow = slots - t.shape[axis]
            if grow <= 0:
                continue
            pad = [(0, 0)] * t.ndim
            pad[axis] = (0, grow)
            widened[key] = jnp.pad(t, pad,
                                   constant_values=-1 if key == "pos" else 0)
        out[kind] = widened
    return out


def widen_cache(cache, prompt_len: int, slots: int):
    """Deprecated alias for :func:`grow_cache` (one-release shim).

    The name now distinguishes the contiguous growth path from the paged
    path, which neither grows nor re-pads.  Delegates unchanged; removal
    after one release.
    """
    import warnings
    warnings.warn(
        "widen_cache is deprecated; use grow_cache (contiguous caches) or "
        "the paged serve path (ContinuousLMEngine), which never re-pads",
        DeprecationWarning, stacklevel=2)
    return grow_cache(cache, prompt_len, slots)


def make_prefill(params, cfg, plan, qmode: str):
    """Jitted prefill: tokens (B, S_p) -> (logits, cache)."""
    return jax.jit(
        lambda toks: T.prefill(params, cfg, plan, tokens=toks, qmode=qmode))


def greedy_token(logits, vocab: int):
    """Greedy next token over the REAL vocab only: the padded unembed tail
    (rows added for TP divisibility, ``cfg.padded_vocab``) holds
    random-init weights and must never be served as an output token."""
    return jnp.argmax(logits[:, -1:, :vocab], -1).astype(jnp.int32)


def make_decode_step(params, cfg, plan, qmode: str):
    """The one greedy scan step shared by every decode realization (this
    CLI's generate and the serving engine's per-bucket program): one
    ``decode_step`` + real-vocab argmax, carry (cache, token, pos)."""
    def step(carry, _):
        cache, tok, pos = carry
        logits, cache = T.decode_step(params, cache, tok, pos, cfg, plan,
                                      qmode=qmode)
        tok = greedy_token(logits, cfg.vocab)
        return (cache, tok, pos + 1), tok

    return step


def make_generate(params, cfg, plan, qmode: str, prompt_len: int,
                  new_tokens: int):
    """One-trace greedy decode: (widened cache, first token) -> (B, S_d).

    The whole token loop is a ``lax.scan`` inside a single jit — one
    dispatch for the full generation — and ``donate_argnums=(0,)`` lets XLA
    reuse the (largest-buffer-in-the-request) KV cache in place.  The
    caller must not reuse the passed cache afterwards.
    """
    step = make_decode_step(params, cfg, plan, qmode)

    def gen(cache, first_tok):
        (_, _, _), toks = jax.lax.scan(
            step, (cache, first_tok, jnp.asarray(prompt_len, jnp.int32)),
            None, length=new_tokens - 1)
        # toks (S_d-1, B, 1) scan-major -> (B, S_d) with the prefill token
        return jnp.concatenate([first_tok, toks[:, :, 0].T], axis=1)

    # CPU can't donate (XLA copies anyway and warns); elsewhere the cache
    # buffers update in place across the whole scan
    donate = () if jax.default_backend() == "cpu" else (0,)
    return jax.jit(gen, donate_argnums=donate)


def serve_once(params, cfg, plan, prompts, new_tokens: int, qmode: str,
               prefill_fn=None, generate_fn=None):
    """One batched request: prefill -> grow -> scanned decode.

    Returns (tokens (B, S_d), wall seconds).  Pass pre-built ``prefill_fn``
    / ``generate_fn`` to measure warm (compile-free) latency.
    """
    B, S_p = prompts.shape
    prefill_fn = prefill_fn or make_prefill(params, cfg, plan, qmode)
    generate_fn = generate_fn or make_generate(params, cfg, plan, qmode,
                                               S_p, new_tokens)
    t0 = time.perf_counter()
    logits, cache = prefill_fn(prompts)
    cache = grow_cache(cache, S_p, S_p + new_tokens)
    first = greedy_token(logits, cfg.vocab)
    gen = generate_fn(cache, first)
    jax.block_until_ready(gen)
    return gen, time.perf_counter() - t0


def run_throughput(params, cfg, qmode: str, args, model_plan=None) -> None:
    """Offered-load throughput mode: drive the request-level engine
    (``repro.launch.engine``) with ``--requests`` independent prompts and
    report requests/s + p50/p99 latency for sequential (max_batch=1) vs
    batched dispatch, plus an offered-rate sweep.  Rows append to
    ``results/bench_serve.json``-style output on stdout."""
    import json

    import numpy as np

    from repro.launch.engine import (LMRunner, ServeEngine, run_offered_load,
                                     warm_engine)
    from repro.launch.mesh import make_serve_mesh

    mesh = make_serve_mesh()
    prompts = [np.random.RandomState(i)
               .randint(0, cfg.vocab, size=(args.prompt_len,))
               .astype(np.int32) for i in range(args.requests)]

    def mk(max_batch):
        return ServeEngine(
            LMRunner(params, cfg, new_tokens=args.new_tokens, qmode=qmode,
                     model_plan=model_plan),
            max_batch=max_batch, flush_deadline_s=args.flush_deadline_ms / 1e3,
            mesh=mesh)

    seq = run_offered_load(warm_engine(mk(1), prompts), prompts, None)
    eng = warm_engine(mk(args.batch), prompts)
    bat = run_offered_load(eng, prompts, None)
    n_dev = 1 if mesh is None else mesh.devices.size
    print(f"arch={cfg.name} devices={n_dev} requests={args.requests} "
          f"prompt_len={args.prompt_len} new_tokens={args.new_tokens}")
    print(f"sequential: {seq['achieved_rps']:.1f} req/s "
          f"p50={seq['p50_ms']}ms p99={seq['p99_ms']}ms")
    print(f"batch={args.batch}: {bat['achieved_rps']:.1f} req/s "
          f"p50={bat['p50_ms']}ms p99={bat['p99_ms']}ms "
          f"({bat['achieved_rps'] / max(seq['achieved_rps'], 1e-9):.2f}x)")
    for mult in (0.5, 1.0, 2.0, 4.0):
        row = run_offered_load(eng, prompts,
                               rate_rps=mult * seq["achieved_rps"])
        print(f"offered {row['offered_rps']:>8} req/s: {json.dumps(row)}")


def run_continuous(params, cfg, qmode: str, args, model_plan=None) -> None:
    """Continuous-batching mode (``--continuous``): drive the paged-KV
    step-granular engine with a mixed prompt/horizon request set and
    report req/s + the queue/service latency split against the bucket
    engine at the same capacity.  The benchmark-grade sweep lives in
    ``benchmarks/bench_serve.py --continuous``."""
    import json

    import numpy as np

    from repro.launch.engine import (ContinuousLMEngine, LMRunner,
                                     ServeEngine, run_offered_load,
                                     warm_engine)

    rng = np.random.RandomState(0)
    gens = (max(args.new_tokens // 2, 1), args.new_tokens,
            args.new_tokens * 2)
    payloads = [
        (rng.randint(0, cfg.vocab,
                     size=(int(rng.choice((args.prompt_len // 2 or 1,
                                           args.prompt_len),)),))
         .astype(np.int32), int(rng.choice(gens)))
        for _ in range(args.requests)]

    bucket = ServeEngine(
        LMRunner(params, cfg, new_tokens=args.new_tokens, qmode=qmode,
                 model_plan=model_plan),
        max_batch=args.batch,
        flush_deadline_s=args.flush_deadline_ms / 1e3)
    cont = ContinuousLMEngine(
        params, cfg, num_slots=args.slots, page_size=args.page_size,
        num_pages=args.pages, new_tokens=args.new_tokens,
        max_seq=args.prompt_len + 2 * args.new_tokens,
        qmode=qmode, model_plan=model_plan)
    rb = run_offered_load(warm_engine(bucket, payloads), payloads, None)
    rc = run_offered_load(warm_engine(cont, payloads), payloads, None)
    print(f"arch={cfg.name} requests={args.requests} mixed prompts/horizons "
          f"slots={args.slots} pages={args.pages}x{args.page_size}")
    print(f"bucket    : {json.dumps(rb)}")
    print(f"continuous: {json.dumps(rc)} "
          f"({rc['achieved_rps'] / max(rb['achieved_rps'], 1e-9):.2f}x)")
    print(f"programs={sorted(cont.program_shapes)} "
          f"pool={cont.pool.stats()}")


def run_chaos(params, cfg, qmode: str, args, model_plan=None) -> None:
    """Fault-injected serving mode (``--chaos-mtbf``): drive the resilient
    engine (``repro.resilience``) under a seeded exponential fault schedule
    with K-step decode epoch checkpoints, then verify every completed
    request against a fault-free run of the same engine configuration and
    print the recovery statistics.  The benchmark-grade sweep lives in
    ``benchmarks/bench_resilience.py``; this is the operational entry."""
    import tempfile

    import numpy as np

    from repro.resilience import (EpochLMRunner, FaultPlan,
                                  ResilientServeEngine)

    prompts = [np.random.RandomState(i)
               .randint(0, cfg.vocab, size=(args.prompt_len,))
               .astype(np.int32) for i in range(args.requests)]

    def mk(ckdir):
        runner = EpochLMRunner(params, cfg, new_tokens=args.new_tokens,
                               epoch_steps=args.epoch_steps, qmode=qmode,
                               model_plan=model_plan)
        return ResilientServeEngine(runner, checkpoint_dir=ckdir,
                                    max_batch=args.batch,
                                    flush_deadline_s=args.flush_deadline_ms
                                    / 1e3, max_retries=1000)

    ckroot = args.checkpoint_dir or tempfile.mkdtemp(prefix="chaos_ckpt_")
    ref = [r.value for r in mk(None).serve(list(prompts))]
    eng = mk(ckroot)
    eng.faults = FaultPlan(args.chaos_mtbf, seed=args.chaos_seed)
    t0 = time.perf_counter()
    res = eng.serve(list(prompts))
    wall = time.perf_counter() - t0
    identical = len(res) == len(ref) and all(
        np.array_equal(r.value, v) for r, v in zip(res, ref))
    s = eng.stats
    print(f"arch={cfg.name} chaos: mtbf={args.chaos_mtbf} steps "
          f"(seed {args.chaos_seed}), K={args.epoch_steps}, "
          f"requests={len(prompts)}")
    print(f"completed {len(res)}/{len(prompts)} in {wall:.2f}s, "
          f"bit-identical to fault-free: {identical}")
    print(f"faults={s['faults']} (power={s['power_losses']} "
          f"drop={s['device_drops']} slow={s['slow_dispatches']} "
          f"staging={s['staging_retries']}) retries={s['retries']} "
          f"dead={s['dead_lettered']}")
    print(f"prefills={s['prefills']} resumes={s['resumes']} "
          f"epochs={s['epochs']} commits={s['commits']} "
          f"executed_steps={s['executed_steps']} "
          f"useful_steps={s['useful_steps']} "
          f"wasted_steps={s['wasted_steps']:.2f}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="phi3-mini-3.8b")
    # BooleanOptionalAction so --no-smoke can actually disable it
    # (store_true with default=True made the flag impossible to turn off)
    ap.add_argument("--smoke", action=argparse.BooleanOptionalAction,
                    default=True)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--quant", default=None, choices=list(PAPER_CONFIGS))
    ap.add_argument("--prequant", action="store_true",
                    help="quantize projection weights to int8 levels once at "
                         "model load (deprecated: --plan-cache subsumes this "
                         "and also pins engines + persists to disk)")
    ap.add_argument("--plan-cache", default=None, metavar="PATH",
                    help="compile-once execution plan (repro.core.plan): if "
                         "PATH.json exists, reload it — a restarted node "
                         "skips requantization and autotuning entirely (the "
                         "intermittency-resume fast path); otherwise compile "
                         "the plan (prequant + engine resolution) and save "
                         "it there")
    ap.add_argument("--autotune", action="store_true",
                    help="with --plan-cache: MEASURE candidate engines per "
                         "GEMM shape on the live backend instead of trusting "
                         "the heuristic cost model")
    ap.add_argument("--throughput", action="store_true",
                    help="request-level offered-load mode: queue+bucket many "
                         "independent requests through launch/engine.py "
                         "(data-parallel across devices) instead of one "
                         "batched call")
    ap.add_argument("--continuous", action="store_true",
                    help="continuous-batching mode: step-granular admission "
                         "into a persistent decode batch over a paged KV "
                         "cache (launch/engine.ContinuousLMEngine), compared "
                         "against the bucket engine on a mixed-length mix")
    ap.add_argument("--slots", type=int, default=4,
                    help="--continuous: persistent decode batch width")
    ap.add_argument("--page-size", type=int, default=16,
                    help="--continuous: tokens per KV page")
    ap.add_argument("--pages", type=int, default=64,
                    help="--continuous: KV page pool size")
    ap.add_argument("--requests", type=int, default=32,
                    help="--throughput: number of independent requests")
    ap.add_argument("--flush-deadline-ms", type=float, default=2.0,
                    help="--throughput: max bucket queueing delay")
    ap.add_argument("--chaos-mtbf", type=float, default=None, metavar="STEPS",
                    help="fault-injected serving: mean decode steps between "
                         "faults (exponential schedule, repro.resilience); "
                         "runs the resilient engine and verifies outputs "
                         "against a fault-free run")
    ap.add_argument("--chaos-seed", type=int, default=0,
                    help="--chaos-mtbf: fault schedule seed")
    ap.add_argument("--epoch-steps", type=int, default=4,
                    help="--chaos-mtbf: decode checkpoint period K (the "
                         "paper's NV write period P, in decode steps)")
    ap.add_argument("--checkpoint-dir", default=None, metavar="DIR",
                    help="--chaos-mtbf: decode epoch checkpoint directory "
                         "(default: a fresh temp dir)")
    args = ap.parse_args()
    from repro.launch.jit_cache import enable_compile_cache

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    if args.quant:
        cfg = dataclasses.replace(cfg, quant=PAPER_CONFIGS[args.quant])
    qmode = "serve" if args.quant and args.quant != "w32a32" else "train"

    params, _ = T.init_lm(jax.random.PRNGKey(0), cfg, SINGLE)
    model_plan = None
    if args.plan_cache and qmode == "serve":
        # the Session facade (repro.api): compile-or-reload the ModelPlan.
        # A cached plan compiled under a different quant/arch is refused
        # (wrong bit widths would silently decode the stored integer
        # levels into garbage rather than erroring on shapes).
        from repro import api

        compiled = api.build(cfg, params=params).compile(
            batch_hints=(args.batch,), prompt_len=args.prompt_len,
            autotune=args.autotune, cache=args.plan_cache)
        model_plan = compiled.plan
        if compiled.reloaded:
            print(f"plan: reloaded {args.plan_cache} in "
                  f"{compiled.compile_s * 1e3:.1f}ms (requantization "
                  f"+ autotune skipped)")
        else:
            print(f"plan: compiled{' +autotune' if args.autotune else ''} in "
                  f"{compiled.compile_s * 1e3:.1f}ms -> {compiled.cache_path}")
        params = model_plan.params
        model_plan.install()  # dense GEMM dispatch becomes a table lookup
    elif args.prequant and qmode == "serve":
        from repro.models.layers import prequantize_params
        params = prequantize_params(params, cfg)
    if args.chaos_mtbf is not None:
        run_chaos(params, cfg, qmode, args, model_plan=model_plan)
        return
    if args.continuous:
        run_continuous(params, cfg, qmode, args, model_plan=model_plan)
        return
    if args.throughput:
        run_throughput(params, cfg, qmode, args, model_plan=model_plan)
        return
    B, S_p, S_d = args.batch, args.prompt_len, args.new_tokens
    prompts = jnp.asarray(
        lm_batch(0, 0, batch=B, seq=S_p, vocab=cfg.vocab)["tokens"])

    prefill_fn = make_prefill(params, cfg, SINGLE, qmode)
    generate_fn = make_generate(params, cfg, SINGLE, qmode, S_p, S_d)
    gen, dt_cold = serve_once(params, cfg, SINGLE, prompts, S_d, qmode,
                              prefill_fn, generate_fn)
    _, dt_warm = serve_once(params, cfg, SINGLE, prompts, S_d, qmode,
                            prefill_fn, generate_fn)
    print(f"arch={cfg.name} quant={args.quant or 'fp'} engine={qmode}"
          f"{' prequant' if args.prequant and qmode == 'serve' else ''}")
    print(f"generated {B}x{S_d} tokens: cold {dt_cold:.2f}s "
          f"({B * S_d / dt_cold:.1f} tok/s incl. compile), "
          f"warm {dt_warm * 1e3:.1f}ms ({B * S_d / dt_warm:.1f} tok/s)")
    for b in range(min(B, 2)):
        print(f"  sample[{b}]: {list(map(int, gen[b][:12]))}")


if __name__ == "__main__":
    main()
