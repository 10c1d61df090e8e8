"""Serve-path benchmark: end-to-end CNN forward + transformer decode.

CNN e2e compares three dataflows (layer-level numbers live in
``bench_conv.py``):

  ``base``      frozen replica of the seed serve forward — float weights
                re-quantized by ``weight_levels`` every call, f32 im2col
                patches, hardwired ``engine="int8"`` GEMM, separate
                rowsum/epilogue pass;
  ``gemm``      PR-1 pipeline: pre-quantized (``core/prequant``) weights,
                integer ``im2col_sliced`` patches, dispatched qGEMM
                (patches still materialize in HBM);
  ``fused``     this PR's auto dispatch — deep-K spatial convs route to
                the implicit-GEMM engine (no patch bytes), the rest to the
                PR-1 engines.

Transformer decode compares the seed per-token Python loop (one jitted
step re-dispatched from the host, argmax synced per token) against the
``lax.scan`` generate in ``repro.launch.serve`` — cold (incl. compile) and
warm reported separately.

Emits the repo's ``name,us_per_call,derived`` CSV plus
``results/bench_serve.json``.  Run standalone::

    PYTHONPATH=src python benchmarks/bench_serve.py [--fast]

or via ``benchmarks/run.py`` (job name ``serve_fused``).
"""
from __future__ import annotations

import dataclasses
import json
import os
import time

import jax
import jax.numpy as jnp

from bench_conv import _conv_oh, _timeit, layer_shapes


# ---------------------------------------------------------------------------
# CNN end-to-end
# ---------------------------------------------------------------------------

def _seed_forward(params, x, spec, quant):
    """The seed serve dataflow, frozen as the benchmark baseline: per-call
    ``weight_levels`` + f32 ``conv_general_dilated_patches`` im2col +
    ``engine="int8"`` GEMM (``quant_conv2d``), with the same norm/pool
    structure as ``cnn_forward``."""
    from repro.core.conv_lowering import conv2d_float, quant_conv2d
    from repro.core.prequant import is_fp_layer
    from repro.models.cnn import _norm_act

    h = x
    for i, (p, s) in enumerate(zip(params, spec)):
        pad = "VALID" if (s.fc or s.k == 1) else "SAME"
        if s.fc and s.k > 1 and h.shape[1] != s.k:
            h = jax.image.resize(h, (h.shape[0], s.k, s.k, h.shape[3]),
                                 "linear")
        if is_fp_layer(s, quant):
            h = conv2d_float(h, p["w"], stride=s.stride, padding=pad)
        else:
            h = quant_conv2d(h, p["w"], stride=s.stride, padding=pad,
                             a_bits=quant.a_bits, w_bits=quant.w_bits,
                             engine="int8")
        h = h + p["b"]
        if i < len(spec) - 1:
            h = _norm_act(h, p["g"], p["beta"], quant, s.role)
        if s.pool:
            h = jax.lax.reduce_window(
                h, 0.0, jax.lax.add, (1, 2, 2, 1), (1, 2, 2, 1), "VALID") / 4.0
    return jnp.mean(h, axis=(1, 2))


def _arch_rows(name, spec, img: int, batch: int, quant, n: int):
    from repro.core.plan import compile_model
    from repro.core.prequant import is_fp_layer, level_dtype, serve_weight_bytes
    from repro.models.cnn import cnn_forward, init_cnn

    auto_quant = dataclasses.replace(quant, engine="auto")
    # the PR-1 engine pick with the conv-aware (implicit) dispatch masked:
    # f32dot is what select_engine returns off-TPU for every layer here
    gemm_quant = dataclasses.replace(quant, engine="f32dot")
    params, _ = init_cnn(jax.random.PRNGKey(0), spec)
    serve_params = compile_model(params, spec, auto_quant, img_hw=img,
                                 batch_hints=(batch,), model=name).params
    x = jax.random.uniform(jax.random.PRNGKey(1), (batch, img, img, 3))

    base_fwd = jax.jit(lambda x: _seed_forward(params, x, spec, quant))
    gemm_fwd = jax.jit(
        lambda x: cnn_forward(serve_params, x, spec, gemm_quant, "serve"))
    auto_fwd = jax.jit(
        lambda x: cnn_forward(serve_params, x, spec, auto_quant, "serve"))
    base_us = _timeit(base_fwd, x, n=n)
    gemm_us = _timeit(gemm_fwd, x, n=n)
    auto_us = _timeit(auto_fwd, x, n=n)

    lvl = jax.numpy.zeros((), level_dtype(quant.a_bits)).dtype.itemsize
    q_layers = [(s, h) for s, h in zip(spec, layer_shapes(spec, img))
                if not is_fp_layer(s, quant)]
    patch_elems = sum(batch * _conv_oh(s, h) ** 2 * s.k * s.k * s.cin
                      for s, h in q_layers)
    # patches that STILL materialize under auto dispatch: only layers the
    # dispatcher keeps on a GEMM engine contribute (implicit-routed layers
    # materialize zero patch bytes)
    from repro.kernels.ops import ConvShape, select_engine
    residual_patch_elems = sum(
        batch * _conv_oh(s, h) ** 2 * s.k * s.k * s.cin
        for s, h in q_layers
        if select_engine(
            batch * _conv_oh(s, h) ** 2, s.k * s.k * s.cin, s.cout,
            quant.a_bits, quant.w_bits,
            conv=ConvShape(h, h, s.k, s.k, s.stride,
                           "VALID" if (s.fc or s.k == 1) else "SAME",
                           batch=batch),
        ) != "implicit")
    return [dict(
        name=f"{name}_e2e", kind="e2e", batch=batch, img=img,
        quant=quant.tag(),
        base_us=round(base_us), gemm_us=round(gemm_us),
        fused_us=round(auto_us),
        speedup=round(base_us / auto_us, 2),
        speedup_vs_gemm=round(gemm_us / auto_us, 2),
        weight_bytes_fp32=serve_weight_bytes(params),
        weight_bytes_prequant=serve_weight_bytes(serve_params),
        # materialized patch traffic: f32 seed -> integer PR-1 -> residual
        # under auto dispatch (implicit-routed layers contribute zero)
        patch_bytes_f32=4 * patch_elems,
        patch_bytes_prequant=lvl * patch_elems,
        patch_bytes_auto_residual=lvl * residual_patch_elems,
        patch_byte_reduction=round(
            lvl * patch_elems / max(lvl * residual_patch_elems, 1), 1),
        hbm_passes_unfused=3, hbm_passes_fused=1)]


# ---------------------------------------------------------------------------
# Plan cache: cold compile+autotune vs warm plan-load (compile amortization)
# ---------------------------------------------------------------------------

def plan_rows(fast: bool = False):
    """Compile-once amortization row (ModelPlan, ``repro.core.plan``).

    ``cold`` = compile_model with measured autotune + first jitted
    dispatch; ``warm`` = load_plan from disk (requantization + autotune
    skipped — the restarted-node / intermittency-resume path) + first
    jitted dispatch in a fresh jit cache.  The plan JSON lands in
    ``results/plan_svhn_cnn.json`` so the trajectory captures both the
    artifact and the amortization, and the measured costs feed the paper's
    Fig.-7-style resume study (``pim/intermittent.plan_resume_study``).
    """
    import numpy as np

    from repro.core.plan import (compile_model, load_plan, plan_forward,
                                 save_plan)
    from repro.core.quant import W1A4
    from repro.kernels import ops
    from repro.models.cnn import init_cnn, svhn_cnn_spec
    from repro.pim.intermittent import plan_resume_study

    spec = svhn_cnn_spec(8 if fast else 20)
    batch, img = 4, 40
    params, _ = init_cnn(jax.random.PRNGKey(0), spec)
    x = jax.random.uniform(jax.random.PRNGKey(1), (batch, img, img, 3))
    os.makedirs("results", exist_ok=True)
    base = "results/plan_svhn_cnn"
    for ext in (".json", ".npz"):
        if os.path.exists(base + ext):
            os.remove(base + ext)
    ops.clear_plan_state()  # measure a genuinely cold compile

    t0 = time.perf_counter()
    plan = compile_model(params, spec, W1A4, batch_hints=(1, batch),
                         img_hw=img, autotune=True, model="svhn_cnn")
    compile_s = time.perf_counter() - t0
    save_plan(plan, base)
    t0 = time.perf_counter()
    cold_fwd = jax.jit(lambda v: plan_forward(plan, v))
    cold_out = np.asarray(cold_fwd(x))
    cold_dispatch_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    plan2 = load_plan(base)
    load_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    warm_fwd = jax.jit(lambda v: plan_forward(plan2, v))  # fresh jit cache
    warm_out = np.asarray(warm_fwd(x))
    warm_dispatch_s = time.perf_counter() - t0

    # resume study at an MTBF where replanning is *possible* but costly
    # (mtbf ~ 3x the compile cost), so both arms report a real efficiency
    study = plan_resume_study(compile_us=compile_s * 1e6,
                              plan_load_us=load_s * 1e6,
                              mtbf_us=3 * compile_s * 1e6,
                              frame_time_us=compile_s * 1e5)
    return [dict(
        name="plan_cache", kind="plan", batch=batch, img=img, quant="w1a4",
        plan_file=base + ".json", fingerprint=plan.fingerprint(),
        engines={lp.name: lp.engine for lp in plan.layers},
        compile_autotune_us=round(compile_s * 1e6),
        plan_load_us=round(load_s * 1e6),
        cold_e2e_us=round((compile_s + cold_dispatch_s) * 1e6),
        warm_e2e_us=round((load_s + warm_dispatch_s) * 1e6),
        amortization=round((compile_s + cold_dispatch_s)
                           / max(load_s + warm_dispatch_s, 1e-9), 1),
        reload_bit_identical=bool(np.array_equal(cold_out, warm_out)),
        resume_efficiency_recompile=round(study["recompile"]["efficiency"], 4),
        resume_efficiency_plan_reload=round(
            study["plan_reload"]["efficiency"], 4))]


# ---------------------------------------------------------------------------
# Transformer decode: python-loop (seed) vs lax.scan generate
# ---------------------------------------------------------------------------

def _loop_decode(params, cfg, plan, prompts, new_tokens: int, qmode: str,
                 prefill=None, step=None):
    """The seed decode: host loop re-dispatching one jitted step per token,
    with a device->host argmax sync in between.  Pass pre-built ``prefill``
    / ``step`` so the warm measurement reuses the jit cache (like a
    long-lived server would); the prefill is jitted the same way as the
    scan path's, so warm loop-vs-scan isolates the DECODE dispatch gap.
    The argmax uses the same real-vocab mask as the scan path (the row
    compares dispatch strategies; vocab policy must not differ)."""
    from repro.launch.serve import greedy_token, grow_cache, make_prefill
    from repro.models import transformer as T

    B, S_p = prompts.shape
    prefill = prefill or make_prefill(params, cfg, plan, qmode)
    step = step or jax.jit(
        lambda c, t, p: T.decode_step(params, c, t, p, cfg, plan,
                                      qmode=qmode))
    t0 = time.perf_counter()
    logits, cache = prefill(prompts)
    cache = grow_cache(cache, S_p, S_p + new_tokens)
    tok = greedy_token(logits, cfg.vocab)
    toks = [tok]
    for t in range(new_tokens - 1):
        lg, cache = step(cache, tok, S_p + t)
        tok = greedy_token(lg, cfg.vocab)
        toks.append(tok)
    gen = jnp.concatenate(toks, axis=1)
    jax.block_until_ready(gen)
    return gen, time.perf_counter() - t0, prefill, step


def decode_rows(fast: bool = False):
    from repro.configs import SINGLE, get_config
    from repro.core.quant import PAPER_CONFIGS
    from repro.data.synthetic import lm_batch
    from repro.launch.serve import make_generate, make_prefill, serve_once
    from repro.models import transformer as T

    cfg = dataclasses.replace(get_config("phi3-mini-3.8b").smoke(),
                              quant=PAPER_CONFIGS["w1a8"])
    qmode = "serve"
    B, S_p, S_d = 2, 8, 8 if fast else 16
    params, _ = T.init_lm(jax.random.PRNGKey(0), cfg, SINGLE)
    prompts = jnp.asarray(
        lm_batch(0, 0, batch=B, seq=S_p, vocab=cfg.vocab)["tokens"])

    loop_gen, loop_cold, pf, step = _loop_decode(params, cfg, SINGLE,
                                                 prompts, S_d, qmode)
    _, loop_warm, _, _ = _loop_decode(params, cfg, SINGLE, prompts, S_d,
                                      qmode, prefill=pf, step=step)

    prefill_fn = make_prefill(params, cfg, SINGLE, qmode)
    generate_fn = make_generate(params, cfg, SINGLE, qmode, S_p, S_d)
    scan_gen, scan_cold = serve_once(params, cfg, SINGLE, prompts, S_d,
                                     qmode, prefill_fn, generate_fn)
    _, scan_warm = serve_once(params, cfg, SINGLE, prompts, S_d, qmode,
                              prefill_fn, generate_fn)
    # the two paths are separately compiled float programs, so an argmax
    # near-tie can legitimately flip a token (ulp-level logit reordering);
    # report the comparison instead of asserting it so the --strict CI
    # gate cannot flake on it
    tokens_match = bool((jnp.asarray(scan_gen) == jnp.asarray(loop_gen)).all())
    return [dict(
        name="decode_scan", kind="decode", arch=cfg.name, batch=B,
        prompt_len=S_p, new_tokens=S_d, quant="w1a8",
        tokens_match_loop=tokens_match,
        loop_cold_us=round(loop_cold * 1e6),
        loop_warm_us=round(loop_warm * 1e6),
        scan_cold_us=round(scan_cold * 1e6),
        scan_warm_us=round(scan_warm * 1e6),
        tok_s_cold=round(B * S_d / scan_cold, 1),
        tok_s_warm=round(B * S_d / scan_warm, 1),
        warm_speedup=round(loop_warm / scan_warm, 2))]


# ---------------------------------------------------------------------------
# Request-level throughput: the serving engine under load (PR 3)
# ---------------------------------------------------------------------------



def throughput_rows(fast: bool = False):
    """Offered-load sweep through ``repro.launch.engine.ServeEngine``.

    Per workload (CNN serve forward, LM generate):
      * ``seq_rps``      closed-loop requests/s with ``max_batch=1`` — the
                         sequential per-request dispatch baseline;
      * ``batch8_rps``   closed-loop with ``max_batch=8`` (coalesced
                         dispatch; identical per-request outputs);
      * an offered-rate sweep at the batched setting, reporting achieved
        requests/s and p50/p99 latency (queueing included) per rate.
    """
    import numpy as np

    from repro.core.plan import compile_model
    from repro.core.quant import PAPER_CONFIGS, W1A4
    from repro.launch.engine import (CNNRunner, LMRunner, ServeEngine,
                                     run_offered_load)
    from repro.models import transformer as T
    from repro.models.cnn import init_cnn, svhn_cnn_spec

    n_req = 24 if fast else 48
    rows = []

    # CNN workload: 40x40 svhn images through the plan-compiled serve
    # forward (engines pinned per layer at compile time)
    spec = svhn_cnn_spec(8)
    params, _ = init_cnn(jax.random.PRNGKey(0), spec)
    cnn_plan = compile_model(params, spec, W1A4, img_hw=40,
                             batch_hints=(1, 8), model="svhn_throughput")
    imgs = [np.random.RandomState(i).uniform(size=(40, 40, 3))
            .astype(np.float32) for i in range(n_req)]

    # max_pending=16 keeps the queue bound real at over-subscribed rates:
    # the sweep's 2x/4x points actually hit QueueFull and go through
    # ServeEngine.submit_retry (bounded backoff) instead of a queue that
    # never fills at these request counts
    def cnn_engine(max_batch):
        return lambda: ServeEngine(CNNRunner(None, spec, None, plan=cnn_plan),
                                   max_batch=max_batch,
                                   flush_deadline_s=0.002, max_pending=16)

    # LM workload: prefill + scanned greedy decode per request, projection
    # engines resolved once into the plan's dense verdict table
    from repro.core.plan import compile_lm

    cfg = dataclasses.replace(get_smoke_lm(), quant=PAPER_CONFIGS["w1a8"])
    lparams, _ = T.init_lm(jax.random.PRNGKey(0), cfg, _single_plan())
    lm_plan = compile_lm(lparams, cfg, batch_hints=(1, 8), prompt_len=8)
    prompts = [np.random.RandomState(i).randint(0, cfg.vocab, size=(8,))
               .astype(np.int32) for i in range(n_req)]

    def lm_engine(max_batch):
        return lambda: ServeEngine(
            LMRunner(None, cfg, new_tokens=8, qmode="serve",
                     model_plan=lm_plan),
            max_batch=max_batch, flush_deadline_s=0.002, max_pending=16)

    from repro.launch.engine import warm_engine

    for name, payloads, mk in (("cnn_svhn", imgs, cnn_engine),
                               ("lm_decode", prompts, lm_engine)):
        seq = run_offered_load(warm_engine(mk(1)(), payloads), payloads,
                               rate_rps=None)
        bat_eng = warm_engine(mk(8)(), payloads)
        bat = run_offered_load(bat_eng, payloads, rate_rps=None)
        row = dict(name=f"throughput_{name}", kind="throughput",
                   n_requests=len(payloads),
                   seq_rps=seq["achieved_rps"], seq_p50_ms=seq["p50_ms"],
                   batch8_rps=bat["achieved_rps"],
                   batch8_p50_ms=bat["p50_ms"],
                   batch8_p99_ms=bat["p99_ms"],
                   mean_batch=bat["mean_batch"],
                   speedup_batch8=round(bat["achieved_rps"]
                                        / max(seq["achieved_rps"], 1e-9), 2))
        # offered-load sweep around the sequential capacity: under-, at-,
        # and over-subscribed (the engine's batching headroom shows up as
        # sustained rps above seq capacity with bounded p99).  One warmed
        # engine serves every rate — the jit cache is the server's.
        sweep = []
        for mult in ((0.5, 2.0) if fast else (0.5, 1.0, 2.0, 4.0)):
            sweep.append(run_offered_load(bat_eng, payloads,
                                          rate_rps=mult * seq["achieved_rps"]))
        row["offered_sweep"] = sweep
        rows.append(row)
    return rows


def continuous_rows(fast: bool = False):
    """Continuous batching vs bucket dispatch on a MIXED prompt/horizon mix.

    The bucket engine fragments a mixed-length workload into one closed
    bucket per (prompt-len, horizon) shape — short requests wait on long
    scans (head-of-line blocking) and ragged buckets pad.  The continuous
    engine admits at step granularity into a persistent paged-KV decode
    batch, so the headline comparison is p99 latency + achieved req/s on
    the same offered load.  Also gates (returned, asserted by the CI fast
    lane via ``--continuous``):

      * decode bit-identity: the batched continuous run's tokens equal a
        fresh continuous engine serving the same requests one at a time;
      * jit-program bounding: the whole replay compiles exactly three
        programs (prefill chunk, decode step, page reset);
      * PV108: the LM plan compiles with the paged geometry declared, so
        the prover has proven the page-table addressing feasible.
    """
    import numpy as np

    from repro.core.plan import compile_lm
    from repro.core.quant import PAPER_CONFIGS
    from repro.launch.engine import (ContinuousLMEngine, LMRunner,
                                     ServeEngine, run_offered_load,
                                     warm_engine)
    from repro.models import transformer as T

    cfg = dataclasses.replace(get_smoke_lm(), quant=PAPER_CONFIGS["w1a8"])
    params, _ = T.init_lm(jax.random.PRNGKey(0), cfg, _single_plan())
    num_slots, page_size, max_seq = 4, 4, 32
    kv_pages = max_seq // page_size
    num_pages = 32 if fast else 64
    n_req = 16 if fast else 64
    lens = (4, 8) if fast else (4, 8, 16)
    gens = (4, 8) if fast else (4, 8, 16)
    # PV108 coverage: the plan declares the paged geometry, so compile-time
    # verification (verify=True default) proves the page-table bounds
    lm_plan = compile_lm(params, cfg, batch_hints=(1, num_slots),
                         prompt_len=max(lens), page_size=page_size,
                         kv_pages=kv_pages)

    rng = np.random.RandomState(0)
    payloads = [
        (rng.randint(0, cfg.vocab,
                     size=(int(rng.choice(lens)),)).astype(np.int32),
         int(rng.choice(gens)))
        for _ in range(n_req)]

    def mk_cont():
        return ContinuousLMEngine(
            params, cfg, num_slots=num_slots, page_size=page_size,
            num_pages=num_pages, max_seq=max_seq, new_tokens=max(gens),
            qmode="serve", model_plan=lm_plan, max_pending=max(16, n_req))

    def mk_bucket():
        return ServeEngine(
            LMRunner(None, cfg, new_tokens=max(gens), qmode="serve",
                     model_plan=lm_plan),
            max_batch=num_slots, flush_deadline_s=0.002,
            max_pending=max(16, n_req))

    # -- restart arm: a FRESH server meets the mixed mix (empty jit cache).
    # The bucket engine compiles one scan program per (prompt-len, horizon,
    # padded-batch) combination it dispatches — the mix's combinatorics land
    # in its p99 — where the continuous engine compiles its three programs
    # and is done.  This is the bounded-jit-cache claim measured, and the
    # arm a power-intermittent node actually lives in.
    restart_b = run_offered_load(mk_bucket(), payloads, rate_rps=None)
    restart_c = run_offered_load(mk_cont(), payloads, rate_rps=None)

    # -- warm steady-state arm: every program either engine can dispatch is
    # pre-compiled.  warm_engine only covers the first payload's shape key;
    # a mixed mix dispatches every (key, padded-size) combination, and any
    # cold compile inside a measured run would be charged to the bucket arm
    bucket = warm_engine(mk_bucket(), payloads)
    by_key = {}
    for p in payloads:
        by_key.setdefault(bucket.runner.shape_key(p), p)
    for p in by_key.values():
        n_pad = 1
        while n_pad <= num_slots:
            bucket.serve([p] * n_pad)
            n_pad *= 2
    cont = warm_engine(mk_cont(), payloads)
    rb = run_offered_load(bucket, payloads, rate_rps=None)
    rc = run_offered_load(cont, payloads, rate_rps=None)

    # decode bit-identity: batched continuous == one-request-at-a-time
    # continuous (same chunk schedule, per-slot-independent numerics)
    seq_eng = mk_cont()
    seq_vals = []
    for p in payloads:
        seq_vals.extend(r.value for r in seq_eng.serve([p]))
    batch_res = mk_cont().serve(list(payloads))
    bit_identical = (len(batch_res) == len(seq_vals) and all(
        np.array_equal(r.value, v) for r, v in zip(batch_res, seq_vals)))

    # mixed offered-load sweep at the same rates through both engines —
    # the headline p99/req/s comparison
    sweep = []
    for mult in ((0.5, 2.0) if fast else (0.5, 1.0, 2.0, 4.0)):
        rate = mult * rb["achieved_rps"]
        sweep.append(dict(
            bucket=run_offered_load(bucket, payloads, rate_rps=rate),
            continuous=run_offered_load(cont, payloads, rate_rps=rate)))

    return [dict(
        name="continuous_lm", kind="continuous", n_requests=n_req,
        prompt_lens=list(lens), horizons=list(gens), slots=num_slots,
        page_size=page_size, num_pages=num_pages,
        # headline: the restart arm — req/s and p99 while the jit cache
        # fills.  The bucket engine's per-(shape, padded-size) compile
        # storm is its p99; the continuous engine's three programs are
        # done after the first requests
        restart_bucket_rps=restart_b["achieved_rps"],
        restart_bucket_p99_ms=restart_b["p99_ms"],
        restart_continuous_rps=restart_c["achieved_rps"],
        restart_continuous_p99_ms=restart_c["p99_ms"],
        restart_speedup_rps=round(restart_c["achieved_rps"]
                                  / max(restart_b["achieved_rps"], 1e-9), 2),
        restart_p99_improvement=round(restart_b["p99_ms"]
                                      / max(restart_c["p99_ms"], 1e-9), 2),
        # warm steady state.  At smoke scale on CPU the bucket engine's
        # fused whole-generation scan amortizes host dispatch across the
        # horizon while the continuous engine pays one host sync per
        # decode step, so the warm crossover needs per-step compute large
        # enough to swamp dispatch (accelerator-scale models); the
        # structural wins that survive every scale are the bounded jit
        # cache (restart arm) and paged KV occupancy (pool stats)
        warm_bucket_rps=rb["achieved_rps"], warm_bucket_p50_ms=rb["p50_ms"],
        warm_bucket_p99_ms=rb["p99_ms"],
        warm_continuous_rps=rc["achieved_rps"],
        warm_continuous_p50_ms=rc["p50_ms"],
        warm_continuous_p99_ms=rc["p99_ms"],
        warm_continuous_queue_p99_ms=rc["queue_p99_ms"],
        warm_continuous_service_p99_ms=rc["service_p99_ms"],
        bit_identical_vs_sequential=bool(bit_identical),
        jit_programs=sorted(str(p) for p in cont.program_shapes),
        n_jit_programs=len(cont.program_shapes),
        pool=cont.pool.stats(),
        plan_fingerprint=lm_plan.fingerprint(),
        offered_sweep=sweep)]


def get_smoke_lm():
    from repro.configs import all_configs

    return all_configs()["smollm-360m"].smoke(
        n_layers=2, d_model=64, n_heads=2, n_kv_heads=1, d_ff=128,
        vocab=64, head_dim=32)


def _single_plan():
    from repro.configs import SINGLE

    return SINGLE


def serve_rows(fast: bool = False):
    from repro.core.quant import W1A4, W1A8
    from repro.models.cnn import alexnet_spec, svhn_cnn_spec

    # e2e latencies are tens of ms; n=8 keeps scheduler noise out of the
    # speedup ratios (n=3 flipped signs run-to-run on a busy host)
    n = 2 if fast else 8
    rows = _arch_rows("svhn_cnn", svhn_cnn_spec(32 if fast else 64), 40,
                      2, W1A4, n)
    if not fast:
        rows += _arch_rows("alexnet", alexnet_spec(), 112, 1, W1A8, n)
    rows += plan_rows(fast=fast)
    rows += decode_rows(fast=fast)
    rows += throughput_rows(fast=fast)
    rows += continuous_rows(fast=fast)
    os.makedirs("results", exist_ok=True)
    with open("results/bench_serve.json", "w") as f:
        json.dump(rows, f, indent=1, default=str)
    return rows


def main():
    import sys

    from repro.launch.jit_cache import enable_compile_cache

    enable_compile_cache()
    fast = "--fast" in sys.argv
    if "--continuous" in sys.argv:
        # CI fast lane: only the continuous-vs-bucket comparison, with the
        # decode bit-identity gate as the exit code (a mismatch means the
        # paged path's numerics drifted from the sequential reference)
        rows = continuous_rows(fast=fast)
        os.makedirs("results", exist_ok=True)
        with open("results/bench_serve_continuous.json", "w") as f:
            json.dump(rows, f, indent=1, default=str)
        print("name,us_per_call,derived")
        for r in rows:
            extra = {k: v for k, v in r.items() if k not in ("name",)}
            print(f"{r['name']},{r['restart_speedup_rps']},{json.dumps(extra)}")
        print("# full rows -> results/bench_serve_continuous.json",
              file=sys.stderr)
        if not all(r["bit_identical_vs_sequential"] for r in rows):
            print("FAIL: continuous decode is not bit-identical to the "
                  "sequential reference", file=sys.stderr)
            sys.exit(1)
        return
    print("name,us_per_call,derived")
    for r in serve_rows(fast=fast):
        us = r.get("fused_us", r.get("scan_warm_us",
                                     r.get("warm_e2e_us",
                                           r.get("batch8_rps",
                                                 r.get("restart_speedup_rps")))))
        extra = {k: v for k, v in r.items() if k not in ("name",)}
        print(f"{r['name']},{us},{json.dumps(extra)}")
    print("# full rows -> results/bench_serve.json", file=sys.stderr)


if __name__ == "__main__":
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
    main()
