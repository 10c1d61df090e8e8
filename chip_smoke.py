#!/usr/bin/env python3
"""Chip smoke test: the CNN and LM serve paths, end to end, on a TPU.

    python3 chip_smoke.py              # one chip: AlexNet-224 + smollm-360m
    python3 chip_smoke.py --chips 4    # four chips: data-parallel CNN serve

Drives the entry points a user calls (``repro.api`` build -> compile ->
serve, ``ContinuousLMEngine``) at the full width of each model, with
seeded random weights, and checks the serve paths' own contracts on the
chip:

* **CNN** — AlexNet at 224x224, w1a4, plan compiled for ``tpu`` (prover
  on).  Every quantized layer must run a Pallas TPU kernel (``fused`` or
  ``implicit``).  16 seeded images served at ``max_batch=8`` must equal,
  bit for bit, the same images served one request at a time and the raw
  jitted ``compiled.forward``.
* **LM** — smollm-360m at its published width (32 layers, d_model 960,
  15/5 heads, vocab 49152), w1a8, compiled plan, served by the
  continuous-batching engine (4 slots, 16-token pages).  6 seeded
  requests (prompts 64-256 tokens, 16-32 new tokens) must all complete
  with in-vocabulary tokens equal to serving each request alone, in
  exactly 3 compiled programs.
* **--chips 4** — only the data-parallel CNN path: ``ServeEngine`` over a
  4-device serve mesh (params replicated, batches sharded) against the
  same requests on one device, bit for bit.

Timings printed on the way are information, not metrics.  The last line
of stdout is ``{"ok": true, "device": {...}}``.  Without a TPU, or
without the ``repro`` sources beside this file, the script exits nonzero
and prints no result.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
SEED = 0


def fail(msg: str) -> None:
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def check(cond: bool, msg: str) -> None:
    if not cond:
        fail(msg)


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileStats:
    """Backend-compile seconds and persistent-cache hits, from JAX's own
    monitoring events (a warm cache shows as hits and fewer seconds)."""

    def __init__(self):
        import jax.monitoring as mon

        self.seconds, self.compiles, self.hits = 0.0, 0, 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.compiles += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def line(self) -> str:
        return (f"compile: {self.compiles} programs, {self.seconds:.1f}s "
                f"getting executables, {self.hits} persistent-cache hits")


def seeded_images(n: int, hw: int, seed: int) -> list:
    return [np.random.RandomState(seed + i).uniform(size=(hw, hw, 3))
            .astype(np.float32) for i in range(n)]


def compile_cnn(spec, quant, img_hw: int, target: str, name: str):
    """build -> compile (prover on) for batch hints 1 and 8."""
    import jax

    from repro import api
    from repro.models.cnn import init_cnn

    params, _ = init_cnn(jax.random.PRNGKey(SEED), spec)
    t0 = time.perf_counter()
    compiled = api.build(spec, quant, params=params, img_hw=img_hw,
                         name=name).compile(target=target,
                                            batch_hints=(1, 8))
    log(f"cnn {name}: plan compiled in {time.perf_counter() - t0:.2f}s "
        f"(backend {compiled.plan.backend}, prover on)")
    for lp in compiled.plan.layers:
        log(f"  {lp.name:>8} k={lp.kh}x{lp.kw}x{lp.cin}->{lp.cout} "
            f"engines={dict(lp.engines)}")
    return compiled


def quantized_engines(compiled) -> set:
    return {eng for lp in compiled.plan.layers if not lp.fp
            for _, eng in lp.engines}


def cnn_phase(compiled, imgs: list, max_batch: int = 8) -> None:
    """Batched serve == per-request serve == jitted compiled.forward."""
    import jax
    import jax.numpy as jnp

    dep = compiled.serve(max_batch=max_batch)
    t0 = time.perf_counter()
    batched = dep.predict(imgs)
    cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    again = dep.predict(imgs)
    warm = time.perf_counter() - t0
    log(f"cnn: {len(imgs)} images at max_batch={max_batch}: cold "
        f"{cold:.2f}s (compile included), warm {warm:.3f}s")
    single = compiled.serve(max_batch=1).predict(imgs)
    fwd = jax.jit(compiled.forward)
    raw = np.concatenate([
        np.asarray(fwd(jnp.asarray(np.stack(imgs[i:i + max_batch]))))
        for i in range(0, len(imgs), max_batch)])
    for i, (b, a, s) in enumerate(zip(batched, again, single)):
        check(b.shape == raw[i].shape and np.isfinite(b).all(),
              f"cnn image {i}: bad logits {b.shape}")
        for name, other in (("a second batched pass", a),
                            ("per-request serve", s),
                            ("jit(compiled.forward)", raw[i])):
            if not np.array_equal(b, other):
                fail(f"cnn image {i}: batched serve differs from {name} "
                     f"(max |diff| {np.max(np.abs(b - other)):.3g})")
    log(f"cnn: batched == per-request == jit(forward), bit for bit "
        f"({len(imgs)} images, logits {batched[0].shape})")


def lm_requests(vocab: int, n: int, seed: int) -> list:
    rng = np.random.RandomState(seed)
    out = []
    for _ in range(n):
        prompt = rng.randint(0, vocab, size=(rng.randint(64, 257),))
        out.append((prompt.astype(np.int32), int(rng.randint(16, 33))))
    return out


def lm_phase(cfg, target: str, requests: list, *, num_slots: int = 4,
             page_size: int = 16) -> None:
    """Continuous engine over a compiled plan: completion, vocabulary
    bounds, and per-request bit-identity to serving alone."""
    import jax

    from repro import api
    from repro.configs import SINGLE
    from repro.core.kv_pages import pages_needed
    from repro.launch.engine import ContinuousLMEngine
    from repro.models import transformer as T

    params, _ = T.init_lm(jax.random.PRNGKey(SEED), cfg, SINGLE)
    t0 = time.perf_counter()
    compiled = api.build(cfg, params=params).compile(
        target=target, batch_hints=(num_slots,), prompt_len=page_size)
    del params
    log(f"lm {cfg.name}: {cfg.n_layers} layers d_model={cfg.d_model} "
        f"heads={cfg.n_heads}/{cfg.n_kv_heads} vocab={cfg.vocab}; plan "
        f"compiled in {time.perf_counter() - t0:.2f}s (prover on)")
    max_seq = max(len(p) + n for p, n in requests)
    num_pages = sum(pages_needed(len(p) + n, page_size) for p, n in requests)
    engine = ContinuousLMEngine(
        None, cfg, num_slots=num_slots, page_size=page_size,
        num_pages=num_pages, max_seq=max_seq, model_plan=compiled.plan)
    t0 = time.perf_counter()
    res = engine.serve(list(requests))
    cold = time.perf_counter() - t0
    check(len(res) == len(requests) and not engine.dead_letters,
          f"lm: {len(res)}/{len(requests)} completed, dead letters "
          f"{engine.dead_letters}")
    n_tok = 0
    for r, (prompt, n) in zip(res, requests):
        toks = np.asarray(r.value)
        check(toks.shape == (n,), f"lm rid {r.rid}: {toks.shape} tokens, "
                                  f"wanted {n}")
        check(bool(((toks >= 0) & (toks < cfg.vocab)).all()),
              f"lm rid {r.rid}: token ids outside [0, {cfg.vocab})")
        n_tok += n
    log(f"lm: {len(res)} requests, {n_tok} tokens in {cold:.2f}s (compile "
        f"included), {engine.stats['steps']} decode steps, "
        f"{engine.stats['prefill_chunks']} prefill chunks")
    t0 = time.perf_counter()
    for r, req in zip(res, requests):
        alone = engine.serve([req])[0].value
        if not np.array_equal(np.asarray(r.value), np.asarray(alone)):
            fail(f"lm rid {r.rid}: continuous-batch tokens differ from "
                 f"serving it alone: {list(r.value)} vs {list(alone)}")
    log(f"lm: each request alone in {time.perf_counter() - t0:.2f}s; "
        f"tokens equal to the batched run")
    shapes = sorted(engine.program_shapes, key=str)
    log(f"lm: program_shapes={shapes}")
    check(len(shapes) == 3, f"lm: {len(shapes)} compiled programs, not 3")


def mesh_phase(compiled, imgs: list, max_batch: int = 8) -> None:
    """Data-parallel serve on every device == the same serve on one."""
    import jax

    from repro.distributed.sharding import batch_sharding
    from repro.launch.mesh import make_serve_mesh

    n_dev = len(jax.devices())
    mesh = make_serve_mesh()
    check(mesh is not None and mesh.devices.size == n_dev,
          f"serve mesh covers {None if mesh is None else mesh.devices.size}"
          f" of {n_dev} devices")
    dep = compiled.serve(max_batch=max_batch, mesh=mesh)
    t0 = time.perf_counter()
    sharded = dep.predict(imgs)
    log(f"mesh: {len(imgs)} images over {n_dev} devices in "
        f"{time.perf_counter() - t0:.2f}s (compile included)")
    for leaf in jax.tree.leaves(dep.engine._params):
        check(leaf.sharding.is_fully_replicated
              and len(leaf.sharding.device_set) == n_dev,
              f"param {leaf.shape} not replicated: {leaf.sharding}")
    x = jax.device_put(np.stack(imgs[:max_batch]), batch_sharding(mesh))
    fn = dep.engine._executable(dep.engine.runner.shape_key(imgs[0]),
                                max_batch)
    out = fn(dep.engine._params, x)
    per_dev = max_batch // n_dev
    for arr, what in ((x, "batch"), (out, "logits")):
        shards = arr.addressable_shards
        check(len({s.device for s in shards}) == n_dev
              and all(s.data.shape[0] == per_dev for s in shards),
              f"{what} not sharded {per_dev} rows per device: "
              f"{[s.data.shape for s in shards]}")
    log(f"mesh: params replicated on {n_dev} devices; batch and logits "
        f"sharded {per_dev} rows per device")
    single = compiled.serve(max_batch=max_batch).predict(imgs)
    for i, (a, b) in enumerate(zip(sharded, single)):
        if not np.array_equal(a, b):
            fail(f"mesh image {i}: {n_dev}-device serve differs from one "
                 f"device (max |diff| {np.max(np.abs(a - b)):.3g})")
    log(f"mesh: {n_dev}-device data-parallel serve == one device, bit for "
        f"bit ({len(imgs)} images)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the data-parallel CNN serve path")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        fail(f"no TPU found (JAX platform {dev.platform!r}); nothing run")
    if len(devices) < args.chips:
        fail(f"--chips {args.chips} but JAX sees {len(devices)} devices")
    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}")

    sys.path.insert(0, os.path.join(HERE, "src"))
    try:
        from repro.configs import get_config
        from repro.core.quant import PAPER_CONFIGS
        from repro.launch.jit_cache import enable_compile_cache
        from repro.models.cnn import alexnet_spec
    except ImportError as e:
        fail(f"repro sources not found beside chip_smoke.py ({e})")
    log(f"compile cache: {enable_compile_cache()}")
    stats = CompileStats()

    t_all = time.perf_counter()
    compiled = compile_cnn(alexnet_spec(), PAPER_CONFIGS["w1a4"], 224,
                           "tpu", "alexnet")
    engines = quantized_engines(compiled)
    check(engines <= {"fused", "implicit"} and compiled.plan.backend == "tpu",
          f"alexnet plan pins {sorted(engines)} on "
          f"{compiled.plan.backend}; expected Pallas TPU kernels only")
    imgs = seeded_images(16, 224, SEED)
    if args.chips == 4:
        mesh_phase(compiled, imgs)
    else:
        cnn_phase(compiled, imgs)
        del compiled
        cfg = dataclasses.replace(get_config("smollm-360m"),
                                  quant=PAPER_CONFIGS["w1a8"])
        lm_phase(cfg, "tpu", lm_requests(cfg.vocab, 6, SEED))
    log(stats.line())
    log(f"total {time.perf_counter() - t_all:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
