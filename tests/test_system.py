"""End-to-end behaviour tests for the paper's system.

Headline properties: the bit-wise (AND-Accumulation) CNN *learns*; the LM
stack trains end-to-end through the distributed trainer (with compressed
gradients and checkpoint/resume); prefill+decode serving is consistent
with teacher forcing.
"""
import dataclasses
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import SINGLE, all_configs
from repro.core.quant import FP32, W1A4, QuantConfig
from repro.data.synthetic import lm_batch, svhn_like
from repro.models.cnn import cnn_loss, init_cnn, svhn_cnn_spec
from repro.train.optimizer import OptConfig, apply_updates, init_opt_state


def _train_cnn(quant: QuantConfig, steps: int = 60, seed: int = 0):
    spec = svhn_cnn_spec(8)
    params, _ = init_cnn(jax.random.PRNGKey(seed), spec)
    ocfg = OptConfig(kind="adamw", lr=3e-3, warmup_steps=10, total_steps=steps)
    ost = init_opt_state(params, ocfg)

    @jax.jit
    def step(params, ost, batch):
        (loss, m), g = jax.value_and_grad(
            lambda p: cnn_loss(p, batch, spec, quant), has_aux=True)(params)
        params, ost, _ = apply_updates(params, g, ost, ocfg)
        return params, ost, m

    losses = []
    for i in range(steps):
        x, y = svhn_like(32, seed=1000 + i)
        params, ost, m = step(params, ost,
                              dict(image=jnp.asarray(x), label=jnp.asarray(y)))
        losses.append(float(m["loss"]))
    x, y = svhn_like(256, seed=99)
    from repro.models.cnn import cnn_forward
    logits = cnn_forward(params, jnp.asarray(x), spec, quant, "train")
    acc = float(jnp.mean(jnp.argmax(logits, -1) == jnp.asarray(y)))
    return losses, acc


@pytest.mark.slow
def test_bitwise_cnn_learns_w1a4():
    losses, acc = _train_cnn(W1A4)
    assert losses[-1] < losses[0] * 0.8, "loss did not decrease"
    assert acc > 0.3, f"quantized CNN failed to beat chance: {acc}"


@pytest.mark.slow
def test_fp32_baseline_learns():
    losses, acc = _train_cnn(FP32)
    assert acc > 0.5


def test_lm_trainer_end_to_end(tmp_path):
    """Distributed Trainer: loss decreases, checkpoint/restore resumes."""
    from repro.launch.mesh import make_host_mesh
    from repro.train.trainer import TrainConfig, Trainer

    cfg = all_configs()["smollm-360m"].smoke(
        n_layers=2, d_model=64, n_heads=2, n_kv_heads=1, d_ff=128,
        vocab=64, head_dim=32)
    mesh = make_host_mesh()
    tr = Trainer(cfg, SINGLE, mesh, OptConfig(lr=3e-3, warmup_steps=5),
                 TrainConfig(steps=30, log_every=10, ckpt_every=10),
                 ckpt_dir=str(tmp_path))
    bf = lambda s, m: {k: jnp.asarray(v) for k, v in
                       lm_batch(s, m, batch=4, seq=16, vocab=64, seed=3).items()}
    hist = tr.run(bf, log=lambda *_: None)
    assert hist[-1]["loss"] < hist[0]["loss"]
    tr2 = Trainer(cfg, SINGLE, mesh, OptConfig(lr=3e-3, warmup_steps=5),
                  TrainConfig(steps=30), ckpt_dir=str(tmp_path))
    assert tr2.restore() and tr2.step == 30


def test_compressed_training_reduces_loss():
    """int8+EF compressed gradients still reduce the loss."""
    from repro.launch.mesh import make_host_mesh
    from repro.train.trainer import TrainConfig, Trainer

    cfg = all_configs()["smollm-360m"].smoke(
        n_layers=2, d_model=64, n_heads=2, n_kv_heads=1, d_ff=128,
        vocab=64, head_dim=32)
    mesh = make_host_mesh()
    tr = Trainer(cfg, SINGLE, mesh, OptConfig(lr=3e-3, warmup_steps=5),
                 TrainConfig(steps=25, log_every=24, compress_grads=True))
    bf = lambda s, m: {k: jnp.asarray(v) for k, v in
                       lm_batch(s, m, batch=4, seq=16, vocab=64, seed=4).items()}
    hist = tr.run(bf, log=lambda *_: None)
    assert hist[-1]["loss"] < hist[0]["loss"]


def test_serve_prefill_then_decode_consistency():
    """Prefill cache + decode continuation == teacher-forced forward."""
    from repro.models import transformer as T
    cfg = all_configs()["phi3-mini-3.8b"].smoke()
    key = jax.random.PRNGKey(0)
    params, _ = T.init_lm(key, cfg, SINGLE)
    B, S_p, S_d = 2, 8, 4
    toks = jax.random.randint(key, (B, S_p + S_d), 0, cfg.vocab)
    logits_p, cache = T.prefill(params, cfg, SINGLE, tokens=toks[:, :S_p])
    # pad the KV slot axis (2) to the decode horizon; new slots are empty
    cache = {kind: {k: jnp.pad(t, [(0, 0), (0, 0), (0, S_d)]
                                + [(0, 0)] * (t.ndim - 3),
                                constant_values=-1 if k == "pos" else 0)
                    for k, t in entry.items()}
             for kind, entry in cache.items()}
    outs = []
    for t in range(S_d):
        lg, cache = T.decode_step(params, cache, toks[:, S_p + t: S_p + t + 1],
                                  S_p + t, cfg, SINGLE)
        outs.append(lg[:, 0])
    dec = np.stack([np.asarray(o) for o in outs], axis=1)
    fwd, _, _ = T.forward(params, cfg, SINGLE, tokens=toks, mode="train")
    np.testing.assert_allclose(dec, np.asarray(fwd[:, S_p:]), atol=2e-2,
                               rtol=1e-2)


@pytest.mark.parametrize("mode,expect", [
    ([], "generated 2x3 tokens"),
    (["--chaos-mtbf", "12", "--epoch-steps", "2", "--requests", "4"],
     "bit-identical to fault-free: True"),
])
def test_serve_cli_serves_through_facade(monkeypatch, capsys, mode, expect):
    """``python -m repro.launch.serve`` compiles a plan and serves on the
    continuous engine, plain or under faults through the resilient
    facade."""
    from repro.launch import jit_cache, serve

    monkeypatch.setattr(jit_cache, "enable_compile_cache", lambda: None)
    monkeypatch.setattr(sys, "argv", [
        "serve", "--arch", "smollm-360m", "--quant", "w1a8", "--batch", "2",
        "--prompt-len", "8", "--new-tokens", "3", *mode])
    serve.main()
    assert expect in capsys.readouterr().out


def test_prequantized_serving_matches_runtime_quant():
    """Pre-quantized int8 weights == runtime quantization (serve path)."""
    from repro.core.quant import W1A8
    from repro.models import transformer as T
    from repro.models.layers import prequantize_params
    cfg = all_configs()["phi3-mini-3.8b"].smoke()
    cfg = dataclasses.replace(cfg, quant=W1A8)
    key = jax.random.PRNGKey(0)
    params, _ = T.init_lm(key, cfg, SINGLE)
    toks = jax.random.randint(key, (2, 8), 0, cfg.vocab)
    ref, _, _ = T.forward(params, cfg, SINGLE, tokens=toks, mode="train",
                          qmode="serve")
    pq = prequantize_params(params, cfg)
    out, _, _ = T.forward(pq, cfg, SINGLE, tokens=toks, mode="train",
                          qmode="serve")
    # per-layer scales (prequant) vs whole-stack scales (runtime): small drift
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2.0,
                               rtol=0.5)
    assert pq["blocks"]["attn"]["attn"]["wq"]["q"].dtype == jnp.int8


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_compile_cache_dir(monkeypatch):
    """Entry points keep JAX's persistent cache at JAX_COMPILATION_CACHE_DIR
    when set (and then change nothing), else at the fixed in-checkout
    directory."""
    from repro.launch import jit_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "cache-from-env")
    assert jit_cache.enable_compile_cache() == "cache-from-env"
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        got = jit_cache.enable_compile_cache()
        assert got == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_chip_smoke_refuses_cpu():
    """Without a TPU the chip smoke test fails and prints no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "no TPU found" in p.stderr
    assert '"ok"' not in p.stdout
