"""The paper's CNN models (§III-A):

* ``svhn_cnn`` — 6 conv + 2 average-pool + 2 FC layers (FC realized as
  1x1 convolutions, as the paper states), for 40x40 SVHN digits.
  First and last layers stay full precision (paper follows DoReFa/XNOR).
* ``alexnet`` — binary-weight AlexNet used for the ImageNet storage /
  energy rows (Fig. 8b, Table II).

Serve mode executes a compiled execution plan (``repro.core.plan``): the
per-layer engine choices, weight pre-quantization, and feasibility checks
all happen ONCE at plan-compile time, and ``cnn_forward(mode="serve")``
just walks the LayerPlan sequence — no per-call dispatch, no
float-vs-prequant branching in the forward.  Training mode keeps the
fake-quant STE conv.  The ``prepare_serve_params`` deprecation shim was
removed (PR 5): pre-quantize through :func:`repro.core.plan.compile_model`
/ ``repro.api.build(...).compile()`` (or, for tests that only need the
raw levels, :func:`repro.core.prequant.prequantize_cnn_params`).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Sequence

import jax
import jax.numpy as jnp

from repro.core.conv_lowering import conv2d_float
from repro.core.prequant import is_fp_layer
from repro.core.quant import (
    QuantConfig,
    quantize_activation,
    quantize_gradient,
    quantize_weight,
)


@dataclasses.dataclass(frozen=True)
class ConvSpec:
    cin: int
    cout: int
    k: int = 3
    stride: int = 1
    pool: bool = False   # 2x2 average pool after this layer
    role: str = "mid"    # first | mid | last
    fc: bool = False     # fully-connected: VALID conv reducing to 1x1


def svhn_cnn_spec(channels: int = 64) -> list[ConvSpec]:
    """6 conv + 2 pool + 2 FC(=1x1 conv) — the paper's SVHN model."""
    c = channels
    return [
        ConvSpec(3, c, 5, role="first"),
        ConvSpec(c, c, 3),
        ConvSpec(c, 2 * c, 3, pool=True),       # avg-pool #1
        ConvSpec(2 * c, 2 * c, 3),
        ConvSpec(2 * c, 4 * c, 3, pool=True),   # avg-pool #2
        ConvSpec(4 * c, 4 * c, 3),
        ConvSpec(4 * c, 8 * c, 1),              # FC-equivalent 1
        ConvSpec(8 * c, 10, 1, role="last"),    # FC-equivalent 2 (10 classes)
    ]


def alexnet_spec() -> list[ConvSpec]:
    """AlexNet conv/FC stack (FCs as convs) for the ImageNet rows."""
    return [
        ConvSpec(3, 96, 11, stride=4, pool=True, role="first"),
        ConvSpec(96, 256, 5, pool=True),
        ConvSpec(256, 384, 3),
        ConvSpec(384, 384, 3),
        ConvSpec(384, 256, 3, pool=True),
        ConvSpec(256, 4096, 6, fc=True),                 # FC6
        ConvSpec(4096, 4096, 1, fc=True),                # FC7
        ConvSpec(4096, 1000, 1, fc=True, role="last"),   # FC8
    ]


def init_cnn(key, spec: Sequence[ConvSpec], dtype=jnp.float32):
    params, axes = [], []
    keys = jax.random.split(key, len(spec))
    for k, s in zip(keys, spec):
        fan_in = s.k * s.k * s.cin
        w = jax.random.normal(k, (s.k, s.k, s.cin, s.cout), dtype) / math.sqrt(fan_in)
        b = jnp.zeros((s.cout,), dtype)
        g = jnp.ones((s.cout,), dtype)  # batch-norm-ish scale (folded form)
        beta = jnp.zeros((s.cout,), dtype)
        params.append(dict(w=w, b=b, g=g, beta=beta))
        axes.append(dict(w=(None, None, None, "mlp"), b=("mlp",), g=("mlp",),
                         beta=("mlp",)))
    return params, axes


def _norm_act(x, g, beta, quant: QuantConfig, role: str, mode: str = "train"):
    """Per-channel norm (BN inference form) + bounded activation.

    The bounded ReLU (clip to [0,1]) is exactly DoReFa's activation domain,
    so quantize_activation is the identity structure the paper assumes.

    Serve mode normalizes with PER-SAMPLE (spatial-only) statistics instead
    of batch statistics: a served request's output must not depend on which
    other requests the engine co-batched it with (request isolation), and
    per-sample stats make the whole serve forward batch-invariant — the
    bit-identity contract `launch/engine.py` batching relies on.  Training
    keeps cross-batch statistics (the usual BN regularizer).
    """
    stat_axes = (1, 2) if mode == "serve" else (0, 1, 2)
    mu = jnp.mean(x, axis=stat_axes, keepdims=True)
    var = jnp.var(x, axis=stat_axes, keepdims=True)
    x = (x - mu) * jax.lax.rsqrt(var + 1e-5) * g + beta
    x = jnp.clip(x, 0.0, 1.0)
    if role == "last" or quant.engine == "fp":
        return x
    return quantize_activation(x, quant.a_bits)


def global_avg_pool(h):
    """Spatial mean (B, H, W, C) -> (B, C) as a fixed pairwise tree of
    elementwise adds.

    A reduce op leaves its summation order to the compiler, which picks it
    per program: XLA:CPU sums the last layer's outputs in another order
    when the weights are jit constants than when they are arguments, and
    the logits then differ in the last bit.  Elementwise adds are never
    reassociated, so this order, and every logit, is the same in each
    program that serves the model (batched, per-request, or closed over
    its weights)."""
    b, hh, ww, c = h.shape
    f = h.reshape(b, hh * ww, c)
    while f.shape[1] > 1:
        half = f.shape[1] // 2
        s = f[:, :half] + f[:, half: 2 * half]
        f = jnp.concatenate([s, f[:, 2 * half:]], axis=1)
    return f[:, 0] / (hh * ww)


def cnn_forward(params, x, spec: Sequence[ConvSpec], quant: QuantConfig,
                mode: str = "train", g_key=None):
    """x (B,H,W,3) in [0,1]. Returns logits (B, n_classes).

    Serve mode compiles (or reuses — the structural pass is cached) an
    execution plan for this (spec, quant, shape, backend) and executes it:
    engine choices are made once per compiled program, not once per layer
    call.  Bit-identical to the pre-plan per-call dispatch — the plan's
    heuristic resolution IS that dispatch, hoisted to trace time.
    """
    if mode == "serve":
        from repro.core.plan import cnn_serve_layers, execute_cnn_layers

        layers = cnn_serve_layers(spec, quant, batch=x.shape[0],
                                  img_hw=(x.shape[1], x.shape[2]))
        return execute_cnn_layers(layers, params, x, quant)
    h = x
    for i, (p, s) in enumerate(zip(params, spec)):
        pad = "VALID" if (s.fc or s.k == 1) else "SAME"
        if s.fc and s.k > 1 and h.shape[1] != s.k:
            # FC over whatever spatial extent remains: pool/crop to k x k
            h = jax.image.resize(h, (h.shape[0], s.k, s.k, h.shape[3]), "linear")
        fp_layer = is_fp_layer(s, quant)
        if fp_layer:
            h = conv2d_float(h, p["w"], stride=s.stride, padding=pad)
        else:  # fake-quant STE training conv
            wq = quantize_weight(p["w"], quant.w_bits)
            hq = h  # already quantized by the previous _norm_act
            h = conv2d_float(hq, wq, stride=s.stride, padding=pad)
        if g_key is not None and not fp_layer:
            h = quantize_gradient(h, quant.g_bits,
                                  jax.random.fold_in(g_key, i))
        h = h + p["b"]
        if i < len(spec) - 1:
            h = _norm_act(h, p["g"], p["beta"], quant, s.role, mode)
        if s.pool:
            h = jax.lax.reduce_window(
                h, 0.0, jax.lax.add, (1, 2, 2, 1), (1, 2, 2, 1), "VALID") / 4.0
    return global_avg_pool(h)  # -> (B, classes)


def cnn_loss(params, batch, spec, quant: QuantConfig, g_key=None):
    logits = cnn_forward(params, batch["image"], spec, quant, "train", g_key)
    labels = batch["label"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    loss = -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))
    acc = jnp.mean(jnp.argmax(logits, -1) == labels)
    return loss, dict(loss=loss, acc=acc)


def count_params(spec: Sequence[ConvSpec]) -> int:
    return sum(s.k * s.k * s.cin * s.cout for s in spec)


def count_acts(spec: Sequence[ConvSpec], img: int) -> int:
    """Peak activation element count for the storage model (Fig. 8)."""
    h = img
    total = img * img * 3
    for s in spec:
        h = max(h // s.stride, 1)
        total += h * h * s.cout
        if s.pool:
            h //= 2
    return total


def count_macs(spec: Sequence[ConvSpec], img: int) -> int:
    """MAC count per image (the paper's '80 FLOPs' ~ 80 MFLOPs on 40x40)."""
    h = img
    total = 0
    for s in spec:
        if s.fc:
            oh = 1
        else:
            oh = max(-(-h // s.stride), 1)
        total += oh * oh * s.k * s.k * s.cin * s.cout
        h = oh
        if s.pool:
            h = max(h // 2, 1)
    return total
