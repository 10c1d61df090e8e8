"""Plain float32 reference of a quantized CNN (DoReFa/XNOR, paper §II).

Follows the configuration file alone and imports nothing of the system
under test.  Per layer, in order:

* an FC layer of kernel k over a larger map first resizes it to k x k
  (``jax.image.resize``, linear);
* a full-precision layer (first/last, ``first_last_fp``) is a float32
  convolution of its input;
* a quantized layer convolves the input's ``a_bits`` levels
  ``round(clip(x, 0, 1) * n) / n`` with the binarized weight
  ``sign(w) * mean|w|`` (one scale per layer);
* then the bias; every layer but the last normalizes each sample over
  its spatial axes (eps 1e-5), scales by ``g``, shifts by ``beta``,
  clips to [0, 1] and, unless it is the last, quantizes to ``a_bits``;
* a 2x2 average pool where the layer says so; the logits are the
  spatial mean of the last layer.

Products run at ``highest`` precision.  ``control=True`` rounds the
full-precision layers' inputs and weights through float8 (e4m3): the
step below the bfloat16 products the chip's default precision gives
them, which the comparison must refuse.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def layers(cfg: dict) -> list[dict]:
    return [dict(dict(stride=1, pool=False, role="mid", fc=False), **l)
            for l in cfg["layers"]]


def init_params(cfg: dict, key: int):
    """The benchmark's weights for this configuration, made on the device
    in one jitted call: per layer ``w`` (k, k, cin, cout) ~ N(0, 1/fan_in),
    ``b`` ~ N(0, 0.1), ``g`` ~ 0.3 + N(0, 0.05), ``beta`` ~ 0.5 + N(0, 0.05)
    (so the normalized activations spread over every level)."""
    ls = layers(cfg)

    @jax.jit
    def make(k):
        out = []
        for kk, l in zip(jax.random.split(k, len(ls)), ls):
            k1, k2, k3, k4 = jax.random.split(kk, 4)
            fan_in = l["k"] * l["k"] * l["cin"]
            c = l["cout"]
            out.append(dict(
                w=jax.random.normal(k1, (l["k"], l["k"], l["cin"], c))
                / np.sqrt(fan_in),
                b=0.1 * jax.random.normal(k2, (c,)),
                g=0.3 + 0.05 * jax.random.normal(k3, (c,)),
                beta=0.5 + 0.05 * jax.random.normal(k4, (c,))))
        return out

    return make(jax.random.PRNGKey(key))


def _fp8(x):
    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def _conv(x, w, stride: int, padding: str):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=jax.lax.Precision.HIGHEST)


@functools.partial(jax.jit, static_argnames=("cfg_key", "control"))
def _forward(params, x, cfg_key, control: bool):
    ls, w_bits, a_bits, first_last_fp = cfg_key
    if w_bits != 1:
        raise ValueError("reference binarizes weights (w_bits 1 only)")
    n = (1 << a_bits) - 1
    h = x.astype(jnp.float32)
    last = len(ls) - 1
    for i, (p, l) in enumerate(zip(params, ls)):
        l = dict(l)
        k = l["k"]
        if l["fc"] and k > 1 and h.shape[1] != k:
            h = jax.image.resize(h, (h.shape[0], k, k, h.shape[3]), "linear")
        pad = "VALID" if (l["fc"] or k == 1) else "SAME"
        if first_last_fp and l["role"] in ("first", "last"):
            hin, w = (_fp8(h), _fp8(p["w"])) if control else (h, p["w"])
            h = _conv(hin, w, l["stride"], pad)
        else:
            hq = jnp.round(jnp.clip(h, 0.0, 1.0) * n) / n
            alpha = jnp.mean(jnp.abs(p["w"]))
            wq = jnp.where(p["w"] >= 0, alpha, -alpha)
            h = _conv(hq, wq, l["stride"], pad)
        h = h + p["b"]
        if i < last:
            mu = jnp.mean(h, axis=(1, 2), keepdims=True)
            var = jnp.var(h, axis=(1, 2), keepdims=True)
            h = (h - mu) * jax.lax.rsqrt(var + 1e-5) * p["g"] + p["beta"]
            h = jnp.clip(h, 0.0, 1.0)
            if l["role"] != "last":
                h = jnp.round(h * n) / n
        if l["pool"]:
            h = jax.lax.reduce_window(h, 0.0, jax.lax.add, (1, 2, 2, 1),
                                      (1, 2, 2, 1), "VALID") / 4.0
    return jnp.mean(h, axis=(1, 2))


def forward(params, x, cfg: dict, control: bool = False) -> np.ndarray:
    """Reference logits (B, classes) for images x (B, H, W, 3) in [0, 1]."""
    q = cfg["quant"]
    key = (tuple(tuple(sorted(l.items())) for l in layers(cfg)),
           q["w_bits"], q["a_bits"], q["first_last_fp"])
    return np.asarray(_forward(params, jnp.asarray(x), key, control))
