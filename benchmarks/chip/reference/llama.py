"""Plain float32 reference of a quantized dense decoder LM (Llama layout).

Follows the configuration file alone and imports nothing of the system
under test.  Pre-norm blocks, each:

    x  = rmsnorm(h) * ln1                      (eps from the config)
    q, k, v = Q(x) @ B(wq), Q(x) @ B(wk), Q(x) @ B(wv)
    q, k = rope(q), rope(k)                    (half-split, rope_theta)
    o  = softmax(q k^T / sqrt(hd) + causal) v  (GQA: q head j reads kv
                                                head j // (H / Hkv))
    h += Q(o) @ B(wo)
    x  = rmsnorm(h) * ln2
    h += Q(silu(Q(x) @ B(w_gate)) * (Q(x) @ B(w_in))) @ B(w_out)

then ``rmsnorm(h) * final_norm`` against the tied embedding for logits.
``B(w) = sign(w) * mean|w|`` per layer and matrix (1-bit weights); ``Q``
quantizes each row to ``a_bits`` signed affine levels,
``s = max|row| / 2^(a-1)``, ``(clip(round(x/s) + z, 0, 2^a - 1) - z) * s``
with ``z = 2^(a-1)``.  Attention runs on float32 q/k/v.  Every product
is at ``highest`` precision.

The control is this reference at ``a_bits - 4`` (int4 levels where the
configuration states int8): the step a later change would be tempted by.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HP = jax.lax.Precision.HIGHEST


def dims(m: dict) -> dict:
    return dict(L=m["num_hidden_layers"], d=m["hidden_size"],
                H=m["num_attention_heads"], Hkv=m["num_key_value_heads"],
                hd=m["head_dim"], ff=m["intermediate_size"],
                V=m["vocab_size"], theta=float(m["rope_theta"]),
                eps=float(m["rms_norm_eps"]))


def init_params(cfg: dict, key: int) -> dict:
    """The benchmark's weights, made on the device in one jitted call:
    embedding ~ N(0, 0.02^2), projections ~ N(0, 1/fan_in), norms 1
    (the program's own initialization convention).  Layers stacked on a
    leading axis."""
    D = dims(cfg)
    L, d, H, Hkv, hd, ff, V = (D[k] for k in ("L", "d", "H", "Hkv", "hd",
                                              "ff", "V"))
    shapes = dict(wq=(d, H * hd), wk=(d, Hkv * hd), wv=(d, Hkv * hd),
                  wo=(H * hd, d), w_in=(d, ff), w_gate=(d, ff),
                  w_out=(ff, d))

    @jax.jit
    def make(k):
        ks = jax.random.split(k, len(shapes) + 1)
        p = dict(embed=0.02 * jax.random.normal(ks[0], (V, d)),
                 final_norm=jnp.ones((d,)),
                 ln1=jnp.ones((L, d)), ln2=jnp.ones((L, d)))
        for kk, (name, (i, o)) in zip(ks[1:], sorted(shapes.items())):
            p[name] = jax.random.normal(kk, (L, i, o)) / math.sqrt(i)
        return p

    return make(jax.random.PRNGKey(key))


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _q_rows(x, bits: int):
    z = float(1 << (bits - 1))
    n = float((1 << bits) - 1)
    s = jnp.max(jnp.abs(x), -1, keepdims=True) / z + 1e-12
    return (jnp.clip(jnp.round(x / s) + z, 0.0, n) - z) * s


def _bin(w):
    a = jnp.mean(jnp.abs(w))
    return jnp.where(w >= 0, a, -a)


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    f = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos[:, None].astype(jnp.float32) * f          # (S, half)
    c, s = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


@functools.partial(jax.jit, static_argnames=("D", "bits"))
def _hidden(p, tokens, D, bits: int):
    D = dict(D)
    S = tokens.shape[0]
    H, Hkv, hd, eps = D["H"], D["Hkv"], D["hd"], D["eps"]
    pos = jnp.arange(S)
    causal = pos[None, :] <= pos[:, None]
    kv_of = jnp.arange(H) // (H // Hkv)

    def mm(x, w):
        return jnp.dot(_q_rows(x, bits), _bin(w), precision=HP)

    def layer(h, w):
        x = _rms(h, w["ln1"], eps)
        q = mm(x, w["wq"]).reshape(S, H, hd)
        k = mm(x, w["wk"]).reshape(S, Hkv, hd)
        v = mm(x, w["wv"]).reshape(S, Hkv, hd)
        q, k = _rope(q, pos, D["theta"]), _rope(k, pos, D["theta"])
        k, v = k[:, kv_of], v[:, kv_of]
        s = jnp.einsum("qhd,khd->hqk", q, k, precision=HP) / math.sqrt(hd)
        s = jnp.where(causal[None], s, -jnp.inf)
        o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v,
                       precision=HP).reshape(S, H * hd)
        h = h + mm(o, w["wo"])
        x = _rms(h, w["ln2"], eps)
        a = jax.nn.silu(mm(x, w["w_gate"])) * mm(x, w["w_in"])
        return h + mm(a, w["w_out"]), None

    keys = ("ln1", "ln2", "wq", "wk", "wv", "wo", "w_in", "w_gate", "w_out")
    h, _ = jax.lax.scan(layer, p["embed"][tokens], {k: p[k] for k in keys})
    return _rms(h, p["final_norm"], eps)


@jax.jit
def _gaps(p, h_ref, h_ctl, target, mask):
    """Per position: the reference's best logit minus its logit at the
    served token, and at the token the control puts first."""
    ref = jnp.dot(h_ref, p["embed"].T, precision=HP)
    best = jnp.max(ref, -1)
    at = jnp.take_along_axis(ref, target[:, None], -1)[:, 0]
    ctl_tok = jnp.argmax(jnp.dot(h_ctl, p["embed"].T, precision=HP), -1)
    at_ctl = jnp.take_along_axis(ref, ctl_tok[:, None], -1)[:, 0]
    return (jnp.where(mask, best - at, 0.0),
            jnp.where(mask, best - at_ctl, 0.0))


def token_gaps(p, cfg: dict, prompt, served, pad_to: int,
               control: bool = False):
    """Widest gap by which a served token's reference logit lies below
    the reference's best, over one request (and the control's, or None).
    The sequence is padded to ``pad_to`` so one program serves them all;
    causal attention keeps the padding out of every compared position."""
    D = dims(cfg)
    bits = int(cfg["quant"]["a_bits"])
    seq = np.concatenate([np.asarray(prompt), np.asarray(served)])
    n_p, n_s = len(prompt), len(served)
    tokens = np.zeros((pad_to,), np.int32)
    tokens[: len(seq)] = seq
    target = np.zeros((pad_to,), np.int32)
    mask = np.zeros((pad_to,), bool)
    # position i predicts token i + 1: served token j sits at n_p + j
    target[n_p - 1: n_p - 1 + n_s] = served
    mask[n_p - 1: n_p - 1 + n_s] = True
    key = tuple(sorted(D.items()))
    h_ref = _hidden(p, jnp.asarray(tokens), key, bits)
    h_ctl = _hidden(p, jnp.asarray(tokens), key, bits - 4) if control else h_ref
    g, gc = _gaps(p, h_ref, h_ctl, jnp.asarray(target), jnp.asarray(mask))
    return float(jnp.max(g)), (float(jnp.max(gc)) if control else None)
