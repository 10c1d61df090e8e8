"""Pallas TPU kernel: fused DoReFa activation quantize + bit-plane pack.

Fuses the EPU Quantizer (paper Fig. 2) with the data-organization step of
Fig. 3: one HBM read of the float activations produces both the integer
levels (for the MXU path) and the packed uint32 bit-planes (for the
faithful AND+popcount path), so the bit-plane layout never round-trips
through HBM unpacked (a 32x traffic saving over quantize-then-pack).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANE = 32
TM, TK = 256, 512  # 256x512 f32 in-tile = 512 KiB VMEM; TK % 32 == 0


def _kernel(a_ref, lv_ref, pk_ref, *, bits: int):
    n = (1 << bits) - 1
    a = jnp.clip(a_ref[...], 0.0, 1.0)
    lv = jnp.clip(jnp.round(a * n), 0, n).astype(jnp.int32)
    lv_ref[...] = lv
    tm, tk = lv.shape
    lanes = lv.reshape(tm, tk // LANE, LANE).astype(jnp.uint32)
    weights = (jnp.uint32(1) << jnp.arange(LANE, dtype=jnp.uint32))[None, None, :]
    for b in range(bits):
        plane = jax.lax.shift_right_logical(lanes, jnp.uint32(b)) & jnp.uint32(1)
        pk_ref[b] = jnp.sum(plane * weights, axis=-1, dtype=jnp.uint32)


def _pad(x, mult, axis):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    cfg = [(0, 0)] * x.ndim
    cfg[axis] = (0, pad)
    return jnp.pad(x, cfg)


@functools.partial(jax.jit, static_argnames=("bits", "interpret", "tm", "tk"))
def quantize_pack_pallas(
    a: jax.Array,  # (M, K) float
    *,
    bits: int,
    interpret: bool = False,
    tm: int = TM,
    tk: int = TK,
):
    """Returns (levels (M,K) int32, packed (bits, M, ceil(K/32)) uint32)."""
    M, K = a.shape
    a_p = _pad(_pad(a, tm, 0), tk, 1)
    Mp, Kp = a_p.shape
    grid = (Mp // tm, Kp // tk)
    levels, packed = pl.pallas_call(
        functools.partial(_kernel, bits=bits),
        grid=grid,
        in_specs=[pl.BlockSpec((tm, tk), lambda i, j: (i, j))],
        out_specs=[
            pl.BlockSpec((tm, tk), lambda i, j: (i, j)),
            pl.BlockSpec((bits, tm, tk // LANE), lambda i, j: (0, i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Mp, Kp), jnp.int32),
            jax.ShapeDtypeStruct((bits, Mp, Kp // LANE), jnp.uint32),
        ],
        name="quantpack",
        interpret=interpret,
    )(a_p)
    kw = -(-K // LANE)
    return levels[:M, :K], packed[:, :M, :kw]
