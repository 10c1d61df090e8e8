"""Pallas TPU kernel: MXU-mapped bit-GEMM (beyond-paper optimized path).

Insight (DESIGN.md §2): a {0,1} bit-plane dot product *is* an integer
matmul, so the MXU's 128x128 systolic adder tree subsumes the paper's 4:2
compressor tree; and because 2^(m+n) shifts distribute over the plane sum,
*all* plane pairs fold into one int8 matmul on the raw integer levels
(nibble-split when bits > 7, handled by the wrapper in ops.py).

Tiles are MXU-aligned (128 multiples); accumulation is int32 in the
revisited output block across the K grid axis.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

TM, TN, TK = 128, 128, 512  # 128x512 int8 A-tile (64KiB) + 512x128 B + 128x128 i32 acc


def _kernel(a_ref, b_ref, o_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += jax.lax.dot_general(
        a_ref[...],
        b_ref[...],
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )


def _pad(x, mult, axis):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    cfg = [(0, 0)] * x.ndim
    cfg[axis] = (0, pad)
    return jnp.pad(x, cfg)


@functools.partial(jax.jit, static_argnames=("interpret", "tm", "tn", "tk"))
def int8_matmul_pallas(
    a: jax.Array,  # (M, K) int8 — integer levels (or a nibble group)
    b: jax.Array,  # (K, N) int8
    *,
    interpret: bool = False,
    tm: int = TM,
    tn: int = TN,
    tk: int = TK,
) -> jax.Array:
    """(M,K) @ (K,N) -> (M,N) int32, MXU-tiled."""
    M, K = a.shape
    _, N = b.shape
    a_p = _pad(_pad(a, tm, 0), tk, 1)
    b_p = _pad(_pad(b, tk, 0), tn, 1)
    Mp, Kp = a_p.shape
    Np = b_p.shape[1]
    grid = (Mp // tm, Np // tn, Kp // tk)
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tm, tk), lambda i, j, k: (i, k)),
            pl.BlockSpec((tk, tn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((tm, tn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), jnp.int32),
        name="bitgemm_mxu",
        interpret=interpret,
    )(a_p, b_p)
    return out[:M, :N]
