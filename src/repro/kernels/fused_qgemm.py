"""Pallas TPU kernel: fused quantize -> bit-GEMM -> affine-dequant serve path.

The serve-side analogue of the paper's in-memory pass (DESIGN.md §2.3): the
SOT-MRAM engine keeps the weight bit-planes C_n(W) resident in the sub-array
and performs AND -> CMP -> shift-accumulate without the operands ever leaving
the array.  On TPU the same locality argument applies to VMEM: the unfused
serve path (``and_accum.quant_dense_forward``) round-trips the int32
activation levels and the EPU rowsum through HBM between three separate
passes (quantize, GEMM, epilogue).  This kernel does all of it in one
``pallas_call``:

  1. DoReFa activation quantization of the float tile (VPU), skipped when the
     caller already holds integer levels (``a_is_levels`` — the conv path
     quantizes once per *image*, before im2col);
  2. the int8 MXU matmul on the integer levels — all 2^(m+n) plane pairs
     folded, nibble-split in-register when a bit-width exceeds 7 (W1A8);
  3. the in-K-loop ``rowsum(A)`` accumulation (the paper's extra EPU popcount
     pass, here a VPU reduction riding the same VMEM residency);
  4. the affine-correction + dequant epilogue
     ``out = (s_a*s_w/2) * (2*acc - 2*z_w*rowsum)`` on the last K step
     (int32 correction, one f32 multiply: ``and_accum.dequant_epilogue``).

Weights arrive PRE-QUANTIZED as int8 levels (``core/prequant.py`` — the
checkpoint-resident C_n(W)); the float weights, the per-call
``weight_levels`` re-quantization, and two HBM round-trips (a_lv int32 +
the separate rowsum reduction) of the unfused path are all gone.

VMEM budget per grid step (defaults, DESIGN.md §2.3): 128x512 f32 A-tile
(256 KiB) + 512x128 int8 W-tile (64 KiB) + two 128x128 int32 scratches
(acc, rowsum; 128 KiB) + 128x128 f32 out (64 KiB) — ~0.5 MiB, leaving room
for double-buffered inputs well under the ~16 MiB VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.and_accum import _nibble_split, epilogue_scalars

TM, TN, TK = 128, 128, 512


def _kernel(s_ref, z_ref, a_ref, w_ref, o_ref, acc_ref, rs_ref, *,
            a_bits: int, w_bits: int, a_is_levels: bool, nk: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        rs_ref[...] = jnp.zeros_like(rs_ref)

    # (1) quantize: float tile -> DoReFa integer levels (identity if the
    # caller pre-quantized; zero-padding maps to level 0 either way)
    if a_is_levels:
        lv = a_ref[...].astype(jnp.int32)
    else:
        n = (1 << a_bits) - 1
        a = jnp.clip(a_ref[...], 0.0, 1.0)
        lv = jnp.clip(jnp.round(a * n), 0, n).astype(jnp.int32)

    # (3) in-K-loop rowsum(A) — the EPU pass fused into the same VMEM
    # residency; stored lane-broadcast so the epilogue subtract is shaped
    rs_ref[...] += jnp.sum(lv, axis=1, dtype=jnp.int32)[:, None]

    # (2) MXU matmul on the levels; in-register nibble split keeps every
    # operand < 2^7 so the systolic array runs int8 x int8 -> int32
    w = w_ref[...].astype(jnp.int32)
    acc = acc_ref[...]
    for ga, sa in _nibble_split(lv, a_bits):
        for gw, sw in _nibble_split(w, w_bits):
            d = jax.lax.dot_general(
                ga.astype(jnp.int8), gw.astype(jnp.int8),
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32,
            )
            acc = acc + (d << (sa + sw))
    acc_ref[...] = acc

    # (4) affine-correction + dequant epilogue, once per output tile —
    # and_accum.dequant_epilogue's exact int32 correction + one f32 multiply
    @pl.when(k == nk - 1)
    def _epilogue():
        e = 2 * acc_ref[...] - z_ref[0] * rs_ref[...]
        o_ref[...] = e.astype(jnp.float32) * s_ref[0]


def _pad(x, mult, axis):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    cfg = [(0, 0)] * x.ndim
    cfg[axis] = (0, pad)
    return jnp.pad(x, cfg)


@functools.partial(
    jax.jit,
    static_argnames=("a_bits", "w_bits", "a_is_levels", "interpret",
                     "tm", "tn", "tk"),
)
def fused_qgemm_pallas(
    a: jax.Array,      # (M, K) float acts in R  (or int levels, a_is_levels)
    w_lv: jax.Array,   # (K, N) int8/int32 pre-quantized weight levels
    s_w: jax.Array,    # weight scale   (w_q = s_w * (levels - z_w))
    z_w: jax.Array,    # weight zero point
    *,
    a_bits: int,
    w_bits: int,
    a_is_levels: bool = False,
    interpret: bool = False,
    tm: int = TM,
    tn: int = TN,
    tk: int = TK,
) -> jax.Array:
    """Fused quantize -> int8 GEMM -> rowsum -> dequant.  Returns f32 (M, N).

    Bit-exact (integer accumulator) w.r.t. ``and_accum.bitgemm_int8`` with
    the same f32 epilogue as ``quant_dense_forward``.
    """
    M, K = a.shape
    N = w_lv.shape[1]
    half, zero2 = epilogue_scalars(s_w, z_w, a_bits)
    a_p = _pad(_pad(a, tm, 0), tk, 1)
    w_p = _pad(_pad(w_lv, tk, 0), tn, 1)
    Mp, Kp = a_p.shape
    Np = w_p.shape[1]
    nk = Kp // tk
    grid = (Mp // tm, Np // tn, nk)
    out = pl.pallas_call(
        functools.partial(_kernel, a_bits=a_bits, w_bits=w_bits,
                          a_is_levels=a_is_levels, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((tm, tk), lambda i, j, k: (i, k)),
            pl.BlockSpec((tk, tn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((tm, tn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((tm, tn), jnp.int32),  # int32 accumulator
            pltpu.VMEM((tm, tn), jnp.int32),  # lane-broadcast rowsum(A)
        ],
        name="fused_qgemm",
        interpret=interpret,
    )(half.reshape(1), zero2.reshape(1), a_p, w_p)
    return out[:M, :N]
