"""Implicit-GEMM quantized conv: the AND-Accumulation conv without im2col.

The im2col lowering (``core/conv_lowering``) materializes patches of shape
(B*OH*OW, kh*kw*Cin) in HBM before the GEMM runs — every input pixel is
written kh*kw times (9x for 3x3), exactly the inter-array data movement the
paper's sub-array kernel mapping (§II-A) avoids: the SOT-MRAM engine sweeps
the kernel over rows *in place*, reading each input row once.  This kernel
is the TPU realization of that dataflow:

  * grid = (batch, output-row tiles, Cout tiles); the integer activation
    levels for one image load into VMEM once per batch index (the index map
    depends only on ``b``, so Pallas's pipelined double-buffering keeps the
    tile resident across every output-row/Cout step — patches never exist
    in HBM);
  * patch extraction happens *in register*: for each (dy, dx) kernel tap
    the halo'd row span is sliced and de-strided (a reshape, no strided
    memory op) into the (TOH*OW, Cin) operand of one MXU dot against the
    matching Cin-row slab of the pre-quantized weight levels — the same
    dy/dx sweep ``im2col_sliced`` performs, minus the concatenate/HBM
    round-trip;
  * the PR-1 fused chain rides along unchanged: nibble-split int8 MXU dots
    (operands < 2^7), the in-loop ``rowsum(A)`` EPU pass, and the affine
    dequant epilogue ``out = s/2 * (2*acc - 2*z_w*rowsum)`` — all inside the same
    ``pallas_call``, one HBM pass over activations.

``conv_implicit_xla`` is the off-TPU realization of the same contract: the
level GEMM *is* an integer convolution, so ``lax.conv_general_dilated`` on
the f32-cast levels (exact under the fp32-mantissa bound, nibble-split when
not) computes the accumulator with zero materialized patch bytes — the
CPU/GPU counterpart of the in-place kernel sweep.

Both realizations are bit-identical to ``im2col_sliced`` + the fused qGEMM:
quantization is elementwise so it commutes with patch extraction, zero
padding maps to level 0 (contributing 0 to both the accumulator and the
rowsum), and the integer contraction is order-invariant.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.and_accum import (_nibble_split, epilogue_scalars,
                                  f32dot_exact)
from repro.core.conv_lowering import _out_hw, pad_split

TOH, TCOUT = 8, 128


def _group_max(bits: int) -> int:
    """Largest level in a ``_nibble_split`` group: unsplit up to 7 bits,
    4-bit nibbles beyond."""
    return (1 << (bits if bits <= 7 else 4)) - 1


def implicit_xla_exact(k: int, a_bits: int, w_bits: int) -> bool:
    """Can :func:`conv_implicit_xla` run exactly for this K?  Every
    group-pair f32 conv must fit the mantissa (``_nibble_split`` only
    splits past 7 bits, so 5-7 bit operands stay whole).  The dispatcher
    must not select the off-TPU implicit engine when this is False."""
    return _group_max(a_bits) * _group_max(w_bits) * max(k, 1) < (1 << 24)


def _kernel(s_ref, z_ref, x_ref, w_ref, o_ref, *, kh: int, kw: int, cin: int,
            stride: int, ow: int, toh: int, a_bits: int, w_bits: int):
    t = pl.program_id(1)
    # halo'd row span for this output-row tile: toh*stride + (kh-1) rows,
    # de-strided below by reshape (no strided memory access)
    span = toh * stride + kh - 1
    # widened to int32 on load: Mosaic refuses the patch reshapes below on
    # packed int8 vectors unless the widths happen to align to its tiling
    # (e.g. a 27-wide 5x5 AlexNet layer, any stride-2 de-stride)
    xt = x_ref[0, pl.ds(t * toh * stride, span)].astype(jnp.int32)

    tn = o_ref.shape[-1]
    acc = jnp.zeros((toh * ow, tn), jnp.int32)
    rs = jnp.zeros((toh * ow, 1), jnp.int32)
    for dy in range(kh):
        for dx in range(kw):
            rows = xt[dy: dy + toh * stride]            # (toh*stride, Wp, C)
            rows = rows.reshape(toh, stride, -1, cin)[:, 0]
            cols = rows[:, dx: dx + ow * stride]
            patch = cols.reshape(toh, ow, stride, cin)[:, :, 0]
            p = patch.reshape(toh * ow, cin)
            # in-K rowsum(A) — the paper's extra EPU popcount pass, fused
            rs = rs + jnp.sum(p, axis=1, dtype=jnp.int32)[:, None]
            wk = w_ref[(dy * kw + dx) * cin: (dy * kw + dx + 1) * cin, :]
            wk = wk.astype(jnp.int32)
            for ga, sa in _nibble_split(p, a_bits):
                for gw, sw in _nibble_split(wk, w_bits):
                    d = jax.lax.dot_general(
                        ga.astype(jnp.int8), gw.astype(jnp.int8),
                        dimension_numbers=(((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.int32,
                    )
                    acc = acc + (d << (sa + sw))
    out = (2 * acc - z_ref[0] * rs).astype(jnp.float32) * s_ref[0]
    o_ref[...] = out.reshape(1, toh, ow, tn)


@functools.partial(
    jax.jit,
    static_argnames=("kh", "kw", "stride", "padding", "a_bits", "w_bits",
                     "interpret", "toh", "tcout"),
)
def conv_implicit_pallas(
    x_lv: jax.Array,   # (B, H, W, Cin) integer activation levels
    w_lv: jax.Array,   # (kh*kw*Cin, Cout) pre-quantized weight levels
    s_w: jax.Array,
    z_w: jax.Array,
    *,
    kh: int,
    kw: int,
    stride: int = 1,
    padding: str = "SAME",
    a_bits: int,
    w_bits: int,
    interpret: bool = False,
    toh: int = TOH,
    tcout: int = TCOUT,
) -> jax.Array:
    """Implicit-GEMM conv on pre-quantized operands.  Returns f32 NHWC.

    Weight layout is (kh, kw, cin)-major on the K axis — the layout
    ``core.prequant.prequantize_conv_weight`` stores and ``im2col_sliced``
    emits, so the kernel is a drop-in for the patch-GEMM path.
    """
    b, h, w, cin = x_lv.shape
    cout = w_lv.shape[1]
    oh, ow = _out_hw(h, w, kh, kw, stride, padding)
    (ph0, _), (pw0, _) = pad_split(h, w, kh, kw, stride, padding)

    toh = min(toh, max(oh, 1))
    ohp = -(-oh // toh) * toh
    tcout = min(tcout, cout)
    coutp = -(-cout // tcout) * tcout
    # halo'd canvas: every in-kernel slice (incl. the padded tail rows whose
    # outputs are cropped) stays in bounds
    hp = ohp * stride + kh - 1
    wp = ow * stride + kw - 1
    x_p = jnp.pad(x_lv, ((0, 0), (ph0, hp - h - ph0), (pw0, wp - w - pw0),
                         (0, 0)))
    w_p = jnp.pad(w_lv, ((0, 0), (0, coutp - cout)))

    half, zero2 = epilogue_scalars(s_w, z_w, a_bits)

    grid = (b, ohp // toh, coutp // tcout)
    out = pl.pallas_call(
        functools.partial(_kernel, kh=kh, kw=kw, cin=cin, stride=stride,
                          ow=ow, toh=toh, a_bits=a_bits, w_bits=w_bits),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            # whole image per batch index: index map ignores (t, j), so the
            # pipelined buffer is fetched once per image and stays resident
            pl.BlockSpec((1, hp, wp, cin), lambda i, t, j: (i, 0, 0, 0)),
            pl.BlockSpec((kh * kw * cin, tcout), lambda i, t, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, toh, ow, tcout),
                               lambda i, t, j: (i, t, 0, j)),
        out_shape=jax.ShapeDtypeStruct((b, ohp, ow, coutp), jnp.float32),
        name="conv_implicit",
        interpret=interpret,
    )(half.reshape(1), zero2.reshape(1), x_p, w_p)
    return out[:, :oh, :, :cout]


@functools.partial(
    jax.jit,
    static_argnames=("kh", "kw", "stride", "padding", "a_bits", "w_bits"),
)
def conv_implicit_xla(
    x_lv: jax.Array,
    w_lv: jax.Array,
    s_w: jax.Array,
    z_w: jax.Array,
    *,
    kh: int,
    kw: int,
    stride: int = 1,
    padding: str = "SAME",
    a_bits: int,
    w_bits: int,
) -> jax.Array:
    """Off-TPU implicit realization: the level GEMM as a direct convolution.

    ``conv_general_dilated`` on the f32-cast levels is exact while every
    partial sum fits the fp32 mantissa (the ``f32dot_exact`` bound with
    K = kh*kw*cin); beyond it the operands nibble-split into <2^4 groups —
    the same folding the MXU kernels use — and each group-pair conv is
    exact.  No patch tensor is ever materialized: XLA's conv loops read
    each input row once per kernel tap from cache, not kh*kw copies from
    memory.
    """
    b, h, w, cin = x_lv.shape
    cout = w_lv.shape[1]
    k = kh * kw * cin
    (ph0, _), (pw0, _) = pad_split(h, w, kh, kw, stride, padding)
    oh, ow = _out_hw(h, w, kh, kw, stride, padding)
    # leading pads are im2col's SAME split; the trailing side covers the
    # full window sweep exactly (negative = crop, matching how the sliced
    # im2col's strided slices simply never read past the last window)
    pads = ((ph0, (oh - 1) * stride + kh - h - ph0),
            (pw0, (ow - 1) * stride + kw - w - pw0))

    w4 = w_lv.reshape(kh, kw, cin, cout)

    def _conv(x, w_):
        return jax.lax.conv_general_dilated(
            x, w_, (stride, stride), pads,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=jax.lax.Precision.HIGHEST,
        )

    x32 = x_lv.astype(jnp.int32)
    if f32dot_exact(k, a_bits, w_bits):
        acc_pairs = [(x32, 0, w4.astype(jnp.int32), 0)]
    else:
        # nibble-split both sides; each exact group-pair partial is cast to
        # int32 below so the shifted ACCUMULATION is integer arithmetic too
        # (summing the partials in f32 would round again past 2^24).  The
        # bound uses the ACTUAL group widths — _nibble_split leaves 5-7 bit
        # operands whole, so assuming 4-bit groups would under-guard.
        if not implicit_xla_exact(k, a_bits, w_bits):
            raise ValueError(f"implicit xla conv inexact even nibble-split "
                             f"(K={k}, a_bits={a_bits}, w_bits={w_bits}); "
                             "use the int8 engine or the Pallas kernel")
        acc_pairs = [(ga, sa, gw, sw)
                     for ga, sa in _nibble_split(x32, a_bits)
                     for gw, sw in _nibble_split(w4.astype(jnp.int32), w_bits)]

    acc = jnp.zeros((b, oh, ow, cout), jnp.int32)
    for ga, sa, gw, sw in acc_pairs:
        d = _conv(ga.astype(jnp.float32), gw.astype(jnp.float32))
        acc = acc + (d.astype(jnp.int32) << (sa + sw))
    ones = jnp.ones((kh, kw, cin, 1), jnp.float32)
    rs_groups = ([(x32, 0)] if f32dot_exact(k, a_bits, 1)
                 else _nibble_split(x32, a_bits))
    rowsum = jnp.zeros((b, oh, ow, 1), jnp.int32)
    for ga, sa in rs_groups:
        rowsum = rowsum + (_conv(ga.astype(jnp.float32),
                                 ones).astype(jnp.int32) << sa)

    # core.and_accum.dequant_epilogue's expression: exact int32 correction,
    # then one f32 multiply (nothing for a compiler to contract into an FMA)
    half, zero2 = epilogue_scalars(s_w, z_w, a_bits)
    return (2 * acc - zero2 * rowsum).astype(jnp.float32) * half
