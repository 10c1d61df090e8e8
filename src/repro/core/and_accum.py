"""AND-Accumulation bit-wise GEMM — the paper's Eq. (1), TPU-adapted.

    I * W = sum_m sum_n 2^(m+n) CMP(AND(C_n(W), C_m(I)))

Three engines, all *integer-exact* and validated against each other:

``planes``  Paper-faithful dataflow in jnp: explicit bit-plane AND,
            popcount via summation (the CMP compressor tree), parallel
            shift realized as the 2^(m+n) static weighting.
``packed``  Same dataflow with planes packed 32/uint32 lane and
            ``lax.population_count`` — the VPU realization; this is the
            dataflow the Pallas kernel in ``repro.kernels.bitgemm`` tiles
            into VMEM.
``int8``    Beyond-paper TPU mapping: a {0,1}-plane dot-product *is* an
            integer matmul, so the MXU's systolic adder tree subsumes the
            4:2 compressor tree.  For bits <= 7 all plane-pair sums are
            folded into a single int8 x int8 -> int32 matmul on the levels
            themselves (the 2^(m+n) shifts distribute:
            sum_mn 2^(m+n) P_m(A)P_n(W) == levels_A . levels_W).

Signed/affine correction: with a = s_a * A (A uint levels) and
w = s_w * (W - z_w), the float GEMM is recovered as
    a @ w = s_a*s_w * (A @ W) - s_a*s_w*z_w * rowsum(A)
(rowsum(A) is one extra popcount pass in hardware — the paper's EPU
handles it; here it is a cheap reduction).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from . import bitplane


def bitgemm_planes(a_lv: jax.Array, w_lv: jax.Array, a_bits: int, w_bits: int) -> jax.Array:
    """Paper-faithful Eq. (1). a_lv (M,K) uint levels, w_lv (K,N) -> int32 (M,N)."""
    pa = bitplane.decompose(a_lv, a_bits)  # (m, M, K)
    pw = bitplane.decompose(w_lv, w_bits)  # (n, K, N)
    out = jnp.zeros((a_lv.shape[0], w_lv.shape[1]), jnp.int32)
    for m in range(a_bits):
        for n in range(w_bits):
            # AND of {0,1} planes == elementwise product; CMP == sum over K.
            cmp = jnp.einsum(
                "mk,kn->mn", pa[m], pw[n], preferred_element_type=jnp.int32
            )
            out = out + (cmp << (m + n))  # parallel shift (ASR analogue)
    return out


def bitgemm_packed(a_lv: jax.Array, w_lv: jax.Array, a_bits: int, w_bits: int) -> jax.Array:
    """uint32-packed AND + popcount (VPU dataflow). Exact, O(M*N*K/32) lanes."""
    pa = bitplane.decompose_packed(a_lv, a_bits, axis=-1)          # (m, M, Kw)
    pw = bitplane.decompose_packed(w_lv.T, w_bits, axis=-1)        # (n, N, Kw)
    out = jnp.zeros((a_lv.shape[0], w_lv.shape[1]), jnp.int32)
    for m in range(a_bits):
        for n in range(w_bits):
            anded = pa[m][:, None, :] & pw[n][None, :, :]          # (M,N,Kw)
            cmp = jnp.sum(bitplane.popcount(anded), axis=-1)
            out = out + (cmp << (m + n))
    return out


def _nibble_split(lv: jax.Array, bits: int):
    """Split integer levels into <=7-bit groups: lv == sum_i grp_i << sh_i.

    int8 MXU operands must stay < 128; W1A8 (the paper's best-accuracy
    point) therefore splits its 8-bit activations into two nibbles — two
    int8 matmuls instead of 8 plane matmuls, still exact.
    """
    if bits <= 7:
        return [(lv, 0)]
    groups, shift = [], 0
    while shift < bits:
        g = min(4, bits - shift)
        groups.append(((jax.lax.shift_right_logical(lv, shift) & ((1 << g) - 1)), shift))
        shift += g
    return groups


def bitgemm_int8(a_lv: jax.Array, w_lv: jax.Array, a_bits: int, w_bits: int) -> jax.Array:
    """MXU mapping: int8 matmul(s) on the integer levels (nibble-split >7b)."""
    out = jnp.zeros((a_lv.shape[0], w_lv.shape[1]), jnp.int32)
    for ga, sa in _nibble_split(a_lv, a_bits):
        for gw, sw in _nibble_split(w_lv, w_bits):
            d = jnp.dot(ga.astype(jnp.int8), gw.astype(jnp.int8),
                        preferred_element_type=jnp.int32)
            out = out + (d << (sa + sw))
    return out


def bitgemm_int8_planewise(a_lv, w_lv, a_bits, w_bits):
    """MXU mapping, plane-pair granularity (the literal Eq. (1) on MXU)."""
    pa = bitplane.decompose(a_lv, a_bits).astype(jnp.int8)
    pw = bitplane.decompose(w_lv, w_bits).astype(jnp.int8)
    out = jnp.zeros((a_lv.shape[0], w_lv.shape[1]), jnp.int32)
    for m in range(a_bits):
        for n in range(w_bits):
            out = out + (jnp.dot(pa[m], pw[n], preferred_element_type=jnp.int32) << (m + n))
    return out


def f32dot_exact(k: int, a_bits: int, w_bits: int) -> bool:
    """Exactness bound for :func:`bitgemm_f32dot`: every partial sum is an
    integer inside the fp32 mantissa."""
    return ((1 << a_bits) - 1) * ((1 << w_bits) - 1) * max(k, 1) < (1 << 24)


def bitgemm_f32dot(a_lv: jax.Array, w_lv: jax.Array, a_bits: int, w_bits: int) -> jax.Array:
    """Float-unit realization of the level GEMM — exact while
    ``a_max * w_max * K < 2^24``.  On CPU/GPU backends XLA lowers integer
    matmuls to scalar loops, so routing the exact computation through the
    float GEMM is the fast path.  The bound is enforced here (shape and
    bit-widths are static), so an explicit ``engine="f32dot"`` cannot
    silently round; HIGHEST precision keeps TPU/GPU matmul units from
    truncating the f32 inputs.
    """
    # defense-in-depth: plan-dispatched calls arrive with this already
    # proven statically (repro.analysis prover, PV101) — only direct
    # un-planned calls can trip it
    if not f32dot_exact(a_lv.shape[-1], a_bits, w_bits):
        raise ValueError(
            f"f32dot engine inexact for a_bits={a_bits}, w_bits={w_bits}, "
            f"K={a_lv.shape[-1]} (accumulator exceeds the fp32 mantissa); "
            "use engine='int8'")
    d = jnp.dot(a_lv.astype(jnp.float32), w_lv.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST)
    return d.astype(jnp.int32)


_ENGINES = {
    "planes": bitgemm_planes,
    "packed": bitgemm_packed,
    "int8": bitgemm_int8,
    "int8_planewise": bitgemm_int8_planewise,
    "f32dot": bitgemm_f32dot,
}


@partial(jax.jit, static_argnames=("a_bits", "w_bits", "engine"))
def bitgemm(a_lv, w_lv, a_bits: int, w_bits: int, engine: str = "int8") -> jax.Array:
    """Integer-level GEMM dispatch. All engines are bit-exact equal
    (``f32dot`` raises when its mantissa bound would make it inexact)."""
    return _ENGINES[engine](a_lv, w_lv, a_bits, w_bits)


def quant_dense_forward(
    a: jax.Array,
    w: jax.Array,
    a_bits: int,
    w_bits: int,
    engine: str = "int8",
) -> jax.Array:
    """Float-in/float-out quantized dense using the integer engine.

    ``a`` (..., K) activations (pre-clipped to [0,1] by the caller's
    activation function, as in DoReFa), ``w`` (K, N) weights.  Returns the
    AND-Accumulation GEMM result de-quantized to float.  Bit-exact w.r.t.
    quantize->float-matmul because every intermediate is an exact int32.
    """
    lead = a.shape[:-1]
    a2 = a.reshape((-1, a.shape[-1]))
    from .quant import activation_levels, weight_levels  # local to avoid cycle

    a_lv, s_a = activation_levels(a2, a_bits)
    w_lv, s_w, z_w = weight_levels(w, w_bits)
    acc = _ENGINES[engine](a_lv, w_lv, a_bits, w_bits)
    out = dequant_epilogue(acc, a_lv, s_w, z_w, a_bits, a.dtype)  # EPU pass
    return out.reshape(lead + (w.shape[-1],))


def epilogue_scalars(s_w, z_w, a_bits: int):
    """``(half_scale, zero2)`` for :func:`dequant_epilogue`: the f32 factor
    ``s_a*s_w/2`` and the integer ``2*z_w``.

    Every DoReFa zero point is a half-integer (``z_w = (2^w_bits - 1)/2``,
    ``core.quant.weight_levels``), so ``2*z_w`` is an exact integer and the
    whole affine correction can run in int32."""
    s_a = jnp.asarray(1.0 / ((1 << a_bits) - 1), jnp.float32)
    half = (s_a * jnp.asarray(s_w, jnp.float32)) * 0.5
    zero2 = jnp.round(2.0 * jnp.asarray(z_w, jnp.float32)).astype(jnp.int32)
    return half, zero2


def dequant_epilogue(acc, a_lv, s_w, z_w, a_bits: int, out_dtype=jnp.float32):
    """Affine-correction + dequant for the unsigned (DoReFa) level GEMM:
    ``out = (s_a*s_w/2) * (2*acc − 2*z_w*rowsum(A))``.

    The correction is exact int32 arithmetic (wrapping intermediates are
    harmless: the result is bounded by the accumulator's own range) and the
    float part is ONE multiply, so there is no multiply-add for a compiler
    to contract into an FMA: every realization — this one, the fused and
    implicit Pallas kernels, the direct-conv tail — rounds identically.
    Single source of truth; the kernels mirror it via
    :func:`epilogue_scalars`."""
    half, zero2 = epilogue_scalars(s_w, z_w, a_bits)
    rowsum = jnp.sum(a_lv, axis=-1, dtype=jnp.int32)
    e = 2 * acc.astype(jnp.int32) - zero2 * rowsum[:, None]
    return e.astype(out_dtype) * half.astype(out_dtype)


def quant_dense_pre_levels(
    a_lv: jax.Array, w_lv: jax.Array, s_w, z_w, a_bits: int, w_bits: int,
    engine: str = "int8", out_dtype=jnp.float32,
) -> jax.Array:
    """Unsigned (DoReFa) dense on PRE-QUANTIZED operands: integer activation
    levels in, int8 weight levels + (s_w, z_w) from the checkpoint in.

    The serve-side core of :func:`quant_dense_forward` with every per-call
    re-quantization removed; same epilogue expression, so outputs are
    bit-identical to the re-quantizing path.
    """
    acc = _ENGINES[engine](a_lv.astype(jnp.int32), w_lv.astype(jnp.int32),
                           a_bits, w_bits)
    return dequant_epilogue(acc, a_lv, s_w, z_w, a_bits, out_dtype)


def quant_dense_forward_pre(
    a: jax.Array, w_lv: jax.Array, s_w, z_w, a_bits: int, w_bits: int,
    engine: str = "int8",
) -> jax.Array:
    """Unsigned quantized dense with pre-quantized weights (float acts in)."""
    from .quant import activation_levels

    lead = a.shape[:-1]
    a_lv, _ = activation_levels(a.reshape((-1, a.shape[-1])), a_bits)
    out = quant_dense_pre_levels(a_lv, w_lv, s_w, z_w, a_bits, w_bits,
                                 engine=engine)
    return out.reshape(lead + (w_lv.shape[-1],)).astype(a.dtype)


def quant_dense_forward_signed(
    a: jax.Array, w: jax.Array, a_bits: int, w_bits: int, engine: str = "int8",
    a_scale_mode: str = "tensor",
) -> jax.Array:
    """Signed-activation quantized dense (transformers): full affine correction.

    a = s_a*(A - z_a), w = s_w*(W - z_w)  =>
    a@w = s_a s_w [A@W - z_w*rowsum(A) - z_a*colsum(W) + K*z_a*z_w]
    All four terms exact int32; only the final scaling is float.

    ``a_scale_mode='row'`` uses a per-row activation absmax (s_a becomes
    (M, 1)) — the correction algebra is unchanged because z_a stays the
    constant 2^(b-1); see ``core.quant.activation_levels_signed_row``.
    """
    from .quant import (activation_levels_signed,
                        activation_levels_signed_row, weight_levels)

    lead = a.shape[:-1]
    K = a.shape[-1]
    a2 = a.reshape((-1, K))
    lv_fn = (activation_levels_signed_row if a_scale_mode == "row"
             else activation_levels_signed)
    a_lv, s_a, z_a = lv_fn(a2, a_bits)
    w_lv, s_w, z_w = weight_levels(w, w_bits)
    acc = _ENGINES[engine](a_lv, w_lv, a_bits, w_bits).astype(jnp.float32)
    rowsum = jnp.sum(a_lv, axis=-1, dtype=jnp.int32).astype(jnp.float32)
    colsum = jnp.sum(w_lv, axis=0, dtype=jnp.int32).astype(jnp.float32)
    out = acc - z_w * rowsum[:, None] - z_a * colsum[None, :] + K * z_a * z_w
    out = (s_a * s_w) * out
    return out.reshape(lead + (w.shape[-1],)).astype(a.dtype)


def quant_dense_forward_signed_pre(
    a: jax.Array, w_lv: jax.Array, s_w, z_w, a_bits: int, w_bits: int,
    engine: str = "int8", a_scale: "float | str | None" = None,
) -> jax.Array:
    """Signed quantized dense with PRE-QUANTIZED weights (int8 levels stored
    in the checkpoint — the TPU analogue of keeping C_n(W) resident in the
    SOT-MRAM sub-array).  4x less weight HBM traffic than fp32 at serve.

    ``a_scale`` selects the activation-scale source: a float installs a
    static (offline-calibrated) scale, the string ``'row'`` a per-row
    dynamic absmax (batch-independent numerics for continuous batching),
    and ``None`` the default per-tensor dynamic absmax."""
    from .quant import activation_levels_signed, activation_levels_signed_row

    lead = a.shape[:-1]
    K = a.shape[-1]
    a2 = a.reshape((-1, K))
    if a_scale == "row":
        a_lv, s_a, z_a = activation_levels_signed_row(a2, a_bits)
    elif a_scale is not None:
        # static (offline-calibrated) activation scale: no dynamic absmax
        # reduction (and no cross-shard all-reduce) on the serve path
        n = (1 << a_bits) - 1
        z_a = jnp.asarray(float(1 << (a_bits - 1)), jnp.float32)
        s_a = jnp.asarray(a_scale, jnp.float32)
        a_lv = jnp.clip(jnp.round(a2.astype(jnp.float32) / s_a) + z_a,
                        0, n).astype(jnp.int32)
    else:
        a_lv, s_a, z_a = activation_levels_signed(a2, a_bits)
    acc = _ENGINES[engine](a_lv, w_lv.astype(jnp.int32), a_bits, w_bits
                           ).astype(jnp.float32)
    rowsum = jnp.sum(a_lv, axis=-1, dtype=jnp.int32).astype(jnp.float32)
    colsum = jnp.sum(w_lv.astype(jnp.int32), axis=0,
                     dtype=jnp.int32).astype(jnp.float32)
    out = acc - z_w * rowsum[:, None] - z_a * colsum[None, :] + K * z_a * z_w
    out = (s_a * s_w) * out
    return out.reshape(lead + (w_lv.shape[-1],)).astype(a.dtype)


def reference_float(a, w, a_bits, w_bits):
    """Quantize-dequantize float matmul — the semantic oracle for the above."""
    from .quant import activation_levels, weight_levels

    a_lv, s_a = activation_levels(a.reshape((-1, a.shape[-1])), a_bits)
    w_lv, s_w, z_w = weight_levels(w, w_bits)
    aq = a_lv.astype(jnp.float32) * s_a
    wq = (w_lv.astype(jnp.float32) - z_w) * s_w
    return (aq @ wq).reshape(a.shape[:-1] + (w.shape[-1],))
