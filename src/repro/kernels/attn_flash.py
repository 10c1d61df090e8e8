"""Quantized flash attention: the AND-Accumulation engine on the serve path.

The LM projections already serve through the paper's bit-wise engine
(``fused_qgemm``); this module extends it to the last unquantized hot
loop — the S^2 attention score GEMM.  One flash-style kernel computes

    out = softmax(dequant(Q_lv @ K_lv^T + affine correction) / sqrt(hd)) @ V

with online-softmax tiling over (q-block x kv-block), never materializing
the S^2 logits.  Q and K are affine-quantized per tensor to ``q_bits`` /
``k_bits`` levels (the same DoReFa level scheme as the dense path); the
score dot runs on integer levels through the nibble-split int8 MXU path of
``fused_qgemm``, and because *both* operands are activations the zero-point
correction needs both rowsums (cf. ``quant_dense_forward_signed_pre``,
which corrects one activation against a weight):

    q_hat @ k_hat^T = s_q s_k [QK^T - z_k rowsum(Q)1^T - z_q 1 rowsum(K)^T
                               + hd z_q z_k]

All four terms are exact int32, so the dequantized logits are *exact*
attention scores of the quantized q/k — the only approximation is the
quantization itself (bounded by s_q, s_k; see :func:`flash_error_bound`).
P @ V stays f32 (softmax weights are not level-valued).

Two realizations of the same arithmetic (mirroring ``conv_implicit``):

* :func:`attn_flash_pallas` — a single ``pallas_call``; grid
  (B*H, q-blocks, kv-blocks) with the (m, l, acc) online-softmax state in
  VMEM scratch carried across the innermost kv dimension.  Causal masking
  skips dead upper-triangle blocks with ``pl.when``; the sliding-window
  variant uses a *banded grid* — the kv grid axis only spans the
  ``ceil((W-1)/t)+1`` blocks that can intersect the window band, with the
  BlockSpec index map sliding the band along the diagonal.
* :func:`attn_flash_xla` — exact off-TPU realization: the centered-level
  identity ``(Q-z_q)(K-z_k)^T`` equals the rowsum-corrected form, and the
  centered levels are integer-valued f32, so a float dot is bit-exact
  while ``2^(q_bits-1) * 2^(k_bits-1) * hd < 2^24``
  (:func:`flash_levels_exact` — holds for every supported head dim).
  Blocked as scan-over-q-blocks with a ``fori_loop`` over exactly the
  valid kv-block range (causal upper triangle and out-of-window bands are
  never visited), and only boundary blocks pay the masking arithmetic —
  interior blocks run mask-free.  Measured at S=32k causal on CPU this is
  ~2.4x over the skip-enabled ``attn_chunked`` scan.

:func:`attn_flash` picks the realization for the live backend (the engine
entry the dispatch layer calls).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.and_accum import _nibble_split

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Quantization helpers (per-tensor affine, the dense path's level scheme)
# ---------------------------------------------------------------------------

def attn_quant_scale(x: jax.Array, bits: int):
    """Per-tensor (scale, zero_point) for signed affine quantization.

    Matches ``core.quant.activation_levels_signed``: z = 2^(bits-1),
    s = absmax / z; levels = clip(round(x/s) + z, 0, 2^bits - 1).
    """
    z = float(1 << (bits - 1))
    s = jnp.max(jnp.abs(x)).astype(jnp.float32) / z + 1e-12
    return s, z


def _levels(x: jax.Array, s, bits: int) -> jax.Array:
    z = float(1 << (bits - 1))
    n = float((1 << bits) - 1)
    return jnp.clip(jnp.round(x.astype(jnp.float32) / s) + z, 0.0, n)


def flash_levels_exact(head_dim: int, q_bits: int, k_bits: int) -> bool:
    """Can the centered-level score dot run exactly on the f32 unit?

    The centered levels are bounded by 2^(bits-1); the dot accumulates
    ``head_dim`` products, so the accumulator magnitude is below
    2^(q_bits-1) * 2^(k_bits-1) * head_dim — exact while under the fp32
    mantissa (2^24).  At 8/8 bits this holds for head_dim < 1024."""
    return (1 << (q_bits - 1)) * (1 << (k_bits - 1)) * head_dim < (1 << 24)


def flash_error_bound(q, k, q_bits: int, k_bits: int) -> float:
    """Worst-case absolute LOGIT error vs unquantized attention.

    Each operand rounds by at most s/2, so a length-hd dot differs by at
    most hd*(s_q*|k|_max + s_k*|q|_max + s_q*s_k/2)/2 before the 1/sqrt(hd)
    scale.  Useful for test tolerances; the post-softmax output error is
    further damped by softmax's 1-Lipschitz property (in the inf-norm,
    scaled by the value range)."""
    hd = q.shape[-1]
    # Host-side helper: callers pass concrete arrays to derive test
    # tolerances, never traced serve values, so these syncs are
    # intentional (the serve path keeps scales traced — attn_quant_scale).
    qm = float(jnp.max(jnp.abs(q)))  # repro-lint: disable=RL002 — pre-jit tolerance helper
    km = float(jnp.max(jnp.abs(k)))  # repro-lint: disable=RL002 — pre-jit tolerance helper
    s_q = qm / (1 << (q_bits - 1)) + 1e-12
    s_k = km / (1 << (k_bits - 1)) + 1e-12
    return hd * (s_q * km + s_k * qm + s_q * s_k / 2) / (2 * math.sqrt(hd))


def _ceil_to(n: int, m: int) -> int:
    return -(-n // m) * m


def _pad_axis(x: jax.Array, target: int, axis: int) -> jax.Array:
    pad = target - x.shape[axis]
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


# ---------------------------------------------------------------------------
# XLA realization (CPU/GPU engine)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "q_bits", "k_bits", "block_q", "block_kv"))
def attn_flash_xla(q, k, v, *, causal: bool = True,
                   window: Optional[int] = None, q_bits: int = 8,
                   k_bits: int = 8, block_q: int = 512,
                   block_kv: int = 512) -> jax.Array:
    """Exact XLA realization of the quantized flash kernel.

    q (B,Sq,H,hd); k,v (B,Skv,H,hd) with KV pre-expanded for GQA
    (``models.layers.expand_kv``).  Positions are the contiguous
    0..S-1 prefill positions (causal/window masks only consume position
    *differences*, so any common offset cancels).  Requires
    :func:`flash_levels_exact` — checked, raises ValueError beyond it.
    """
    # defense-in-depth: plan-dispatched flash verdicts arrive with this
    # already proven statically (repro.analysis prover, PV101)
    if not flash_levels_exact(q.shape[-1], q_bits, k_bits):
        raise ValueError(
            f"flash centered-level dot inexact at head_dim={q.shape[-1]}, "
            f"q_bits={q_bits}, k_bits={k_bits} (accumulator exceeds the "
            "fp32 mantissa)")
    B, Sq, H, hd = q.shape
    Skv = k.shape[1]
    s_q, z_q = attn_quant_scale(q, q_bits)
    s_k, z_k = attn_quant_scale(k, k_bits)
    # centered levels: (lv - z) in [-2^(b-1), 2^(b-1)-1]; the centered dot
    # IS the rowsum-corrected form (expand (Q-z_q)(K-z_k)^T), kept as
    # integer-valued f32 so XLA uses the fast float unit exactly
    qc = _levels(q, s_q, q_bits) - z_q
    kc = _levels(k, s_k, k_bits) - z_k
    scale = s_q * s_k / math.sqrt(hd)

    bq = min(block_q, Sq)
    bk = min(block_kv, Skv)
    Sq_p, Skv_p = _ceil_to(Sq, bq), _ceil_to(Skv, bk)
    qc = _pad_axis(qc, Sq_p, 1)
    kc = _pad_axis(kc, Skv_p, 1)
    vp = _pad_axis(v, Skv_p, 1)
    Nq, Nk = Sq_p // bq, Skv_p // bk
    qt = qc.reshape(B, Nq, bq, H, hd).transpose(1, 0, 3, 2, 4)
    kt = kc.reshape(B, Nk, bk, H, hd).transpose(1, 0, 3, 2, 4)
    vt = vp.reshape(B, Nk, bk, H, hd).transpose(1, 0, 3, 2, 4).astype(
        jnp.float32)
    # the last kv block holding real rows: blocks past it exist only when
    # causal padding makes the diagonal reach them, and stay masked
    j_pad = (Skv - 1) // bk

    def q_body(_, qx):
        qi, i = qx  # (B,H,bq,hd), scalar block index
        jhi = (jnp.minimum(((i + 1) * bq - 1) // bk, Nk - 1)
               if causal else Nk - 1)
        jlo = (jnp.maximum((i * bq - (window - 1)) // bk, 0)
               if window is not None else 0)

        def kv_step(j, carry):
            m_run, l_run, acc = carry
            kj = jax.lax.dynamic_index_in_dim(kt, j, 0, keepdims=False)
            vj = jax.lax.dynamic_index_in_dim(vt, j, 0, keepdims=False)
            s = jnp.einsum("bhqd,bhsd->bhqs", qi, kj,
                           preferred_element_type=jnp.float32) * scale

            def masked(s):
                iq = i * bq + jnp.arange(bq)
                jk = j * bk + jnp.arange(bk)
                m = (jk < Skv)[None, :] & jnp.ones((bq, 1), bool)
                if causal:
                    m &= jk[None, :] <= iq[:, None]
                if window is not None:
                    m &= jk[None, :] > iq[:, None] - window
                s = jnp.where(m[None, None], s, NEG_INF)
                m_new = jnp.maximum(m_run, jnp.max(s, axis=-1))
                return m_new, jnp.exp(s - m_new[..., None]) * m[None, None]

            def plain(s):
                m_new = jnp.maximum(m_run, jnp.max(s, axis=-1))
                return m_new, jnp.exp(s - m_new[..., None])

            # only boundary blocks pay the mask arithmetic: the causal
            # diagonal (j == jhi), the window's trailing edge (j == jlo),
            # and the kv padding block.  Interior blocks are fully valid.
            boundary = j >= j_pad
            if causal:
                boundary |= j == jhi
            if window is not None:
                boundary |= j == jlo
            m_new, p = jax.lax.cond(boundary, masked, plain, s)
            corr = jnp.exp(m_run - m_new)
            l_run = l_run * corr + jnp.sum(p, axis=-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bhqs,bhsd->bhqd", p, vj, preferred_element_type=jnp.float32)
            return (m_new, l_run, acc)

        init = (jnp.full((B, H, bq), NEG_INF, jnp.float32),
                jnp.zeros((B, H, bq), jnp.float32),
                jnp.zeros((B, H, bq, hd), jnp.float32))
        m_run, l_run, acc = jax.lax.fori_loop(jlo, jhi + 1, kv_step, init)
        out = acc / jnp.maximum(l_run, 1e-30)[..., None]
        return None, out

    _, outs = jax.lax.scan(q_body, None, (qt, jnp.arange(Nq)))
    out = outs.transpose(1, 0, 3, 2, 4).reshape(B, Sq_p, H, hd)
    return out[:, :Sq].astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas realization (TPU engine; interpret-mode correctness off-TPU)
# ---------------------------------------------------------------------------

def _flash_kernel(scal_ref, zint_ref, q_ref, k_ref, v_ref, o_ref,
                  m_ref, l_ref, acc_ref, *, q_bits, k_bits, causal, window,
                  tq, tk, seq_kv, nj, nwin):
    """One (bh, i, j) grid step of the online-softmax sweep.

    scal_ref (SMEM f32): [s_q*s_k/sqrt(hd)]; zint_ref (SMEM i32):
    [z_q, z_k].  Scratch m/l (tq, 128) f32 (lane-replicated row stats),
    acc (tq, hd) f32 — carried across the innermost kv grid dim.
    """
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # absolute kv block: the banded (window) grid slides j's nwin-wide
    # band along the diagonal; the causal grid visits the full row
    jb = i - (nwin - 1) + j if nwin is not None else j
    hd = q_ref.shape[-1]
    active = jb * tk < seq_kv
    if nwin is not None:
        active &= jb >= 0
    if causal:
        active &= jb * tk <= (i + 1) * tq - 1

    @pl.when(active)
    def _compute():
        z_q, z_k = zint_ref[0], zint_ref[1]
        ql = q_ref[0].astype(jnp.int32)   # (tq, hd) levels
        kl = k_ref[0].astype(jnp.int32)   # (tk, hd)
        acc = jnp.zeros((tq, tk), jnp.int32)
        # nibble-split int8 MXU dots, folded with shifts (fused_qgemm's
        # accumulation); contraction over the head dim of both operands
        for gq, sq in _nibble_split(ql, q_bits):
            for gk, sk in _nibble_split(kl, k_bits):
                d = jax.lax.dot_general(
                    gq.astype(jnp.int8), gk.astype(jnp.int8),
                    dimension_numbers=(((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.int32)
                acc += d << (sq + sk)
        # both operands are activations: both rowsums enter the correction
        rs_q = jnp.sum(ql, axis=1)        # (tq,)
        rs_k = jnp.sum(kl, axis=1)        # (tk,)
        corr = (acc - z_k * rs_q[:, None] - z_q * rs_k[None, :]
                + hd * z_q * z_k)
        logits = corr.astype(jnp.float32) * scal_ref[0]

        iq = jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 0) + i * tq
        jk = jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 1) + jb * tk
        msk = jk < seq_kv
        if causal:
            msk &= jk <= iq
        if window is not None:
            msk &= jk > iq - window
        logits = jnp.where(msk, logits, NEG_INF)

        m_old = m_ref[:, :1]                                   # (tq, 1)
        m_new = jnp.maximum(m_old, jnp.max(logits, axis=1, keepdims=True))
        p = jnp.exp(logits - m_new) * msk                      # (tq, tk)
        cf = jnp.exp(m_old - m_new)                            # (tq, 1)
        l_new = l_ref[:, :1] * cf + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * cf + jax.lax.dot_general(
            p, v_ref[0].astype(jnp.float32),
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == nj - 1)
    def _epilogue():
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def attn_flash_pallas(q, k, v, *, causal: bool = True,
                      window: Optional[int] = None, q_bits: int = 8,
                      k_bits: int = 8, block_q: int = 1024,
                      block_kv: int = 1024,
                      interpret: bool = False) -> jax.Array:
    """Single-``pallas_call`` quantized flash attention (shapes as
    :func:`attn_flash_xla`).  The sliding-window variant requires
    ``block_q == block_kv`` (the banded grid slides in whole blocks)."""
    B, Sq, H, hd = q.shape
    Skv = k.shape[1]
    s_q, z_q = attn_quant_scale(q, q_bits)
    s_k, z_k = attn_quant_scale(k, k_bits)
    ql = _levels(q, s_q, q_bits).astype(jnp.int32)
    kl = _levels(k, s_k, k_bits).astype(jnp.int32)

    tq = min(block_q, Sq)
    tk = min(block_kv, Skv)
    if window is not None:
        tq = tk = min(tq, tk)
    Sq_p, Skv_p = _ceil_to(Sq, tq), _ceil_to(Skv, tk)
    ql = _pad_axis(ql, Sq_p, 1)
    kl = _pad_axis(kl, Skv_p, 1)
    vp = _pad_axis(v, Skv_p, 1)
    Nq, Nk = Sq_p // tq, Skv_p // tk

    # (B,S,H,hd) -> (B*H, S, hd): one grid row per (batch, head)
    ql = ql.transpose(0, 2, 1, 3).reshape(B * H, Sq_p, hd)
    kl = kl.transpose(0, 2, 1, 3).reshape(B * H, Skv_p, hd)
    vp = vp.transpose(0, 2, 1, 3).reshape(B * H, Skv_p, hd)

    nwin = None
    if window is not None:
        # blocks that can intersect the (W-1)-deep band plus the diagonal
        nwin = min(Nk, -(-(window - 1) // tk) + 1)
        nj = nwin
        kv_index = lambda b, i, j: (b, jnp.maximum(i - (nwin - 1) + j, 0), 0)
    else:
        nj = Nk
        kv_index = lambda b, i, j: (b, j, 0)

    scal = jnp.asarray([s_q * s_k / math.sqrt(hd)], jnp.float32)
    zint = jnp.asarray([int(z_q), int(z_k)], jnp.int32)

    kernel = functools.partial(
        _flash_kernel, q_bits=q_bits, k_bits=k_bits, causal=causal,
        window=window, tq=tq, tk=tk, seq_kv=Skv, nj=nj, nwin=nwin)
    out = pl.pallas_call(
        kernel,
        grid=(B * H, Nq, nj),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, tq, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, tk, hd), kv_index),
            pl.BlockSpec((1, tk, hd), kv_index),
        ],
        out_specs=pl.BlockSpec((1, tq, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq_p, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((tq, 128), jnp.float32),
            pltpu.VMEM((tq, 128), jnp.float32),
            pltpu.VMEM((tq, hd), jnp.float32),
        ],
        name="attn_flash",
        interpret=interpret,
    )(scal, zint, ql, kl, vp)
    out = out.reshape(B, H, Sq_p, hd).transpose(0, 2, 1, 3)
    return out[:, :Sq]


# ---------------------------------------------------------------------------
# Paged attention (continuous-batching serve path)
# ---------------------------------------------------------------------------
#
# KV lives in a shared block pool (NP+1, ps, Hkv, hd) — NP fixed-size pages
# plus one reserved, never-written null page — and each decode slot owns an
# ordered page-table row (P page indices, padded with the null page).  The
# engine gathers a slot's KV through its table row and attends with the
# device-side position buffer ``ppos`` ((NP+1, ps), -1 = never written) as
# the validity mask, so ragged final pages and table padding cost a mask,
# not a copy.  All reductions are SLOT-LOCAL by construction (per-slot
# quantization scales, per-slot softmax): a slot's output bits depend only
# on its own row content — the property that makes step-granular join/
# leave bit-identical to running the same engine one request at a time.


def _paged_slot_scales(q, pool_k, ppos, table, bits: int):
    """Per-SLOT affine scales for the quantized paged dot.

    s_q[b] from slot b's own query rows; s_k[b] from slot b's gathered K
    masked by ``ppos >= 0`` — stale content in freed-and-reused pages (and
    the null page) can never perturb a live slot's scale."""
    z = float(1 << (bits - 1))
    s_q = jnp.max(jnp.abs(q).astype(jnp.float32), axis=(1, 2, 3)) / z + 1e-12
    kg = jnp.abs(pool_k[table]).astype(jnp.float32)    # (B, P, ps, Hkv, hd)
    valid = (ppos[table] >= 0)[..., None, None]
    s_k = jnp.max(jnp.where(valid, kg, 0.0), axis=(1, 2, 3, 4)) / z + 1e-12
    return s_q, s_k


def _paged_expand_idx(n_q_real: int, n_q_padded: int, hkv: int):
    """GQA head map for the gathered KV (layers.expand_kv's rule, inlined —
    importing it from models.layers would be circular)."""
    g = max(n_q_real // hkv, 1)
    return jnp.minimum(jnp.arange(n_q_padded) // g, hkv - 1)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "quantized", "bits", "n_q_heads"))
def attn_paged_xla(q, pool_k, pool_v, ppos, table, q_pos, *,
                   causal: bool = True, window: Optional[int] = None,
                   quantized: bool = False, bits: int = 8,
                   n_q_heads: Optional[int] = None) -> jax.Array:
    """Gather realization of paged attention (CPU/GPU engine; the oracle
    for the Pallas kernel).

    q (B, S, Hp, hd); pool_k/pool_v (NP+1, ps, Hkv, hd); ppos (NP+1, ps);
    table (B, P) page indices; q_pos (B, S) absolute query positions with
    -1 marking invalid (padding) rows.  Logits are materialized at
    (B, Hp, S, P*ps) — the paged geometries are decode steps and prefill
    chunks, so S and P*ps are both small by design.
    """
    B, S, Hp, hd = q.shape
    NP1, ps, Hkv, _ = pool_k.shape
    P = table.shape[1]
    n_q = n_q_heads or Hp
    kg = pool_k[table].reshape(B, P * ps, Hkv, hd)
    vg = pool_v[table].reshape(B, P * ps, Hkv, hd)
    pos_g = ppos[table].reshape(B, P * ps)
    if quantized:
        if not flash_levels_exact(hd, bits, bits):
            raise ValueError(
                f"paged centered-level dot inexact at head_dim={hd}, "
                f"bits={bits}")
        z = float(1 << (bits - 1))
        s_q, s_k = _paged_slot_scales(q, pool_k, ppos, table, bits)
        qc = _levels(q, s_q[:, None, None, None], bits) - z
        kc = _levels(kg, s_k[:, None, None, None], bits) - z
    else:
        qc = q.astype(jnp.float32)
        kc = kg.astype(jnp.float32)
    if Hkv != Hp:
        idx = _paged_expand_idx(n_q, Hp, Hkv)
        kc = jnp.take(kc, idx, axis=2)
        vg = jnp.take(vg, idx, axis=2)
    logits = jnp.einsum("bqhd,bshd->bhqs", qc, kc,
                        preferred_element_type=jnp.float32)
    if quantized:
        logits = logits * (s_q * s_k / math.sqrt(hd))[:, None, None, None]
    else:
        logits = logits / math.sqrt(hd)
    m = (pos_g >= 0)[:, None, None, :]
    if causal:
        m = m & (pos_g[:, None, None, :] <= q_pos[:, None, :, None])
    if window is not None:
        m = m & (pos_g[:, None, None, :] > q_pos[:, None, :, None] - window)
    logits = jnp.where(m, logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqs,bshd->bqhd", p, vg.astype(jnp.float32))
    return out.astype(q.dtype)


def paged_block_pages(page_size: int, table_pages: int) -> int:
    """Pages :func:`attn_paged_pallas` takes per grid step: enough for one
    128-key lane block (8 pages of 16 tokens), at most the whole table.
    A slot's causal call computes the blocks up to the one holding its
    last valid query position ``t``: ``t // (page_size * k) + 1`` of
    them, 0 for a slot with no valid row (``t = -1``)."""
    return max(1, min(128 // page_size, table_pages))


def _paged_kernel(tbl_ref, live_ref, scal_ref, zint_ref, qpos_ref, q_ref,
                  *refs, bits, causal, window, n_q_heads, n_blocks,
                  block_pages):
    """One (slot b, table block j) grid step: ``block_pages`` pages side
    by side on the key axis.

    The KV BlockSpecs are *page-indexed through the scalar-prefetched
    table* (one operand per page of the block, ``tbl[b, j*k + i]``), so the
    kernel sees slot b's pages as contiguous head-major blocks; the null
    page arrives fully masked (its positions are all -1).  Blocks past the
    slot's last live one (``live[b]``) repeat that block's indices — no
    DMA — and skip their compute: every key there lies past the slot's
    last query, which the causal mask already zeroes, so online softmax
    would leave (m, l, acc) unchanged bit for bit.  Online-softmax scratch
    is carried across the block dimension, one (S, 128)/(S, hd) band per
    query head.
    """
    k_refs = refs[:block_pages]
    v_refs = refs[block_pages: 2 * block_pages]
    pos_ref, o_ref, m_ref, l_ref, acc_ref = refs[2 * block_pages:]
    b, j = pl.program_id(0), pl.program_id(1)
    Hp, S, hd = q_ref.shape[1], q_ref.shape[2], q_ref.shape[3]
    Hkv = k_refs[0].shape[1]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j < live_ref[b])
    def _block():
        z_q, z_k = zint_ref[0], zint_ref[1]
        scal = scal_ref[b]
        pos = pos_ref[0, 0]               # (1, k*ps) key positions, -1 dead
        iq = qpos_ref[0]                  # (S, 1) query positions, -1 dead
        kw = pos.shape[1]
        msk = jnp.broadcast_to(pos >= 0, (S, kw))
        if causal:
            msk &= pos <= iq
        if window is not None:
            msk &= pos > iq - window
        # each KV head's block once: levels, nibbles, row sums, values
        ks, vs = [], []
        for h in range(Hkv):
            kl = jnp.concatenate([r[0, h] for r in k_refs], axis=0)
            ks.append(([(gk.astype(jnp.int8), sk)
                        for gk, sk in _nibble_split(kl, bits)],
                       jnp.sum(kl, axis=1)))
            vs.append(jnp.concatenate(
                [r[0, h].astype(jnp.float32) for r in v_refs], axis=0))

        g = max(n_q_heads // Hkv, 1)
        for jq in range(Hp):              # unrolled: Hp is small & static
            jkv = min(jq // g, Hkv - 1)
            k_nib, rs_k = ks[jkv]
            ql = q_ref[0, jq]                          # (S, hd) levels
            acc = jnp.zeros((S, kw), jnp.int32)
            for gq, sq in _nibble_split(ql, bits):
                for gk, sk in k_nib:
                    d = jax.lax.dot_general(
                        gq.astype(jnp.int8), gk,
                        dimension_numbers=(((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.int32)
                    acc += d << (sq + sk)
            rs_q = jnp.sum(ql, axis=1)
            corr = (acc - z_k * rs_q[:, None] - z_q * rs_k[None, :]
                    + hd * z_q * z_k)
            logits = jnp.where(msk, corr.astype(jnp.float32) * scal, NEG_INF)

            m_old = m_ref[jq, :, :1]
            m_new = jnp.maximum(m_old, jnp.max(logits, axis=1, keepdims=True))
            pw = jnp.exp(logits - m_new) * msk
            cf = jnp.exp(m_old - m_new)
            l_new = l_ref[jq, :, :1] * cf + jnp.sum(pw, axis=1, keepdims=True)
            acc_ref[jq] = acc_ref[jq] * cf + jax.lax.dot_general(
                pw, vs[jkv], dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[jq] = jnp.broadcast_to(m_new, (S, 128))
            l_ref[jq] = jnp.broadcast_to(l_new, (S, 128))

    @pl.when(j == n_blocks - 1)
    def _epilogue():
        for jq in range(Hp):
            l = jnp.maximum(l_ref[jq, :, :1], 1e-30)
            o_ref[0, jq] = (acc_ref[jq] / l).astype(o_ref.dtype)


def attn_paged_pallas(q, pool_k, pool_v, ppos, table, q_pos, *,
                      causal: bool = True, window: Optional[int] = None,
                      bits: int = 8, n_q_heads: Optional[int] = None,
                      interpret: bool = False) -> jax.Array:
    """Pallas realization (quantized path only; shapes as
    :func:`attn_paged_xla`).

    ``PrefetchScalarGridSpec`` prefetches the page table and each slot's
    live block count, so the KV BlockSpec index maps select pages
    *through* the table — the gather never materializes on the host side
    of the kernel.  The grid is ``(B, ceil(P / k))`` with ``k =
    paged_block_pages(ps, P)`` pages a step; the table is padded with the
    null page (index NP) to a whole number of blocks.  A causal call
    computes a slot's blocks up to the one that holds its last valid query
    position — the keys past it are all masked, and the engine writes
    position ``t`` only into column ``t // ps`` — and none for a slot with
    no valid row; a non-causal call computes the whole table.  Per-slot
    scales are a cheap host prepass: s_k is scattered onto the pages
    through the table (each real page has exactly one owner; the null
    page's winner is irrelevant — its ppos keeps it fully masked).

    Every block's last two dims are whole array dims, the TPU tiling rule
    for blocks narrower than (8, 128): queries and pages go head-major
    (``(.., H, S|ps, hd)``), query positions a column ``(B, S, 1)``, the
    key positions of each table block a row ``(B, nblk, 1, k*ps)``.
    """
    B, S, Hp, hd = q.shape
    NP1, ps, Hkv, _ = pool_k.shape
    P = table.shape[1]
    if not flash_levels_exact(hd, bits, bits):
        raise ValueError(
            f"paged centered-level dot inexact at head_dim={hd}, bits={bits}")
    z = float(1 << (bits - 1))
    table = table.astype(jnp.int32)
    q_pos = q_pos.astype(jnp.int32)
    s_q, s_k = _paged_slot_scales(q, pool_k, ppos, table, bits)
    page_scale = jnp.ones((NP1,), jnp.float32).at[table.reshape(-1)].set(
        jnp.repeat(s_k, P), mode="drop")
    ql = _levels(q, s_q[:, None, None, None], bits).astype(jnp.int32)
    kl = _levels(pool_k, page_scale[:, None, None, None], bits
                 ).astype(jnp.int32)
    scal = (s_q * s_k / math.sqrt(hd)).astype(jnp.float32)       # (B,)
    zint = jnp.asarray([int(z), int(z)], jnp.int32)

    k = paged_block_pages(ps, P)
    nblk = -(-P // k)
    tbl = jnp.pad(table, ((0, 0), (0, nblk * k - P)), constant_values=NP1 - 1)
    if causal:
        live = jnp.minimum(jnp.max(q_pos, axis=1) // (ps * k) + 1, nblk)
    else:
        live = jnp.full((B,), nblk, jnp.int32)
    kpos = ppos[tbl].reshape(B, nblk, 1, k * ps)

    kernel = functools.partial(
        _paged_kernel, bits=bits, causal=causal, window=window,
        n_q_heads=n_q_heads or Hp, n_blocks=nblk, block_pages=k)

    # index maps take the grid indices first, the prefetched scalars last;
    # past a slot's last live block they repeat it, so no DMA is issued
    def block(b, j, live):
        return jnp.minimum(j, jnp.maximum(live[b] - 1, 0))

    def page(i):
        return lambda b, j, tbl, live: (tbl[b, block(b, j, live) * k + i],
                                        0, 0, 0)

    kv_specs = [pl.BlockSpec((1, Hkv, ps, hd), page(i)) for i in range(k)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, nblk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),                # scal (B,)
            pl.BlockSpec(memory_space=pltpu.SMEM),                # zint (2,)
            pl.BlockSpec((1, S, 1), lambda b, j, tbl, live: (b, 0, 0)),
            pl.BlockSpec((1, Hp, S, hd),
                         lambda b, j, tbl, live: (b, 0, 0, 0)),
            *kv_specs,
            *kv_specs,
            pl.BlockSpec((1, 1, 1, k * ps), lambda b, j, tbl, live: (
                b, block(b, j, live), 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, Hp, S, hd),
                               lambda b, j, tbl, live: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Hp, S, 128), jnp.float32),
            pltpu.VMEM((Hp, S, 128), jnp.float32),
            pltpu.VMEM((Hp, S, hd), jnp.float32),
        ],
    )
    kt = kl.transpose(0, 2, 1, 3)
    vt = pool_v.transpose(0, 2, 1, 3)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hp, S, hd), q.dtype),
        name="attn_paged",
        interpret=interpret,
    )(tbl, live, scal, zint, q_pos.reshape(B, S, 1),
      ql.transpose(0, 2, 1, 3), *([kt] * k), *([vt] * k), kpos)
    return out.transpose(0, 2, 1, 3)


def attn_paged(q, pool_k, pool_v, ppos, table, q_pos, *,
               causal: bool = True, window: Optional[int] = None,
               quantized: bool = False, bits: int = 8,
               n_q_heads: Optional[int] = None) -> jax.Array:
    """Backend-dispatched paged attention (the ``paged`` engine entry):
    native Pallas kernel on TPU when quantized, the gather realization
    elsewhere (and always for fp configs — the Pallas kernel is the
    integer-levels path)."""
    n_q = n_q_heads or q.shape[2]
    if quantized and jax.default_backend() == "tpu":
        return attn_paged_pallas(q, pool_k, pool_v, ppos, table, q_pos,
                                 causal=causal, window=window, bits=bits,
                                 n_q_heads=n_q, interpret=False)
    return attn_paged_xla(q, pool_k, pool_v, ppos, table, q_pos,
                          causal=causal, window=window, quantized=quantized,
                          bits=bits, n_q_heads=n_q)


def attn_flash(q, k, v, *, causal: bool = True, window: Optional[int] = None,
               q_bits: int = 8, k_bits: int = 8,
               block_q: Optional[int] = None,
               block_kv: Optional[int] = None) -> jax.Array:
    """Backend-dispatched quantized flash attention (the engine entry):
    native Pallas kernel on TPU, the exact XLA realization elsewhere.

    ``block_q/block_kv=None`` takes each realization's tuned default
    (MXU-sized 1024 for the Pallas grid; cache-sized 512 for the XLA
    scan — measured on the S=32k CPU sweep, ``benchmarks/bench_attn.py``).
    """
    if jax.default_backend() == "tpu":
        return attn_flash_pallas(q, k, v, causal=causal, window=window,
                                 q_bits=q_bits, k_bits=k_bits,
                                 block_q=block_q or 1024,
                                 block_kv=block_kv or 1024,
                                 interpret=False)
    return attn_flash_xla(q, k, v, causal=causal, window=window,
                          q_bits=q_bits, k_bits=k_bits,
                          block_q=block_q or 512, block_kv=block_kv or 512)
