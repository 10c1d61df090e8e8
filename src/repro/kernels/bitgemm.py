"""Pallas TPU kernel: paper-faithful packed AND + popcount bit-GEMM.

Dataflow (paper Fig. 3, TPU-adapted per DESIGN.md §2):
  * activations / weights arrive as bit-planes packed 32/lane in uint32
    along the contraction axis K (``Kw = K/32`` words);
  * one grid step loads an (m, TM, TKw) activation tile and an
    (n, TN, TKw) weight tile into VMEM;
  * for every plane pair (m,n): VPU AND -> ``population_count`` (the 4:2
    compressor tree analogue) -> lane-sum -> ``<< (m+n)`` (the ASR
    analogue, a static integer weight) -> accumulate into the int32 out
    tile, revisited across the K grid dimension.

This kernel exists to make the paper's exact dataflow measurable on TPU;
`bitgemm_mxu.py` is the beyond-paper MXU mapping that wins on roofline
(see EXPERIMENTS.md §Perf hillclimb #1).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# VMEM budget per tile (see DESIGN.md): the (TM, TN, TKw) AND intermediate
# dominates: 64*128*128*4B = 4 MiB, under v5e's 16 MiB scoped VMEM with
# double-buffered inputs (m,64,128)+(n,128,128) uint32 tiles.  TN and TKw
# are the TPU's 128-lane minimum: Mosaic refuses narrower block widths.
TM, TN, TKW = 64, 128, 128


def _kernel(a_ref, w_ref, o_ref, *, a_bits: int, w_bits: int):
    """a_ref (a_bits, TM, TKw) u32 | w_ref (w_bits, TN, TKw) u32 | o (TM,TN) i32."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    acc = jnp.zeros((o_ref.shape[0], o_ref.shape[1]), jnp.int32)
    for m in range(a_bits):
        a_pl = a_ref[m]                                # (TM, TKw) uint32
        for n in range(w_bits):
            w_pl = w_ref[n]                            # (TN, TKw) uint32
            anded = a_pl[:, None, :] & w_pl[None, :, :]  # row-parallel AND
            cmp = jax.lax.population_count(anded).astype(jnp.int32)
            acc = acc + (jnp.sum(cmp, axis=-1) << (m + n))
    o_ref[...] += acc


def _pad(x, mult, axis):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    cfg = [(0, 0)] * x.ndim
    cfg[axis] = (0, pad)
    return jnp.pad(x, cfg)


@functools.partial(
    jax.jit, static_argnames=("a_bits", "w_bits", "interpret", "tm", "tn", "tkw")
)
def bitgemm_packed_pallas(
    a_planes: jax.Array,  # (a_bits, M, Kw) uint32
    w_planes: jax.Array,  # (w_bits, N, Kw) uint32  (weights pre-transposed)
    *,
    a_bits: int,
    w_bits: int,
    interpret: bool = False,
    tm: int = TM,
    tn: int = TN,
    tkw: int = TKW,
) -> jax.Array:
    """Returns (M, N) int32 == sum_k popcount(a & w) weighted by 2^(m+n)."""
    _, M, Kw = a_planes.shape
    _, N, _ = w_planes.shape
    a_p = _pad(_pad(a_planes, tm, 1), tkw, 2)
    w_p = _pad(_pad(w_planes, tn, 1), tkw, 2)
    Mp, Kwp, Np = a_p.shape[1], a_p.shape[2], w_p.shape[1]
    grid = (Mp // tm, Np // tn, Kwp // tkw)
    out = pl.pallas_call(
        functools.partial(_kernel, a_bits=a_bits, w_bits=w_bits),
        grid=grid,
        in_specs=[
            pl.BlockSpec((a_bits, tm, tkw), lambda i, j, k: (0, i, k)),
            pl.BlockSpec((w_bits, tn, tkw), lambda i, j, k: (0, j, k)),
        ],
        out_specs=pl.BlockSpec((tm, tn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), jnp.int32),
        name="bitgemm",
        interpret=interpret,
    )(a_p, w_p)
    return out[:M, :N]
