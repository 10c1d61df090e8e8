"""Quantized flash attention (kernels/attn_flash) + attention dispatch.

Pins five contracts:

* **Exactness vs the quantization** — both realizations (Pallas
  interpret-mode and the XLA engine) are *bit-faithful* to the reference
  "quantize q/k, full softmax attention on the dequantized logits"
  computation across bit widths, masking variants, and GQA: the only
  approximation the flash engine introduces is the documented affine
  quantization of q/k, never the tiling.
* **Closeness to unquantized attention** — within a bits-dependent
  empirical bound (the worst case is :func:`flash_error_bound`).
* **Chunked-skip bit-identity** — skipping fully-masked kv chunks leaves
  ``attn_chunked`` bit-identical to the compute-and-zero dataflow.
* **Chunk-plan padding** — awkward sequence lengths (S=1021) keep a
  bounded chunk count instead of degenerating to a 1021-step scan.
* **Plan carriage** — ``compile_lm`` resolves the attention engine once,
  serializes it, and a reloaded plan dispatches it by table lookup.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels.attn_flash import (attn_flash_pallas, attn_flash_xla,
                                      attn_paged_pallas, attn_paged_xla,
                                      attn_quant_scale, flash_error_bound,
                                      flash_levels_exact, _levels)
from repro.models.layers import (_chunk_plan, _mask, attn_banded,
                                 attn_chunked, attn_full, expand_kv)


@pytest.fixture(autouse=True)
def _clean_dispatch_state():
    ops.clear_plan_state()
    yield
    ops.clear_plan_state()


def _qkv(S, heads=3, hd=16, batch=2, kv_heads=None, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (batch, S, heads, hd), jnp.float32)
    k = jax.random.normal(ks[1], (batch, S, kv_heads or heads, hd),
                          jnp.float32)
    v = jax.random.normal(ks[2], (batch, S, kv_heads or heads, hd),
                          jnp.float32)
    return q, k, v


def _ref_quant_full(q, k, v, *, causal, window, q_bits, k_bits):
    """Quantize q/k exactly as the kernel does, then plain full attention
    on the dequantized logits — the kernel's ground truth."""
    hd = q.shape[-1]
    s_q, z_q = attn_quant_scale(q, q_bits)
    s_k, z_k = attn_quant_scale(k, k_bits)
    qd = (_levels(q, s_q, q_bits) - z_q) * s_q
    kd = (_levels(k, s_k, k_bits) - z_k) * s_k
    pos = jnp.arange(q.shape[1])
    return attn_full(qd, kd, v, causal=causal, window=window,
                     q_pos=pos, kv_pos=pos)


CASES = [(8, 8), (4, 4), (8, 4)]
MASKS = [(True, None), (False, None), (True, 24)]


@pytest.mark.parametrize("q_bits,k_bits", CASES)
@pytest.mark.parametrize("causal,window", MASKS)
def test_flash_faithful_to_quantized_reference(q_bits, k_bits, causal,
                                               window):
    """Tiling is exact: both realizations match the quantize-then-full
    reference to f32 summation-order noise, including non-multiple S
    (padding) and boundary blocks."""
    q, k, v = _qkv(100)
    ref = _ref_quant_full(q, k, v, causal=causal, window=window,
                          q_bits=q_bits, k_bits=k_bits)
    for fn in (attn_flash_xla,
               functools.partial(attn_flash_pallas, interpret=True)):
        out = fn(q, k, v, causal=causal, window=window, q_bits=q_bits,
                 k_bits=k_bits, block_q=32, block_kv=32)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=0)


@pytest.mark.parametrize("q_bits,k_bits", CASES)
def test_flash_gqa_expanded_kv(q_bits, k_bits):
    """GQA serve shape: kv expanded onto TP-padded query heads before the
    kernel (6 padded q heads over 2 kv heads, 4 real)."""
    q, k, v = _qkv(64, heads=6, kv_heads=2, seed=3)
    ke, ve = expand_kv(k, v, 4, 6)
    ref = _ref_quant_full(q, ke, ve, causal=True, window=None,
                          q_bits=q_bits, k_bits=k_bits)
    out = attn_flash_xla(q, ke, ve, causal=True, window=None,
                         q_bits=q_bits, k_bits=k_bits, block_q=32,
                         block_kv=32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=0)


@pytest.mark.parametrize("q_bits,k_bits,tol", [(8, 8, 0.12), (4, 4, 0.9),
                                               (8, 4, 0.6)])
def test_flash_close_to_unquantized(q_bits, k_bits, tol):
    """Documented exactness bound: the only error vs full-precision
    attention is the q/k quantization (worst case flash_error_bound on
    the logits; the output deviation is far smaller in practice)."""
    q, k, v = _qkv(128, seed=5)
    pos = jnp.arange(128)
    ref = attn_full(q, k, v, causal=True, window=None, q_pos=pos,
                    kv_pos=pos)
    out = attn_flash_xla(q, k, v, causal=True, window=None, q_bits=q_bits,
                         k_bits=k_bits, block_q=64, block_kv=64)
    assert flash_error_bound(q, k, q_bits, k_bits) > 0
    assert float(jnp.max(jnp.abs(out - ref))) < tol


def test_flash_levels_exact_bound():
    assert flash_levels_exact(256, 8, 8)      # every supported head dim
    assert not flash_levels_exact(1024, 8, 8)
    with pytest.raises(ValueError, match="inexact"):
        q, k, v = _qkv(32, hd=1024, heads=1, batch=1)
        attn_flash_xla(q, k, v)


# ---------------------------------------------------------------------------
# attn_chunked: skip + chunk-plan satellites
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("causal,window", [(True, None), (True, 48),
                                           (False, None)])
def test_chunked_skip_bit_identity(causal, window):
    """Skipping a fully-masked kv chunk leaves the carry untouched, which
    is bit-identical to computing it (its mask zeroes every weight)."""
    q, k, v = _qkv(256, seed=7)
    pos = jnp.arange(256)
    kw = dict(causal=causal, window=window, q_pos=pos, kv_pos=pos,
              q_chunk=64, kv_chunk=64)
    skip = attn_chunked(q, k, v, skip_masked=True, **kw)
    dense = attn_chunked(q, k, v, skip_masked=False, **kw)
    np.testing.assert_array_equal(np.asarray(skip), np.asarray(dense))
    ref = attn_full(q, k, v, causal=causal, window=window, q_pos=pos,
                    kv_pos=pos)
    np.testing.assert_allclose(np.asarray(skip), np.asarray(ref),
                               atol=2e-5, rtol=0)


def test_chunk_plan_stays_bounded():
    """S=1021 used to degenerate to chunk=1 (a 1021-step scan); the padded
    plan keeps the chunk at the target."""
    assert _chunk_plan(1021, 256) == (256, 1024)
    assert _chunk_plan(1021, 1024) == (1021, 1021)
    assert _chunk_plan(32768 + 256, 1024) == (1024, 33792)
    q, k, v = _qkv(1021, seed=9)
    pos = jnp.arange(1021)
    out = attn_chunked(q, k, v, causal=True, window=None, q_pos=pos,
                       kv_pos=pos, q_chunk=256, kv_chunk=256)
    ref = attn_full(q, k, v, causal=True, window=None, q_pos=pos,
                    kv_pos=pos)
    assert out.shape == ref.shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=0)


# ---------------------------------------------------------------------------
# Attention edge cases the new kernel must honor (satellite coverage)
# ---------------------------------------------------------------------------

def test_banded_ragged_and_oversized_window():
    q, k, v = _qkv(100, seed=11)
    pos = jnp.arange(100)
    # Sq not a multiple of W
    for W in (32, 256):  # 100 % 32 != 0; window 256 > S
        ref = attn_full(q, k, v, causal=True, window=W, q_pos=pos,
                        kv_pos=pos)
        out = attn_banded(q, k, v, window=W, q_pos=pos, kv_pos=pos)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=0)


def test_mask_negative_kv_positions():
    iq = jnp.asarray([0, 1, 5])
    jk = jnp.asarray([-1, 0, 3, -1])
    m = np.asarray(_mask(iq, jk, True, None))
    assert not m[:, 0].any() and not m[:, 3].any()  # invalid slots
    assert m[2, 2] and not m[1, 2]                  # causal on the rest
    mw = np.asarray(_mask(iq, jk, True, 2))
    assert mw[1, 1] and not mw[2, 1]                # window lower bound


def test_expand_kv_tp_padded_heads():
    q, k, v = _qkv(8, heads=2, kv_heads=2, seed=13)
    ke, ve = expand_kv(k, v, 4, 6)  # 4 real q heads padded to 6, 2 kv
    assert ke.shape[2] == 6
    # real heads map in groups of g=2; padded heads reuse the last kv head
    for j, src in enumerate([0, 0, 1, 1, 1, 1]):
        np.testing.assert_array_equal(np.asarray(ke[:, :, j]),
                                      np.asarray(k[:, :, src]))


# ---------------------------------------------------------------------------
# Paged attention: the Pallas kernel against its gather realization
# ---------------------------------------------------------------------------

def _paged_problem(S, heads, kv_heads, hd=16, ps=8, ctx=(12, 5), P=3,
                   spare=None, seed=0):
    """Slots over a shuffled page pool whose last page is the null page.

    Slot b has positions ``0..ctx[b]-1`` written across ``ceil(ctx[b]/ps)``
    pages (the last one ragged; ``ctx[b] = 0`` is an idle slot with no
    valid query row), then ``spare[b]`` more pages in its table row that
    hold a stale tenant's K/V and the positions of their own columns — all
    past the slot's context, which causality must hide.  Four more pages
    are owned by no table and hold stale K/V and positions the table must
    never expose.  Rows pad to ``P`` columns with the null page.  The
    queries are the last ``S`` positions of each context; rows before
    position 0 are invalid (-1)."""
    B = len(ctx)
    spare = spare or (0,) * B
    live = [-(-c // ps) for c in ctx]
    owned = sum(live) + sum(spare)
    n_pages = owned + 4
    null = n_pages
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    pool_k = jax.random.normal(ks[0], (n_pages + 1, ps, kv_heads, hd))
    pool_v = jax.random.normal(ks[1], (n_pages + 1, ps, kv_heads, hd))
    rng = np.random.RandomState(seed)
    order = iter(rng.permutation(n_pages))
    ppos = np.full((n_pages + 1, ps), -1, np.int32)
    table = np.full((B, P), null, np.int32)
    for b in range(B):
        for col in range(live[b] + spare[b]):
            page = table[b, col] = next(order)
            span = np.arange(col * ps, (col + 1) * ps)
            ppos[page] = np.where(span < ctx[b], span, -1) if col < live[b] \
                else span
    for page in order:                 # unowned stale tenants
        ppos[page] = rng.randint(0, 4 * ps, size=ps)
    last = np.asarray(ctx, np.int32) - 1
    q_pos = last[:, None] - (S - 1) + np.arange(S, dtype=np.int32)[None]
    q_pos = np.where(q_pos >= 0, q_pos, -1)
    q = jax.random.normal(ks[2], (B, S, heads, hd))
    return q, pool_k, pool_v, jnp.asarray(ppos), jnp.asarray(table), \
        jnp.asarray(q_pos)


# Paged geometries beside the base one (two slots, 8-token pages, a 3-page
# table): 16-token pages take 8 pages a kernel block, 8-token pages 16.
PAGED_GEOMETRIES = {
    "base": {},
    # an idle slot; a 10-page table, not a multiple of the 8-page block
    "idle-slot": dict(ps=16, P=10, ctx=(0, 40, 150)),
    # live pages ending exactly at a block edge (8) and mid-block (13)
    "block-edge": dict(ps=16, P=20, ctx=(128, 200)),
    # stale pages past the live bound: slot 0's reach into a skipped block
    "stale-past-live": dict(ps=16, P=20, ctx=(70, 150), spare=(5, 2)),
    # 8-token pages: 16 a block; a block edge, mid-block, an idle slot
    "page-8": dict(ps=8, P=40, ctx=(128, 300, 0), spare=(3, 0, 0)),
}


@pytest.mark.parametrize("S,heads,kv_heads,geom", [
    pytest.param(1, 4, 2, "base", id="1-4-2"),
    pytest.param(4, 3, 3, "base", id="4-3-3"),
    pytest.param(4, 6, 2, "base", id="4-6-2"),
    pytest.param(1, 3, 1, "idle-slot", id="1-3-1-idle-slot"),
    pytest.param(4, 6, 2, "idle-slot", id="4-6-2-idle-slot"),
    pytest.param(1, 4, 2, "block-edge", id="1-4-2-block-edge"),
    pytest.param(4, 3, 3, "block-edge", id="4-3-3-block-edge"),
    pytest.param(1, 6, 2, "stale-past-live", id="1-6-2-stale-past-live"),
    pytest.param(4, 4, 2, "stale-past-live", id="4-4-2-stale-past-live"),
    pytest.param(1, 4, 2, "page-8", id="1-4-2-page-8"),
    pytest.param(4, 3, 1, "page-8", id="4-3-1-page-8"),
])
def test_paged_pallas_matches_gather_realization(S, heads, kv_heads, geom):
    """The Pallas paged kernel (interpret mode) computes the same
    attention as ``attn_paged_xla``: exact int32 logits, so only the
    online softmax's f32 summation order separates them — decode (S=1)
    and prefill-chunk (S=4) shapes, GQA, ragged pages, null-page padding,
    stale unowned pages, stale pages past a slot's live bound, idle
    slots, live pages ending on and between block edges, and tables that
    are not a whole number of blocks.  Invalid query rows (an idle slot's)
    have no softmax to compare; the kernel writes them as zeros."""
    args = _paged_problem(S, heads, kv_heads, **PAGED_GEOMETRIES[geom])
    ref = attn_paged_xla(*args, quantized=True, bits=8, n_q_heads=heads)
    out = attn_paged_pallas(*args, bits=8, n_q_heads=heads, interpret=True)
    assert out.shape == ref.shape
    valid = np.asarray(args[-1]) >= 0
    np.testing.assert_allclose(np.asarray(out)[valid], np.asarray(ref)[valid],
                               atol=2e-5, rtol=0)
    assert not np.asarray(out)[~valid].any()


def test_paged_pallas_slot_output_independent_of_other_slots():
    """A slot's output bits do not depend on the other slots' lengths:
    idle, shortened (fewer live blocks over the same pages) or longer
    than it, with other queries."""
    q, pk, pv, ppos, table, q_pos = _paged_problem(
        1, 6, 2, **PAGED_GEOMETRIES["page-8"])
    base = attn_paged_pallas(q, pk, pv, ppos, table, q_pos, bits=8,
                             n_q_heads=6, interpret=True)
    null = pk.shape[0] - 1
    idle = (table.at[1:].set(null), q_pos.at[1:].set(-1))
    short = (table, q_pos.at[1:].set(jnp.asarray([[5], [200]])))
    q2 = q.at[1:].set(jax.random.normal(jax.random.PRNGKey(9), q[1:].shape))
    for tbl, qp in (idle, short):
        out = attn_paged_pallas(q2, pk, pv, ppos, tbl, qp, bits=8,
                                n_q_heads=6, interpret=True)
        np.testing.assert_array_equal(np.asarray(out[0]),
                                      np.asarray(base[0]))


# ---------------------------------------------------------------------------
# Plan carriage: compile_lm resolves, serializes, reload dispatches
# ---------------------------------------------------------------------------

def _lm_cfg():
    from repro.configs import all_configs
    from repro.core.quant import W1A8

    return dataclasses.replace(
        all_configs()["smollm-360m"].smoke(
            n_layers=2, d_model=64, n_heads=2, n_kv_heads=1, d_ff=128,
            vocab=64, head_dim=32),
        quant=dataclasses.replace(W1A8, engine="auto"))


def test_lm_plan_carries_attention_engine(tmp_path):
    from repro.configs import SINGLE
    from repro.core import plan as P
    from repro.models import transformer as T

    cfg = _lm_cfg()
    params, _ = T.init_lm(jax.random.PRNGKey(0), cfg, SINGLE)
    plan = P.compile_lm(params, cfg, backend="cpu", batch_hints=(1,),
                        prompt_len=8192)
    rows = [lp for lp in plan.layers if lp.op == "attn"]
    assert rows and all(lp.attn_engine == lp.engine for lp in rows)
    # quantized W1A8 serve at S=8192 resolves the flash engine
    assert plan.attn_table and set(plan.attn_table.values()) == {"flash"}
    # round trip: the verdict survives serialization
    plan2 = P.load_plan(P.save_plan(plan, str(tmp_path / "attnplan")))
    assert plan2.attn_table == plan.attn_table
    assert [lp.attn_engine for lp in plan2.layers] == \
           [lp.attn_engine for lp in plan.layers]
    # an active plan turns dispatch into a table lookup (and overrides the
    # heuristic: the same geometry resolves "chunked" once we pin it)
    key = next(iter(plan.attn_table))
    attn = ops.AttnShape(seq_q=key[1], seq_kv=key[1], heads=key[2],
                         head_dim=key[3], causal=key[4],
                         window=key[5] or None, quantized=key[6])
    with plan2.activate():
        assert ops.select_attn_engine(attn, "cpu") == "flash"
        pinned = dataclasses.replace(plan2)
        pinned.attn_table = {key: "chunked"}
        with pinned.activate():
            assert ops.select_attn_engine(attn, "cpu") == "chunked"
        assert ops.select_attn_engine(attn, "cpu") == "flash"
    assert ops.select_attn_engine(attn, "cpu") == "flash"  # heuristic


def test_attention_fwd_flash_dispatch():
    """Layer-level integration: attention_fwd with the flash engine stays
    within quantization error of the full engine on the serve path."""
    from repro.configs import SINGLE
    from repro.models.layers import attention_fwd, init_attention

    cfg = _lm_cfg()
    p, _ = init_attention(jax.random.PRNGKey(0), cfg, SINGLE)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, cfg.d_model))
    full, _ = attention_fwd(p, x, cfg, SINGLE, mode="train",
                            engine="full", qmode="serve")
    flash, _ = attention_fwd(p, x, cfg, SINGLE, mode="train",
                             engine="flash", qmode="serve")
    chunk, _ = attention_fwd(p, x, cfg, SINGLE, mode="train",
                             engine="chunked", qmode="serve")
    assert float(jnp.max(jnp.abs(flash - full))) < 0.35
    np.testing.assert_allclose(np.asarray(chunk), np.asarray(full),
                               atol=2e-4, rtol=0)


def test_resolve_attn_engine_thresholds():
    from repro.models.layers import resolve_attn_engine

    cfg = _lm_cfg()
    kw = dict(heads=2, causal=True, window=None)
    r = resolve_attn_engine
    assert r(cfg, seq_q=64, seq_kv=64, **kw) == "full"
    assert r(cfg, seq_q=8192, seq_kv=8192, **kw) == "chunked"
    assert r(cfg, seq_q=8192, seq_kv=8192, qmode="serve", **kw) == "flash"
    # train numerics never change: flash requires the quantized serve path
    assert r(cfg, seq_q=8192, seq_kv=8192, qmode="train", **kw) == "chunked"
    fp = dataclasses.replace(cfg, quant=dataclasses.replace(
        cfg.quant, engine="fp"))
    assert r(fp, seq_q=8192, seq_kv=8192, qmode="serve", **kw) == "chunked"
    full = dataclasses.replace(cfg, full_attn_analysis=True)
    assert r(full, seq_q=8192, seq_kv=8192, qmode="serve", **kw) == "full"
