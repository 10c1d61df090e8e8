"""Pallas kernel validation: shape/dtype sweeps vs ref.py oracles
(interpret=True executes the kernel body on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref

SHAPES = [(5, 70, 9), (17, 130, 33), (64, 64, 64), (3, 33, 5), (130, 600, 140),
          (1, 1, 1), (128, 512, 128)]
BITS = [(1, 1), (4, 1), (8, 2), (2, 2), (4, 3)]


@pytest.mark.parametrize("M,K,N", SHAPES)
@pytest.mark.parametrize("ab,wb", BITS[:3])
def test_bitgemm_faithful_vs_ref(M, K, N, ab, wb):
    k1, k2 = jax.random.split(jax.random.PRNGKey(M * 1000 + K + N))
    a_lv = jax.random.randint(k1, (M, K), 0, 1 << ab).astype(jnp.int32)
    w_lv = jax.random.randint(k2, (K, N), 0, 1 << wb).astype(jnp.int32)
    gold = np.asarray(ref.bitgemm_ref(a_lv, w_lv, ab, wb))
    out = np.asarray(ops.bitgemm_faithful(a_lv, w_lv, ab, wb, interpret=True))
    assert (out == gold).all()


@pytest.mark.parametrize("M,K,N", SHAPES)
@pytest.mark.parametrize("ab,wb", BITS)
def test_bitgemm_mxu_vs_ref(M, K, N, ab, wb):
    k1, k2 = jax.random.split(jax.random.PRNGKey(M + K * 7 + N))
    a_lv = jax.random.randint(k1, (M, K), 0, 1 << ab).astype(jnp.int32)
    w_lv = jax.random.randint(k2, (K, N), 0, 1 << wb).astype(jnp.int32)
    gold = np.asarray(ref.bitgemm_ref(a_lv, w_lv, ab, wb))
    out = np.asarray(ops.bitgemm_mxu(a_lv, w_lv, ab, wb, interpret=True))
    assert (out == gold).all()


def test_bitgemm_mxu_8bit_nibble_split():
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    a_lv = jax.random.randint(k1, (9, 96), 0, 256).astype(jnp.int32)
    w_lv = jax.random.randint(k2, (96, 7), 0, 256).astype(jnp.int32)
    gold = np.asarray(a_lv) @ np.asarray(w_lv)
    out = np.asarray(ops.bitgemm_mxu(a_lv, w_lv, 8, 8, interpret=True))
    assert (out == gold).all()


@pytest.mark.parametrize("M,K", [(5, 70), (256, 512), (17, 31), (300, 1000)])
@pytest.mark.parametrize("bits", [1, 4, 8])
def test_quantize_pack_vs_ref(M, K, bits):
    a = jax.random.uniform(jax.random.PRNGKey(M + K), (M, K), minval=-0.5,
                           maxval=1.5)
    lv, pk = ops.quantize_pack(a, bits, interpret=True)
    lv_r, pk_r = ref.quantpack_ref(a, bits)
    assert (np.asarray(lv) == np.asarray(lv_r)).all()
    assert (np.asarray(pk) == np.asarray(pk_r)).all()


@given(st.integers(1, 40), st.integers(1, 120), st.integers(1, 20),
       st.integers(1, 4), st.integers(1, 2), st.integers(0, 2**31 - 1))
@settings(max_examples=10, deadline=None)
def test_bitgemm_property(M, K, N, ab, wb, seed):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    a_lv = jax.random.randint(k1, (M, K), 0, 1 << ab).astype(jnp.int32)
    w_lv = jax.random.randint(k2, (K, N), 0, 1 << wb).astype(jnp.int32)
    gold = np.asarray(a_lv) @ np.asarray(w_lv)
    assert (np.asarray(ops.bitgemm_mxu(a_lv, w_lv, ab, wb, interpret=True))
            == gold).all()
    assert (np.asarray(ops.bitgemm_faithful(a_lv, w_lv, ab, wb, interpret=True))
            == gold).all()


def test_quant_dense_kernel_end_to_end():
    from repro.core.and_accum import quant_dense_forward
    a = jax.random.uniform(jax.random.PRNGKey(0), (33, 100))
    w = jax.random.normal(jax.random.PRNGKey(1), (100, 17))
    for path in ("mxu", "faithful"):
        out = ops.quant_dense_kernel(a, w, 4, 2, path=path)
        exp = quant_dense_forward(a, w, 4, 2)
        np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                                   rtol=1e-4, atol=1e-4)


def test_int8_matmul_dtypes():
    from repro.kernels.bitgemm_mxu import int8_matmul_pallas
    a = jax.random.randint(jax.random.PRNGKey(0), (37, 129), -128, 127,
                           dtype=jnp.int32).astype(jnp.int8)
    b = jax.random.randint(jax.random.PRNGKey(1), (129, 65), -128, 127,
                           dtype=jnp.int32).astype(jnp.int8)
    out = np.asarray(int8_matmul_pallas(a, b, interpret=True))
    gold = np.asarray(a, np.int32) @ np.asarray(b, np.int32)
    assert (out == gold).all()
    assert out.dtype == np.int32
