"""AND-Accumulation engine equivalence (paper Eq. 1) — property tests.

All four engines must agree *bit-exactly* on integer levels, and the
dequantized GEMM must match the quantize->float-matmul oracle.
"""
import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core import and_accum, bitplane
from repro.core.quant import activation_levels_signed, weight_levels

ENGINES = ["planes", "packed", "int8", "int8_planewise"]


@given(
    st.integers(1, 24), st.integers(1, 80), st.integers(1, 24),
    st.integers(1, 8), st.integers(1, 8), st.integers(0, 2**31 - 1),
)
@settings(max_examples=25, deadline=None)
def test_engines_bit_exact(M, K, N, a_bits, w_bits, seed):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    a_lv = jax.random.randint(k1, (M, K), 0, 1 << a_bits).astype(jnp.int32)
    w_lv = jax.random.randint(k2, (K, N), 0, 1 << w_bits).astype(jnp.int32)
    gold = np.asarray(a_lv) @ np.asarray(w_lv)  # plain integer GEMM identity
    for eng in ENGINES:
        out = np.asarray(and_accum._ENGINES[eng](a_lv, w_lv, a_bits, w_bits))
        assert (out == gold).all(), eng


@given(st.integers(1, 6), st.integers(1, 6), st.integers(0, 2**31 - 1))
@settings(max_examples=15, deadline=None)
def test_quant_dense_matches_reference(a_bits, w_bits, seed):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    a = jax.random.uniform(k1, (7, 50))
    w = jax.random.normal(k2, (50, 11))
    ref = and_accum.reference_float(a, w, a_bits, w_bits)
    for eng in ENGINES:
        out = and_accum.quant_dense_forward(a, w, a_bits, w_bits, engine=eng)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)


def test_signed_affine_correction_exact():
    a = jax.random.normal(jax.random.PRNGKey(0), (9, 64)) * 3
    w = jax.random.normal(jax.random.PRNGKey(1), (64, 13))
    for (ab, wb) in [(8, 1), (4, 2), (8, 8)]:
        al, sa, za = activation_levels_signed(a, ab)
        wl, sw, zw = weight_levels(w, wb)
        ref = ((np.asarray(al) - float(za)) * float(sa)) @ (
            (np.asarray(wl) - float(zw)) * float(sw))
        out = and_accum.quant_dense_forward_signed(a, w, ab, wb)
        np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-4, atol=1e-4)


@given(st.integers(1, 200), st.integers(0, 2**31 - 1))
@settings(max_examples=25, deadline=None)
def test_pack_unpack_roundtrip(K, seed):
    x = jax.random.randint(jax.random.PRNGKey(seed), (3, K), 0, 2)
    p = bitplane.pack_bits(bitplane.pad_to_lane(x))
    assert (np.asarray(bitplane.unpack_bits(p, k=K)) == np.asarray(x)).all()


@given(st.integers(1, 8), st.integers(0, 2**31 - 1))
@settings(max_examples=20, deadline=None)
def test_decompose_compose_roundtrip(bits, seed):
    lv = jax.random.randint(jax.random.PRNGKey(seed), (4, 9), 0, 1 << bits)
    planes = bitplane.decompose(lv, bits)
    assert (np.asarray(bitplane.compose(planes)) == np.asarray(lv)).all()
    # plane values are {0,1}
    assert set(np.unique(np.asarray(planes))) <= {0, 1}


def test_conv_lowering_matches_float_conv():
    from repro.core import conv_lowering as cl
    from repro.core.quant import activation_levels as alv
    x = jax.random.uniform(jax.random.PRNGKey(2), (2, 8, 8, 3))
    w = jax.random.normal(jax.random.PRNGKey(3), (3, 3, 3, 4)) * 0.2
    a_l, s_a = alv(x, 4)
    w_l, s_w, z_w = weight_levels(w, 2)
    xq = a_l.astype(jnp.float32) * s_a
    wq = (w_l.astype(jnp.float32) - z_w) * s_w
    for stride, pad in [(1, "SAME"), (2, "VALID")]:
        ref = cl.conv2d_float(xq, wq, stride=stride, padding=pad)
        out = cl.quant_conv2d(x, w, stride=stride, padding=pad,
                              a_bits=4, w_bits=2)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)


def test_compressor_truth_table():
    from repro.core.compressor import compressor_outputs
    for bits in range(32):
        x = [(bits >> i) & 1 for i in range(5)]
        s, c, co = compressor_outputs(*x)
        assert sum(x) == s + 2 * (c + co), x
