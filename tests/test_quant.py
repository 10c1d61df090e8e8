"""Quantizer unit + property tests (paper Table I closed forms, DoReFa)."""
import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core.quant import (
    PAPER_CONFIGS, QuantConfig, activation_levels, activation_levels_signed,
    fake_quant_act_signed, quantize_activation, quantize_gradient,
    quantize_weight, weight_levels,
)


def test_table1_complexity_columns():
    """Paper Table I, computation-complexity columns, exactly."""
    expect = {  # (W,I): (inference, training) with 8-bit gradients
        (1, 1): (1, 9), (1, 4): (4, 12), (1, 8): (8, 16), (2, 2): (4, 20),
    }
    for (w, i), (inf, tr) in expect.items():
        cfg = QuantConfig(w_bits=w, a_bits=i, g_bits=8)
        assert cfg.inference_complexity == inf
        assert cfg.training_complexity == tr


def test_paper_configs_registry():
    assert set(PAPER_CONFIGS) == {"w32a32", "w1a1", "w1a4", "w1a8", "w2a2"}


@given(st.integers(1, 8), st.integers(0, 2**31 - 1))
@settings(max_examples=30, deadline=None)
def test_activation_levels_bounds(bits, seed):
    a = jax.random.uniform(jax.random.PRNGKey(seed), (17,), minval=-2, maxval=3)
    lv, s = activation_levels(a, bits)
    assert int(jnp.min(lv)) >= 0 and int(jnp.max(lv)) <= (1 << bits) - 1
    # dequantized value approximates clip(a, 0, 1) within half a level
    np.testing.assert_allclose(np.asarray(lv) * float(s),
                               np.clip(np.asarray(a), 0, 1),
                               atol=0.5 / ((1 << bits) - 1) + 1e-6)


@given(st.integers(2, 8), st.integers(0, 2**31 - 1))
@settings(max_examples=30, deadline=None)
def test_weight_levels_roundtrip(bits, seed):
    w = jax.random.normal(jax.random.PRNGKey(seed), (33,))
    lv, s, z = weight_levels(w, bits)
    wq_int = (np.asarray(lv, np.float64) - float(z)) * float(s)
    wq_float = np.asarray(quantize_weight(w, bits))
    np.testing.assert_allclose(wq_int, wq_float, atol=1e-6)


def test_binary_weight_is_scaled_sign():
    w = jnp.asarray([0.5, -0.2, 0.1, -0.9])
    wq = np.asarray(quantize_weight(w, 1))
    alpha = float(jnp.mean(jnp.abs(w)))
    np.testing.assert_allclose(np.abs(wq), alpha, rtol=1e-6)
    assert (np.sign(wq) == np.sign(np.asarray(w))).all()


@given(st.integers(1, 8), st.integers(0, 2**31 - 1))
@settings(max_examples=25, deadline=None)
def test_signed_levels_affine(bits, seed):
    a = jax.random.normal(jax.random.PRNGKey(seed), (25,)) * 4
    lv, s, z = activation_levels_signed(a, bits)
    assert int(jnp.min(lv)) >= 0 and int(jnp.max(lv)) <= (1 << bits) - 1
    deq = (np.asarray(lv, np.float64) - float(z)) * float(s)
    fq = np.asarray(fake_quant_act_signed(a, bits), np.float64)
    np.testing.assert_allclose(deq, fq, atol=1e-5)


def test_ste_gradients_pass_through():
    f = lambda x: jnp.sum(quantize_activation(x, 2))
    g = jax.grad(f)(jnp.asarray([0.3, 0.7, -0.5, 1.5]))
    # STE: identity grad inside [0,1], zero outside (clip region)
    np.testing.assert_allclose(np.asarray(g), [1.0, 1.0, 0.0, 0.0])


def test_gradient_quantization_levels():
    key = jax.random.PRNGKey(0)

    def f(x):
        return jnp.sum(jnp.square(quantize_gradient(x, 4, key)))

    x = jax.random.normal(key, (64,))
    g = jax.grad(f)(x)
    # quantized gradient has at most 2^4 distinct levels (up to fp noise)
    lv = np.unique(np.round(np.asarray(g), 6))
    assert len(lv) <= 16 + 1
    assert np.isfinite(np.asarray(g)).all()
