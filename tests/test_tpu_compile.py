"""Compile the main-path Pallas kernels for a TPU v5e, without a chip.

Interpret mode (every other kernel test) runs the kernel bodies on CPU; it
cannot see what the TPU's Mosaic compiler refuses — misaligned vector
reshapes, block shapes that break the (8, 128) tiling rule, scalar
prefetch misuse.  These tests lower and compile each kernel at the widths
the serve paths run (AlexNet at 224 and 227, the SVHN CNN at 40,
smollm-360m's projections, the binary AND+popcount GEMM, flash prefill
and paged decode) for a
described v5e chip, and check that every kernel's ``name`` reaches its
lowered call, where a device trace finds it.  Nothing runs, so they say
nothing about results or speed.

The topology is described inside a module fixture (never at import time):
only one process may load the TPU library, and every test worker imports
this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.plan import compile_model
from repro.core.prequant import level_dtype
from repro.core.quant import W1A4
from repro.kernels import ops
from repro.kernels.attn_flash import attn_flash_pallas, attn_paged_pallas
from repro.kernels.bitgemm import bitgemm_packed_pallas
from repro.kernels.bitgemm_mxu import int8_matmul_pallas
from repro.kernels.conv_implicit import conv_implicit_pallas
from repro.kernels.fused_qgemm import fused_qgemm_pallas
from repro.kernels.quantpack import quantize_pack_pallas
from repro.models.cnn import alexnet_spec, svhn_cnn_spec


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU compiler here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(one_chip, fn, *shapes):
    """Lower + compile ``fn`` for one described v5e chip; returns the
    compiled text so callers can check the Mosaic kernel is in it."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("m,k,n,a_bits", [
    (8, 9216, 4096, 4),     # AlexNet FC6 at batch 8 (w1a4)
    (8, 4096, 4096, 4),     # AlexNet FC7
    (4096, 960, 2560, 8),   # smollm-360m FFN up-projection, 4096 rows (w1a8)
    (64, 2560, 960, 8),     # smollm-360m FFN down-projection
])
def test_fused_qgemm_compiles(one_chip, m, k, n, a_bits):
    def fn(a, w, s, z):
        return fused_qgemm_pallas(a, w, s, z, a_bits=a_bits, w_bits=1,
                                  a_is_levels=True)

    text = _compile(one_chip, fn, ((m, k), level_dtype(a_bits)),
                    ((k, n), level_dtype(1)), ((), jnp.float32),
                    ((), jnp.float32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("m,k,n", [
    (8, 1 << 15, 1024),     # the smallest K TpuTarget routes to `faithful`
    (64, 1 << 16, 256),     # m*n at TpuTarget's faithful_mn_max
])
def test_faithful_bitgemm_compiles(one_chip, m, k, n):
    """The binary AND+popcount kernel a TPU plan pins at w1a1, large K."""
    def fn(a, w):
        return ops.bitgemm_faithful(a, w, 1, 1, interpret=False)

    text = _compile(one_chip, fn, ((m, k), jnp.int32), ((k, n), jnp.int32))
    assert "tpu_custom_call" in text


# (spec, input size) whose TPU plans route layers to the implicit kernel;
# AlexNet at 227 has the 27-wide 5x5 layer Mosaic once refused
CNN_CASES = {
    "alexnet-224": (alexnet_spec, 224),
    "alexnet-227": (alexnet_spec, 227),
    "svhn-40": (lambda: svhn_cnn_spec(64), 40),
}


@pytest.mark.parametrize("batch", [1, 8])
@pytest.mark.parametrize("model", sorted(CNN_CASES))
def test_conv_implicit_compiles_at_planned_layers(one_chip, model, batch):
    """Every layer a TPU plan pins to ``implicit`` compiles for v5e — no
    plan pins an engine the chip's compiler refuses."""
    spec_fn, hw = CNN_CASES[model]
    plan = compile_model(None, spec_fn(), W1A4, backend="tpu",
                         batch_hints=(batch,), img_hw=hw)
    layers = [lp for lp in plan.layers if lp.engine_at(batch) == "implicit"]
    assert layers, f"{model}: no implicit layer planned at batch {batch}"
    for lp in layers:
        def fn(x, w, s, z, lp=lp):
            return conv_implicit_pallas(
                x, w, s, z, kh=lp.kh, kw=lp.kw, stride=lp.stride,
                padding=lp.padding, a_bits=lp.a_bits, w_bits=lp.w_bits)

        text = _compile(
            one_chip, fn,
            ((batch, lp.in_h, lp.in_w, lp.cin), level_dtype(lp.a_bits)),
            ((lp.k, lp.cout), level_dtype(lp.w_bits)),
            ((), jnp.float32), ((), jnp.float32))
        assert "tpu_custom_call" in text, lp.name


@pytest.mark.parametrize("h,cin,cout,stride", [
    (15, 64, 64, 1),        # odd width, cin below one lane tile
    (21, 64, 128, 2),       # stride-2 de-stride at an odd width
])
def test_conv_implicit_compiles_off_tile_widths(one_chip, h, cin, cout,
                                                stride):
    def fn(x, w, s, z):
        return conv_implicit_pallas(x, w, s, z, kh=3, kw=3, stride=stride,
                                    padding="SAME", a_bits=4, w_bits=1)

    _compile(one_chip, fn, ((1, h, h, cin), level_dtype(4)),
             ((9 * cin, cout), level_dtype(1)), ((), jnp.float32),
             ((), jnp.float32))


def test_attn_flash_compiles_at_smollm_prefill(one_chip):
    """smollm-360m prefill: S=2048, 15 heads, head_dim 64."""
    def fn(q, k, v):
        return attn_flash_pallas(q, k, v, causal=True, q_bits=8, k_bits=8)

    qkv = ((1, 2048, 15, 64), jnp.bfloat16)
    assert "tpu_custom_call" in _compile(one_chip, fn, qkv, qkv, qkv)


@pytest.mark.parametrize("batch,seq,n_pages,table", [
    pytest.param(4, 1, 108, 18, id="4-1"),
    pytest.param(1, 16, 108, 18, id="1-16"),
    pytest.param(16, 1, 2048, 128, id="16-1-cell"),
    pytest.param(1, 16, 2048, 128, id="1-16-cell"),
])
def test_attn_paged_compiles_at_smollm_serve(one_chip, batch, seq, n_pages,
                                             table):
    """The continuous engine's two paged shapes for smollm-360m: a decode
    step and a 16-token prefill chunk, over 16-token bf16 pages of 5 KV
    heads; an 18-page table over 108 pages, and the chat-offline cell's
    geometry (16 slots, a 128-page table over 2,048 pages)."""
    ps, hkv, hd = 16, 5, 64

    def fn(q, pk, pv, ppos, tbl, qpos):
        return attn_paged_pallas(q, pk, pv, ppos, tbl, qpos, bits=8,
                                 n_q_heads=15)

    pool = ((n_pages + 1, ps, hkv, hd), jnp.bfloat16)
    text = _compile(one_chip, fn, ((batch, seq, 15, hd), jnp.bfloat16),
                    pool, pool, ((n_pages + 1, ps), jnp.int32),
                    ((batch, table), jnp.int32), ((batch, seq), jnp.int32))
    assert "tpu_custom_call" in text


# Each kernel's ``pallas_call`` name, a function that calls it, and its
# operands: the name reaches the lowered Mosaic call, so a device trace
# names the kernel's op (``conv_implicit.3``, not its caller's).
KERNEL_NAMES = {
    "conv_implicit": (
        lambda x, w, s, z: conv_implicit_pallas(x, w, s, z, kh=3, kw=3,
                                                a_bits=4, w_bits=1),
        [((1, 16, 16, 128), jnp.int8), ((9 * 128, 128), jnp.int8),
         ((), jnp.float32), ((), jnp.float32)]),
    "fused_qgemm": (
        lambda a, w, s, z: fused_qgemm_pallas(a, w, s, z, a_bits=4, w_bits=1,
                                              a_is_levels=True),
        [((8, 512), jnp.int8), ((512, 256), jnp.int8), ((), jnp.float32),
         ((), jnp.float32)]),
    "attn_flash": (
        lambda q, k, v: attn_flash_pallas(q, k, v, q_bits=8, k_bits=8),
        [((1, 256, 2, 64), jnp.bfloat16)] * 3),
    "attn_paged": (
        lambda q, pk, pv, ppos, tbl, qpos: attn_paged_pallas(
            q, pk, pv, ppos, tbl, qpos, bits=8, n_q_heads=2),
        [((2, 1, 2, 64), jnp.bfloat16), ((9, 16, 1, 64), jnp.bfloat16),
         ((9, 16, 1, 64), jnp.bfloat16), ((9, 16), jnp.int32),
         ((2, 4), jnp.int32), ((2, 1), jnp.int32)]),
    "bitgemm": (
        lambda a, w: bitgemm_packed_pallas(a, w, a_bits=2, w_bits=1),
        [((2, 8, 128), jnp.uint32), ((1, 128, 128), jnp.uint32)]),
    "bitgemm_mxu": (int8_matmul_pallas,
                    [((128, 256), jnp.int8), ((256, 128), jnp.int8)]),
    "quantpack": (lambda a: quantize_pack_pallas(a, bits=4),
                  [((128, 256), jnp.float32)]),
}


@pytest.mark.parametrize("name", sorted(KERNEL_NAMES))
def test_kernel_name_in_lowered_text(one_chip, name):
    """``quantpack`` does not lower for the TPU (Mosaic has no reductions
    over unsigned integers, and no plan routes to it): its name is checked
    on the traced call instead."""
    fn, shapes = KERNEL_NAMES[name]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    if name == "quantpack":
        assert f"name={name}\n" in str(jax.make_jaxpr(fn)(*args))
    else:
        text = jax.jit(fn).lower(*args).as_text()
        assert f'kernel_name = "{name}"' in text
