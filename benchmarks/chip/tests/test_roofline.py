import json
import os

import pytest

from harness.common import BENCH_DIR, load_module


def roof(name):
    return load_module(os.path.join(BENCH_DIR, "roofline", name + ".py"))


def config(name):
    with open(os.path.join(BENCH_DIR, "configs", name + ".json")) as f:
        return json.load(f)


PEAKS = {"bf16_flops": 197e12, "int8_ops": 393e12, "hbm_bytes_per_s": 819e9}


def test_alexnet_macs_per_image():
    c = config("alexnet-w1a4")
    layers = roof("cnn_layers").walk(c)
    assert sum(l["macs"] for l in layers) == 1_256_523_776
    assert c["macs_per_image"] == 1_256_523_776
    # conv1 and FC8 full precision; 56 -> 28 -> 14 -> 7 -> FC6 at 6x6
    assert [l["fp"] for l in layers] == [True] + [False] * 6 + [True]
    assert [l["h_out"] for l in layers] == [56, 28, 14, 14, 14, 1, 1, 1]
    assert layers[5]["h_in"] == 6


def test_smollm_projection_weights():
    c = config("smollm-360m-w1a8")
    assert roof("lm_ops").projection_weights(c) == 314_572_800


def test_conv_implicit_hand_count():
    k = roof("conv_implicit")
    conv2 = dict(k=5, cin=96, cout=256, h_in=28, h_out=28, macs=28 * 28 * 25 * 96 * 256)
    ops, nbytes = k.ops_bytes(conv2, 32)
    assert ops == 2 * 32 * 28 * 28 * 5 * 5 * 96 * 256
    assert nbytes == 32 * 28 * 28 * 96 + 5 * 5 * 96 * 256 + 32 * 28 * 28 * 256 * 4
    assert k.least_time(conv2, 32, PEAKS) == pytest.approx(ops / 393e12)


def test_fused_qgemm_hand_count():
    k = roof("fused_qgemm")
    fc6 = dict(k=6, cin=256, cout=4096)
    ops, nbytes = k.ops_bytes(fc6, 32)
    assert ops == 2 * 32 * 9216 * 4096
    assert nbytes == 32 * 9216 + 9216 * 4096 + 32 * 4096 * 4
    # weight bytes bound it
    assert k.least_time(fc6, 32, PEAKS) == pytest.approx(nbytes / 819e9)


def test_attn_paged_counts_live_context():
    k = roof("attn_paged")
    m = dict(num_attention_heads=15, num_key_value_heads=5, head_dim=64)
    t = k.least_time([1, 1], [100, 300], m, PEAKS)
    nbytes = 4.0 * 400 * 5 * 64 + 4.0 * 2 * 15 * 64
    qk = 2.0 * 400 * 15 * 64
    assert t == pytest.approx(max(qk / 393e12 + qk / 197e12, nbytes / 819e9))


def test_attn_paged_found_by_name_as_by_its_operands():
    """The kernel's ``pallas_call`` name finds the calls and the device
    time that its operand layouts (the scalar-prefetched page table, the
    per-slot scales, the two zero points) found before it was named."""
    import re

    from harness.trace import Trace

    operands = re.compile(r'custom_call_target="tpu_custom_call", '
                          r"operand_layout_constraints=\{s32\[\d+,\d+\]\{1,0\}, "
                          r"f32\[\d+\]\{0\}, s32\[2\]\{0\}")
    with open(os.path.join(BENCH_DIR, "tests", "data",
                           "smollm_chat_offline_trace.json")) as f:
        t = Trace(json.load(f))
    by_name = t.matching(roof("attn_paged").match)
    assert by_name[0] == 64
    assert by_name == t.matching(lambda name: operands.search(name))


def test_peaks_table_has_a_source_and_refuses_unknown_devices():
    from harness.common import BenchError, load_peaks

    with open(os.path.join(BENCH_DIR, "roofline", "peaks.json")) as f:
        assert "Google Cloud" in json.load(f)["source"]
    assert load_peaks("TPU v5 lite")["int8_ops"] == 393e12
    with pytest.raises(BenchError):
        load_peaks("no such chip")
