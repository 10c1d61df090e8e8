"""The trace -> metrics reduction on small traces recorded on the chip
(one v5e; op names shortened to the HLO name, the Mosaic calls kept whole):

* ``alexnet_batch_trace.json``: the first 117 ms of a traced
  ``alexnet-w1a4.batch`` window, six closed-loop rounds of two 32-image
  buckets, with the harness's host spans;
* ``smollm_chat_offline_trace.json``: 403 ms of a traced
  ``smollm-360m-w1a8.chat-offline`` window (one v5e, seed 2718281829):
  one batch-1 prefill chunk of a replacement, then one decode step of 16
  live slots, with the harness's and the engine's host spans, and under
  ``spans`` the harness's step records (``t`` in seconds from the
  trace's start)."""
import json
import os

import pytest

from harness.common import BENCH_DIR, Run, load_module
from harness.trace import Trace, _union

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "data", "alexnet_batch_trace.json")
LM_TRACE = "smollm_chat_offline_trace.json"
PEAKS = {"bf16_flops": 197e12, "int8_ops": 393e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def trace():
    with open(DATA) as f:
        return Trace(json.load(f))


def test_union_merges_and_clips():
    assert _union([(0, 5), (3, 8), (10, 12), (-4, -1)], 0, 11) == [[0, 8],
                                                                  [10, 11]]


def test_busy_is_the_union_of_op_intervals(trace):
    busy = trace.busy_s()
    assert 0 < busy < trace.window_s
    total = sum(d for evs in trace.data["ops"].values() for _, _, d in evs)
    assert busy <= total * 1e-9 + 1e-12


def _kernel(name):
    return load_module(os.path.join(BENCH_DIR, "roofline", name + ".py"))


def test_kernels_found_by_name(trace):
    conv_calls, conv_s = trace.matching(_kernel("conv_implicit").match)
    fc_calls, fc_s = trace.matching(_kernel("fused_qgemm").match)
    # twelve buckets: conv2-5 on the implicit kernel, FC6-7 on fused
    assert (conv_calls, fc_calls) == (48, 24)
    assert conv_s > fc_s > 0
    assert trace.matching(_kernel("attn_paged").match) == (0, 0.0)


def test_breakdown_names_ops_and_gaps(trace):
    b = trace.breakdown()
    assert len(b["device_ops"]) == 10
    assert b["device_ops"][0][0].startswith("conv_implicit_pallas")
    assert all(t > 0 for _, t in b["device_ops"] + b["idle_gaps"])
    idle = sum(t for _, t in b["idle_gaps"])
    assert idle == pytest.approx(trace.window_s - trace.busy_s())
    # the device waits while the host stacks the next bucket
    assert b["idle_gaps"][0][0] == "collate"


def test_paged_kernel_found_by_its_name():
    with open(os.path.join(HERE, "data", LM_TRACE)) as f:
        t = Trace(json.load(f))
    calls, device_s = t.matching(_kernel("attn_paged").match)
    # 32 layers of one prefill chunk and one decode step
    assert calls == 64 and device_s > 0.5 * t.window_s
    assert t.matching(_kernel("conv_implicit").match) == (0, 0.0)
    # the layer loop contains the kernel: it is not listed beside it
    names = [n for n, _ in t.breakdown()["device_ops"]]
    assert names[0].startswith("attn_paged")
    assert not any(n.startswith("while") for n in names)


def test_roofline_and_idle_readers(trace):
    with open(os.path.join(BENCH_DIR, "configs", "alexnet-w1a4.json")) as f:
        config = json.load(f)
    run = Run(cell={}, config=config, traffic={}, t0=0.0, t1=1.0,
              trace=trace, peaks=PEAKS)
    run.find = lambda kind, name, ext: os.path.join(BENCH_DIR, kind,
                                                    name + ext)
    run.trace_span = (0.0, 1.0)
    run.spans = {"collate": [dict(t=0.5, dt=0.002, batch=32)] * 12,
                 "serve.dispatch": [dict(t=0.503, dt=0.001)] * 12}
    run.counters = {"engines": [{32: "fp"}] + [{32: "implicit"}] * 4
                    + [{32: "fused"}] * 2 + [{32: "fp"}]}
    layers = _kernel("cnn_layers").walk(config)
    k = _kernel("conv_implicit")
    least = 12 * sum(k.least_time(l, 32, PEAKS) for l in layers[1:5])
    _, device_s = trace.matching(k.match)
    got = load_module(os.path.join(BENCH_DIR, "metrics",
                                   "conv_implicit_roofline.py")).read(run)
    assert got == pytest.approx(100 * least / device_s)
    assert 0 < got < 100
    idle = load_module(os.path.join(BENCH_DIR, "metrics",
                                    "idle_share.cnn.py")).read(run)
    assert idle == pytest.approx(100 * (1 - trace.busy_s() / trace.window_s))
