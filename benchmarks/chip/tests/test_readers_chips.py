"""The CNN readers on one chip and on a four-chip data mesh.

On the committed one-chip trace (``alexnet_batch_spans_trace.json``) each
reader that takes the cell's ``chips`` reads the number it read before it
took them, to the bit.  On a mesh made of that trace copied onto four
device planes, with buckets of four times the rows, each reads the
quantity it reads on one chip: the same per-chip kernel work over four
times the device time, four times the images over four chips' peak."""
import json
import math
import os

import pytest

from harness.common import (REPO_ROOT, Manifest, Run, dispatched_batches,
                            load_module)
from harness.trace import Trace

HERE = os.path.dirname(os.path.abspath(__file__))
MANIFEST = Manifest.load(os.path.join(REPO_ROOT, "BENCHMARK.json"))
PEAKS = {"bf16_flops": 197e12, "int8_ops": 393e12, "hbm_bytes_per_s": 819e9,
         "hbm_bytes": 16e9}
HINTS = (1, 2, 4, 8, 16, 32)
ENGINES = [{b: e for b in HINTS}
           for e in ["fp"] + ["implicit"] * 4 + ["fused"] * 2 + ["fp"]]
# read on this trace by the readers as they were before they took the
# cell's chips and counted a bucket by its dispatch (one bucket of 32 rows
# to each ``collate`` span; here the same 12 buckets as by dispatch)
ONE_CHIP = {"conv_implicit_roofline": 6.99467611922864,
            "fused_qgemm_roofline": 37.319906793252045,
            "mfu.cnn": 2.229948212789812}
MESH_READERS = sorted(ONE_CHIP) + ["idle_share.cnn", "idle_in_stage.cnn",
                                   "idle_in_wait.cnn", "cnn_collate_ms",
                                   "cnn_put_ms"]


def _data() -> dict:
    path = os.path.join(HERE, "data", "alexnet_batch_spans_trace.json")
    with open(path) as f:
        return json.load(f)


def _run(data: dict, chips: int) -> Run:
    """A run over ``data``: each recorded ``collate`` a bucket of 32 rows a
    chip, each ``serve.wait`` answering that many images, and the
    engine's spans as the drivers merge them."""
    trace = Trace(data)
    lo = trace.lo
    cell = dict(MANIFEST.cell("alexnet-w1a4.batch"), chips=chips)
    spans: dict = {}
    for name, s, d in data["host"]:
        rec = dict(t=(s - lo) * 1e-9, dt=d * 1e-9)
        if name == "collate":
            rec["batch"] = 32 * chips
        spans.setdefault(name, []).append(rec)
    requests = [dict(t_arrive=0.0, t_done=r["t"] + r["dt"])
                for r in spans["serve.wait"] for _ in range(32 * chips)]
    run = Run(cell=cell, config=MANIFEST.config("alexnet-w1a4"),
              traffic=MANIFEST.traffic("batch"), t0=0.0, t1=trace.window_s,
              trace=trace, spans=spans, requests=requests,
              counters={"engines": ENGINES}, peaks=PEAKS)
    run.find = MANIFEST.find
    run.trace_span = (0.0, trace.window_s)
    return run


def _read(metric: str, run: Run):
    return load_module(MANIFEST.find("metrics", metric, ".py")).read(run)


def _four_planes(data: dict) -> dict:
    (ops,) = data["ops"].values()
    return dict(data, ops={f"/device:TPU:{i}": ops for i in range(4)})


@pytest.mark.parametrize("metric", sorted(ONE_CHIP))
def test_one_chip_reading_is_unchanged(metric):
    assert _read(metric, _run(_data(), 1)) == ONE_CHIP[metric]


@pytest.mark.parametrize("metric", MESH_READERS)
def test_mesh_reads_what_one_chip_reads(metric):
    """Four chips each doing the one chip's work read the one chip's
    share; the host-side readers see the same spans."""
    one = _read(metric, _run(_data(), 1))
    mesh = _read(metric, _run(_four_planes(_data()), 4))
    assert one is not None and mesh is not None
    assert mesh == pytest.approx(one, rel=1e-12)


def test_mesh_roofline_counts_each_chip_call():
    """On the mesh the least time is of every chip's 32-row call, under
    the plan's verdict at 32 (a 128-row bucket has no verdict of its own),
    and the kernel's device time is summed over the four planes."""
    data = _four_planes(_data())
    run = _run(data, 4)
    k = load_module(MANIFEST.find("roofline", "conv_implicit", ".py"))
    layers = load_module(MANIFEST.find("roofline", "cnn_layers", ".py")
                         ).walk(run.config)
    buckets = len(run.spans["collate"])
    calls, device_s = run.trace.matching(k.match)
    assert calls == 4 * Trace(_data()).matching(k.match)[0]
    least = buckets * 4 * sum(k.least_time(l, 32, PEAKS) for l in layers[1:5])
    assert _read("conv_implicit_roofline", run) == pytest.approx(
        100.0 * least / device_s, rel=1e-12)
    # at 128 rows a chip the same calls would count four times the work
    assert 128 not in ENGINES[1]


def test_mfu_divides_by_the_chips():
    run = _run(_data(), 1)
    one = _read("mfu.cnn", run)
    run.cell = dict(run.cell, chips=4)
    assert _read("mfu.cnn", run) == pytest.approx(one / 4, rel=1e-12)


def test_at_most_half_the_cells_ask_for_four_chips():
    cells = MANIFEST.data["workloads"]
    four = [w["name"] for w in cells if w["chips"] == 4]
    assert all(w["chips"] in (1, 4) for w in cells)
    assert len(four) <= max(1, math.floor(len(cells) / 2)), four


@pytest.mark.parametrize("metric", ["conv_implicit_roofline",
                                    "fused_qgemm_roofline"])
def test_roofline_counts_the_buckets_whose_programs_ran(metric):
    """A bucket counts where its program was dispatched inside the host's
    traced window, not where its staging began: the first bucket here was
    staged before the window and runs inside it; the last two are staged
    and dispatched after the window's end.  The buckets counted are those
    whose kernel calls the trace holds."""
    run = _run(_data(), 1)
    run.spans["collate"].insert(0, dict(t=-0.002, dt=0.002, batch=32))
    run.trace_span = (0.0, 0.109)
    name = metric.rsplit("_", 1)[0]
    k = load_module(MANIFEST.find("roofline", name, ".py"))
    layers = [l for l in load_module(MANIFEST.find(
        "roofline", "cnn_layers", ".py")).walk(run.config)
        if ENGINES[l["index"]][32] == k.ENGINE]
    calls, device_s = run.trace.matching(k.match)
    buckets = len(dispatched_batches(run))
    assert buckets == 11 and calls == buckets * len(layers)
    least = buckets * sum(k.least_time(l, 32, PEAKS) for l in layers)
    assert _read(metric, run) == pytest.approx(100.0 * least / device_s,
                                               rel=1e-12)
