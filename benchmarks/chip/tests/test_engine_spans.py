"""The readers of the CNN engine's own spans (``ServeEngine.record_spans``),
on a small trace recorded on the chip with the recorder on:

* ``alexnet_batch_spans_trace.json``: the first 120 ms of a traced
  ``alexnet-w1a4.batch`` window (one v5e, seed 1414213562), five
  closed-loop rounds of two 32-image buckets, with the harness's spans
  and the engine's ``serve.*`` spans on the device's clock (op names
  shortened to the HLO name, the Mosaic calls kept whole).

The older ``alexnet_batch_trace.json`` has no engine spans, as a program
without the recorder gives none: there the readers give nothing."""
import json
import os

import pytest

from harness.common import BENCH_DIR, Run, load_module
from harness.overlap import CLOCK_SLACK_S, _gaps, _overlap
from harness.trace import Trace

HERE = os.path.dirname(os.path.abspath(__file__))
PEAKS = {"bf16_flops": 197e12, "int8_ops": 393e12, "hbm_bytes_per_s": 819e9}
ENGINE_SPANS = ("serve.stage", "serve.collate", "serve.put",
                "serve.dispatch", "serve.harvest", "serve.wait",
                "serve.split")


def _trace(name):
    with open(os.path.join(HERE, "data", name)) as f:
        return Trace(json.load(f))


@pytest.fixture(scope="module")
def trace():
    return _trace("alexnet_batch_spans_trace.json")


def _run(trace, spans=None, slack=0.0):
    with open(os.path.join(BENCH_DIR, "configs", "alexnet-w1a4.json")) as f:
        config = json.load(f)
    run = Run(cell={}, config=config, traffic={}, t0=0.0, t1=1.0,
              trace=trace, peaks=PEAKS, spans=spans or {})
    run.find = lambda kind, name, ext: os.path.join(BENCH_DIR, kind,
                                                    name + ext)
    run.trace_span = (0.0, trace.window_s + slack)
    run.counters = {"engines": [{32: "fp"}] + [{32: "implicit"}] * 4
                    + [{32: "fused"}] * 2 + [{32: "fp"}]}
    return run


def _read(metric, run):
    return load_module(os.path.join(BENCH_DIR, "metrics",
                                    metric + ".py")).read(run)


def test_gaps_and_overlap():
    assert _gaps([[2, 3], [5, 8]], 0, 10) == [[0, 2], [3, 5], [8, 10]]
    assert _gaps([[0, 10]], 0, 10) == []
    assert _overlap([[0, 2], [3, 5], [8, 10]], [[1, 4], [9, 20]]) == 3


def test_trace_holds_engine_spans(trace):
    names = {n for n, _, _ in trace.data["host"]}
    assert set(ENGINE_SPANS) <= names
    assert {"collate", "pump"} <= names


def test_idle_in_stage_and_wait_lie_within_idle(trace):
    run = _run(trace)
    stage = _read("idle_in_stage.cnn", run)
    wait = _read("idle_in_wait.cnn", run)
    idle = _read("idle_share.cnn", run)
    assert stage > 0 and wait is not None and wait >= 0
    assert stage + wait <= idle + 0.1


@pytest.mark.parametrize("metric", ["idle_in_stage.cnn", "idle_in_wait.cnn"])
def test_idle_readers_refuse_misaligned_clocks(trace, metric):
    assert _read(metric, _run(trace, slack=0.5 * CLOCK_SLACK_S)) is not None
    for slack in (-2 * CLOCK_SLACK_S, 2 * CLOCK_SLACK_S):
        assert _read(metric, _run(trace, slack=slack)) is None


@pytest.mark.parametrize("metric", ["idle_in_stage.cnn", "idle_in_wait.cnn",
                                    "cnn_put_ms"])
def test_readers_give_nothing_without_engine_spans(metric):
    """A program without the recorder (the older recorded trace)."""
    run = _run(_trace("alexnet_batch_trace.json"),
               spans={"collate": [dict(t=0.5, dt=0.002, batch=32)]})
    assert _read(metric, run) is None


def test_cnn_put_ms_reads_put_spans_in_the_window(trace):
    run = _run(trace, spans={"serve.put": [dict(t=0.2, dt=0.002),
                                           dict(t=0.4, dt=0.004),
                                           dict(t=1.5, dt=0.1)]})
    assert _read("cnn_put_ms", run) == pytest.approx(3.0)


def test_engine_spans_do_not_reach_collate_readers(trace):
    """``serve.collate`` is the engine's own span over the same call the
    harness's ``collate`` wraps: the readers count each bucket once."""
    harness = {"collate": [dict(t=0.05, dt=0.002, batch=32)] * 12,
               "serve.dispatch": [dict(t=0.053, dt=0.001)] * 12}
    both = dict(harness, **{n: [dict(t=0.05, dt=0.004, batch=32, padded=32,
                                     bucket=0)] * 12 for n in ENGINE_SPANS
                            if n != "serve.dispatch"})
    for metric in ("cnn_collate_ms", "conv_implicit_roofline",
                   "fused_qgemm_roofline"):
        alone = _read(metric, _run(trace, spans=harness))
        assert alone is not None
        assert _read(metric, _run(trace, spans=both)) == alone, metric


def test_overlap_splits_what_the_midpoint_rule_gives_collate(trace):
    """``Trace.breakdown`` gives each idle gap whole to the innermost span
    over its midpoint: here most of the idle time goes to the harness's
    ``collate``, which overlaps less than half of it.  By overlap the
    same idle time splits between staging and the wait on output."""
    from harness.overlap import idle_in

    gaps = dict(trace.breakdown()["idle_gaps"])
    idle_s = sum(gaps.values())
    collate_s = idle_in(_run(trace), "collate") / 100 * trace.window_s
    assert gaps["collate"] > 0.5 * idle_s > collate_s
    assert "serve.harvest" in gaps
    run = _run(trace)
    stage = _read("idle_in_stage.cnn", run)
    wait = _read("idle_in_wait.cnn", run)
    assert stage > idle_in(run, "collate") and wait > 10.0
    assert stage + wait > 0.7 * _read("idle_share.cnn", run)


@pytest.mark.parametrize("traced", [False, True])
def test_drivers_merge_engine_spans_only_in_traced_runs(traced):
    """``record_engine_spans`` turns the engine's recorder on only when
    the run is traced, and merges its records beside the harness's."""
    from types import SimpleNamespace

    from harness.common import record_engine_spans

    rec = SimpleNamespace(records={})
    calls = []

    def record_spans():
        calls.append(1)
        return rec

    run = Run(cell={}, config={}, traffic={},
              spans={"collate": [dict(t=0.1, dt=0.002, batch=32)]})
    merge = record_engine_spans(run, SimpleNamespace(record_spans=record_spans),
                                SimpleNamespace(enabled=traced))
    rec.records["serve.put"] = [dict(t=0.2, dt=0.001, id=0, parent=None)]
    merge()
    assert calls == ([1] if traced else [])
    assert ("serve.put" in run.spans) is traced
    assert len(run.spans["collate"]) == 1
