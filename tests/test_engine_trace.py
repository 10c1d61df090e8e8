"""The engines' span recorder (launch/engine.SpanRecorder) and per-token
stamps.

The recorder is off by default and then records nothing; turned on, it
keeps one set of ``serve.*`` spans per CNN bucket and ``lm.*`` spans per
admission and model step, on the engine's clock, and changes no output.
``Result.token_times`` stamps every LM token when its logits reach the
host, recorder on or off.
"""
import dataclasses
import itertools

import jax
import numpy as np
import pytest

from repro.configs import SINGLE, all_configs
from repro.core.prequant import prequantize_cnn_params
from repro.core.quant import PAPER_CONFIGS, W1A4
from repro.launch.engine import (CNNRunner, ContinuousLMEngine, ServeEngine,
                                 SpanRecorder)
from repro.models import transformer as T
from repro.models.cnn import init_cnn, svhn_cnn_spec

SPEC = svhn_cnn_spec(8)
_params, _ = init_cnn(jax.random.PRNGKey(0), SPEC)
SERVE_PARAMS = prequantize_cnn_params(_params, SPEC, W1A4)
IMGS = [np.random.RandomState(i).uniform(size=(16, 16, 3)).astype(np.float32)
        for i in range(5)]

LM_CFG = dataclasses.replace(
    all_configs()["smollm-360m"].smoke(
        n_layers=2, d_model=64, n_heads=2, n_kv_heads=1, d_ff=128, vocab=64,
        head_dim=32),
    quant=PAPER_CONFIGS["w1a8"])
LM_PARAMS, _ = T.init_lm(jax.random.PRNGKey(0), LM_CFG, SINGLE)
# three requests: (prompt, new tokens); prompts of 1, 2 and 3 chunks of 4
LM_PAYLOADS = [(np.arange(3, dtype=np.int32) + 1, 4),
               (np.arange(7, dtype=np.int32) + 5, 3),
               (np.arange(9, dtype=np.int32) + 2, 5)]

CNN_SPANS = {"serve.stage": None, "serve.collate": "serve.stage",
             "serve.put": "serve.stage", "serve.dispatch": None,
             "serve.harvest": None, "serve.wait": "serve.harvest",
             "serve.split": "serve.harvest"}


def _fake_clock():
    """A clock that advances by one on every read."""
    return itertools.count().__next__


def _cnn_engine(**kw):
    return ServeEngine(CNNRunner(SERVE_PARAMS, SPEC, W1A4), max_batch=4, **kw)


def _lm_engine(**kw):
    return ContinuousLMEngine(LM_PARAMS, LM_CFG, num_slots=2, page_size=4,
                              num_pages=16, max_seq=16, **kw)


def _by_id(records):
    return {r["id"]: (name, r) for name, recs in records.items()
            for r in recs}


def _assert_nested(records):
    """Every span lies inside its parent."""
    ids = _by_id(records)
    for recs in records.values():
        for r in recs:
            if r["parent"] is not None:
                _, p = ids[r["parent"]]
                assert p["t"] <= r["t"]
                assert r["t"] + r["dt"] <= p["t"] + p["dt"]


@pytest.mark.parametrize("make", [_cnn_engine, _lm_engine])
def test_recorder_off_records_nothing(make):
    eng = make()
    payloads = IMGS if make is _cnn_engine else LM_PAYLOADS
    eng.serve(payloads)
    assert eng.spans is None
    if make is _cnn_engine:
        assert eng.stats == dict(dispatches=2, requests=5, padded_rows=0,
                                 put_chunks=2)
    else:
        assert eng.stats["requests"] == 3 and eng.stats["dispatches"] == (
            eng.stats["prefill_chunks"] + eng.stats["steps"])


def test_recorder_nests_spans():
    rec = SpanRecorder(_fake_clock())
    outer = rec.begin()
    rec.begin()                       # left open, as an exception would
    inner = rec.begin()
    rec.end("inner", inner, flag=True)
    rec.end("outer", outer)           # closes the abandoned span with it
    assert rec.records["inner"] == [dict(t=2, dt=1, id=2, parent=1,
                                         flag=True)]
    assert rec.records["outer"] == [dict(t=0, dt=4, id=0, parent=None)]
    rec.begin()
    rec.abandon()
    assert rec.records["outer"][0]["parent"] is None
    last = rec.begin()
    assert rec.end("last", last) == 7 and rec.records["last"][0]["parent"] \
        is None


def test_cnn_spans_per_bucket():
    """5 images at max_batch 4, served twice: four buckets (4 and 1 rows,
    twice), each with one of every span, and one compile per padded
    batch."""
    eng = _cnn_engine(clock=_fake_clock())
    rec = eng.record_spans()
    assert eng.record_spans() is rec
    eng.serve(IMGS)
    eng.serve(IMGS)
    records = rec.records
    assert set(records) == set(CNN_SPANS)
    buckets = [r["bucket"] for r in records["serve.stage"]]
    assert buckets == [r["id"] for r in records["serve.stage"]]
    assert len(set(buckets)) == 4
    ids = _by_id(records)
    for name, parent in CNN_SPANS.items():
        assert [r["bucket"] for r in records[name]] == buckets, name
        for r in records[name]:
            want = None if parent is None else ids[r["parent"]][0]
            assert want == parent, name
            if parent is not None:
                assert ids[r["parent"]][1]["bucket"] == r["bucket"]
    _assert_nested(records)
    collate = records["serve.collate"]
    assert [(r["batch"], r["padded"]) for r in collate] == [(4, 4), (1, 1)] * 2
    row = IMGS[0].nbytes
    assert [r["bytes"] for r in records["serve.put"]] == [
        r["padded"] * row for r in collate]
    assert [r["chunks"] for r in records["serve.put"]] == [1] * 4
    built = [(r["padded"], d["built"])
             for r, d in zip(collate, records["serve.dispatch"])]
    assert built == [(4, True), (1, True), (4, False), (1, False)]
    assert eng.stats == dict(dispatches=4, requests=10, padded_rows=0,
                             put_chunks=4)


def test_cnn_put_span_counts_chunks(monkeypatch):
    """A bucket staged in row chunks: ``serve.put`` carries ``chunks`` and
    still the bucket's whole ``bytes``; ``serve.collate`` stays one span
    per bucket with its ``batch``/``padded``."""
    from repro.launch import engine

    row = IMGS[0].nbytes
    monkeypatch.setattr(engine, "_PUT_CHUNK_BYTES", row)
    eng = _cnn_engine(clock=_fake_clock())
    rec = eng.record_spans()
    eng.serve(IMGS[:3])               # one bucket: 3 rows padded to 4
    eng.serve(IMGS)                   # 4 rows, then 1
    collate, put = rec.records["serve.collate"], rec.records["serve.put"]
    assert [(r["batch"], r["padded"]) for r in collate] == [
        (3, 4), (4, 4), (1, 1)]
    assert [(r["chunks"], r["bytes"]) for r in put] == [
        (4, 4 * row), (4, 4 * row), (1, row)]
    assert [r["bucket"] for r in put] == [r["bucket"] for r in collate]
    assert eng.stats["put_chunks"] == 9
    _assert_nested(rec.records)


@pytest.mark.parametrize("make", [_cnn_engine, _lm_engine])
def test_outputs_identical_with_recorder_on(make):
    payloads = IMGS if make is _cnn_engine else LM_PAYLOADS
    off = make().serve(payloads)
    eng = make()
    eng.record_spans()
    on = eng.serve(payloads)
    assert [r.rid for r in on] == [r.rid for r in off]
    for a, b in zip(on, off):
        np.testing.assert_array_equal(a.value, b.value)
        assert a.value.dtype == b.value.dtype
    assert all(r.token_times == () for r in on) == (make is _cnn_engine)


def test_lm_token_times_and_step_spans():
    eng = _lm_engine(clock=_fake_clock())
    rec = eng.record_spans()
    res = eng.serve(LM_PAYLOADS)
    records = rec.records
    assert [len(r.value) for r in res] == [n for _, n in LM_PAYLOADS]
    for r in res:
        assert len(r.token_times) == len(r.value)
        assert list(r.token_times) == sorted(r.token_times)
    assert len(records["lm.decode_step"]) == eng.stats["steps"]
    assert len(records["lm.prefill_chunk"]) == eng.stats["prefill_chunks"] \
        == 1 + 2 + 3
    assert len(records["lm.dispatch"]) == len(records["lm.logits_wait"]) \
        == eng.stats["dispatches"]
    # the first token is stamped at the end of its request's last chunk
    admit_rid = {a["id"]: a["rid"] for a in records["lm.admit"]}
    last_chunk = {}
    for c in records["lm.prefill_chunk"]:
        last_chunk[admit_rid[c["parent"]]] = c["t"] + c["dt"]
    assert {r.rid: r.token_times[0] for r in res} == last_chunk
    ids = _by_id(records)
    for name in ("lm.dispatch", "lm.logits_wait"):
        assert {ids[r["parent"]][0] for r in records[name]} == {
            "lm.prefill_chunk", "lm.decode_step"}
    assert {ids[r["parent"]][0] for r in records["lm.reset_pages"]} == {
        "lm.admit"}
    assert all(r["parent"] is None for n in ("lm.admit", "lm.decode_step")
               for r in records[n])
    _assert_nested(records)
    # the step spans carry the live rows the roofline readers need
    for s in records["lm.decode_step"]:
        assert (s["rows"], s["seq"]) == (2, 1)
        assert s["q"] == [1] * len(s["ctx"]) and 1 <= len(s["ctx"]) <= 2
    assert [(c["q"], c["ctx"]) for c in records["lm.prefill_chunk"]] == [
        ([3], [3]), ([4], [4]), ([3], [7]), ([4], [4]), ([4], [8]), ([1], [9])]
    assert [s["step"] for s in records["lm.decode_step"]] == list(
        range(eng.stats["steps"]))


def test_lm_dispatch_counts_paged_kernel_blocks(monkeypatch):
    """``lm.dispatch`` carries the paged kernel's grid: 64-token pages over
    a 4-page table make blocks of 2 pages (128 keys), 2 a slot.  Request 0
    (126-token prompt, 4 tokens) prefills in two chunks, request 1 (3
    tokens, 2 out) in one, each one live block of 2; the decode steps
    write positions (126, 3), (127, idle), (128, idle): live 1 + 1, 1 + 0
    and 2 + 0 of 2 x 2.  With spans off nothing is counted."""
    from repro.kernels import attn_flash

    def make():
        return ContinuousLMEngine(LM_PARAMS, LM_CFG, num_slots=2,
                                  page_size=64, num_pages=8, max_seq=256)

    payloads = [(np.arange(126, dtype=np.int32) % 60 + 1, 4),
                (np.arange(3, dtype=np.int32) + 1, 2)]
    eng = make()
    rec = eng.record_spans()
    on = eng.serve(payloads)
    assert [(d["live_blocks"], d["grid_blocks"])
            for d in rec.records["lm.dispatch"]] == [
        (1, 2), (1, 2), (1, 2), (2, 4), (1, 4), (2, 4)]

    def refuse(*a):
        raise AssertionError("counted with spans off")

    monkeypatch.setattr(attn_flash, "paged_block_pages", refuse)
    off = make().serve(payloads)
    for a, b in zip(on, off):
        np.testing.assert_array_equal(a.value, b.value)


def test_lm_spans_survive_a_power_loss(tmp_path):
    """A power loss inside an admission's prefill unwinds past its open
    span: later spans still nest, and resumed requests keep one stamp per
    token."""
    from repro.resilience.faults import FaultPlan

    # prefill polls: one per chunk, so the fifth is the third request's
    # second chunk, after the first commits
    faults = FaultPlan.scripted([("prefill", 4, "power_loss"),
                                 ("decode", 2, "power_loss")])
    eng = _lm_engine(checkpoint_dir=str(tmp_path), epoch_steps=1,
                     faults=faults, clock=_fake_clock())
    rec = eng.record_spans()
    ref = _lm_engine().serve(LM_PAYLOADS)
    res = eng.serve(LM_PAYLOADS)
    assert eng.stats["power_losses"] == 2 and rec.records["lm.commit"]
    for a, b in zip(res, ref):
        np.testing.assert_array_equal(a.value, b.value)
        assert len(a.token_times) == len(a.value)
        assert list(a.token_times) == sorted(a.token_times)
    assert all(r["parent"] is None for r in rec.records["lm.admit"])
