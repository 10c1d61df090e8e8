"""Request-level serving engine (launch/engine.py, DESIGN.md §7).

Headline contract: batching is invisible — a request's result is
bit-identical whether it ran alone (sequential per-request dispatch), in a
full bucket, in a ragged padded bucket, or sharded across devices, for
every conv engine the dispatcher can pick.  Plus the LM facade: an LM
plan serves on the continuous engine, with nothing added numerically.
"""
import dataclasses
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import SINGLE, all_configs
from repro.core.quant import PAPER_CONFIGS, W1A4
from repro.launch import engine as engine_mod
from repro.launch.engine import (BucketBatcher, CNNRunner, QueueFull,
                                 Request, ServeEngine, run_offered_load)
from repro.models import transformer as T
from repro.core.prequant import prequantize_cnn_params
from repro.models.cnn import cnn_forward, init_cnn, svhn_cnn_spec


# ---------------------------------------------------------------------------
# BucketBatcher: pure queue/bucketing logic (no jax)
# ---------------------------------------------------------------------------

def _req(rid, payload="p", t=0.0):
    return Request(rid, payload, t)


def test_batcher_flushes_full_bucket():
    b = BucketBatcher(max_batch=3, flush_deadline_s=1.0)
    assert b.add(_req(0), "k", now=0.0) is None
    assert b.add(_req(1), "k", now=0.0) is None
    full = b.add(_req(2), "k", now=0.0)
    assert full is not None and [r.rid for r in full.requests] == [0, 1, 2]
    assert b.pending() == 0


def test_batcher_separates_shape_keys():
    b = BucketBatcher(max_batch=2, flush_deadline_s=1.0)
    assert b.add(_req(0), ("cnn", 40), now=0.0) is None
    assert b.add(_req(1), ("cnn", 32), now=0.0) is None
    full = b.add(_req(2), ("cnn", 40), now=0.0)
    assert full is not None and full.key == ("cnn", 40)
    assert b.pending() == 1  # the 32-key request still queued


def test_batcher_deadline_flush():
    b = BucketBatcher(max_batch=8, flush_deadline_s=0.010)
    b.add(_req(0), "k", now=0.0)
    assert b.take_expired(now=0.005) == []       # young bucket stays
    exp = b.take_expired(now=0.011)              # oldest waited past deadline
    assert len(exp) == 1 and exp[0].requests[0].rid == 0
    assert b.pending() == 0


def test_batcher_deadline_exact_boundary():
    """The deadline comparison is inclusive: a bucket whose oldest request
    has waited EXACTLY flush_deadline_s flushes now, not one poll later
    (pollers quantize time; an exclusive compare would add a full poll
    interval of tail latency)."""
    b = BucketBatcher(max_batch=8, flush_deadline_s=0.010)
    b.add(_req(0), "k", now=0.0)
    exp = b.take_expired(now=0.010)
    assert len(exp) == 1 and exp[0].requests[0].rid == 0
    assert b.pending() == 0


def test_batcher_take_all_drains_partials():
    b = BucketBatcher(max_batch=8, flush_deadline_s=1.0)
    b.add(_req(0), "a", now=0.0)
    b.add(_req(1), "b", now=0.0)
    assert sorted(bk.key for bk in b.take_all()) == ["a", "b"]
    assert b.pending() == 0


# ---------------------------------------------------------------------------
# CNN path: bit-identity across engines, bucket shapes, ragged tails
# ---------------------------------------------------------------------------

SPEC = svhn_cnn_spec(8)
_params, _ = init_cnn(jax.random.PRNGKey(0), SPEC)
SERVE_PARAMS = prequantize_cnn_params(_params, SPEC, W1A4)
IMGS = [np.random.RandomState(i).uniform(size=(16, 16, 3)).astype(np.float32)
        for i in range(6)]


def _cnn_engine(quant, max_batch):
    return ServeEngine(CNNRunner(SERVE_PARAMS, SPEC, quant),
                       max_batch=max_batch)


@pytest.mark.parametrize("engine", ["auto", "implicit", "fused"])
def test_cnn_batched_bit_identical_to_sequential(engine):
    """Batched engine output == sequential per-request loop, per conv
    engine: auto dispatch, forced implicit (patch-free), forced fused
    (Pallas interpret)."""
    quant = dataclasses.replace(W1A4, engine=engine)
    n = 3 if engine == "fused" else len(IMGS)  # interpret mode is slow
    imgs = IMGS[:n]
    seq = _cnn_engine(quant, 1).serve(imgs)          # per-request dispatches
    bat = _cnn_engine(quant, 4).serve(imgs)          # incl. ragged tail
    for s, b in zip(seq, bat):
        np.testing.assert_array_equal(s.value, b.value)
    # and against the raw jitted batched forward, no engine machinery at all
    ref = np.asarray(jax.jit(
        lambda x: cnn_forward(SERVE_PARAMS, x, SPEC, quant, "serve"))(
            jnp.asarray(np.stack(imgs))))
    for i, b in enumerate(bat):
        np.testing.assert_array_equal(b.value, ref[i])


def test_cnn_ragged_buckets_and_padding_metadata():
    """Every split of 5 requests pads its final bucket; results must not
    see the padding (padded rows are copies of row 0, sliced off)."""
    ref = [r.value for r in _cnn_engine(W1A4, 1).serve(IMGS[:5])]
    for max_batch in (2, 3, 4, 8):
        res = _cnn_engine(W1A4, max_batch).serve(IMGS[:5])
        for i, r in enumerate(res):
            np.testing.assert_array_equal(r.value, ref[i])
            assert r.batch <= max_batch
            # pow2 growth capped at bucket capacity: a FULL bucket never
            # pads above max_batch (no dead rows on the steady-state path)
            assert r.batch <= r.padded <= max_batch
    # 5 reqs at max_batch=4 -> buckets of 4 and 1: the tail padded to 1
    res = _cnn_engine(W1A4, 4).serve(IMGS[:5])
    assert res[-1].batch == 1 and res[-1].padded == 1
    # non-pow2 capacity: full bucket of 3 dispatches at exactly 3
    res = _cnn_engine(W1A4, 3).serve(IMGS[:3])
    assert all(r.batch == 3 and r.padded == 3 for r in res)


def test_cnn_mixed_shape_buckets():
    """Different image shapes never share a dispatch; results match the
    per-shape references."""
    small = [np.random.RandomState(100 + i).uniform(size=(12, 12, 3))
             .astype(np.float32) for i in range(2)]
    eng = _cnn_engine(W1A4, 4)
    res = eng.serve([IMGS[0], small[0], IMGS[1], small[1]])
    assert eng.stats["dispatches"] == 2  # one per shape key
    ref16 = [r.value for r in _cnn_engine(W1A4, 1).serve(IMGS[:2])]
    ref12 = [r.value for r in _cnn_engine(W1A4, 1).serve(small)]
    np.testing.assert_array_equal(res[0].value, ref16[0])
    np.testing.assert_array_equal(res[2].value, ref16[1])
    np.testing.assert_array_equal(res[1].value, ref12[0])
    np.testing.assert_array_equal(res[3].value, ref12[1])


# Row-chunked staging: ``_PUT_CHUNK_BYTES`` set small so that SVHN-sized
# buckets (3,072 B an image) go up in chunks, as a 224x224 AlexNet bucket
# does at the real threshold.
ROW = IMGS[0].nbytes
MORE_IMGS = IMGS + [np.random.RandomState(10 + i).uniform(size=(16, 16, 3))
                    .astype(np.float32) for i in range(4)]


@pytest.mark.parametrize("n,max_batch,chunk_bytes,chunks", [
    (8, 8, 2 * ROW, [4]),            # full bucket: 8 rows in 4 chunks
    (5, 8, 2 * ROW, [4]),            # 5 requests padded to 8 rows
    (6, 6, 5000, [4]),               # 6 rows in 4 uneven chunks (2,2,1,1)
    (3, 3, 1, [3]),                  # capped at the bucket's rows
    (10, 8, 3 * ROW, [4, 1]),        # a full bucket, then 2 rows in one
])
def test_cnn_chunked_staging_bit_identical(monkeypatch, n, max_batch,
                                           chunk_bytes, chunks):
    """A bucket staged as row chunks and joined on the device serves the
    same logits, bit for bit, as the same bucket in one put."""
    imgs = MORE_IMGS[:n]
    one = _cnn_engine(W1A4, max_batch)
    ref = one.serve(imgs)
    assert one.stats["put_chunks"] == one.stats["dispatches"]
    monkeypatch.setattr(engine_mod, "_PUT_CHUNK_BYTES", chunk_bytes)
    eng = _cnn_engine(W1A4, max_batch)
    res = eng.serve(imgs)
    assert eng.stats["put_chunks"] == sum(chunks)
    assert eng.stats["dispatches"] == len(chunks)
    for a, b in zip(res, ref):
        assert (a.batch, a.padded) == (b.batch, b.padded)
        np.testing.assert_array_equal(a.value, b.value)


def test_cnn_chunked_staging_one_program_per_padded_batch(monkeypatch):
    """The chunk count follows the padded batch, so a full and a padded
    bucket of 8 rows share one program, compiled once."""
    monkeypatch.setattr(engine_mod, "_PUT_CHUNK_BYTES", 2 * ROW)
    eng = _cnn_engine(W1A4, 8)
    eng.serve(MORE_IMGS[:8])
    eng.serve(MORE_IMGS[:5])
    eng.serve(MORE_IMGS[:1])
    assert eng.stats["put_chunks"] == 4 + 4 + 1
    assert sorted(padded for (_, padded, _) in eng._fns) == [1, 8]
    assert all(fn._cache_size() == 1 for fn in eng._fns.values())


@pytest.mark.parametrize("kind", ["cnn", "resilient"])
def test_small_and_checked_buckets_stage_in_one_put(monkeypatch, kind):
    """A sub-threshold CNN bucket and ``ResilientServeEngine``'s checked
    staging each issue exactly one put per bucket and serve what the plain
    engine serves."""
    ref = _cnn_engine(W1A4, 4).serve(IMGS)
    if kind == "cnn":
        eng = _cnn_engine(W1A4, 4)
    else:
        from repro.resilience import ResilientServeEngine

        monkeypatch.setattr(engine_mod, "_PUT_CHUNK_BYTES", 1)
        eng = ResilientServeEngine(CNNRunner(SERVE_PARAMS, SPEC, W1A4),
                                   max_batch=4)
    res = eng.serve(IMGS)
    assert eng.stats["put_chunks"] == eng.stats["dispatches"] == 2
    for a, b in zip(res, ref):
        np.testing.assert_array_equal(a.value, b.value)


def test_engine_single_device_fallback_and_stats():
    """On one device the engine must take the plain-jit path (mesh None)."""
    from repro.launch.mesh import make_serve_mesh

    if len(jax.devices()) == 1:
        assert make_serve_mesh() is None
    eng = _cnn_engine(W1A4, 4)
    assert eng.mesh is None or eng._n_data == len(jax.devices())
    res = eng.serve(IMGS[:4])
    assert eng.stats == dict(dispatches=1, requests=4, padded_rows=0,
                             put_chunks=1)
    assert all(r.latency_s >= 0 for r in res)


def test_queue_backpressure():
    eng = ServeEngine(CNNRunner(SERVE_PARAMS, SPEC, W1A4), max_batch=4,
                      max_pending=2)
    eng.submit(IMGS[0])
    eng.submit(IMGS[1])
    with pytest.raises(QueueFull):
        eng.submit(IMGS[2])
    assert len(eng.drain()) == 2  # queued work is never lost to QueueFull
    # max_pending counts REQUESTS even once buckets close: max_batch=1
    # turns every submit into a ready bucket, and the second must still
    # trip the bound (not slip through as "one bucket")
    eng2 = ServeEngine(CNNRunner(SERVE_PARAMS, SPEC, W1A4), max_batch=1,
                       max_pending=1)
    eng2.submit(IMGS[0])
    with pytest.raises(QueueFull):
        eng2.submit(IMGS[1])


def test_queue_drain_then_resubmit_roundtrip():
    """After QueueFull, drain() relieves the pressure and the SAME payloads
    resubmit cleanly; every rid maps to the result of its own payload
    across the drain boundary (rids never recycle)."""
    eng = ServeEngine(CNNRunner(SERVE_PARAMS, SPEC, W1A4), max_batch=2,
                      max_pending=2)
    ref = [r.value for r in _cnn_engine(W1A4, 1).serve(IMGS[:4])]
    rid_to_img = {eng.submit(IMGS[0]): 0, eng.submit(IMGS[1]): 1}
    with pytest.raises(QueueFull):
        eng.submit(IMGS[2])
    first = eng.drain()
    assert sorted(r.rid for r in first) == sorted(rid_to_img)
    rid_to_img.update({eng.submit(IMGS[2]): 2, eng.submit(IMGS[3]): 3})
    second = eng.drain()
    assert {r.rid for r in second}.isdisjoint({r.rid for r in first})
    for r in first + second:
        np.testing.assert_array_equal(r.value, ref[rid_to_img[r.rid]])


def test_submit_retry_backoff_until_admitted():
    """submit_retry turns QueueFull into bounded jittered backoff: with the
    queue full, retries pump (dispatching relieves the pressure) and the
    request is admitted — no sleep escapes into the test (injected fake)."""
    eng = ServeEngine(CNNRunner(SERVE_PARAMS, SPEC, W1A4), max_batch=2,
                      max_pending=2)
    eng.submit(IMGS[0])
    eng.submit(IMGS[1])     # full bucket -> _ready; queue at max_pending
    slept = []
    rid = eng.submit_retry(IMGS[2], attempts=3, base_s=0.001, max_s=0.004,
                           sleep=slept.append)
    assert rid == 2
    # first attempt hit QueueFull, pump() dispatched the ready bucket,
    # second attempt was admitted after exactly one jittered backoff
    assert len(slept) == 1 and 0.0005 <= slept[0] < 0.0015
    assert len(eng.drain()) == 3


def test_submit_retry_exhausts_and_reraises():
    """When nothing can relieve the pressure (all load in one open partial
    bucket below max_batch), submit_retry re-raises QueueFull after its
    attempt budget — overload surfaces, it doesn't block forever."""
    eng = ServeEngine(CNNRunner(SERVE_PARAMS, SPEC, W1A4), max_batch=8,
                      max_pending=1, flush_deadline_s=1e9)
    eng.submit(IMGS[0])     # partial bucket: pump() can't flush it
    slept = []
    with pytest.raises(QueueFull):
        eng.submit_retry(IMGS[1], attempts=4, base_s=0.001, max_s=0.002,
                         sleep=slept.append)
    # attempts-1 sleeps (no sleep after the final failure), delays
    # exponential then capped, each jittered in [0.5, 1.5) of nominal
    assert len(slept) == 3
    for d, nominal in zip(slept, (0.001, 0.002, 0.002)):
        assert 0.5 * nominal <= d < 1.5 * nominal
    assert len(eng.drain()) == 1  # the queued request was never lost


def test_serve_closed_loop_survives_tiny_max_pending():
    """serve() must complete (flushing partial buckets in place) even when
    max_pending is smaller than a bucket — closed loop never sheds."""
    eng = ServeEngine(CNNRunner(SERVE_PARAMS, SPEC, W1A4), max_batch=4,
                      max_pending=2)
    res = eng.serve(IMGS[:5])
    assert len(res) == 5
    ref = [r.value for r in _cnn_engine(W1A4, 1).serve(IMGS[:5])]
    for r, v in zip(res, ref):
        np.testing.assert_array_equal(r.value, v)


def test_flush_deadline_dispatches_partial_bucket():
    t = [0.0]
    eng = ServeEngine(CNNRunner(SERVE_PARAMS, SPEC, W1A4), max_batch=8,
                      flush_deadline_s=0.010, clock=lambda: t[0])
    eng.submit(IMGS[0])
    eng.pump()
    assert not eng._results            # deadline not reached: still queued
    t[0] = 0.011
    eng.pump()                         # expired -> dispatched alone
    assert 0 in eng._results and eng._results[0].batch == 1


def test_submit_retry_jitter_is_seeded_and_injectable():
    """Backoff jitter comes from an engine-owned seeded RNG: two engines
    built with the same retry_rng seed sleep the identical sequence, a
    different seed diverges, and a RandomState instance passes through —
    retry timing is reproducible, never ambient-global."""
    def delays(retry_rng):
        eng = ServeEngine(CNNRunner(SERVE_PARAMS, SPEC, W1A4), max_batch=8,
                          max_pending=1, flush_deadline_s=1e9,
                          retry_rng=retry_rng)
        eng.submit(IMGS[0])
        slept = []
        with pytest.raises(QueueFull):
            eng.submit_retry(IMGS[1], attempts=4, base_s=0.001, max_s=0.008,
                             sleep=slept.append)
        return slept

    assert delays(7) == delays(7)
    assert delays(7) != delays(8)
    assert delays(np.random.RandomState(7)) == delays(7)


def test_offered_load_closed_loop_counts():
    eng = ServeEngine(CNNRunner(SERVE_PARAMS, SPEC, W1A4), max_batch=4)
    row = run_offered_load(eng, IMGS, rate_rps=None)
    assert row["n_requests"] == len(IMGS)
    assert row["achieved_rps"] > 0 and row["p99_ms"] >= row["p50_ms"]


def test_offered_load_splits_queue_wait_from_service():
    """run_offered_load decomposes latency: queue-wait (submit -> dispatch)
    and service (dispatch -> done) are reported separately and their p50s
    compose to about the end-to-end p50 for a serial engine."""
    eng = ServeEngine(CNNRunner(SERVE_PARAMS, SPEC, W1A4), max_batch=2)
    row = run_offered_load(eng, IMGS, rate_rps=None)
    for k in ("queue_p50_ms", "queue_p99_ms", "service_p50_ms",
              "service_p99_ms"):
        assert k in row and np.isfinite(row[k]) and row[k] >= 0
    assert row["queue_p99_ms"] >= row["queue_p50_ms"]
    assert row["service_p99_ms"] >= row["service_p50_ms"]
    # components never exceed the end-to-end envelope
    assert row["queue_p50_ms"] <= row["p99_ms"]
    assert row["service_p50_ms"] <= row["p99_ms"]


# ---------------------------------------------------------------------------
# LM path: the facade serves an LM plan on the continuous engine
# ---------------------------------------------------------------------------

def _lm_setup():
    cfg = dataclasses.replace(
        all_configs()["smollm-360m"].smoke(
            n_layers=2, d_model=64, n_heads=2, n_kv_heads=1, d_ff=128,
            vocab=64, head_dim=32),
        quant=PAPER_CONFIGS["w1a8"])
    params, _ = T.init_lm(jax.random.PRNGKey(0), cfg, SINGLE)
    return cfg, params


def test_lm_engine_exact_vs_direct_forward_same_composition():
    """The facade adds NOTHING numerically: ``compiled.serve()`` on an LM
    plan builds a continuous engine whose tokens equal a hand-built
    ``ContinuousLMEngine`` over the same plan, request for request, at the
    same composition (5 requests on 4 slots: a full batch, then one
    admitted into a freed slot), with the same dispatch counts."""
    from repro import api
    from repro.launch.engine import ContinuousLMEngine

    cfg, params = _lm_setup()
    prompts = [np.random.RandomState(i).randint(0, cfg.vocab, size=(8,))
               .astype(np.int32) for i in range(5)]
    compiled = api.build(cfg, params=params).compile(batch_hints=(1, 4),
                                                     prompt_len=8)
    dep = compiled.serve(max_batch=4, new_tokens=6)
    assert isinstance(dep.engine, ContinuousLMEngine)
    assert dep.engine.num_slots == 4
    res = dep.engine.serve(prompts)
    hand = ContinuousLMEngine(None, cfg, num_slots=4, new_tokens=6,
                              model_plan=compiled.plan)
    ref = hand.serve(prompts)
    assert dep.stats == hand.stats
    for a, b in zip(res, ref):
        np.testing.assert_array_equal(a.value, b.value)
    assert all(r.value.shape == (6,) for r in res)
    # tokens come from the REAL vocab, never the padded unembed tail
    assert all(int(r.value.max()) < cfg.vocab for r in res)
    # serving is deterministic: a fresh deployment reproduces exactly
    again = compiled.serve(max_batch=4, new_tokens=6).predict(prompts)
    for a, b in zip(res, again):
        np.testing.assert_array_equal(a.value, b)


# ---------------------------------------------------------------------------
# multi-device: shard_map data parallelism (8 forced host devices)
# ---------------------------------------------------------------------------

MD_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax, jax.numpy as jnp
from repro.core.quant import W1A4
from repro.distributed.sharding import batch_sharding, data_parallel
from repro.launch.engine import CNNRunner, ServeEngine
from repro.launch.mesh import make_serve_mesh
from repro.core.prequant import prequantize_cnn_params
from repro.models.cnn import cnn_forward, init_cnn, svhn_cnn_spec

spec = svhn_cnn_spec(8)
params, _ = init_cnn(jax.random.PRNGKey(0), spec)
sp = prequantize_cnn_params(params, spec, W1A4)
imgs = [np.random.RandomState(i).uniform(size=(16, 16, 3)).astype(np.float32)
        for i in range(19)]  # ragged: 16 + 3
mesh = make_serve_mesh()
assert mesh is not None and mesh.devices.size == 8, mesh
# the mesh path keeps one sharded put per bucket, whatever the chunk size
import repro.launch.engine as E
E._PUT_CHUNK_BYTES = 1
runner = CNNRunner(sp, spec, W1A4)
eng = ServeEngine(runner, max_batch=16, mesh=mesh)
res = eng.serve(imgs)
assert eng.stats["dispatches"] == 2, eng.stats
assert eng.stats["put_chunks"] == 2, eng.stats
# ragged tail (3) padded up to the device count
assert res[-1].padded % 8 == 0 and res[-1].batch == 3, res[-1]
# 1) engine plumbing is exact: a direct shard_map call on the same padded
#    batch reproduces every served row bit-for-bit
fwd = jax.jit(data_parallel(runner.make_forward(runner.shape_key(imgs[0])), mesh))
full = jax.device_put(runner.collate(imgs[:16], 16), batch_sharding(mesh))
direct = np.asarray(fwd(sp, full))
for i in range(16):
    np.testing.assert_array_equal(res[i].value, direct[i])
# 2) semantics match the single-device per-request path (separate compiled
#    programs under a different device topology: fp layers drift at ulp ->
#    quant-level scale, so allclose + class equality, not bitwise)
f1 = jax.jit(lambda x: cnn_forward(sp, x, spec, W1A4, "serve"))
for i, r in enumerate(res):
    ref = np.asarray(f1(jnp.asarray(imgs[i])[None]))[0]
    np.testing.assert_allclose(r.value, ref, rtol=2e-2, atol=2e-2)
    assert r.value.argmax() == ref.argmax(), i
print("MULTIDEVICE OK")
"""


@pytest.mark.slow
def test_engine_multidevice_sharded_subprocess():
    """Data-parallel shard_map dispatch on 8 forced host devices is
    bit-identical to the single-device per-request path."""
    env = dict(os.environ, PYTHONPATH="src")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run([sys.executable, "-c", MD_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=600,
                       cwd=os.path.dirname(os.path.dirname(__file__)))
    assert "MULTIDEVICE OK" in p.stdout, p.stdout + p.stderr
