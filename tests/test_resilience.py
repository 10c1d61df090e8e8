"""Executable intermittency resilience (repro.resilience, DESIGN.md §11).

Headline contract: under a seeded FaultPlan, every completed request's
output is BIT-IDENTICAL to the fault-free run.  LMs serve through the
resilient facade on the continuous engine: kills in prefill and mid-decode
resume from the last epoch commit.  CNNs serve through
``ResilientServeEngine``: kills at staging and dispatch requeue the bucket
idempotently (same rid, one result, no duplicates).  Plus: deterministic
fault schedules, bounded retries -> dead letters, deadlines, and
degraded-plan fallback.
"""
import dataclasses
import json

import jax
import numpy as np
import pytest

from repro.configs import SINGLE, all_configs
from repro.core.quant import PAPER_CONFIGS, W1A4
from repro.core.prequant import prequantize_cnn_params
from repro.launch.engine import CNNRunner, ServeEngine
from repro.models import transformer as T
from repro.models.cnn import init_cnn, svhn_cnn_spec
from repro.resilience import (DegradePolicy, DeviceDrop, FaultPlan,
                              PowerLoss, ResilienceConfig,
                              ResilientServeEngine)

VOCAB = 64
NEW_TOKENS = 7          # 6 decode steps; epoch_steps=2 -> commits every 2


# ---------------------------------------------------------------------------
# FaultPlan: determinism, validation, site/kind discipline
# ---------------------------------------------------------------------------

def test_fault_plan_deterministic_and_logged():
    def events(seed):
        p = FaultPlan(3.0, seed=seed)
        for _ in range(40):
            p.poll("decode", dt=2.0)
        return [(e.kind, e.site, e.t, e.offset, e.seq) for e in p.log]

    a, b = events(5), events(5)
    assert a and a == b                      # same seed -> same schedule
    assert events(6) != a                    # different seed -> different
    # at most one event per poll, clock stops at the fault
    p = FaultPlan(0.5, seed=0)
    ev = p.poll("decode", dt=4.0)
    assert ev is not None and ev.offset <= 4.0 and p._t == ev.t


def test_fault_plan_validation():
    with pytest.raises(ValueError):
        FaultPlan(0.0)
    with pytest.raises(ValueError):
        FaultPlan(-1.0)
    with pytest.raises(ValueError):
        FaultPlan(1.0, weights={"meteor_strike": 1.0})
    with pytest.raises(ValueError):
        FaultPlan.scripted([("nowhere", 0, "power_loss")])
    with pytest.raises(ValueError):
        # device_drop is not physically meaningful during staging
        FaultPlan.scripted([("staging", 0, "device_drop")])
    assert FaultPlan(None).poll("decode") is None   # never fires


def test_fault_plan_scripted_fires_nth_poll_per_site():
    p = FaultPlan.scripted([("decode", 1, "power_loss"),
                            ("staging", 0, "staging_corruption")])
    assert p.poll("staging", dt=0.5).kind == "staging_corruption"
    assert p.poll("decode") is None
    assert p.poll("decode").kind == "power_loss"
    assert p.poll("decode") is None
    assert [e.kind for e in p.log] == ["staging_corruption", "power_loss"]


def test_fault_plan_site_restricted_kinds():
    p = FaultPlan(0.1, seed=1)       # fires on nearly every poll
    for _ in range(50):
        p.poll("staging", dt=1.0)
    assert p.log
    assert all(e.kind in ("power_loss", "staging_corruption")
               for e in p.log)


# ---------------------------------------------------------------------------
# LM chaos: the resilient facade on the continuous engine
# ---------------------------------------------------------------------------

def _lm_setup():
    cfg = dataclasses.replace(
        all_configs()["smollm-360m"].smoke(
            n_layers=2, d_model=64, n_heads=2, n_kv_heads=1, d_ff=128,
            vocab=VOCAB, head_dim=32),
        quant=PAPER_CONFIGS["w1a8"])
    params, _ = T.init_lm(jax.random.PRNGKey(0), cfg, SINGLE)
    return cfg, params


@pytest.fixture(scope="module")
def lm():
    """Four prompts on two slots: requests 0 and 1 are admitted in the
    first pump, before the first commit (step 0); requests 2 and 3 after
    the commit at step 4, when the first two retire at step 6."""
    from repro import api

    cfg, params = _lm_setup()
    compiled = api.build(cfg, params=params).compile(batch_hints=(1, 2),
                                                     prompt_len=8)
    prompts = [np.random.RandomState(i).randint(0, VOCAB, size=(8,))
               .astype(np.int32) for i in range(4)]

    def mk(fault_plan=None, ckdir=None):
        return compiled.serve(
            max_batch=2, new_tokens=NEW_TOKENS,
            resilience=ResilienceConfig(fault_plan=fault_plan,
                                        checkpoint_dir=ckdir,
                                        epoch_steps=2)).engine

    ref_eng = mk()
    ref = [r.value for r in ref_eng.serve(prompts)]
    return dict(compiled=compiled, prompts=prompts, mk=mk, ref=ref,
                ref_stats=dict(ref_eng.stats))


def _assert_identical(results, ref):
    assert len(results) == len(ref)
    for r, v in zip(results, ref):
        np.testing.assert_array_equal(r.value, v)


def test_fault_plan_json_roundtrip_all_modes(tmp_path):
    """to_json/from_json round-trips the CONSTRUCTION spec: a reloaded
    plan replays the identical event schedule in every mode (the one
    on-disk format shared by chaos tests, bench_resilience, and fleet
    outage timelines)."""
    def replay(p, n=30):
        return [(e.kind, e.site, e.t, e.offset)
                for _ in range(n) for e in [p.poll("decode", dt=2.0)]
                if e is not None]

    random_p = FaultPlan(3.0, seed=11, weights={"power_loss": 1.0})
    scripted = FaultPlan.scripted([("decode", 2, "power_loss"),
                                   ("decode", 5, "device_drop")])
    timeline = FaultPlan.timeline([(1.5, "power_loss"), (9.0, "power_loss")])
    for plan in (random_p, scripted, timeline, FaultPlan(None)):
        spec = json.loads(json.dumps(plan.to_json()))
        assert replay(FaultPlan.from_json(spec)) == replay(
            FaultPlan.from_json(plan.to_json()))
    # polling state is NOT serialized: a mid-run plan still round-trips
    # to a fresh equivalent plan
    half = FaultPlan(3.0, seed=11, weights={"power_loss": 1.0})
    replay(half, n=7)
    assert replay(FaultPlan.from_json(half.to_json())) == replay(
        FaultPlan(3.0, seed=11, weights={"power_loss": 1.0}))
    # file round-trip + version guard
    path = tmp_path / "plan.json"
    scripted.save(path)
    assert replay(FaultPlan.load(path)) == replay(
        FaultPlan.scripted([("decode", 2, "power_loss"),
                            ("decode", 5, "device_drop")]))
    with pytest.raises(ValueError, match="version"):
        FaultPlan.from_json({"version": 99})


def test_lm_kill_in_prefill_bit_identical(lm, tmp_path):
    """A kill in request 0's prefill, before the first commit, restarts
    cold with every request enqueued again: no prompt is prefilled twice
    and every request completes bit-identical."""
    eng = lm["mk"](FaultPlan.scripted([("prefill", 0, "power_loss")]),
                   ckdir=str(tmp_path))
    res = eng.serve(lm["prompts"])
    assert eng.stats["power_losses"] == 1
    assert eng.stats["prefill_chunks"] == lm["ref_stats"]["prefill_chunks"]
    _assert_identical(res, lm["ref"])


def test_lm_kill_in_later_prefill_resumes_from_commit(lm, tmp_path):
    """A kill in request 2's prefill resumes from the step-4 commit: the
    two earlier prompts are not prefilled again."""
    eng = lm["mk"](FaultPlan.scripted([("prefill", 2, "power_loss")]),
                   ckdir=str(tmp_path))
    res = eng.serve(lm["prompts"])
    assert eng.stats["power_losses"] == 1
    assert eng.stats["commits"] > 0
    assert eng.stats["prefill_chunks"] == lm["ref_stats"]["prefill_chunks"]
    _assert_identical(res, lm["ref"])


def test_lm_kill_mid_decode_resumes_from_epoch(lm, tmp_path):
    """A kill at decode step 4 must NOT rerun prefill: the engine restores
    the step-2 commit — the software NV-FA partial-state retention —
    redoes step 3 only, and still produces bit-identical tokens."""
    eng = lm["mk"](FaultPlan.scripted([("decode", 3, "power_loss")]),
                   ckdir=str(tmp_path))
    res = eng.serve(lm["prompts"])
    s, ref = eng.stats, lm["ref_stats"]
    assert s["power_losses"] == 1
    assert s["prefill_chunks"] == ref["prefill_chunks"]   # prefill ran once
    assert s["steps"] == ref["steps"] + 1                 # step 3 redone
    _assert_identical(res, lm["ref"])


def test_lm_kill_without_checkpoints_restarts_clean(lm):
    """No checkpoint dir = the volatile P=0 baseline: the kill restarts
    from cold, the two admitted requests are prefilled again, and every
    request still completes bit-identical."""
    eng = lm["mk"](FaultPlan.scripted([("decode", 1, "power_loss")]))
    res = eng.serve(lm["prompts"])
    s, ref = eng.stats, lm["ref_stats"]
    assert s["power_losses"] == 1 and s["commits"] == 0
    assert s["prefill_chunks"] == ref["prefill_chunks"] + 2
    assert eng.pool.used_pages == 0
    _assert_identical(res, lm["ref"])


def test_lm_device_drop_and_slow_dispatch(lm, tmp_path):
    """A device drop reboots the LM engine like a power loss and resumes
    from the step-0 commit; a slow dispatch costs latency only."""
    faults = FaultPlan.scripted([("decode", 0, "device_drop"),
                                 ("decode", 2, "slow_dispatch")])
    eng = lm["mk"](faults, ckdir=str(tmp_path))
    res = eng.serve(lm["prompts"])
    assert [e.kind for e in faults.log] == ["device_drop", "slow_dispatch"]
    assert eng.stats["power_losses"] == 1
    _assert_identical(res, lm["ref"])


def test_lm_random_chaos_bit_identical(lm, tmp_path):
    """Seeded exponential schedule (not scripted): everything completes and
    matches the fault-free run bit for bit."""
    eng = lm["mk"](FaultPlan(6.0, seed=3), ckdir=str(tmp_path))
    res = eng.serve(lm["prompts"])
    assert eng.stats["power_losses"] >= 1          # chaos actually happened
    assert [r.rid for r in res] == list(range(len(lm["prompts"])))
    assert not eng.dead_letters
    _assert_identical(res, lm["ref"])


def test_lm_idempotent_requeue_no_duplicate_results(lm, tmp_path):
    """Requests a kill takes from the schedule keep their rid; one Result
    per rid, and rids are exactly the submitted ones."""
    eng = lm["mk"](FaultPlan.scripted([("prefill", 0, "power_loss"),
                                       ("decode", 1, "power_loss")]),
                   ckdir=str(tmp_path))
    rids = [eng.submit(p) for p in lm["prompts"]]
    res = eng.drain()
    assert eng.stats["power_losses"] == 2
    assert [r.rid for r in res] == sorted(rids)
    _assert_identical(res, lm["ref"])


def test_lm_kill_after_handback_answers_each_request_once(lm, tmp_path):
    """The second round's first prefill is killed after the first round
    was drained: the step-10 commit still holds two of those requests in
    flight and two results.  They were handed back, so the restore drops
    them (pages freed); the second round's requests are enqueued again
    and answered once each."""
    eng = lm["mk"](FaultPlan.scripted([("prefill", 4, "power_loss")]),
                   ckdir=str(tmp_path))
    first = eng.serve(lm["prompts"])
    second = eng.serve(lm["prompts"])
    assert eng.stats["power_losses"] == 1
    assert [r.rid for r in first] == [0, 1, 2, 3]
    assert [r.rid for r in second] == [4, 5, 6, 7]
    assert eng.pool.used_pages == 0
    _assert_identical(first, lm["ref"])
    _assert_identical(second, lm["ref"])


def test_lm_epoch_steps_must_be_positive(lm):
    with pytest.raises(ValueError, match="epoch_steps"):
        lm["compiled"].serve(max_batch=2, resilience=ResilienceConfig(
            epoch_steps=0))


# ---------------------------------------------------------------------------
# CNN path: single-shot dispatch kills, vs the PLAIN engine's output
# ---------------------------------------------------------------------------

SPEC = svhn_cnn_spec(8)
_params, _ = init_cnn(jax.random.PRNGKey(0), SPEC)
CNN_PARAMS = prequantize_cnn_params(_params, SPEC, W1A4)
IMGS = [np.random.RandomState(i).uniform(size=(16, 16, 3)).astype(np.float32)
        for i in range(4)]
CNN_REF = [r.value for r in ServeEngine(CNNRunner(CNN_PARAMS, SPEC, W1A4),
                                        max_batch=4).serve(IMGS)]


def test_cnn_dispatch_kill_bit_identical_to_plain_engine():
    ref = ServeEngine(CNNRunner(CNN_PARAMS, SPEC, W1A4),
                      max_batch=4).serve(IMGS)
    eng = ResilientServeEngine(
        CNNRunner(CNN_PARAMS, SPEC, W1A4),
        fault_plan=FaultPlan.scripted([("dispatch", 0, "power_loss"),
                                       ("staging", 1,
                                        "staging_corruption")]),
        max_batch=4)
    res = eng.serve(IMGS)
    assert eng.stats["power_losses"] == 1
    assert eng.stats["staging_retries"] == 1
    for a, b in zip(ref, res):
        np.testing.assert_array_equal(a.value, b.value)


def test_cnn_kill_in_staging_bit_identical():
    eng = ResilientServeEngine(
        CNNRunner(CNN_PARAMS, SPEC, W1A4),
        fault_plan=FaultPlan.scripted([("staging", 0, "power_loss")]),
        max_batch=4)
    res = eng.serve(IMGS)
    assert eng.stats["power_losses"] == 1 and eng.stats["retries"] == 4
    _assert_identical(res, CNN_REF)


def test_cnn_staging_corruption_detected_and_restaged():
    eng = ResilientServeEngine(
        CNNRunner(CNN_PARAMS, SPEC, W1A4),
        fault_plan=FaultPlan.scripted([("staging", 0,
                                        "staging_corruption")]),
        max_batch=4)
    res = eng.serve(IMGS)
    assert eng.stats["staging_retries"] == 1        # checksum caught it
    assert eng.stats["faults"] == 0                 # not a kill
    _assert_identical(res, CNN_REF)


def test_cnn_slow_dispatch_costs_latency_only():
    eng = ResilientServeEngine(
        CNNRunner(CNN_PARAMS, SPEC, W1A4),
        fault_plan=FaultPlan.scripted([("dispatch", 0, "slow_dispatch")]),
        max_batch=4)
    res = eng.serve(IMGS)
    assert eng.stats["slow_dispatches"] == 1 and eng.stats["faults"] == 0
    _assert_identical(res, CNN_REF)


def test_cnn_idempotent_requeue_no_duplicate_results():
    """Killed-bucket requests keep their rid; one Result per rid, and rids
    are exactly the submitted ones."""
    eng = ResilientServeEngine(
        CNNRunner(CNN_PARAMS, SPEC, W1A4),
        fault_plan=FaultPlan.scripted([("dispatch", 0, "power_loss"),
                                       ("dispatch", 1, "power_loss")]),
        max_batch=4)
    rids = [eng.submit(img) for img in IMGS]
    res = eng.drain()
    assert [r.rid for r in res] == sorted(rids)
    assert len({r.rid for r in res}) == len(rids)
    _assert_identical(res, CNN_REF)


def test_cnn_staging_kill_requeued_without_duplicates():
    """A staging kill of the second of two buckets requeues just that
    bucket's requests: each rid gets one result, the first bucket's are
    not served twice."""
    eng = ResilientServeEngine(
        CNNRunner(CNN_PARAMS, SPEC, W1A4),
        fault_plan=FaultPlan.scripted([("staging", 1, "power_loss")]),
        max_batch=2)
    res = eng.serve(IMGS)
    assert eng.stats["retries"] == 2 and eng.stats["dispatches"] == 2
    assert eng.stats["requests"] == len(IMGS)
    assert [r.rid for r in res] == list(range(len(IMGS)))
    _assert_identical(res, CNN_REF)


def test_mesh_rejected():
    class FakeMesh:
        pass

    with pytest.raises(ValueError):
        ResilientServeEngine(CNNRunner(CNN_PARAMS, SPEC, W1A4),
                             mesh=FakeMesh())


# ---------------------------------------------------------------------------
# Recovery policy: retries bounded, deadlines, dead letters
# ---------------------------------------------------------------------------

def test_retry_exhaustion_dead_letters():
    eng = ResilientServeEngine(
        CNNRunner(CNN_PARAMS, SPEC, W1A4),
        fault_plan=FaultPlan.scripted(
            [("dispatch", i, "power_loss") for i in range(3)]),
        max_batch=4, max_retries=2)
    res = eng.serve(IMGS)
    assert res == []
    assert set(eng.dead_letters) == set(range(4))
    assert all("retries exhausted" in v for v in eng.dead_letters.values())
    assert eng.stats["dead_lettered"] == 4
    # the engine stays serviceable: the next submit round succeeds (poll 3
    # has no scripted fault) and gets fresh rids
    res2 = eng.serve(IMGS)
    assert len(res2) == 4 and set(eng.dead_letters) == set(range(4))


def test_deadline_dead_letters_with_fake_clock():
    t = [0.0]
    eng = ResilientServeEngine(
        CNNRunner(CNN_PARAMS, SPEC, W1A4),
        fault_plan=FaultPlan.scripted([("dispatch", 0, "power_loss")]),
        max_batch=4, deadline_s=5.0, clock=lambda: t[0],
        backoff_base_s=0.0, backoff_max_s=0.0)
    for img in IMGS:
        eng.submit(img)     # 4th submit fills the bucket
    t[0] = 1.0
    eng.pump()              # dispatch -> scripted kill -> requeued, in time
    t[0] = 10.0             # past every deadline before the retry lands
    res = eng.drain()
    assert res == []
    assert all(v == "deadline" for v in eng.dead_letters.values())
    assert len(eng.dead_letters) == 4


def test_backoff_schedule_is_bounded_and_jittered():
    eng = ResilientServeEngine(
        CNNRunner(CNN_PARAMS, SPEC, W1A4),
        fault_plan=FaultPlan.scripted(
            [("dispatch", i, "power_loss") for i in range(4)]),
        max_batch=1, max_retries=4, backoff_base_s=0.01, backoff_max_s=0.03,
        clock=lambda: 0.0)
    eng.submit(IMGS[0])
    delays = []
    for _ in range(4):
        eng._flush_all()                      # dispatch -> kill -> requeue
        (eligible_at, _), = eng._retry
        delays.append(eligible_at)
        eng._admit_retries(force=True)
    # exponential growth up to the cap, jitter in [0.5, 1.5) of nominal
    for d, nominal in zip(delays, (0.01, 0.02, 0.03, 0.03)):
        assert 0.5 * nominal <= d < 1.5 * nominal


# ---------------------------------------------------------------------------
# Graceful degradation: plan fallback under fault pressure / energy budget
# ---------------------------------------------------------------------------

def test_degrade_policy_triggers():
    p = DegradePolicy(fault_window=4, fault_threshold=2)
    p.record_fault()
    assert not p.should_degrade()
    p.record_fault()
    assert p.should_degrade()
    p.reset()
    assert not p.should_degrade()
    # old faults age out of the window
    p2 = DegradePolicy(fault_window=2, fault_threshold=2)
    p2.record_fault()
    p2.record_dispatch()
    p2.record_fault()
    assert not p2.should_degrade()
    # energy budget trigger
    p3 = DegradePolicy(energy_budget_pj=100.0)
    p3.record_dispatch(60.0)
    assert not p3.should_degrade()
    p3.record_dispatch(60.0)
    assert p3.should_degrade()
    with pytest.raises(ValueError):
        DegradePolicy(fault_window=0)
    with pytest.raises(ValueError):
        DegradePolicy(energy_budget_pj=-1.0)


def _cnn_compile(quant):
    from repro import api

    return api.build(SPEC, quant, params=_params, img_hw=16).compile(
        batch_hints=(1, 4))


@pytest.fixture(scope="module")
def compiled_pair():
    """The svhn CNN compiled at w1a8 (primary) and w1a4 (fallback): the
    CPU target prices 8-bit activations above 4-bit ones and 4-bit ones
    the same as 1-bit, so this pair has a cheaper fallback."""
    return (_cnn_compile(PAPER_CONFIGS["w1a8"]),
            _cnn_compile(PAPER_CONFIGS["w1a4"]), IMGS)


def test_degrade_swaps_to_fallback_plan(compiled_pair):
    """Two dispatch kills trip the policy; the engine swaps to the w1a4
    fallback plan, retries with a FRESH budget, and completes with no dead
    letters — outputs bit-identical to the fallback plan served fault-free
    (the accuracy-for-progress trade, executed)."""
    primary, fallback, imgs = compiled_pair
    ref_dep = fallback.serve(resilience=ResilienceConfig(), max_batch=4)
    ref = [r.value for r in ref_dep.engine.serve(imgs)]

    dep = primary.serve(resilience=ResilienceConfig(
        fault_plan=FaultPlan.scripted([("dispatch", 0, "power_loss"),
                                       ("dispatch", 1, "power_loss")]),
        degrade=DegradePolicy(fault_window=4, fault_threshold=2)),
        fallback=fallback, max_batch=4)
    eng = dep.engine
    res = eng.serve(imgs)
    assert eng.stats["degrades"] == 1
    assert not eng.dead_letters
    assert all(v == 1 for v in eng.result_runner.values())
    _assert_identical(res, ref)


def test_energy_budget_degrades_between_batches(compiled_pair):
    """No faults at all: a tiny modeled energy budget alone forces the
    fallback for the SECOND batch (result_runner records who served what),
    exercising plan_energy_pj as the budget currency."""
    from repro.core.plan import plan_energy_pj

    primary, fallback, imgs = compiled_pair
    e = plan_energy_pj(primary.plan)
    assert e > 0 and plan_energy_pj(fallback.plan) < e
    dep = primary.serve(resilience=ResilienceConfig(
        degrade=DegradePolicy(energy_budget_pj=e)),  # first dispatch spends
        fallback=fallback, max_batch=4)
    eng = dep.engine
    first = eng.serve(imgs)
    assert eng.stats["degrades"] == 1
    second = eng.serve(imgs)
    by_runner = {r.rid: eng.result_runner[r.rid] for r in first + second}
    assert set(by_runner.values()) == {0, 1}
    assert all(eng.result_runner[r.rid] == 1 for r in second)


def test_degrade_policy_edge_cases():
    """Window/threshold/recover_after degenerate values + streak algebra."""
    # zero-width pressure window is rejected at construction, not silently
    # never-triggering (deque(maxlen=0) would drop every observation)
    with pytest.raises(ValueError):
        DegradePolicy(fault_window=0, fault_threshold=1)
    with pytest.raises(ValueError):
        DegradePolicy(recover_after=0)
    # streak: builds on clean dispatches, zeroes on any fault, survives
    # exactly the recover_after boundary
    p = DegradePolicy(recover_after=2)
    p.record_dispatch()
    assert p.clean_streak() == 1 and not p.should_recover()
    p.record_fault()
    assert p.clean_streak() == 0
    p.record_dispatch()
    p.record_dispatch()
    assert p.should_recover()
    p.reset()
    assert p.clean_streak() == 0 and not p.should_recover()
    # recover_after=None: degrades are one-way no matter the streak
    q = DegradePolicy()
    for _ in range(100):
        q.record_dispatch()
    assert not q.should_recover()


def test_equal_energy_fallback_keeps_unit_scale(compiled_pair):
    """A fallback whose modeled energy EQUALS the primary's gives no
    effective MTBF gain: the engine still swaps (forward progress may come
    from the fresh retry budget) but the energy-weighted fault clock must
    keep scale 1.0 — degrading to an equally hungry plan must not dilate
    fault exposure."""
    from repro.core.plan import plan_energy_pj

    primary, _, imgs = compiled_pair
    clone = _cnn_compile(PAPER_CONFIGS["w1a8"])   # same quant as primary
    assert plan_energy_pj(clone.plan) == plan_energy_pj(primary.plan) > 0
    dep = primary.serve(resilience=ResilienceConfig(
        fault_plan=FaultPlan.scripted([("dispatch", 0, "power_loss"),
                                       ("dispatch", 1, "power_loss")]),
        degrade=DegradePolicy(fault_window=4, fault_threshold=2)),
        fallback=clone, max_batch=4)
    eng = dep.engine
    res = eng.serve(imgs)
    assert eng.stats["degrades"] == 1
    assert eng._energy_scale == 1.0
    assert len(res) == len(imgs) and not eng.dead_letters


def test_recovery_rearms_primary_plan(compiled_pair):
    """After a fault-pressure degrade, ``recover_after`` consecutive clean
    dispatches re-arm the primary: the next batch is served by runner 0
    with outputs bit-identical to the primary's fault-free run, the energy
    scale is restored to 1.0, and stats['recoveries'] records it."""
    primary, fallback, imgs = compiled_pair
    ref_dep = primary.serve(resilience=ResilienceConfig(), max_batch=4)
    ref = [r.value for r in ref_dep.engine.serve(imgs)]

    dep = primary.serve(resilience=ResilienceConfig(
        fault_plan=FaultPlan.scripted([("dispatch", 0, "power_loss"),
                                       ("dispatch", 1, "power_loss")]),
        degrade=DegradePolicy(fault_window=4, fault_threshold=2,
                              recover_after=1)),
        fallback=fallback, max_batch=4)
    eng = dep.engine
    first = eng.serve(imgs)              # kills -> degrade -> clean dispatch
    assert eng.stats["degrades"] == 1
    assert eng.stats["recoveries"] == 1  # the completing dispatch re-arms
    assert eng._active == 0 and eng._energy_scale == 1.0
    assert all(eng.result_runner[r.rid] == 1 for r in first)
    second = eng.serve(imgs)             # back on the primary plan
    assert all(eng.result_runner[r.rid] == 0 for r in second)
    _assert_identical(second, ref)


# ---------------------------------------------------------------------------
# Facade: api serve(resilience=...) wiring
# ---------------------------------------------------------------------------

def test_resilience_fields_unread_by_the_plan_kind_raise(compiled_pair, lm):
    """A config field the plan's engine would ignore raises PlanError:
    checkpoints and epochs on a CNN plan, retries, backoff and degrade on
    an LM plan."""
    from repro.core.plan import PlanError

    primary = compiled_pair[0]
    for field, value in (("checkpoint_dir", "unused"), ("epoch_steps", 2)):
        with pytest.raises(PlanError, match=f"CNN.*'{field}'"):
            primary.serve(resilience=ResilienceConfig(**{field: value}))
    for field, value in (("max_retries", 5), ("backoff_base_s", 0.5),
                         ("backoff_max_s", 2.0),
                         ("degrade", DegradePolicy())):
        with pytest.raises(PlanError, match=f"LM.*'{field}'"):
            lm["compiled"].serve(resilience=ResilienceConfig(**{field: value}))


def test_api_serve_resilience_roundtrip(compiled_pair):
    """A CNN plan's resilient deployment is a ResilientServeEngine; a
    dispatch kill requeues the bucket and the results equal the plain
    deployment's."""
    primary, _, imgs = compiled_pair
    ref = primary.serve(max_batch=4).predict(imgs)
    dep = primary.serve(resilience=ResilienceConfig(
        fault_plan=FaultPlan.scripted([("dispatch", 0, "power_loss")])),
        max_batch=4)
    assert isinstance(dep.engine, ResilientServeEngine)
    res = dep.engine.serve(imgs)
    assert dep.engine.stats["power_losses"] == 1
    assert dep.engine.stats["retries"] == len(imgs)
    _assert_identical(res, ref)


def test_api_serve_resilience_lm_kill_resumes(lm, tmp_path):
    """An LM plan's resilient deployment is the continuous engine with
    epoch checkpoints on disk: a kill at decode step 5 resumes from the
    step-4 commit, and the tokens equal the plain deployment's."""
    from repro.launch.engine import ContinuousLMEngine

    plain = lm["compiled"].serve(max_batch=2, new_tokens=NEW_TOKENS)
    ref = plain.predict(lm["prompts"])
    dep = lm["compiled"].serve(resilience=ResilienceConfig(
        fault_plan=FaultPlan.scripted([("decode", 4, "power_loss")]),
        checkpoint_dir=str(tmp_path), epoch_steps=2),
        max_batch=2, new_tokens=NEW_TOKENS)
    assert isinstance(dep.engine, ContinuousLMEngine)
    assert dep.engine.epoch_steps == 2
    res = dep.engine.serve(lm["prompts"])
    assert dep.stats["power_losses"] == 1
    assert dep.stats["steps"] == plain.stats["steps"]   # nothing redone
    assert any(tmp_path.iterdir())                      # commits on disk
    _assert_identical(res, ref)


def test_exception_types():
    ev_args = ("power_loss", "decode", 1.0, 0.5, 0)
    from repro.resilience import FaultEvent

    with pytest.raises(PowerLoss):
        FaultPlan.raise_for(FaultEvent(*ev_args))
    with pytest.raises(DeviceDrop):
        FaultPlan.raise_for(FaultEvent("device_drop", "decode", 1.0, 0.5, 0))
    # latency/corruption kinds are handled in place, never raised
    FaultPlan.raise_for(FaultEvent("slow_dispatch", "decode", 1.0, 0.5, 0))
