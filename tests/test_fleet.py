"""Fleet simulator + co-design search (repro.fleet, DESIGN.md §14).

Covers: trace determinism and serialization, the fluid node walk's
physics invariants against closed forms, fleet aggregation, the
simulator-vs-live-engine validation contract (the engine-accounting
mirror AND one real continuous-engine replay through the resilient
facade), and the co-design
search (SLO enforcement, baseline win, Pareto bookkeeping).
"""
import json

import numpy as np
import pytest

from repro.fleet import (DAY_S, HarvestTrace, NodeConfig, TraceSpec,
                         assign_slos, candidate_space, codesign,
                         fleet_report, generate_fleet,
                         make_trace, measured_efficiency, outage_faultplan,
                         predict_engine_stats, rescale_outages,
                         simulate_fleet, simulate_node)
from repro.fleet import sim as fleet_sim
from repro.resilience.faults import POWER_LOSS, FaultPlan


def _const_trace(power_mw: float, duration_s: float = 3600.0,
                 dt_s: float = 60.0) -> HarvestTrace:
    spec = TraceSpec(node_id="n0", archetype="thermal", seed=0, dt_s=dt_s,
                     duration_s=duration_s)
    n = spec.n_samples
    return HarvestTrace(spec, np.full(n, float(power_mw)))


def _cfg(**kw) -> NodeConfig:
    base = dict(node_id="n0", quant="w1a4", target="sot_mram", period=5,
                frame_energy_uj=50.0, frame_time_us=100.0, nv_write_us=1.0,
                resume_us=0.0, cap_uj=10_000.0, wake_frac=0.5)
    base.update(kw)
    return NodeConfig(**base)


# ---------------------------------------------------------------------------
# Traces
# ---------------------------------------------------------------------------

def test_trace_determinism_and_prefix_stability():
    """Same spec -> bit-identical trace; node i's spec never depends on
    the fleet size (growth appends, never reshuffles)."""
    spec = TraceSpec(node_id="a", archetype="solar", seed=7)
    np.testing.assert_array_equal(make_trace(spec).power_mw,
                                  make_trace(spec).power_mw)
    big, small = generate_fleet(12, seed=3), generate_fleet(5, seed=3)
    assert big[:5] == small
    assert generate_fleet(12, seed=3) == big
    assert generate_fleet(12, seed=4) != big


def test_trace_archetype_shapes():
    """Solar is zero at night, rf never drops below its floor, thermal
    dropouts reach exactly zero; power is never negative."""
    solar = make_trace(TraceSpec("s", "solar", 1))
    night = int(3 * 3600 / solar.dt_s)      # 03:00, well before sunrise
    assert solar.power_mw[night] == 0.0 and solar.power_mw.max() > 0
    rf = make_trace(TraceSpec("r", "rf", 1, params=dict(floor_mw=2.0)))
    assert rf.power_mw.min() >= 2.0
    thermal = make_trace(TraceSpec("t", "thermal", 1,
                                   params=dict(mean_gap_s=1800.0)))
    assert thermal.power_mw.min() == 0.0    # at least one dropout landed
    for tr in (solar, rf, thermal):
        assert (tr.power_mw >= 0).all() and tr.harvested_j() > 0


def test_trace_serialization_roundtrip():
    spec = TraceSpec("n1", "rf", 42, params=dict(burst_mw=80.0))
    assert TraceSpec.from_json(json.loads(json.dumps(spec.to_json()))) == spec
    tr = make_trace(spec)
    # spec-first form regenerates; embedded form restores verbatim
    lean = HarvestTrace.from_json(json.loads(json.dumps(tr.to_json())))
    np.testing.assert_array_equal(lean.power_mw, tr.power_mw)
    fat = HarvestTrace.from_json(
        json.loads(json.dumps(tr.to_json(embed_power=True))))
    np.testing.assert_array_equal(fat.power_mw, tr.power_mw)
    bad = tr.to_json(embed_power=True)
    bad["power_mw"] = bad["power_mw"][:-1]
    with pytest.raises(ValueError, match="length"):
        HarvestTrace.from_json(bad)


def test_trace_spec_validation():
    with pytest.raises(ValueError, match="archetype"):
        TraceSpec("x", "nuclear", 0)
    with pytest.raises(ValueError, match="positive"):
        TraceSpec("x", "solar", 0, dt_s=0.0)
    with pytest.raises(ValueError, match="cover"):
        TraceSpec("x", "solar", 0, dt_s=60.0, duration_s=30.0)
    with pytest.raises(ValueError, match="weights"):
        generate_fleet(2, mix=(("solar", 0.0), ("rf", 0.0)))


# ---------------------------------------------------------------------------
# Fluid node simulation
# ---------------------------------------------------------------------------

def test_node_config_validation():
    for bad in (dict(period=0), dict(frame_energy_uj=0.0),
                dict(frame_time_us=-1.0), dict(nv_write_us=-0.1),
                dict(cap_uj=0.0), dict(wake_frac=0.0), dict(wake_frac=1.5)):
        with pytest.raises(ValueError):
            _cfg(**bad)


def test_node_ample_harvest_matches_closed_form():
    """Harvest above active power: the node never fails and commits
    exactly int(duration / block_s) * P frames (one closed form vs the
    segment-walking loop)."""
    cfg = _cfg(resume_us=0.0)
    assert cfg.p_active_ujps == pytest.approx(500_000.0)   # 0.5 W
    trace = _const_trace(600.0)                            # 0.6 W harvest
    r = simulate_node(trace, cfg)
    assert r["failures"] == 0 and not r["dead"]
    expected = int(trace.duration_s / cfg.block_s) * cfg.period
    assert r["committed_frames"] == expected
    assert r["on_s"] == pytest.approx(trace.duration_s)
    assert r["off_s"] == 0.0
    assert 0.0 < r["efficiency"] <= 1.0
    # resume debt is paid before any productive block
    cfg2 = _cfg(resume_us=5e5)                             # 0.5 s reboot
    r2 = simulate_node(trace, cfg2)
    assert r2["resume_s"] == pytest.approx(0.5)
    assert r2["committed_frames"] == int(
        (trace.duration_s - 0.5) / cfg2.block_s) * cfg2.period


def test_node_duty_cycle_physics():
    """Insufficient harvest: the node duty-cycles; energy and time are
    conserved and every outage loses at most P in-flight frames."""
    cfg = _cfg()
    trace = _const_trace(100.0, duration_s=7200.0)   # 0.1 W vs 0.5 W draw
    r = simulate_node(trace, cfg)
    assert r["failures"] > 10                        # real duty cycling
    assert r["on_s"] + r["off_s"] == pytest.approx(trace.duration_s)
    # consumed energy can exceed harvested only by the boot buffer charge
    assert r["consumed_j"] <= r["harvested_j"] + cfg.cap_uj * 1e-6 + 1e-9
    assert r["wasted_frames"] <= r["failures"] * cfg.period
    assert r["committed_frames"] % cfg.period == 0
    # the walk is deterministic: identical reruns, bit for bit
    assert simulate_node(trace, cfg) == r


def test_node_bulk_cycle_path_consistent_with_segment_walk():
    """The closed-form k-cycle fast path must agree with walking the same
    constant-power span chopped into many segments (which interrupts
    cycles at boundaries and takes the incremental path)."""
    cfg = _cfg(frame_time_us=2**10, period=3, cap_uj=500.0)
    coarse = simulate_node(_const_trace(20.0, duration_s=7200.0,
                                        dt_s=7200.0), cfg)
    fine = simulate_node(_const_trace(20.0, duration_s=7200.0, dt_s=30.0),
                         cfg)
    assert coarse["failures"] == pytest.approx(fine["failures"], abs=1)
    assert coarse["committed_frames"] == pytest.approx(
        fine["committed_frames"], rel=1e-3)
    assert coarse["on_s"] == pytest.approx(fine["on_s"], rel=1e-6)
    assert coarse["harvested_j"] == pytest.approx(fine["harvested_j"])


def test_node_dead_and_outage_collection():
    """No harvest at all: the boot buffer runs out once, then darkness —
    outage instants are on the work clock (frames) and capped at
    ``collect_outages``."""
    cfg = _cfg(cap_uj=30.0)      # buffer worth ~0.6 frames: dead node
    r = simulate_node(_const_trace(0.0), cfg, collect_outages=4)
    assert r["dead"] and r["failures"] == 1
    assert r["committed_frames"] == 0.0
    assert len(r["outage_frames"]) == 1
    cfg2 = _cfg(cap_uj=10_000.0)
    r2 = simulate_node(_const_trace(100.0, duration_s=7200.0), cfg2,
                       collect_outages=4)
    assert len(r2["outage_frames"]) == 4
    assert all(b > a for a, b in zip(r2["outage_frames"],
                                     r2["outage_frames"][1:]))


def test_fleet_report_aggregates_and_archetypes():
    specs = generate_fleet(6, seed=1, duration_s=3600.0)
    traces = [make_trace(s) for s in specs]
    cfgs = [_cfg(node_id=s.node_id) for s in specs]
    results = simulate_fleet(traces, cfgs)
    rep = fleet_report(results, specs)
    assert rep["nodes"] == 6
    assert rep["inferences_per_day"] == pytest.approx(
        sum(r["inferences_per_day"] for r in results))
    arch = rep["archetypes"]
    assert sum(a["nodes"] for a in arch.values()) == 6
    assert sum(a["inferences_per_day"] for a in arch.values()) == (
        pytest.approx(rep["inferences_per_day"]))
    with pytest.raises(ValueError, match="configs"):
        simulate_fleet(traces, cfgs[:-1])


# ---------------------------------------------------------------------------
# Discrete arm: engine mirror + live validation
# ---------------------------------------------------------------------------

def _live_lm(epoch_steps: int, ckdir, fault_plan=None, max_batch: int = 4):
    """The continuous engine live_validation replays through: the same
    tiny smoke LM, built by the resilient facade."""
    import dataclasses

    import jax

    from repro import api
    from repro.configs import SINGLE, all_configs
    from repro.core.quant import PAPER_CONFIGS
    from repro.models import transformer as T
    from repro.resilience import ResilienceConfig

    cfg = dataclasses.replace(
        all_configs()["smollm-360m"].smoke(
            n_layers=2, d_model=64, n_heads=2, n_kv_heads=1, d_ff=128,
            vocab=64, head_dim=32),
        quant=PAPER_CONFIGS["w1a8"])
    params, _ = T.init_lm(jax.random.PRNGKey(0), cfg, SINGLE)
    compiled = api.build(cfg, params=params).compile(
        batch_hints=(1, max_batch), prompt_len=8)
    return compiled.serve(max_batch=max_batch, new_tokens=7,
                          resilience=ResilienceConfig(
                              fault_plan=fault_plan, checkpoint_dir=ckdir,
                              epoch_steps=epoch_steps)).engine


_PROMPTS = [np.random.RandomState(i).randint(0, 64, size=(8,))
            .astype(np.int32) for i in range(8)]


def test_sim_constants_mirror_engine(tmp_path):
    """sim.py keeps jax out of the fluid path by mirroring the continuous
    engine's poll charges and page geometry as local constants — pin them
    to a live engine: its fault clock advances by exactly PREFILL_DT per
    prefill chunk plus DECODE_DT per decode step."""
    faults = FaultPlan(None)
    eng = _live_lm(2, str(tmp_path), fault_plan=faults)
    eng.serve(_PROMPTS)
    s = eng.stats
    assert (eng.page_size, eng.pool.num_pages, eng.chunk) == (
        fleet_sim.PAGE_SIZE, fleet_sim.NUM_PAGES, fleet_sim.PAGE_SIZE)
    assert faults._t == (fleet_sim.PREFILL_DT * s["prefill_chunks"]
                         + fleet_sim.DECODE_DT * s["steps"])
    assert faults._site_calls == {"prefill": s["prefill_chunks"],
                                  "decode": s["steps"]}


def test_epoch_schedule_mirror(tmp_path):
    """The mirror's commit schedule matches a live engine's for epoch
    lengths from one step to longer than any request."""
    for k in (1, 2, 3, 7):
        eng = _live_lm(k, str(tmp_path / f"k{k}"))
        eng.serve(_PROMPTS)
        p = predict_engine_stats(FaultPlan(None), n_requests=8, new_tokens=7,
                                 epoch_steps=k, max_batch=4)
        for key in ("commits", "steps", "prefill_chunks", "dispatches"):
            assert p[key] == eng.stats[key], (k, key)


@pytest.mark.parametrize("k", [1, 2, 3, 6])
def test_mirror_commit_schedule(k):
    """One wave of four requests (6 decode steps): a commit lands before
    decode steps 0, k, 2k, ... and a kill during step j resumes from the
    last of them, so exactly j mod k steps are redone and no prompt is
    prefilled again."""
    kw = dict(n_requests=4, new_tokens=7, epoch_steps=k, max_batch=4)
    free = predict_engine_stats(FaultPlan(None), **kw)
    assert free["commits"] == len(range(0, 6, k))
    assert free["steps"] == 6 and free["prefill_chunks"] == 4
    for j in range(6):
        # the work clock reads 4 (prefills) + j when decode step j starts
        s = predict_engine_stats(outage_faultplan([4.0 + j + 0.5]), **kw)
        assert s["power_losses"] == 1 and s["completed"] == 4
        assert s["steps"] == 6 + j % k
        assert s["prefill_chunks"] == 4
        assert s["wasted_steps"] == 0.5


def test_predict_engine_stats_fault_free():
    s = predict_engine_stats(FaultPlan(None), n_requests=8, new_tokens=7,
                             epoch_steps=2, max_batch=4)
    # two waves of four: 4 prefill chunks and 6 decode steps each
    assert s["prefill_chunks"] == 8 and s["steps"] == 12
    assert s["dispatches"] == s["useful_dispatches"] == 20
    assert s["requests"] == s["completed"] == 8 and s["power_losses"] == 0
    assert s["commits"] == 6           # before steps 0, 2, 4, 6, 8, 10
    assert measured_efficiency(s) == pytest.approx(1.0)


def test_predict_engine_stats_timeline_kills():
    """A mid-decode power loss wastes the partial window and resumes from
    the last commit; a loss before the first commit restarts cold with
    every request enqueued again, and all still complete."""
    plan = outage_faultplan([6.0])       # during decode step 2
    s = predict_engine_stats(plan, n_requests=4, new_tokens=7,
                             epoch_steps=2, max_batch=4)
    assert s["power_losses"] == 1 and s["completed"] == 4
    assert s["prefill_chunks"] == 4      # resumed, not prefilled again
    assert s["steps"] == 6 + 1           # step 2 redone from the commit
    assert 0 < s["wasted_steps"] <= 1.0
    assert measured_efficiency(s) < 1.0
    cold = predict_engine_stats(outage_faultplan([1.5]), n_requests=4,
                                new_tokens=7, epoch_steps=2, max_batch=4)
    assert cold["power_losses"] == 1 and cold["completed"] == 4
    assert cold["prefill_chunks"] == 1 + 4   # one chunk ran before the loss
    assert cold["steps"] == 6 and cold["commits"] == 3


def test_outage_faultplan_json_shared_format():
    """The fleet's outage schedule and the chaos FaultPlan share one JSON
    format: timeline events survive the round trip and replay identically."""
    plan = outage_faultplan([1.5, 4.0, 4.0])
    clone = FaultPlan.from_json(json.loads(json.dumps(plan.to_json())))
    kw = dict(n_requests=8, new_tokens=7, epoch_steps=2, max_batch=4)
    assert predict_engine_stats(plan, **kw) == predict_engine_stats(
        clone, **kw)
    with pytest.raises(ValueError, match="every site"):
        FaultPlan.timeline([(1.0, "staging_corruption")])
    with pytest.raises(ValueError, match="non-decreasing"):
        FaultPlan.timeline([(2.0, POWER_LOSS), (1.0, POWER_LOSS)])


def test_rescale_outages():
    assert rescale_outages([10.0, 20.0], 40.0, 8.0) == [2.0, 4.0]
    assert rescale_outages([], 0.0, 8.0) == []


@pytest.mark.slow
def test_live_validation_matches_engine(tmp_path):
    """THE acceptance-criteria contract: the simulator's engine-accounting
    mirror matches a real continuous-engine replay of an outage timeline —
    integer counters exactly, floats within tol, tokens as without
    faults."""
    from repro.fleet import live_validation

    # the first outage comes during the first prefills, before any commit
    # (a cold restart), the second mid-decode after one
    v = live_validation([3.0, 9.5], checkpoint_dir=str(tmp_path),
                        n_requests=8, new_tokens=7, epoch_steps=2,
                        max_batch=4, tol=1e-6)
    assert v["ok"], v["deltas"]
    assert v["measured"]["power_losses"] == 2
    assert v["completed"] == 8 and v["dead_letters"] == 0
    assert v["bit_identical"]
    assert all(d == 0 for k, d in v["deltas"].items()
               if k in fleet_sim._VALIDATE_INT_KEYS)
    # both outages after the first commit (decode steps 2 and 7)
    w = live_validation([6.5, 13.5], checkpoint_dir=str(tmp_path / "b"),
                        n_requests=8, new_tokens=7, epoch_steps=2,
                        max_batch=4, tol=1e-6)
    assert w["ok"], w["deltas"]
    assert w["completed"] == 8 and w["measured"]["power_losses"] == 2


# ---------------------------------------------------------------------------
# Co-design search
# ---------------------------------------------------------------------------

# synthetic frontier: cheap-but-inaccurate vs costly-but-accurate, plus a
# dominated target that Pareto pruning must drop
_ACC = {"wA": 5.0, "wB": 10.0}
_COSTS = {("wA", "fast"): (100.0, 200.0), ("wA", "slow"): (150.0, 400.0),
          ("wB", "fast"): (40.0, 120.0), ("wB", "slow"): (60.0, 300.0)}


def test_candidate_space_prunes_dominated_targets():
    cands = candidate_space(_COSTS, quants=("wA", "wB"),
                            targets=("fast", "slow"), periods=(1, 10))
    assert ("wA", "slow", 1) not in cands      # dominated in energy AND time
    assert {("wA", "fast", 1), ("wA", "fast", 10),
            ("wB", "fast", 1), ("wB", "fast", 10)} == set(cands)


def test_assign_slos_deterministic():
    a = assign_slos(50, seed=9, levels=(6.0, 13.0))
    assert a == assign_slos(50, seed=9, levels=(6.0, 13.0))
    assert set(a) == {6.0, 13.0}


def test_codesign_beats_baseline_and_enforces_slo():
    """Heterogeneous SLOs: strict nodes need the accurate quant, loose
    nodes run the cheap one — per-node choice must beat the best uniform
    config, with zero SLO violations and the codesign point on the
    Pareto frontier."""
    specs = generate_fleet(8, seed=2, duration_s=6 * 3600.0)
    traces = [make_trace(s) for s in specs]
    slos = [5.5 if i % 2 else 12.0 for i in range(8)]
    out = codesign(traces, slos, accuracy=_ACC, costs=_COSTS,
                   candidates=candidate_space(_COSTS, quants=("wA", "wB"),
                                              targets=("fast", "slow"),
                                              periods=(1, 10)),
                   node_kw=dict(cap_uj=10_000.0))
    assert out["slo_violations"] == 0
    assert all(a["error_pct"] <= a["slo_error_pct"]
               for a in out["assignments"])
    # strict nodes are forced onto wA; loose nodes pick the cheaper wB
    assert all(a["quant"] == "wA" for a in out["assignments"][1::2])
    assert out["baseline"]["quant"] == "wA"    # only wA fits every SLO
    assert out["win_vs_baseline"] > 1.0
    assert out["inferences_per_day"] >= out["baseline"]["inferences_per_day"]
    kinds = {p["kind"] for p in out["pareto"]}
    assert "codesign" in kinds
    # determinism: the whole search replays bit-for-bit
    out2 = codesign(traces, slos, accuracy=_ACC, costs=_COSTS,
                    candidates=candidate_space(_COSTS, quants=("wA", "wB"),
                                               targets=("fast", "slow"),
                                               periods=(1, 10)),
                    node_kw=dict(cap_uj=10_000.0))
    assert json.dumps(out, sort_keys=True, default=str) == json.dumps(
        out2, sort_keys=True, default=str)


def test_codesign_infeasible_slo_raises():
    specs = generate_fleet(2, seed=0, duration_s=3600.0)
    traces = [make_trace(s) for s in specs]
    with pytest.raises(ValueError, match="SLO"):
        codesign(traces, [4.0, 12.0], accuracy=_ACC, costs=_COSTS,
                 candidates=candidate_space(
                     _COSTS, quants=("wA", "wB"), targets=("fast", "slow"),
                     periods=(1,)))


@pytest.mark.slow
def test_frame_cost_table_real_plans():
    """Structure-only compiles priced via plan_cost_on: Table-II currency
    with sane orderings (more activation bits cost more energy on the
    same PIM target; fp-free)."""
    from repro.fleet import frame_cost_table

    costs = frame_cost_table(quants=("w1a4", "w1a8"),
                             targets=("sot_mram", "reram"))
    for (q, t), (e, lat) in costs.items():
        assert e > 0 and lat > 0
    assert costs[("w1a8", "sot_mram")][0] > costs[("w1a4", "sot_mram")][0]
    assert costs[("w1a8", "reram")][0] > costs[("w1a8", "sot_mram")][0]
