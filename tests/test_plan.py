"""Compile-once execution plans (repro.core.plan, DESIGN.md §8).

Pins four contracts:

* **Golden dispatch table** — ``compile_model``'s engine choice for every
  paper CNN layer (svhn, alexnet) at batch 1 and 8 on CPU.  A heuristic /
  cost-model regression shows up here as a readable dict diff, not as a
  perf mystery three benchmarks later.
* **Plan-time validation** — explicit ``QuantConfig.engine`` overrides
  that are infeasible for the backend/shape raise :class:`PlanError`
  naming the layer, instead of failing inside a ``pallas_call``.
* **Bit-identity** — plan-compiled serve output equals the legacy
  ``engine="auto"`` dispatch (CNN and LM), and a serialized plan reloaded
  from disk reproduces it WITHOUT requantizing or re-autotuning.
* **Plan-keyed program caches** — the serving engine never shares a
  compiled program between two different plans.
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro.core import plan as P
from repro.core.quant import QuantConfig, W1A4, W1A8
from repro.kernels import ops
from repro.models.cnn import ConvSpec, cnn_forward, init_cnn, svhn_cnn_spec


@pytest.fixture(autouse=True)
def _clean_dispatch_state():
    """Plan installs / autotune verdicts must never leak across tests."""
    ops.clear_plan_state()
    yield
    ops.clear_plan_state()


def _small_setup(channels=8, img=16, batch=2, quant=W1A4, seed=0):
    spec = svhn_cnn_spec(channels)
    params, _ = init_cnn(jax.random.PRNGKey(seed), spec)
    x = jax.random.uniform(jax.random.PRNGKey(seed + 1),
                           (batch, img, img, 3))
    return spec, params, x


# ---------------------------------------------------------------------------
# Golden dispatch table (paper CNNs, CPU, batch 1 and 8)
# ---------------------------------------------------------------------------

GOLDEN_CPU = {
    "svhn": {
        "conv0": {1: "fp", 8: "fp"},
        "conv1": {1: "implicit", 8: "implicit"},
        # channel-expanding with cin below the measured cin=96 CPU
        # crossover (svhn L2 ran implicit at 0.63x gemm, crossover
        # 32->64 at 0.77x in bench_conv): route the patch GEMM
        "conv2": {1: "f32dot", 8: "f32dot"},
        "conv3": {1: "implicit", 8: "implicit"},
        "conv4": {1: "f32dot", 8: "f32dot"},
        "conv5": {1: "f32dot", 8: "implicit"},
        "conv6": {1: "f32dot", 8: "f32dot"},
        "conv7": {1: "fp", 8: "fp"},
    },
    "alexnet": {
        "conv0": {1: "fp", 8: "fp"},
        "conv1": {1: "implicit", 8: "implicit"},
        "conv2": {1: "f32dot", 8: "implicit"},
        "conv3": {1: "f32dot", 8: "implicit"},
        "conv4": {1: "f32dot", 8: "implicit"},
        "fc5": {1: "f32dot", 8: "f32dot"},
        "fc6": {1: "f32dot", 8: "f32dot"},
        "fc7": {1: "fp", 8: "fp"},
    },
}


def test_golden_dispatch_table_cpu():
    from repro.configs.paper_cnn import ALEXNET_SPEC, SVHN_SPEC

    got = {}
    for name, spec, img, quant in (("svhn", SVHN_SPEC, 40, W1A4),
                                   ("alexnet", ALEXNET_SPEC, 112, W1A8)):
        plan = P.compile_model(None, spec, quant, backend="cpu",
                               batch_hints=(1, 8), img_hw=img, model=name)
        got[name] = {lp.name: dict(lp.engines) for lp in plan.layers}
    assert got == GOLDEN_CPU


def test_structure_only_plan_cannot_execute():
    spec, _, x = _small_setup()
    plan = P.compile_model(None, spec, W1A4, img_hw=16)
    with pytest.raises(P.PlanError, match="structure-only"):
        P.plan_forward(plan, x)


# ---------------------------------------------------------------------------
# Plan-time validation of explicit engine overrides
# ---------------------------------------------------------------------------

def test_plan_error_fused_on_cpu_names_layer():
    spec, params, _ = _small_setup()
    with pytest.raises(P.PlanError, match=r"layer 1 \(conv1.*Pallas"):
        P.compile_model(params, spec,
                        dataclasses.replace(W1A4, engine="fused"),
                        backend="cpu", img_hw=16)


def test_plan_error_f32dot_mantissa_bound():
    # W8A8 at K=3*3*64: the f32dot accumulator bound (2^24) is exceeded
    spec = [ConvSpec(3, 64, 3, role="first"), ConvSpec(64, 64, 3),
            ConvSpec(64, 10, 1, role="last")]
    quant = QuantConfig(w_bits=8, a_bits=8, engine="f32dot")
    with pytest.raises(P.PlanError, match=r"layer 1 .*mantissa"):
        P.compile_model(None, spec, quant, backend="cpu", img_hw=16)


def test_plan_error_implicit_on_1x1():
    spec = [ConvSpec(3, 8, 3, role="first"), ConvSpec(8, 8, 1),
            ConvSpec(8, 10, 1, role="last")]
    with pytest.raises(P.PlanError, match=r"layer 1 .*1x1"):
        P.compile_model(None, spec,
                        dataclasses.replace(W1A4, engine="implicit"),
                        backend="cpu", img_hw=16)


def test_feasible_override_passes_strict_validation():
    spec, params, x = _small_setup()
    quant = dataclasses.replace(W1A4, engine="f32dot")
    plan = P.compile_model(params, spec, quant, backend="cpu", img_hw=16)
    assert all(lp.engine == "f32dot" and lp.engine_source == "override"
               for lp in plan.layers if not lp.fp)
    # and the permissive compat path still matches it bit-for-bit
    np.testing.assert_array_equal(
        np.asarray(P.plan_forward(plan, x)),
        np.asarray(cnn_forward(plan.params, x, spec, quant, "serve")))


# ---------------------------------------------------------------------------
# Bit-identity: plan execution vs legacy auto dispatch; float checkpoints
# ---------------------------------------------------------------------------

def test_plan_forward_bit_identical_to_auto_dispatch():
    spec, params, x = _small_setup()
    plan = P.compile_model(params, spec, W1A4, batch_hints=(1, 2), img_hw=16)
    ref = np.asarray(cnn_forward(plan.params, x, spec, W1A4, "serve"))
    out = np.asarray(P.plan_forward(plan, x))
    np.testing.assert_array_equal(out, ref)
    # float checkpoint through the same plan structure (trace-time prequant)
    from_float = np.asarray(cnn_forward(params, x, spec, W1A4, "serve"))
    np.testing.assert_array_equal(out, from_float)


def test_prepare_serve_params_shim_is_gone():
    """The PR-4 deprecation shim was removed on schedule; compile_model's
    params payload is the (only) prequantization path and matches the raw
    prequantize step it wraps."""
    spec, params, _ = _small_setup()
    import repro.models.cnn as cnn_mod
    from repro.core.prequant import prequantize_cnn_params

    assert not hasattr(cnn_mod, "prepare_serve_params")
    sp = prequantize_cnn_params(params, spec, W1A4)
    plan = P.compile_model(params, spec, W1A4, img_hw=16)
    for a, b in zip(jax.tree.leaves(sp), jax.tree.leaves(plan.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_engine_at_hint_policy():
    lp = P.LayerPlan(
        index=0, name="conv0", op="conv", role="mid", fp=False, kh=3, kw=3,
        stride=1, padding="SAME", cin=8, cout=8, in_h=16, in_w=16, out_h=16,
        out_w=16, k=72, a_bits=4, w_bits=1, engine="f32dot",
        engine_source="heuristic",
        engines=((1, "f32dot"), (4, "implicit"), (16, "int8")))
    assert lp.engine_at(1) == "f32dot"       # exact hint
    assert lp.engine_at(4) == "implicit"     # exact hint
    assert lp.engine_at(8) == "implicit"     # largest hint below
    assert lp.engine_at(64) == "int8"        # largest hint below
    assert lp.engine_at(0) == "f32dot"       # below every hint -> smallest


# ---------------------------------------------------------------------------
# Serialization: reload skips requantization and autotuning
# ---------------------------------------------------------------------------

def test_roundtrip_reload_is_bit_identical_and_never_requantizes(
        tmp_path, monkeypatch):
    spec, params, x = _small_setup()
    plan = P.compile_model(params, spec, W1A4, batch_hints=(1, 2),
                           img_hw=16, model="svhn_rt")
    expected = np.asarray(P.plan_forward(plan, x))
    path = P.save_plan(plan, str(tmp_path / "plan_rt"))
    assert path.endswith(".json") and (tmp_path / "plan_rt.npz").exists()

    plan2 = P.load_plan(str(tmp_path / "plan_rt"))
    assert plan2.fingerprint() == plan.fingerprint()
    # a reloaded plan must never touch the quantizers again
    import repro.core.quant as quant_mod

    def _forbidden(*a, **kw):
        raise AssertionError("requantization after plan reload")

    monkeypatch.setattr(quant_mod, "weight_levels", _forbidden)
    out = np.asarray(P.plan_forward(plan2, x))
    np.testing.assert_array_equal(out, expected)
    # level dtypes survive the npz round trip (int8 stays int8)
    for p, p2 in zip(plan.params, plan2.params):
        if "w_lv" in p:
            assert p2["w_lv"].dtype == p["w_lv"].dtype


def test_plan_version_gate(tmp_path):
    spec, params, _ = _small_setup()
    plan = P.compile_model(params, spec, W1A4, img_hw=16)
    base = str(tmp_path / "plan_v")
    P.save_plan(plan, base)
    import json

    with open(base + ".json") as f:
        meta = json.load(f)
    meta["version"] = -1
    with open(base + ".json", "w") as f:
        json.dump(meta, f)
    with pytest.raises(P.PlanError, match="version"):
        P.load_plan(base)


# ---------------------------------------------------------------------------
# Measured autotune
# ---------------------------------------------------------------------------

def test_autotune_compiles_measured_plan_and_caches(tmp_path):
    spec, params, x = _small_setup()
    plan = P.compile_model(params, spec, W1A4, batch_hints=(2,), img_hw=16,
                           autotune=True, model="svhn_at")
    assert all(lp.engine_source == "autotuned"
               for lp in plan.layers if not lp.fp)
    for lp in plan.layers:
        if not lp.fp:
            assert lp.engine in ("implicit", "f32dot", "int8")
    assert plan.autotune  # measurements recorded into the plan
    # every measured verdict has >= 1 timing, best == recorded engine
    for key, (eng, times) in plan.autotune.items():
        if times:
            assert eng == min(times, key=times.get)
    # autotuned plan output is bit-identical to the heuristic plan's
    ref_plan = P.compile_model(params, spec, W1A4, batch_hints=(2,),
                               img_hw=16)
    np.testing.assert_array_equal(np.asarray(P.plan_forward(plan, x)),
                                  np.asarray(P.plan_forward(ref_plan, x)))
    # reload restores the measurement cache: recompiling with autotune in a
    # "fresh process" (cleared caches) performs ZERO new measurements
    P.save_plan(plan, str(tmp_path / "plan_at"))
    ops.clear_plan_state()
    P.load_plan(str(tmp_path / "plan_at"))
    n_cached = len(ops._AUTOTUNE_CACHE)
    assert n_cached == len(plan.autotune) > 0
    plan3 = P.compile_model(params, spec, W1A4, batch_hints=(2,), img_hw=16,
                            autotune=True)
    assert len(ops._AUTOTUNE_CACHE) == n_cached  # no re-measurement
    assert {lp.name: lp.engine for lp in plan3.layers} == \
           {lp.name: lp.engine for lp in plan.layers}


# ---------------------------------------------------------------------------
# LM plans: dense verdict table, activation scoping, round trip
# ---------------------------------------------------------------------------

def _lm_setup():
    from repro.configs import SINGLE, all_configs
    from repro.models import transformer as T

    cfg = dataclasses.replace(
        all_configs()["smollm-360m"].smoke(
            n_layers=2, d_model=64, n_heads=2, n_kv_heads=1, d_ff=128,
            vocab=64, head_dim=32),
        quant=dataclasses.replace(W1A8, engine="auto"))
    params, _ = T.init_lm(jax.random.PRNGKey(0), cfg, SINGLE)
    return cfg, params, T, SINGLE


def test_lm_plan_bit_identical_and_scoped(tmp_path):
    cfg, params, T, SINGLE = _lm_setup()
    from repro.models.layers import prequantize_params

    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, cfg.vocab)
    ref, _ = T.prefill(prequantize_params(params, cfg), cfg, SINGLE,
                       tokens=toks, qmode="serve")
    plan = P.compile_lm(params, cfg, batch_hints=(2,), prompt_len=8)
    assert plan.dense_table and all(v in P.SIGNED_ENGINES
                                    for v in plan.dense_table.values())
    with plan.activate():
        assert ops._PLAN_TABLE  # verdicts live while active
        out, _ = T.prefill(plan.params, cfg, SINGLE, tokens=toks,
                           qmode="serve")
    assert not ops._PLAN_TABLE  # and are removed after
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))
    # round trip through disk
    P.load_plan(P.save_plan(plan, str(tmp_path / "lmplan")))
    plan2 = P.load_plan(str(tmp_path / "lmplan"))
    assert plan2.dense_table == plan.dense_table
    with plan2.activate():
        out2, _ = T.prefill(plan2.params, cfg, SINGLE, tokens=toks,
                            qmode="serve")
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(out2))


def test_lm_runner_with_model_plan_matches_legacy():
    """The continuous engine over a compiled plan serves the same tokens
    as over prequantized params with per-trace engine dispatch."""
    cfg, params, T, SINGLE = _lm_setup()
    from repro.launch.engine import ContinuousLMEngine
    from repro.models.layers import prequantize_params

    prompts = [np.random.RandomState(i).randint(0, cfg.vocab, size=(8,))
               .astype(np.int32) for i in range(3)]
    plan = P.compile_lm(params, cfg, batch_hints=(4,), prompt_len=8)
    res = ContinuousLMEngine(None, cfg, num_slots=4, new_tokens=5,
                             model_plan=plan).serve(prompts)
    legacy = ContinuousLMEngine(prequantize_params(params, cfg), cfg,
                                num_slots=4, new_tokens=5).serve(prompts)
    for a, b in zip(res, legacy):
        np.testing.assert_array_equal(a.value, b.value)


# ---------------------------------------------------------------------------
# Review-fix regressions: reload guards, table restore, heuristic purity,
# interruptible resume
# ---------------------------------------------------------------------------

def test_check_plan_matches_rejects_mismatched_config(tmp_path):
    """A plan reloaded under a different quant config must refuse to serve
    (wrong bit widths would decode the stored levels into garbage)."""
    spec, params, _ = _small_setup()
    plan = P.compile_model(params, spec, W1A4, img_hw=16, model="m")
    P.save_plan(plan, str(tmp_path / "p"))
    loaded = P.load_plan(str(tmp_path / "p"))
    assert P.check_plan_matches(loaded, quant=W1A4, model="m") is loaded
    with pytest.raises(P.PlanError, match="w1a8"):
        P.check_plan_matches(loaded, quant=W1A8)
    with pytest.raises(P.PlanError, match="model"):
        P.check_plan_matches(loaded, model="other")
    # plan_exists normalizes a trailing .json (the CLI accepts both forms)
    assert P.plan_exists(str(tmp_path / "p"))
    assert P.plan_exists(str(tmp_path / "p.json"))
    assert not P.plan_exists(str(tmp_path / "missing"))


def test_activate_restores_installed_table():
    """activate() on top of a process-wide install() must restore the
    installed verdicts on exit, not uninstall them."""
    cfg, params, T, SINGLE = _lm_setup()
    plan = P.compile_lm(params, cfg, batch_hints=(2,), prompt_len=8)
    plan.install()
    try:
        before = dict(ops._PLAN_TABLE)
        with plan.activate():
            pass
        assert ops._PLAN_TABLE == before  # install() survives activate()
        # a disjoint plan's activation is also fully reversible
        other = {("dense", 7, 7, 8, 1, "cpu"): "int8"}
        ops.install_plan_table(other)
        with plan.activate():
            assert ops._PLAN_TABLE[("dense", 7, 7, 8, 1, "cpu")] == "int8"
        assert ops._PLAN_TABLE[("dense", 7, 7, 8, 1, "cpu")] == "int8"
    finally:
        ops.clear_plan_state()


def test_heuristic_compile_is_pure_under_foreign_state():
    """compile_model without autotune must ignore installed plan tables and
    cached autotune verdicts — 'heuristic' plans are deterministic."""
    spec, params, _ = _small_setup()
    ref = P.compile_model(params, spec, W1A4, img_hw=16)
    # poison every dispatch-state source select_engine consults
    for lp in ref.layers:
        if lp.fp:
            continue
        ops.install_plan_table(
            {ops.dense_plan_key(lp.k, lp.cout, lp.a_bits, lp.w_bits,
                                "cpu"): "int8"})
        for b, _ in lp.engines:
            key = ops.autotune_key(
                b * lp.out_h * lp.out_w, lp.k, lp.cout, lp.a_bits,
                lp.w_bits, "cpu",
                ops.ConvShape(lp.in_h, lp.in_w, lp.kh, lp.kw, lp.stride,
                              lp.padding, batch=b))
            ops._AUTOTUNE_CACHE[key] = ("int8", {})
    poisoned = P.compile_model(params, spec, W1A4, img_hw=16)
    assert {lp.name: dict(lp.engines) for lp in poisoned.layers} == \
           {lp.name: dict(lp.engines) for lp in ref.layers}
    assert poisoned.fingerprint() == ref.fingerprint()


def test_forward_progress_resume_window_is_interruptible():
    """The replan/restart window runs on the same failure-prone supply: a
    resume longer than the MTBF must compound (more failures, less
    progress) and still terminate via the budget hard-stop."""
    from repro.pim.intermittent import forward_progress

    kw = dict(n_frames=50, frame_time_us=100.0, mtbf_us=300.0,
              checkpoint_period_frames=5, seed=3)
    free = forward_progress(resume_us=0.0, **kw)
    costly = forward_progress(resume_us=600.0, **kw)  # 2x MTBF per replan
    assert costly["failures"] > free["failures"]  # resume itself fails
    assert costly["efficiency"] < free["efficiency"]
    assert costly["total_time_us"] <= kw["n_frames"] * 100.0 * 50 + 600.0


# ---------------------------------------------------------------------------
# Serving engine: program caches keyed on the plan
# ---------------------------------------------------------------------------

def test_serve_engine_program_cache_keyed_on_plan():
    spec, params, _ = _small_setup()
    from repro.launch.engine import CNNRunner, ServeEngine

    imgs = [np.random.RandomState(i).uniform(size=(16, 16, 3))
            .astype(np.float32) for i in range(3)]
    plan_a = P.compile_model(params, spec, W1A4, img_hw=16, model="a")
    plan_f = P.compile_model(params, spec,
                             dataclasses.replace(W1A4, engine="f32dot"),
                             img_hw=16, model="f")
    assert plan_a.fingerprint() != plan_f.fingerprint()
    res_a = ServeEngine(CNNRunner(None, spec, None, plan=plan_a),
                        max_batch=4).serve(imgs)
    res_f = ServeEngine(CNNRunner(None, spec, None, plan=plan_f),
                        max_batch=4).serve(imgs)
    from repro.core.prequant import prequantize_cnn_params
    sp = prequantize_cnn_params(params, spec, W1A4)
    legacy = ServeEngine(CNNRunner(sp, spec, W1A4), max_batch=4).serve(imgs)
    for a, f, l in zip(res_a, res_f, legacy):
        np.testing.assert_array_equal(a.value, l.value)
        np.testing.assert_array_equal(f.value, l.value)  # engines all exact
    # cache keys carry the fingerprint
    eng = ServeEngine(CNNRunner(None, spec, None, plan=plan_a), max_batch=4)
    eng.serve(imgs[:1])
    assert all(k[2] == plan_a.fingerprint() for k in eng._fns)
