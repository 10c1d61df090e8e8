"""The public API surface (repro.api, DESIGN.md §9).

Pins the PR-5 contracts:

* **Target registry** — unknown targets error naming the available ones;
  legacy aliases resolve; the cpu/tpu targets' cost tables reproduce the
  PR-4 golden dispatch tables; `sot_mram` reproduces the Table II
  arithmetic bit-for-bit against the spec-walk reference.
* **Session round trip** — ``build(spec, quant).compile(target="cpu")``
  serves bit-identically to the PR-4 plan path, and ``.simulate`` on the
  SAME compiled plan reproduces the paper's headline vs-ReRAM ratios.
* **Mapper fixes** — pooled/stride spatial bookkeeping against the
  paper's Fig. 3 dims; ``accel_cost`` rejects empty works.
* **Deprecation policy** — importing ``repro.pim.accelsim`` emits exactly
  one DeprecationWarning; ``models/cnn.prepare_serve_params`` is gone.
"""
import dataclasses
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro import api
from repro.core import plan as P
from repro.core.quant import QuantConfig, W1A4
from repro.kernels import ops
from repro.models.cnn import ConvSpec, init_cnn, svhn_cnn_spec


@pytest.fixture(autouse=True)
def _clean_dispatch_state():
    ops.clear_plan_state()
    yield
    ops.clear_plan_state()


# ---------------------------------------------------------------------------
# Target registry
# ---------------------------------------------------------------------------

def test_unknown_target_names_available():
    with pytest.raises(ValueError) as e:
        api.get_target("tpu_v9000")
    msg = str(e.value)
    for name in ("cpu", "tpu", "sot_mram", "imce", "reram", "cmos_asic"):
        assert name in msg
    assert "tpu_v9000" in msg


def test_registry_contents_and_aliases():
    assert set(api.available_targets()) >= {
        "cpu", "tpu", "sot_mram", "imce", "reram", "cmos_asic"}
    # legacy accelsim/jax spellings resolve to the canonical targets
    assert api.get_target("proposed") is api.get_target("sot_mram")
    assert api.get_target("asic") is api.get_target("cmos_asic")
    assert api.target_for_backend("gpu") is api.get_target("cpu")
    # a backend with no dispatch table is refused, never served on the
    # CPU tables; a PIM design is no serve backend either
    for backend in ("weird_pjrt", "sot_mram"):
        with pytest.raises(ValueError, match="no compute target"):
            api.target_for_backend(backend)
    kinds = {n: api.get_target(n).kind for n in api.available_targets()}
    assert kinds["cpu"] == kinds["tpu"] == "compute"
    assert kinds["sot_mram"] == kinds["reram"] == "pim"


def test_register_target_is_open():
    t = api.PIMTarget(name="_test_feFET", device=api.get_target("imce").device,
                      energy_scale=1.0, area_mm2=1.0)
    api.register_target(t)
    try:
        assert api.get_target("_test_feFET") is t
    finally:
        from repro.api import targets as targets_mod
        targets_mod._REGISTRY.pop("_test_feFET")


def test_cpu_tpu_targets_reproduce_golden_dispatch():
    """The targets' cost tables ARE the PR-4 crossover constants: the
    compile pass (which now dispatches through the targets) must still
    produce the golden CPU engine tables, and target.select_engine must
    agree with select_engine for every (layer, batch) cell."""
    from test_plan import GOLDEN_CPU
    from repro.configs.paper_cnn import ALEXNET_SPEC, SVHN_SPEC
    from repro.core.quant import W1A8

    cpu = api.get_target("cpu")
    tpu = api.get_target("tpu")
    for name, spec, img, quant in (("svhn", SVHN_SPEC, 40, W1A4),
                                   ("alexnet", ALEXNET_SPEC, 112, W1A8)):
        plan = P.compile_model(None, spec, quant, backend="cpu",
                               batch_hints=(1, 8), img_hw=img, model=name)
        assert {lp.name: dict(lp.engines) for lp in plan.layers} \
            == GOLDEN_CPU[name]
        for lp in plan.layers:
            if lp.fp:
                continue
            for b, eng in lp.engines:
                conv = ops.ConvShape(lp.in_h, lp.in_w, lp.kh, lp.kw,
                                     lp.stride, lp.padding, batch=b)
                m = b * lp.out_h * lp.out_w
                assert cpu.select_engine(m, lp.k, lp.cout, lp.a_bits,
                                         lp.w_bits, conv) == eng
                # the tpu table is exercised through the same interface
                assert tpu.select_engine(m, lp.k, lp.cout, lp.a_bits,
                                         lp.w_bits, conv) in (
                    "implicit", "fused", "faithful")


def test_sot_mram_svhn_bit_identical_to_spec_walk():
    """Table II arithmetic through the registry == the legacy spec-walk
    pipeline, bit-for-bit (same works, same accel_cost float order, same
    fitted energy scale) — for every design and dataset."""
    from repro.api import reports
    from repro.pim.energy import DESIGNS
    from repro.pim.mapper import accel_cost, model_work

    legacy_scale = dict(proposed=0.6602, imce=0.5586, reram=0.3662,
                        asic=0.661)
    for design in ("proposed", "imce", "reram", "asic"):
        for ds_name, ds in reports.DATASETS.items():
            works = model_work(ds["spec"](), ds["img"], 1, 1)
            ref = accel_cost(DESIGNS[design], works)
            got = reports.simulate(design, ds_name)
            assert got["energy_uj"] == ref["energy_uj"] * legacy_scale[design]
            assert got["latency_us"] == ref["latency_us"]
            assert got["macs"] == ref["macs"]
            assert got["row_ops"] == ref["row_ops"]


# ---------------------------------------------------------------------------
# Session round trip (the acceptance criterion)
# ---------------------------------------------------------------------------

def _setup(channels=8, img=16, quant=W1A4):
    spec = svhn_cnn_spec(channels)
    params, _ = init_cnn(jax.random.PRNGKey(0), spec)
    return spec, params


def test_api_roundtrip_serve_bit_identical_and_simulates_claims():
    """build -> compile(cpu) -> serve is bit-identical to the PR-4 plan
    path, and .simulate on the SAME compiled plan reproduces the paper's
    ~5.4x/9x vs-ReRAM headline (abstract / §III-C,D)."""
    from repro.launch.engine import CNNRunner, ServeEngine

    spec, params = _setup()
    imgs = [np.random.RandomState(i).uniform(size=(16, 16, 3))
            .astype(np.float32) for i in range(5)]
    model = api.build(spec, W1A4, params=params, img_hw=16, name="svhn_api")
    compiled = model.compile(target="cpu", batch_hints=(1, 4))

    dep = compiled.serve(max_batch=4)
    got = dep.predict(imgs)
    # PR-4 path: compile_model + ServeEngine(CNNRunner(plan=...))
    pr4_plan = P.compile_model(params, spec, W1A4, backend="cpu",
                               batch_hints=(1, 4), img_hw=16,
                               model="svhn_api")
    ref = ServeEngine(CNNRunner(None, spec, None, plan=pr4_plan),
                      max_batch=4).serve(imgs)
    for g, r in zip(got, ref):
        np.testing.assert_array_equal(g, r.value)
    # and against the raw (jitted, like every engine dispatch) plan
    # executor, no engine machinery at all
    raw = np.asarray(jax.jit(lambda v: P.plan_forward(compiled.plan, v))(
        np.stack(imgs)[:4]))
    for i in range(4):
        np.testing.assert_array_equal(got[i], raw[i])

    # the SAME compiled plan prices the paper's accelerators
    proposed = compiled.simulate(target="sot_mram")
    reram = compiled.simulate(target="reram")
    ratios = proposed.vs(reram)
    assert ratios["energy"] == pytest.approx(5.4, rel=0.15)
    assert ratios["speed"] == pytest.approx(9.0, rel=0.15)
    imce = compiled.simulate(target="imce")
    assert proposed.vs(imce)["speed"] == pytest.approx(3.0, rel=0.15)
    # per-layer breakdown covers every layer and sums to the total order
    assert len(proposed.layers) == len(spec)
    assert proposed.area_mm2 == 2.60 and proposed.fps_per_mm2 > 0


def test_compile_rejects_pim_target_with_guidance():
    spec, params = _setup()
    with pytest.raises(P.PlanError, match="simulate"):
        api.build(spec, W1A4, params=params, img_hw=16).compile(
            target="sot_mram")


def test_session_cache_roundtrip(tmp_path):
    """compile(cache=...) saves; a second compile reloads (no requant) and
    serves bit-identically; api.load guards against config mismatch."""
    spec, params = _setup()
    x = jax.random.uniform(jax.random.PRNGKey(1), (2, 16, 16, 3))
    model = api.build(spec, W1A4, params=params, img_hw=16, name="rt")
    base = str(tmp_path / "plan_api")
    c1 = model.compile(target="cpu", cache=base)
    assert not c1.reloaded and c1.cache_path.endswith(".json")
    ref = np.asarray(c1.forward(x))

    c2 = model.compile(target="cpu", cache=base)
    assert c2.reloaded
    assert c2.fingerprint() == c1.fingerprint()
    np.testing.assert_array_equal(np.asarray(c2.forward(x)), ref)

    loaded = api.load(base, quant=W1A4, model="rt")
    np.testing.assert_array_equal(np.asarray(loaded.forward(x)), ref)
    from repro.core.quant import W1A8
    with pytest.raises(P.PlanError, match="w1a8"):
        api.load(base, quant=W1A8)
    # an explicitly requested target must hold for the cached plan too: a
    # cpu plan is not a valid answer to compile(target="tpu")
    with pytest.raises(P.PlanError, match="backend"):
        model.compile(target="tpu", cache=base)


def test_plans_carry_per_layer_cost_estimates():
    """Compiled plans are annotated with the compile target's per-layer
    (energy_pj, cycles, bytes_moved) roofline estimate, and the estimates
    survive serialization."""
    spec, params = _setup()
    plan = P.compile_model(None, spec, W1A4, backend="cpu", img_hw=16)
    for lp in plan.layers:
        assert len(lp.cost) == 3 and all(c > 0 for c in lp.cost)
    # deeper layers move more bytes than the 10-class head
    assert plan.layers[1].cost[2] > plan.layers[-1].cost[2]
    import json
    meta = plan.meta()
    assert json.dumps(meta)  # serializable
    rt = P._layer_from_json(json.loads(json.dumps(
        P._layer_to_json(plan.layers[1]))))
    assert rt.cost == plan.layers[1].cost


def _tiny_lm():
    from repro.configs import SINGLE, all_configs
    from repro.core.quant import W1A8
    from repro.models import transformer as T

    cfg = dataclasses.replace(
        all_configs()["smollm-360m"].smoke(
            n_layers=2, d_model=64, n_heads=2, n_kv_heads=1, d_ff=128,
            vocab=64, head_dim=32),
        quant=dataclasses.replace(W1A8, engine="auto"))
    params, _ = T.init_lm(jax.random.PRNGKey(0), cfg, SINGLE)
    return cfg, params


def test_lm_session_serve_matches_direct_plan():
    """The facade's LM deployment serves what a continuous engine built by
    hand over a directly compiled plan serves."""
    from repro.launch.engine import ContinuousLMEngine

    cfg, params = _tiny_lm()
    prompts = [np.random.RandomState(i).randint(0, cfg.vocab, size=(8,))
               .astype(np.int32) for i in range(3)]
    compiled = api.build(cfg, params=params).compile(batch_hints=(4,),
                                                     prompt_len=8)
    got = compiled.serve(max_batch=4, new_tokens=5).predict(prompts)
    direct_plan = P.compile_lm(params, cfg, batch_hints=(4,), prompt_len=8)
    ref = ContinuousLMEngine(None, cfg, num_slots=4, new_tokens=5,
                             model_plan=direct_plan).serve(prompts)
    for g, r in zip(got, ref):
        np.testing.assert_array_equal(g, r.value)
    with pytest.raises(P.PlanError, match="CNN"):
        compiled.simulate(target="sot_mram")


def test_lm_serve_mixed_lengths_in_one_continuous_batch():
    """Prompts of 3 to 20 tokens (one or two prefill chunks) with horizons
    1 to 9 share one continuous batch, and each request's tokens equal
    serving it alone."""
    cfg, params = _tiny_lm()
    lens, horizons = (5, 20, 8, 3, 12), (3, 9, 1, 6, 4)
    payloads = [(np.random.RandomState(i).randint(0, cfg.vocab, size=(n,))
                 .astype(np.int32), h)
                for i, (n, h) in enumerate(zip(lens, horizons))]
    compiled = api.build(cfg, params=params).compile(batch_hints=(1, 3),
                                                     prompt_len=8)
    dep = compiled.serve(max_batch=3)
    got = dep.predict(payloads)
    # the requests overlapped: fewer decode steps than one after another
    assert dep.stats["steps"] < sum(h - 1 for h in horizons)
    assert dep.stats["prefill_chunks"] == 6
    alone = compiled.serve(max_batch=3)
    for g, p, h in zip(got, payloads, horizons):
        assert g.shape == (h,)
        [ref] = alone.predict([p])
        np.testing.assert_array_equal(g, ref)


def test_lm_serve_refuses_mesh_and_fallback():
    """The continuous engine serves on one device and has no degrade
    path: ``mesh`` and ``fallback`` on an LM plan raise PlanError, plain
    or resilient."""
    from repro.resilience import ResilienceConfig

    cfg, params = _tiny_lm()
    compiled = api.build(cfg, params=params).compile(prompt_len=8)
    for kw in (dict(mesh=object()), dict(fallback=compiled),
               dict(fallback=compiled, resilience=ResilienceConfig())):
        with pytest.raises(P.PlanError, match="one device"):
            compiled.serve(**kw)


@pytest.mark.parametrize("arch", ["deepseek-moe-16b", "rwkv6-1.6b",
                                  "recurrentgemma-9b"])
def test_lm_serve_refuses_non_attention_blocks(arch):
    """The continuous engine's paged KV cache holds attention layers only:
    serving an MoE, RWKV or recurrent/local-window model raises PlanError
    naming its block kinds, before any engine is built."""
    from repro.configs import all_configs

    cfg, params = _tiny_lm()
    compiled = api.build(cfg, params=params).compile(prompt_len=8)
    other = all_configs()[arch]
    kinds = sorted(set(other.blocks_pattern) - {"attn"})
    foreign = dataclasses.replace(compiled, model=api.build(other))
    with pytest.raises(P.PlanError, match="attention layers only") as e:
        foreign.serve()
    assert str(kinds) in str(e.value)


def test_predict_raises_for_a_request_without_result():
    """``predict`` returns one value per payload: a request that was
    dead-lettered (no retries left after a kill) raises instead of
    shortening the list."""
    from repro.resilience import FaultPlan, ResilienceConfig

    spec = svhn_cnn_spec(8)
    params, _ = init_cnn(jax.random.PRNGKey(0), spec)
    compiled = api.build(spec, W1A4, params=params, img_hw=16).compile(
        batch_hints=(1, 2))
    imgs = [np.random.RandomState(i).uniform(size=(16, 16, 3))
            .astype(np.float32) for i in range(2)]
    dep = compiled.serve(max_batch=2, resilience=ResilienceConfig(
        fault_plan=FaultPlan.scripted([("dispatch", 0, "power_loss")]),
        max_retries=0))
    with pytest.raises(RuntimeError, match=r"\[0, 1\] have no result"):
        dep.predict(imgs)
    got = dep.predict(imgs)                 # the next round is served
    ref = compiled.serve(max_batch=2).predict(imgs)
    for g, r in zip(got, ref, strict=True):
        np.testing.assert_array_equal(g, r)


# ---------------------------------------------------------------------------
# Mapper fixes (satellite): Fig. 3 spatial bookkeeping + empty-works guard
# ---------------------------------------------------------------------------

def _walk_dims(spec, img):
    from repro.pim.mapper import layer_work

    hw, dims = img, []
    for s in spec:
        _, out = layer_work(s, hw, 1, 1)
        dims.append((hw, out))
        hw = out
    return dims


def test_layer_work_fig3_svhn_dims():
    """The paper's Fig. 3 SVHN walk: 40 -> 40 -> 40 ->(pool) 20 -> 20
    ->(pool) 10 -> 10 -> 10 -> 10 (FC-equivalent 1x1 tail)."""
    dims = _walk_dims(svhn_cnn_spec(8), 40)
    assert dims == [(40, 40), (40, 40), (40, 20), (20, 20), (20, 10),
                    (10, 10), (10, 10), (10, 10)]


def test_layer_work_stride_then_pool_order():
    """Pool halving applies AFTER the ceil-div stride output (stride-2
    conv on 9 -> ceil(9/2)=5 -> pool -> 2), floored at 1 for degenerate
    pooled maps, and a bad input extent is a loud error."""
    from repro.pim.mapper import layer_work

    w, out = layer_work(ConvSpec(4, 8, 3, stride=2, pool=True), 9, 1, 1)
    assert out == 2 and w.macs == 5 * 5 * 3 * 3 * 4 * 8
    # pooled 1x1 map floors at 1 instead of collapsing to 0 (LeNet's
    # pooled-FC stage) — downstream layers keep nonzero work
    _, out = layer_work(ConvSpec(4, 8, 5, pool=True, fc=True), 14, 1, 1)
    assert out == 1
    with pytest.raises(ValueError, match=">= 1"):
        layer_work(ConvSpec(4, 8, 3), 0, 1, 1)


def test_accel_cost_rejects_empty_works():
    from repro.pim.energy import DESIGNS
    from repro.pim.mapper import accel_cost

    with pytest.raises(ValueError, match="empty works"):
        accel_cost(DESIGNS["proposed"], [])


def test_works_from_layers_matches_model_work():
    """Plan-geometry works == spec-walk works for the paper models at
    every evaluated W:I config (the bit-for-bit bridge reports.simulate
    stands on)."""
    from repro.api.reports import DATASETS
    from repro.pim.mapper import model_work, works_from_layers

    for ds in DATASETS.values():
        spec = ds["spec"]()
        for (m_b, n_b) in ((1, 1), (8, 1), (2, 2)):
            plan = P.compile_model(
                None, spec, QuantConfig(w_bits=n_b, a_bits=m_b, g_bits=8),
                backend="cpu", img_hw=ds["img"])
            assert works_from_layers(plan.layers) == \
                model_work(spec, ds["img"], m_b, n_b)


# ---------------------------------------------------------------------------
# Deprecation policy
# ---------------------------------------------------------------------------

def test_accelsim_shim_warns_exactly_once():
    """Importing the legacy entry point emits one DeprecationWarning (and
    only one — re-import is free), and its numbers still match the api."""
    code = (
        "import warnings, sys\n"
        "with warnings.catch_warnings(record=True) as w:\n"
        "    warnings.simplefilter('always')\n"
        "    import repro.pim.accelsim as A1\n"
        "    import repro.pim.accelsim as A2\n"
        "dep = [x for x in w if issubclass(x.category, DeprecationWarning)\n"
        "       and 'accelsim' in str(x.message)]\n"
        "assert len(dep) == 1, [str(x.message) for x in dep]\n"
        "assert 'repro.api' in str(dep[0].message)\n"
        "import repro.api.reports as R\n"
        "assert A1.simulate('proposed', 'mnist') == "
        "R.simulate('sot_mram', 'mnist')\n"
        "print('OK')\n"
    )
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=_src_env())
    assert out.returncode == 0, out.stderr
    assert "OK" in out.stdout


def _src_env():
    import os

    env = dict(os.environ)
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    return env
