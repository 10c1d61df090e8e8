"""Session facade: build -> compile -> serve / simulate / save.

One object model over the previously-scattered entry points
(``models/cnn`` free functions, ``core/plan.compile_model``,
``launch/serve`` CLI plumbing, ``pim/accelsim`` free functions):

    model    = build(spec, quant, params=params)      # CNN (ConvSpec list)
    model    = build(cfg, params=params)              # LM  (ArchConfig)
    compiled = model.compile(target="cpu", batch_hints=(1, 8),
                             autotune=True, cache="results/plan")
    engine   = compiled.serve(max_batch=8)            # Deployment handle
    report   = compiled.simulate(target="sot_mram")   # CostReport
    compiled.save("results/plan"); load("results/plan")

``compile`` wraps :func:`repro.core.plan.compile_model` /
:func:`~repro.core.plan.compile_lm` — the ModelPlan IR stays the single
compiled artifact; the facade only decides *which* compile pass runs and
wires the result into the serving engine and the cost models.  A compute
:class:`~repro.api.targets.HardwareTarget` parameterizes compilation (its
dispatch table picks the engines); any target parameterizes simulation.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Optional

import numpy as np

from repro.core.quant import QuantConfig
from .targets import Cost, LayerGeometry, PIMTarget, get_target


def _is_lm(spec) -> bool:
    """An LM ArchConfig (has a transformer geometry + its own quant);
    anything sequence-like is a CNN ConvSpec list."""
    return hasattr(spec, "n_layers") and hasattr(spec, "quant")


# ---------------------------------------------------------------------------
# Cost report
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class CostReport:
    """Per-model cost on one target, with the per-layer breakdown.

    PIM targets fill the area-normalized columns the paper reports
    (``fps_per_mm2``, ``eff_per_mm2``); compute targets report the roofline
    totals only.  ``vs(other)`` gives the paper's headline ratio form:
    energy-efficiency and speed of *this* report over ``other``.
    """

    target: str
    energy_uj: float
    latency_us: float
    fps: float
    macs: int
    row_ops: int
    bytes_moved: float
    layers: tuple                  # ((layer_name, Cost), ...)
    area_mm2: Optional[float] = None
    fps_per_mm2: Optional[float] = None
    gops_per_w: Optional[float] = None
    eff_per_mm2: Optional[float] = None

    def vs(self, other: "CostReport") -> dict:
        """Headline ratios: how much more efficient/faster this target is
        than ``other`` (paper abstract form: proposed-vs-rival)."""
        return dict(
            energy=other.energy_uj / self.energy_uj,
            speed=self.fps / other.fps,
        )

    def rows(self) -> list[dict]:
        """CSV-able per-layer rows (benchmarks convention)."""
        return [dict(layer=name, energy_pj=round(c.energy_pj, 1),
                     cycles=round(c.cycles, 1),
                     bytes_moved=round(c.bytes_moved))
                for name, c in self.layers]


# ---------------------------------------------------------------------------
# Deployment: the serve handle
# ---------------------------------------------------------------------------

class Deployment:
    """A live serving handle over the plan's engine: a CNN plan's
    :class:`repro.launch.engine.ServeEngine` (or its resilient subclass)
    or an LM plan's :class:`repro.launch.engine.ContinuousLMEngine`.

    Thin by design — the engine's queue/dispatch semantics are the
    contract (DESIGN.md §7, §13); this wrapper only ties its lifetime to
    the compiled plan and offers the closed-loop ``predict`` convenience.
    An LM's values are its generated tokens.
    """

    def __init__(self, engine, compiled: "CompiledModel"):
        self.engine = engine
        self.compiled = compiled

    def predict(self, payloads) -> list[np.ndarray]:
        """Closed-loop serve: submit all payloads, drain, and return one
        value per payload, in order.  Raises ``RuntimeError`` if a
        request has no result (it was dead-lettered).  Results of requests
        submitted earlier through :meth:`submit` are drained with them and
        not returned."""
        payloads = list(payloads)
        # serve() submits in order, and both engines number requests
        # consecutively from _next_rid
        first = self.engine._next_rid
        got = {r.rid: r.value for r in self.engine.serve(payloads)}
        rids = range(first, first + len(payloads))
        missing = [rid for rid in rids if rid not in got]
        if missing:
            raise RuntimeError(f"requests {missing} have no result; see "
                               "the engine's dead_letters")
        return [got[rid] for rid in rids]

    # queue-level passthroughs for open-loop drivers
    def submit(self, payload, t_submit=None) -> int:
        return self.engine.submit(payload, t_submit=t_submit)

    def pump(self) -> None:
        self.engine.pump()

    def drain(self):
        return self.engine.drain()

    def record_spans(self):
        """Turn on the engine's in-memory span recorder and return it
        (:class:`repro.launch.engine.SpanRecorder`; the resilient CNN
        engine records none)."""
        return self.engine.record_spans()

    @property
    def stats(self) -> dict:
        return self.engine.stats


# ---------------------------------------------------------------------------
# Model (the session) and CompiledModel
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Model:
    """An uncompiled model: spec/config + quantization + (optional) params.

    The session object — holds everything ``compile`` needs.  ``params``
    may be a float checkpoint (prequantized during compile) or None for a
    structure-only session (engine-table inspection, cost simulation).
    """

    kind: str                       # "cnn" | "lm"
    spec: Any                       # ConvSpec list (cnn) | ArchConfig (lm)
    quant: QuantConfig
    params: Any = None
    img_hw: Any = 40                # cnn input size (int or (h, w))
    name: str = "cnn"

    def compile(self, *, target: str | None = None, batch_hints=(1,),
                autotune: bool = False, prompt_len: int = 16,
                cache: str | None = None,
                verify: bool = True) -> "CompiledModel":
        """Compile this model against a compute target.

        ``target`` names a registered compute target (``cpu``/``tpu``);
        None uses the live jax backend.  ``cache`` points at a plan file:
        if present it is reloaded (guarded by
        :func:`repro.core.plan.check_plan_matches` — requantization and
        autotune are skipped), otherwise the freshly compiled plan is
        saved there.  ``verify`` gates the static plan prover
        (:func:`repro.analysis.verify_plan`) on both the fresh-compile and
        the cache-reload path.
        """
        from repro.core import plan as P

        backend = None
        if target is not None:
            t = get_target(target)
            if t.kind != "compute":
                raise P.PlanError(
                    f"target {target!r} is a simulated PIM design — compile "
                    "against a compute target (cpu/tpu) and pass the PIM "
                    "target to .simulate() instead")
            backend = t.name
        t0 = time.perf_counter()
        if cache and P.plan_exists(cache):
            # the requested target (or, with none requested, the live
            # backend) must also hold for a cached plan — a TPU plan pins
            # Pallas-only engines that would only interpret on CPU;
            # check_plan_matches raises the readable recompile error
            import jax

            plan = P.check_plan_matches(
                P.load_plan(cache), quant=self.quant, model=self.name,
                backend=backend or jax.default_backend())
            if verify:
                from repro.analysis.prover import assert_plan_verified

                assert_plan_verified(plan)
            return CompiledModel(plan, model=self, cache_path=cache,
                                 reloaded=True,
                                 compile_s=time.perf_counter() - t0)
        if self.kind == "lm":
            plan = P.compile_lm(self.params, self.spec, backend=backend,
                                batch_hints=batch_hints,
                                prompt_len=prompt_len, autotune=autotune,
                                verify=verify)
        else:
            plan = P.compile_model(self.params, self.spec, self.quant,
                                   backend=backend, batch_hints=batch_hints,
                                   img_hw=self.img_hw, autotune=autotune,
                                   model=self.name, verify=verify)
        path = P.save_plan(plan, cache) if cache else None
        return CompiledModel(plan, model=self, cache_path=path,
                             reloaded=False,
                             compile_s=time.perf_counter() - t0)


@dataclasses.dataclass
class CompiledModel:
    """A compiled ModelPlan with the full lifecycle attached."""

    plan: Any                       # repro.core.plan.ModelPlan
    model: Optional[Model] = None
    cache_path: Optional[str] = None
    reloaded: bool = False
    compile_s: float = 0.0

    @property
    def params(self):
        return self.plan.params

    @property
    def quant(self) -> QuantConfig:
        return self.plan.quant

    def fingerprint(self) -> str:
        return self.plan.fingerprint()

    # -- execution ----------------------------------------------------------

    def forward(self, x):
        """One batched CNN forward through the plan (jit-compatible)."""
        from repro.core import plan as P

        if self.plan.kind != "cnn":
            raise P.PlanError("forward() executes CNN plans; use serve() "
                              "for LM generation")
        return P.plan_forward(self.plan, x)

    def serve(self, *, max_batch: int = 8, flush_deadline_s: float = 0.005,
              mesh=None, max_pending: int = 4096,
              new_tokens: int = 16, qmode: str = "serve",
              resilience=None, fallback: "CompiledModel | None" = None,
              ) -> Deployment:
        """Stand up the request-level serving engine on this plan.

        A CNN plan gets the bucket engine (``ServeEngine``); an LM plan
        gets the continuous-batching engine (``ContinuousLMEngine``) with
        ``max_batch`` decode slots and the engine's page geometry.  The
        continuous engine serves on one device and has no degrade path,
        so ``mesh`` and ``fallback`` are refused for an LM plan.

        The paged KV cache holds attention layers only, so an LM whose
        blocks are not all ``attn`` (MoE, recurrent, local-window) is
        refused with ``PlanError``.

        ``resilience`` (a :class:`repro.resilience.ResilienceConfig`)
        swaps in the fault-surviving engine: seeded fault injection, and
        for a CNN plan retry/dead-letter recovery and — with ``fallback``
        (a lower-bit CompiledModel of the same architecture) — degraded-
        plan fallback; for an LM plan crash-consistent decode epoch
        checkpoints (DESIGN.md §11).  A config field the plan's kind does
        not read, set away from its default, raises ``PlanError``.
        """
        from repro.core.plan import PlanError

        if resilience is not None:
            resilience.check_kind(self.plan.kind)
        if self.plan.kind == "lm":
            from repro.launch.engine import ContinuousLMEngine

            if self.model is None:
                raise PlanError(
                    "serving an LM plan needs its ArchConfig (cache "
                    "geometry, vocab) — reload through "
                    "api.build(cfg, ...).compile(cache=...) or "
                    "api.load(path, spec=cfg)")
            if mesh is not None or fallback is not None:
                raise PlanError(
                    "the continuous LM engine serves on one device and has "
                    "no degrade path: mesh and fallback apply to CNN plans "
                    "only")
            kinds = sorted(set(self.model.spec.blocks_pattern) - {"attn"})
            if kinds:
                raise PlanError(
                    f"the continuous LM engine's paged KV cache holds "
                    f"attention layers only; this model has {kinds} blocks")
            kw = {}
            if resilience is not None:
                kw = dict(checkpoint_dir=resilience.checkpoint_dir,
                          epoch_steps=resilience.epoch_steps,
                          deadline_s=resilience.deadline_s,
                          faults=resilience.fault_plan)
            engine = ContinuousLMEngine(
                None, self.model.spec, num_slots=max_batch,
                new_tokens=new_tokens, qmode=qmode, model_plan=self.plan,
                max_pending=max_pending, **kw)
            return Deployment(engine, self)
        if resilience is not None:
            from repro.resilience import build_resilient_engine

            engine = build_resilient_engine(
                self, resilience, fallback=fallback, max_batch=max_batch,
                flush_deadline_s=flush_deadline_s, max_pending=max_pending,
                mesh=mesh)
            return Deployment(engine, self)
        from repro.launch.engine import CNNRunner, ServeEngine

        spec = self.model.spec if self.model is not None else None
        engine = ServeEngine(CNNRunner(None, spec, None, plan=self.plan),
                             max_batch=max_batch,
                             flush_deadline_s=flush_deadline_s, mesh=mesh,
                             max_pending=max_pending)
        return Deployment(engine, self)

    # -- simulation ---------------------------------------------------------

    def simulate(self, target: str = "sot_mram") -> CostReport:
        """Price this compiled plan on a hardware target.

        PIM targets reproduce the legacy ``pim/accelsim`` arithmetic
        bit-for-bit (same works, same ``accel_cost``, same fitted energy
        scale); compute targets report the roofline annotation totals.
        """
        from repro.core import plan as P
        from repro.pim.mapper import effective_bits, works_from_layers

        if self.plan.kind != "cnn":
            raise P.PlanError("simulate() prices CNN plans (the paper's "
                              f"scope); this plan is {self.plan.kind!r}")
        t = get_target(target)
        layers = self.plan.layers
        if isinstance(t, PIMTarget):
            works = works_from_layers(layers)
            r = t.report(works)
            per_layer = tuple(
                (lp.name, t.cost(LayerGeometry(lp.out_h * lp.out_w, lp.k,
                                               lp.cout),
                                 *effective_bits(lp)))
                for lp in layers)
            return CostReport(
                target=t.name, energy_uj=r["energy_uj"],
                latency_us=r["latency_us"], fps=r["fps"], macs=r["macs"],
                row_ops=r["row_ops"],
                bytes_moved=sum(c.bytes_moved for _, c in per_layer),
                layers=per_layer, area_mm2=r["area_mm2"],
                fps_per_mm2=r["fps_per_mm2"], gops_per_w=r["gops_per_w"],
                eff_per_mm2=r["eff_per_mm2"])
        per_layer = []
        total = Cost(0.0, 0.0, 0.0)
        macs = 0
        for lp in layers:
            ab, wb = effective_bits(lp)
            geom = LayerGeometry(lp.out_h * lp.out_w, lp.k, lp.cout)
            c = t.cost(geom, ab, wb)
            macs += geom.macs
            per_layer.append((lp.name, c))
            total = total + c
        latency_us = total.cycles / (t.clock_ghz * 1e3)
        return CostReport(
            target=t.name, energy_uj=total.energy_pj * 1e-6,
            latency_us=latency_us,
            fps=1e6 / latency_us if latency_us else float("inf"),
            macs=macs, row_ops=0, bytes_moved=total.bytes_moved,
            layers=tuple(per_layer))

    # -- persistence --------------------------------------------------------

    def save(self, path: str) -> str:
        from repro.core.plan import save_plan

        self.cache_path = save_plan(self.plan, path)
        return self.cache_path


# ---------------------------------------------------------------------------
# Entry points
# ---------------------------------------------------------------------------

def build(spec, quant: QuantConfig | None = None, *, params=None,
          img_hw=40, name: str | None = None) -> Model:
    """Open a session: ``spec`` is a ConvSpec list (CNN) or an ArchConfig
    (LM — its own ``quant`` is used unless overridden)."""
    if _is_lm(spec):
        q = quant if quant is not None else spec.quant
        cfg = spec if quant is None else dataclasses.replace(spec, quant=quant)
        return Model(kind="lm", spec=cfg, quant=q, params=params,
                     name=name or getattr(cfg, "name", "lm"))
    if quant is None:
        raise TypeError("build(spec, quant): CNN specs carry no quant "
                        "config of their own — pass one explicitly")
    return Model(kind="cnn", spec=tuple(spec), quant=quant, params=params,
                 img_hw=img_hw, name=name or "cnn")


def load(path: str, *, spec=None, quant: QuantConfig | None = None,
         model: str | None = None,
         backend: str | None = None) -> CompiledModel:
    """Reload a persisted plan as a CompiledModel (optionally guarded
    against the caller's live configuration — see
    :func:`repro.core.plan.check_plan_matches`).  Pass ``backend=`` when
    the plan will be executed (a plan compiled for another backend may pin
    engines that cannot run here); omit it for pure inspection."""
    from repro.core.plan import check_plan_matches, load_plan

    plan = check_plan_matches(load_plan(path), quant=quant, model=model,
                              backend=backend)
    m = None
    if spec is not None:
        m = build(spec, quant if quant is not None else plan.quant,
                  name=plan.model)
    return CompiledModel(plan, model=m, cache_path=path, reloaded=True)
