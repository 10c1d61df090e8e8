"""Compile-once execution plans: the ModelPlan IR (DESIGN.md §8).

The paper's accelerator decides the mapping of every conv layer onto
SOT-MRAM sub-arrays *once*, ahead of execution, and keeps the mapped
bit-planes resident so power loss never forces recomputation (§II, §IV).
This module is the software analogue: :func:`compile_model` (CNNs) and
:func:`compile_lm` (transformers) run every serve-time decision the
inference stack used to make per call — engine dispatch, weight
pre-quantization, feasibility validation — exactly once, producing a
:class:`ModelPlan` that the whole stack then executes:

* one :class:`LayerPlan` record per layer (op kind, shapes, bits, chosen
  engine + how it was chosen, per-batch-hint engine table);
* the pre-quantized serve params (int8 levels + scales — the MRAM-resident
  C_n(W) analogue) as the plan's payload;
* a dense-GEMM verdict table that :func:`repro.kernels.ops.select_engine`
  consults while the plan is active, so transformer projections dispatch
  by lookup instead of heuristic;
* serialization to disk (JSON metadata + npz levels): a restarted node —
  the power-intermittency story — reloads the plan and skips
  requantization, autotuning, and engine search entirely
  (``pim/intermittent.plan_resume_study`` quantifies the win).

Engine choices resolve in three ways, recorded per layer as
``engine_source``: ``override`` (an explicit ``QuantConfig.engine``,
validated against backend/shape feasibility at compile time — infeasible
combinations raise :class:`PlanError` naming the layer instead of failing
deep inside a ``pallas_call``), ``autotuned`` (candidate engines timed on
the live backend via :func:`repro.kernels.ops.autotune_engine`), or
``heuristic`` (the cost model — the no-autotune default, bit-identical in
choice to the pre-plan per-call dispatch).
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.prequant import is_fp_layer, prequantize_cnn_params
from repro.core.quant import QuantConfig
from repro.kernels import ops

PLAN_VERSION = 1

# Engines valid for the signed (affine-corrected) transformer serve path —
# the fused/faithful Pallas epilogues implement the unsigned DoReFa
# correction only, mirroring models/layers._signed_engine.
SIGNED_ENGINES = ("planes", "packed", "int8", "f32dot")


class PlanError(ValueError):
    """A plan could not be compiled: an explicit engine override is
    infeasible for the backend/shape, or a serialized plan is invalid.
    The message names the offending layer."""


@dataclasses.dataclass(frozen=True)
class LayerPlan:
    """One layer's compiled execution record.

    ``engine`` is the verdict at the primary batch hint; ``engines`` holds
    the full ``(batch_hint, engine)`` table (every engine is bit-exact, so
    a hint miss costs performance, never correctness).
    """

    index: int
    name: str
    op: str                 # "conv" | "dense"
    role: str               # first | mid | last
    fp: bool                # full-precision layer (no bitwise engine)
    kh: int
    kw: int
    stride: int
    padding: str
    cin: int
    cout: int
    in_h: int
    in_w: int
    out_h: int
    out_w: int
    k: int                  # GEMM depth (kh*kw*cin for convs)
    a_bits: int
    w_bits: int
    engine: str             # "fp" for fp layers
    engine_source: str      # fp | override | autotuned | heuristic
    engines: tuple          # ((batch_hint, engine), ...)
    pool: bool = False
    fc: bool = False
    # per-image (energy_pj, cycles, bytes_moved) estimate from the compile
    # target's cost model (repro.api.targets) — annotation only, never
    # consulted by execution
    cost: tuple = ()
    # attention layers (op == "attn") carry their resolved realization
    # (full/chunked/banded/flash) here; "" for conv/dense rows
    attn_engine: str = ""

    def engine_at(self, batch: int) -> str:
        """Verdict for ``batch``: exact hint, else the largest hint not
        above it (engine crossovers are monotonic in batch), else the
        smallest hint."""
        table = dict(self.engines)
        if batch in table:
            return table[batch]
        below = [b for b, _ in self.engines if b <= batch]
        return table[max(below)] if below else table[min(dict(self.engines))]


@dataclasses.dataclass
class ModelPlan:
    """A compiled, serializable execution plan for one model + backend."""

    kind: str                       # "cnn" | "lm"
    model: str
    backend: str
    quant: QuantConfig
    batch_hints: tuple
    layers: tuple                   # tuple[LayerPlan, ...]
    params: object = None           # pre-quantized serve pytree (or None)
    dense_table: dict = dataclasses.field(default_factory=dict)
    autotune: dict = dataclasses.field(default_factory=dict)
    # attention dispatch verdicts: attn_plan_key -> engine.  A separate
    # table from dense_table — attention engines (full/chunked/banded/
    # flash) name realizations of the softmax dataflow, not level-GEMM
    # engines, so consumers of dense_table never see them.
    attn_table: dict = dataclasses.field(default_factory=dict)
    version: int = PLAN_VERSION

    # -- identity -----------------------------------------------------------

    def meta(self) -> dict:
        """JSON-ready metadata (everything except the params arrays)."""
        return dict(
            version=self.version, kind=self.kind, model=self.model,
            backend=self.backend, quant=dataclasses.asdict(self.quant),
            batch_hints=list(self.batch_hints),
            layers=[_layer_to_json(lp) for lp in self.layers],
            dense_table=[[list(k), v] for k, v in
                         sorted(self.dense_table.items())],
            attn_table=[[list(k), v] for k, v in
                        sorted(self.attn_table.items())],
            autotune=[[list(k), v[0], v[1]] for k, v in
                      sorted(self.autotune.items(), key=lambda kv: kv[0])],
        )

    def fingerprint(self) -> str:
        """Stable short hash of the plan metadata — program-cache key
        material for :class:`repro.launch.engine.ServeEngine`."""
        blob = json.dumps(self.meta(), sort_keys=True).encode()
        return hashlib.sha256(blob).hexdigest()[:12]

    # -- dispatch installation ---------------------------------------------

    def _dispatch_table(self) -> dict:
        """Every verdict this plan installs (dense GEMMs + attention)."""
        return {**self.dense_table, **self.attn_table}

    def install(self) -> "ModelPlan":
        """Install this plan's dense + attention verdicts process-wide
        (long-lived server: one plan, installed once at startup)."""
        ops.install_plan_table(self._dispatch_table())
        return self

    @contextlib.contextmanager
    def activate(self):
        """Scoped install: dense and attention dispatch consult this plan's
        tables while the context is open (covers jit *trace* time — traced
        programs keep the planned engines forever after).  Exit restores
        the PRIOR state of every key this plan touched, so activating on
        top of a process-wide :meth:`install` (or a nested activation)
        never uninstalls the outer plan's verdicts."""
        table = self._dispatch_table()
        prior = {k: ops._PLAN_TABLE[k] for k in table
                 if k in ops._PLAN_TABLE}
        ops.install_plan_table(table)
        try:
            yield self
        finally:
            ops.remove_plan_table({k: None for k in table
                                   if k not in prior})
            if prior:
                ops.install_plan_table(prior)


# ---------------------------------------------------------------------------
# Engine resolution (shared by the CNN and LM compile passes)
# ---------------------------------------------------------------------------

def _resolve_engine(quant: QuantConfig, m: int, k: int, n: int, backend: str,
                    conv, *, strict: bool, autotune: bool,
                    layer_desc: str) -> tuple[str, str]:
    """One layer's engine verdict -> (engine, source)."""
    if quant.engine not in ("auto", "fp"):
        if strict:
            ok, reason = ops.engine_feasible(quant.engine, m, k, n,
                                             quant.a_bits, quant.w_bits,
                                             backend, conv)
            if not ok:
                raise PlanError(
                    f"{layer_desc}: explicit engine {quant.engine!r} is "
                    f"infeasible on backend {backend!r}: {reason}")
        return quant.engine, "override"
    if autotune:
        eng, _ = ops.autotune_engine(m, k, n, quant.a_bits, quant.w_bits,
                                     backend, conv)
        return eng, "autotuned"
    # the PURE cost model, never select_engine: a compiling plan must not
    # absorb verdicts from whatever other plan happens to be installed or
    # autotune state happens to be cached — 'heuristic' plans are
    # deterministic functions of (spec, quant, shape, backend) only
    return (ops.cost_model_engine(m, k, n, quant.a_bits, quant.w_bits,
                                  backend, conv), "heuristic")


# ---------------------------------------------------------------------------
# CNN compile pass
# ---------------------------------------------------------------------------

def _plan_cnn_layers(spec, quant: QuantConfig, *, batches, img_hw, backend,
                     strict: bool, autotune: bool):
    """Structural pass: trace the forward's shape evolution and resolve one
    engine per (layer, batch hint).  Mirrors ``models/cnn.cnn_forward``
    exactly (fc resize, SAME/VALID policy, 2x2 pools)."""
    from repro.core.conv_lowering import _out_hw

    layers = []
    in_h, in_w = img_hw
    for i, s in enumerate(spec):
        pad = "VALID" if (s.fc or s.k == 1) else "SAME"
        if s.fc and s.k > 1 and in_h != s.k:
            in_h = in_w = s.k       # cnn_forward resizes to (k, k)
        out_h, out_w = _out_hw(in_h, in_w, s.k, s.k, s.stride, pad)
        kdim = s.k * s.k * s.cin
        name = f"{'fc' if s.fc else 'conv'}{i}"
        fp = is_fp_layer(s, quant)
        if fp:
            engines = tuple((b, "fp") for b in batches)
            source = "fp"
        else:
            resolved = []
            for b in batches:
                conv = ops.ConvShape(in_h, in_w, s.k, s.k, s.stride, pad,
                                     batch=b)
                eng, source = _resolve_engine(
                    quant, b * out_h * out_w, kdim, s.cout, backend, conv,
                    strict=strict, autotune=autotune,
                    layer_desc=f"layer {i} ({name}, {s.k}x{s.k} "
                               f"cin={s.cin} cout={s.cout} batch={b})")
                resolved.append((b, eng))
            engines = tuple(resolved)
        layers.append(LayerPlan(
            index=i, name=name, op="conv", role=s.role, fp=fp,
            kh=s.k, kw=s.k, stride=s.stride, padding=pad,
            cin=s.cin, cout=s.cout, in_h=in_h, in_w=in_w,
            out_h=out_h, out_w=out_w, k=kdim,
            a_bits=quant.a_bits, w_bits=quant.w_bits,
            engine=engines[0][1], engine_source=source, engines=engines,
            pool=s.pool, fc=s.fc))
        in_h, in_w = out_h, out_w
        if s.pool:
            # floor at 1: a pooled 1x1 map (LeNet's pooled-FC stage, which
            # exists only as a mapper/cost model) must not collapse the
            # downstream walk to zero extent (matches pim/mapper.layer_work)
            in_h, in_w = max(in_h // 2, 1), max(in_w // 2, 1)
    return tuple(layers)


def _annotate_costs(layers: tuple, backend: str) -> tuple:
    """Attach the compile target's per-layer (energy_pj, cycles,
    bytes_moved) roofline estimate (repro.api.targets) to each LayerPlan.
    Pure and deterministic — part of the plan's fingerprint."""
    from repro.api.targets import LayerGeometry, target_for_backend
    from repro.pim.mapper import effective_bits

    t = target_for_backend(backend)
    out = []
    for lp in layers:
        ab, wb = effective_bits(lp)
        c = t.cost(LayerGeometry(lp.out_h * lp.out_w, lp.k, lp.cout), ab, wb)
        out.append(dataclasses.replace(
            lp, cost=(c.energy_pj, c.cycles, c.bytes_moved)))
    return tuple(out)


def _is_prequantized(params) -> bool:
    return any(isinstance(p, dict) and "w_lv" in p for p in params)


def compile_model(params, spec, quant: QuantConfig, *, backend=None,
                  batch_hints=(1,), img_hw=40, autotune: bool = False,
                  model: str = "cnn", verify: bool = True) -> ModelPlan:
    """Compile a CNN serve plan: validate/resolve engines for every layer at
    every batch hint, pre-quantize the weights once, collect any autotune
    measurements.  ``params=None`` produces a structure-only plan (engine
    table inspection, golden tests).  Explicit ``quant.engine`` overrides
    that are infeasible on ``backend`` raise :class:`PlanError` here — at
    compile time, naming the layer — instead of failing inside a kernel.

    ``verify=True`` (default) runs the static plan prover
    (:func:`repro.analysis.verify_plan`, DESIGN.md §12) over the result —
    bit-range exactness, int32 overflow, feasibility, table and cost
    invariants — raising :class:`repro.analysis.PlanVerificationError`
    (a :class:`PlanError`) on any violation.  ``verify=False`` is the
    escape hatch for deliberately out-of-contract plans.
    """
    backend = backend or jax.default_backend()
    if isinstance(img_hw, int):
        img_hw = (img_hw, img_hw)
    batch_hints = tuple(int(b) for b in batch_hints) or (1,)
    layers = _annotate_costs(
        _plan_cnn_layers(tuple(spec), quant, batches=batch_hints,
                         img_hw=tuple(img_hw), backend=backend,
                         strict=True, autotune=autotune), backend)
    serve_params = None
    if params is not None:
        serve_params = (params if _is_prequantized(params)
                        else prequantize_cnn_params(params, spec, quant))
    tuned = {}
    if autotune:  # heuristic plans carry no measurements (determinism)
        for lp in layers:
            if lp.fp:
                continue
            for b, _ in lp.engines:
                key = ops.autotune_key(
                    b * lp.out_h * lp.out_w, lp.k, lp.cout, lp.a_bits,
                    lp.w_bits, backend,
                    ops.ConvShape(lp.in_h, lp.in_w, lp.kh, lp.kw,
                                  lp.stride, lp.padding, batch=b))
                if key in ops._AUTOTUNE_CACHE:
                    tuned[key] = ops._AUTOTUNE_CACHE[key]
    plan = ModelPlan(kind="cnn", model=model, backend=backend, quant=quant,
                     batch_hints=batch_hints, layers=layers,
                     params=serve_params, autotune=tuned)
    if verify:
        from repro.analysis.prover import assert_plan_verified

        assert_plan_verified(plan)
    return plan


# Structural layers for the compat path (`cnn_forward(mode="serve")` without
# an explicit plan): cached per (spec, quant, shape, backend).  The dispatch
# epoch stays in the key as a safety valve — heuristic resolution is pure
# today, but any future verdict source must not serve stale cached layers.
@functools.lru_cache(maxsize=512)
def _cached_cnn_layers(spec_t, quant, batch, img_hw, backend, _epoch):
    return _plan_cnn_layers(spec_t, quant, batches=(batch,), img_hw=img_hw,
                            backend=backend, strict=False, autotune=False)


def cnn_serve_layers(spec, quant: QuantConfig, *, batch: int, img_hw,
                     backend=None):
    """Per-call plan for the legacy ``cnn_forward`` entry point: identical
    engine choices to the pre-plan per-layer dispatch (permissive about
    explicit overrides — the correctness suites force interpret-mode Pallas
    engines on CPU through this path)."""
    backend = backend or jax.default_backend()
    return _cached_cnn_layers(tuple(spec), quant, int(batch),
                              (int(img_hw[0]), int(img_hw[1])), backend,
                              ops.dispatch_epoch())


# ---------------------------------------------------------------------------
# CNN execution — the single serve dataflow (no per-layer branching)
# ---------------------------------------------------------------------------

def _layer_weights(p: dict, lp: LayerPlan):
    """Uniform weight access: plan params carry pre-quantized levels; float
    checkpoints prequantize at trace time (once per compiled program)."""
    if "w_lv" in p:
        return p["w_lv"], p["s_w"], p["z_w"]
    from repro.core.prequant import prequantize_conv_weight

    return prequantize_conv_weight(p["w"], lp.w_bits)


def execute_cnn_layers(layers, params, x, quant: QuantConfig):
    """Run the compiled layer sequence.  x (B,H,W,C) in [0,1] -> logits."""
    from repro.core.conv_lowering import conv2d_float, quant_conv2d_pre
    from repro.models.cnn import _norm_act, global_avg_pool

    h = x
    last = len(layers) - 1
    for lp, p in zip(layers, params):
        if lp.fc and lp.kh > 1 and h.shape[1] != lp.kh:
            h = jax.image.resize(h, (h.shape[0], lp.kh, lp.kw, h.shape[3]),
                                 "linear")
        if lp.fp:
            h = conv2d_float(h, p["w"], stride=lp.stride, padding=lp.padding)
        else:
            w_lv, s_w, z_w = _layer_weights(p, lp)
            h = quant_conv2d_pre(
                h, w_lv, s_w, z_w, kh=lp.kh, kw=lp.kw, stride=lp.stride,
                padding=lp.padding, a_bits=lp.a_bits, w_bits=lp.w_bits,
                engine=lp.engine)
        h = h + p["b"]
        if lp.index < last:
            h = _norm_act(h, p["g"], p["beta"], quant, lp.role, "serve")
        if lp.pool:
            h = jax.lax.reduce_window(
                h, 0.0, jax.lax.add, (1, 2, 2, 1), (1, 2, 2, 1), "VALID") / 4.0
    return global_avg_pool(h)


def plan_energy_pj(plan: ModelPlan) -> float:
    """Modeled energy of one forward through the plan, in pJ — the sum of
    the per-layer roofline cost annotations.  This is the currency of the
    resilience degrade policy's energy budget
    (:class:`repro.resilience.degrade.DegradePolicy`): per-sample, so a
    dispatch of padded batch B spends ``B * plan_energy_pj(plan)``.
    Layers compiled without annotations contribute zero."""
    return float(sum(lp.cost[0] for lp in plan.layers if lp.cost))


def plan_cost_on(plan: ModelPlan, target) -> dict:
    """Re-price one forward pass of a compiled CNN plan on any PIM
    :class:`repro.api.targets.HardwareTarget` (name or instance).

    The plan's own per-layer ``cost`` annotations are priced against the
    compile-time target; a fleet of heterogeneous nodes needs the *same*
    plan priced on *different* accelerators without recompiling.  This is
    the Table-II-pinned arithmetic (same works, same ``accel_cost``, same
    fitted energy scale as ``pim/accelsim``), so the absolutes agree
    bit-for-bit with ``CompiledModel.simulate``; it is the per-frame
    ``(energy_uj, latency_us)`` currency of ``repro.fleet.sim``.
    """
    from repro.api.targets import PIMTarget, get_target
    from repro.pim.mapper import works_from_layers

    if plan.kind != "cnn":
        raise PlanError(f"plan_cost_on prices CNN plans (the paper's "
                        f"frame-per-inference scope); this plan is "
                        f"{plan.kind!r}")
    t = get_target(target) if isinstance(target, str) else target
    if not isinstance(t, PIMTarget):
        raise PlanError(
            f"plan_cost_on prices PIM targets (got {t.name!r}); compute "
            f"targets carry their cost in the plan's own annotations — "
            f"sum lp.cost or use CompiledModel.simulate")
    report = dict(t.report(works_from_layers(plan.layers)))
    report["target"] = t.name
    return report


def layers_for_batch(plan: ModelPlan, batch: int):
    """The plan's layer sequence with engines re-pinned for ``batch`` (see
    :meth:`LayerPlan.engine_at` for the hint-miss policy)."""
    return tuple(dataclasses.replace(lp, engine=lp.engine_at(batch))
                 for lp in plan.layers)


def plan_forward(plan: ModelPlan, x, params=None):
    """Execute a compiled CNN plan.  ``params`` defaults to the plan's own
    serve params; pass them explicitly when they arrive as jit arguments
    (e.g. device-put replicas inside the serving engine)."""
    if plan.kind != "cnn":
        raise PlanError(f"plan_forward executes CNN plans, got {plan.kind!r}")
    params = plan.params if params is None else params
    if params is None:
        raise PlanError("structure-only plan (compiled with params=None) "
                        "cannot execute")
    return execute_cnn_layers(layers_for_batch(plan, int(x.shape[0])),
                              params, x, plan.quant)


# ---------------------------------------------------------------------------
# LM compile pass
# ---------------------------------------------------------------------------

def compile_lm(params, cfg, *, backend=None, batch_hints=(1,),
               prompt_len: int = 16, autotune: bool = False,
               page_size: int | None = None, kv_pages: int | None = None,
               verify: bool = True) -> ModelPlan:
    """Compile a transformer serve plan: pre-quantize every projection once
    and resolve one engine verdict per distinct (K, N) GEMM shape into the
    plan's dense table (consulted by ``select_engine`` while the plan is
    active).  Verdicts are ``m``-free — one entry covers prefill and every
    decode step (see :func:`repro.kernels.ops.dense_plan_key`).

    ``page_size``/``kv_pages`` declare the paged-KV serve geometry of the
    continuous-batching engine (``launch/engine.ContinuousLMEngine``:
    ``kv_pages`` = page-table width = per-request page budget): the plan
    then carries a ``paged`` attention verdict for the decode-step shape,
    and the prover's PV108 check proves the page-indexed gather feasible
    (int32 addressing, VMEM-bounded grid step) before the engine ever
    dispatches it.

    ``verify=True`` (default) runs the static plan prover over the result
    (see :func:`compile_model`); ``verify=False`` bypasses it.
    """
    from repro.models.layers import PREQUANT_KEYS, prequantize_params

    backend = backend or jax.default_backend()
    quant = cfg.quant
    batch_hints = tuple(int(b) for b in batch_hints) or (1,)
    quantized = not (quant.engine == "fp" or quant.w_bits >= 32)
    serve_params = prequantize_params(params, cfg) if quantized else params

    layers, table = [], {}
    if quantized:
        from repro.api.targets import LayerGeometry, target_for_backend

        cost_target = target_for_backend(backend)
        shapes: dict[tuple, str] = {}
        for kind, tree in sorted(params["blocks"].items()):
            for sub, sv in sorted(tree.items()):
                if not isinstance(sv, dict):
                    continue
                for kname, v in sorted(sv.items()):
                    if kname in PREQUANT_KEYS:
                        shapes.setdefault(
                            (int(v.shape[-2]), int(v.shape[-1])),
                            f"{kind}.{sub}.{kname}")
        for i, ((K, N), name) in enumerate(sorted(shapes.items())):
            m = batch_hints[0] * prompt_len
            eng, source = _resolve_engine(
                quant, m, K, N, backend, None, strict=True,
                autotune=autotune, layer_desc=f"projection {name} (K={K}, "
                                              f"N={N})")
            if eng not in SIGNED_ENGINES:
                # fused/faithful epilogues are unsigned-only; the signed
                # serve path realizes the same accumulation on int8
                # (mirrors models/layers._signed_engine)
                eng = "int8"
            table[ops.dense_plan_key(K, N, quant.a_bits, quant.w_bits,
                                     backend)] = eng
            c = cost_target.cost(LayerGeometry(m, K, N), quant.a_bits,
                                 quant.w_bits)
            layers.append(LayerPlan(
                index=i, name=name, op="dense", role="mid", fp=False,
                kh=0, kw=0, stride=1, padding="", cin=K, cout=N,
                in_h=0, in_w=0, out_h=0, out_w=0, k=K,
                a_bits=quant.a_bits, w_bits=quant.w_bits, engine=eng,
                engine_source=source,
                engines=tuple((b, eng) for b in batch_hints),
                cost=(c.energy_pj, c.cycles, c.bytes_moved)))
    # attention realization: one verdict per distinct window geometry
    # (global-attention kinds share one; attn_local brings the window).
    # Resolved on the PURE target decision procedure, mirroring the dense
    # heuristic path — a compiling plan must not absorb another installed
    # plan's verdicts.
    attn_table = _plan_lm_attention(params, cfg, quant, backend,
                                    batch_hints, prompt_len, layers,
                                    page_size=page_size, kv_pages=kv_pages)
    tuned = {}
    if autotune:  # heuristic plans carry no measurements (determinism)
        tuned = {k: v for k, v in ops._AUTOTUNE_CACHE.items()
                 if k[0] == "dense" and any(k[2:4] == (lp.k, lp.cout)
                                            for lp in layers)}
    plan = ModelPlan(kind="lm", model=getattr(cfg, "name", "lm"),
                     backend=backend, quant=quant, batch_hints=batch_hints,
                     layers=tuple(layers), params=serve_params,
                     dense_table=table, attn_table=attn_table,
                     autotune=tuned)
    if verify:
        from repro.analysis.prover import assert_plan_verified

        assert_plan_verified(plan)
    return plan


def _plan_lm_attention(params, cfg, quant: QuantConfig, backend: str,
                       batch_hints: tuple, prompt_len: int,
                       layers: list, page_size: int | None = None,
                       kv_pages: int | None = None) -> dict:
    """Resolve and record the attention engine per window geometry.

    Appends one ``op="attn"`` :class:`LayerPlan` row per verdict to
    ``layers`` and returns the :func:`repro.kernels.ops.attn_plan_key`
    table the plan installs for dispatch.  With ``page_size``/``kv_pages``
    set, one extra row records the paged decode-step verdict (10-tuple
    key; see :func:`repro.kernels.ops.attn_plan_key`).
    """
    from repro.api.targets import target_for_backend
    from repro.models.layers import attn_quantized

    cost_target = target_for_backend(backend)
    attn_table: dict = {}
    seen: set = set()
    for kind in sorted(params["blocks"]):
        if kind not in ("attn", "moe", "attn_local"):
            continue
        window = cfg.window if kind == "attn_local" else None
        if window in seen:
            continue
        seen.add(window)
        attn = ops.AttnShape(
            seq_q=prompt_len, seq_kv=prompt_len, heads=cfg.n_heads,
            head_dim=cfg.hd, causal=bool(cfg.causal), window=window,
            batch=batch_hints[0],
            quantized=attn_quantized(quant, "serve"),
            banded_ok=bool(getattr(cfg, "banded_attn", False)))
        eng = cost_target.select_attn_engine(attn)
        if (getattr(cfg, "full_attn_analysis", False)
                and eng in ("chunked", "flash")):
            eng = "full"  # the analysis contract pins materialized logits
        attn_table[ops.attn_plan_key(attn, backend)] = eng
        c = cost_target.attn_cost(attn)
        layers.append(LayerPlan(
            index=len(layers), name=f"attn[{kind}]", op="attn", role="mid",
            fp=not attn.quantized, kh=0, kw=0, stride=1, padding="",
            cin=cfg.d_model, cout=cfg.d_model, in_h=0, in_w=0,
            out_h=0, out_w=0, k=cfg.hd, a_bits=quant.a_bits,
            w_bits=quant.w_bits, engine=eng, engine_source="heuristic",
            engines=tuple((b, eng) for b in batch_hints),
            cost=(c.energy_pj, c.cycles, c.bytes_moved), attn_engine=eng))
    if page_size is not None:
        if not kv_pages or kv_pages < 1:
            raise ValueError(f"page_size={page_size} needs kv_pages >= 1 "
                             f"(per-request page budget), got {kv_pages}")
        # the continuous engine's decode-step geometry: one query token per
        # slot against a page-table extent of kv_pages pages.  batch is the
        # slot count (the largest co-resident decode batch)
        attn = ops.AttnShape(
            seq_q=1, seq_kv=page_size * kv_pages, heads=cfg.n_heads,
            head_dim=cfg.hd, causal=bool(cfg.causal), window=None,
            batch=max(batch_hints),
            quantized=attn_quantized(quant, "serve"),
            page_size=page_size)
        eng = cost_target.select_attn_engine(attn)
        attn_table[ops.attn_plan_key(attn, backend)] = eng
        c = cost_target.attn_cost(attn)
        layers.append(LayerPlan(
            index=len(layers), name=f"attn[paged {kv_pages}x{page_size}]",
            op="attn", role="mid", fp=not attn.quantized, kh=0, kw=0,
            stride=1, padding="", cin=cfg.d_model, cout=cfg.d_model,
            in_h=0, in_w=0, out_h=0, out_w=0, k=cfg.hd,
            a_bits=quant.a_bits, w_bits=quant.w_bits, engine=eng,
            engine_source="heuristic",
            engines=tuple((b, eng) for b in batch_hints),
            cost=(c.energy_pj, c.cycles, c.bytes_moved), attn_engine=eng))
    return attn_table


# ---------------------------------------------------------------------------
# Serialization: JSON metadata + npz weight levels
# ---------------------------------------------------------------------------

def _layer_to_json(lp: LayerPlan) -> dict:
    d = dataclasses.asdict(lp)
    d["engines"] = [list(e) for e in lp.engines]
    d["cost"] = list(lp.cost)
    return d


def _layer_from_json(d: dict) -> LayerPlan:
    d = dict(d)
    d["engines"] = tuple((int(b), str(e)) for b, e in d["engines"])
    d["cost"] = tuple(float(c) for c in d.get("cost", ()))
    return LayerPlan(**d)


def _skeletonize(tree, prefix: str, out: dict):
    """Nested dict/list pytree -> JSON skeleton + flat {path: ndarray}."""
    if isinstance(tree, dict):
        return {k: _skeletonize(v, f"{prefix}/{k}", out)
                for k, v in tree.items()}
    if isinstance(tree, (list, tuple)):
        return [_skeletonize(v, f"{prefix}/{i}", out)
                for i, v in enumerate(tree)]
    out[prefix] = np.asarray(tree)
    return {"__leaf__": prefix}


def _reconstitute(skel, npz):
    if isinstance(skel, dict):
        if set(skel) == {"__leaf__"}:
            return jnp.asarray(npz[skel["__leaf__"]])
        return {k: _reconstitute(v, npz) for k, v in skel.items()}
    if isinstance(skel, list):
        return [_reconstitute(v, npz) for v in skel]
    raise PlanError(f"invalid params skeleton node: {skel!r}")


def _plan_base(path: str) -> str:
    return path[:-5] if path.endswith(".json") else path


def plan_exists(path: str) -> bool:
    """Is a serialized plan present at ``path`` (with or without .json)?"""
    return os.path.exists(_plan_base(path) + ".json")


def check_plan_matches(plan: ModelPlan, *, quant: QuantConfig | None = None,
                       model: str | None = None,
                       backend: str | None = None) -> ModelPlan:
    """Guard a reloaded plan against the caller's live configuration.

    A plan compiled under a different quant config would silently decode
    its stored integer levels with the wrong bit widths (garbage outputs,
    no shape error) — so mismatches raise :class:`PlanError` telling the
    operator to recompile, instead of serving wrong numbers.
    """
    if quant is not None and plan.quant != quant:
        raise PlanError(
            f"plan was compiled for quant {plan.quant.tag()!r} "
            f"(engine={plan.quant.engine!r}) but the current config is "
            f"{quant.tag()!r} (engine={quant.engine!r}) — delete the plan "
            "file or point --plan-cache elsewhere to recompile")
    if model is not None and plan.model != model:
        raise PlanError(f"plan was compiled for model {plan.model!r}, "
                        f"current model is {model!r} — recompile")
    if backend is not None and plan.backend != backend:
        raise PlanError(f"plan was compiled for backend {plan.backend!r}, "
                        f"live backend is {backend!r} — recompile")
    return plan


def save_plan(plan: ModelPlan, path: str) -> str:
    """Write ``<path>.json`` (metadata) + ``<path>.npz`` (weight levels).

    Returns the JSON path.  The pair is self-contained: a fresh process
    reloads it and serves without touching the original checkpoint,
    requantizing, or re-running autotune.
    """
    base = _plan_base(path)
    os.makedirs(os.path.dirname(os.path.abspath(base)), exist_ok=True)
    meta = plan.meta()
    if plan.params is not None:
        arrays: dict[str, np.ndarray] = {}
        meta["params_skel"] = _skeletonize(plan.params, "p", arrays)
        np.savez(base + ".npz", **arrays)
        meta["params_npz"] = os.path.basename(base) + ".npz"
    else:
        meta["params_skel"] = None
        meta["params_npz"] = None
    with open(base + ".json", "w") as f:
        json.dump(meta, f, indent=1, sort_keys=True)
    return base + ".json"


def load_plan(path: str) -> ModelPlan:
    """Reload a serialized plan — the intermittency-resume fast path.

    Restores the autotune verdicts into the process-wide cache (so even
    plan *recompiles* skip measurement) and rebuilds the serve params from
    the npz levels; nothing is requantized.
    """
    base = _plan_base(path)
    with open(base + ".json") as f:
        meta = json.load(f)
    if meta.get("version") != PLAN_VERSION:
        raise PlanError(f"plan version {meta.get('version')!r} != "
                        f"{PLAN_VERSION} (recompile the plan)")
    params = None
    if meta.get("params_skel") is not None:
        npz_path = os.path.join(os.path.dirname(os.path.abspath(base)),
                                meta["params_npz"])
        with np.load(npz_path) as npz:
            params = _reconstitute(meta["params_skel"], npz)
    dense_table = {tuple(k): v for k, v in meta["dense_table"]}
    attn_table = {tuple(k): v for k, v in meta.get("attn_table", [])}
    autotune = {tuple(k): (eng, times)
                for k, eng, times in meta.get("autotune", [])}
    if autotune:
        ops._AUTOTUNE_CACHE.update(autotune)
        ops._DISPATCH_EPOCH[0] += 1
    return ModelPlan(
        kind=meta["kind"], model=meta["model"], backend=meta["backend"],
        quant=QuantConfig(**meta["quant"]),
        batch_hints=tuple(meta["batch_hints"]),
        layers=tuple(_layer_from_json(d) for d in meta["layers"]),
        params=params, dense_table=dense_table, attn_table=attn_table,
        autotune=autotune, version=meta["version"])
