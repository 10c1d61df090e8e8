"""Executable intermittency resilience (DESIGN.md §11).

Makes the paper's power-intermittency claim (§II-B3, Fig. 7) a property of
the *running* serve stack instead of only the analytic
``pim/intermittent.forward_progress`` model:

* :class:`FaultPlan` — seeded deterministic fault schedules (power loss,
  device drop, slow dispatch, staging corruption) on a logical work clock;
* :class:`ResilientServeEngine` — a CNN ServeEngine that survives the
  schedule: checked staging, idempotent re-enqueue, bounded backoff
  retries, deadlines, dead letters;
* :class:`DegradePolicy` — fall back to a pre-compiled lower-bit plan
  under fault pressure or an energy budget.

LMs survive the schedule in ``launch/engine.ContinuousLMEngine`` itself:
crash-consistent decode epoch checkpoints through the atomic train
Checkpointer (the software NV-FA), resumed after a kill.

Entry point: the facade, for either kind of plan::

    compiled = api.build(cfg, params=p).compile()
    dep = compiled.serve(resilience=ResilienceConfig(
        fault_plan=FaultPlan(mtbf=32.0, seed=0),
        checkpoint_dir="results/ckpt", epoch_steps=4))
"""
from __future__ import annotations

import dataclasses

from .degrade import DegradePolicy
from .engine import ResilientServeEngine
from .faults import (DEVICE_DROP, POWER_LOSS, SITE_KINDS, SLOW_DISPATCH,
                     STAGING_CORRUPTION, DeviceDrop, FaultError, FaultEvent,
                     FaultPlan, PowerLoss)

__all__ = [
    "FaultPlan", "FaultEvent", "FaultError", "PowerLoss", "DeviceDrop",
    "POWER_LOSS", "DEVICE_DROP", "SLOW_DISPATCH", "STAGING_CORRUPTION",
    "SITE_KINDS", "DegradePolicy", "ResilientServeEngine",
    "ResilienceConfig", "build_resilient_engine",
]


@dataclasses.dataclass
class ResilienceConfig:
    """Everything the facade needs to stand up a resilient engine.

    An LM plan's continuous engine reads ``fault_plan``,
    ``checkpoint_dir``, ``epoch_steps`` and ``deadline_s``; the retry,
    backoff and degrade fields drive the CNN engine (see
    :meth:`check_kind`)."""

    fault_plan: FaultPlan | None = None     # None = fault-free reference arm
    checkpoint_dir: str | None = None       # LM: None = volatile (P=0)
    epoch_steps: int = 4                    # checkpoint period K (paper's P)
    max_retries: int = 3
    deadline_s: float | None = None
    backoff_base_s: float = 0.01
    backoff_max_s: float = 1.0
    degrade: DegradePolicy | None = None

    def check_kind(self, kind: str) -> None:
        """Raise ``PlanError`` if a field that a ``kind`` ("cnn" | "lm")
        plan's engine does not read is set away from its default."""
        from repro.core.plan import PlanError

        unread = (("max_retries", "backoff_base_s", "backoff_max_s",
                   "degrade") if kind == "lm"
                  else ("checkpoint_dir", "epoch_steps"))
        bad = [f.name for f in dataclasses.fields(self)
               if f.name in unread and getattr(self, f.name) != f.default]
        if bad:
            raise PlanError(f"a resilient {kind.upper()} deployment does "
                            f"not read ResilienceConfig fields {bad}")


def build_resilient_engine(compiled, config: ResilienceConfig, *,
                           fallback=None, **engine_kw) -> ResilientServeEngine:
    """Resilient engine over a CNN :class:`repro.api.session.CompiledModel`
    (an LM plan's resilient engine comes from ``compiled.serve``).

    ``fallback`` is another CompiledModel (same architecture, lower bit
    width) compiled ahead of time; with ``config.degrade`` set, the engine
    swaps to it under fault pressure / energy exhaustion.
    """
    from repro.launch.engine import CNNRunner

    def _runner(c):
        return CNNRunner(None, c.model.spec if c.model is not None else None,
                         None, plan=c.plan)

    fallbacks = () if fallback is None else (_runner(fallback),)
    return ResilientServeEngine(
        _runner(compiled),
        fault_plan=config.fault_plan,
        max_retries=config.max_retries,
        deadline_s=config.deadline_s,
        backoff_base_s=config.backoff_base_s,
        backoff_max_s=config.backoff_max_s,
        degrade=config.degrade,
        fallbacks=fallbacks,
        **engine_kw)
