"""Fault-surviving CNN serve engine: recovery and degradation.

``ServeEngine`` (launch/engine.py) made many requests fast; this subclass
makes them survive the paper's operating environment — a power-intermittent
node (§II-B3) — without giving up the bit-identity contract:

* every bucket is bracketed by :class:`repro.resilience.faults.FaultPlan`
  hook points (staging, then the single-shot dispatch); a CNN bucket is
  one program, so there is no partial state to checkpoint;
* a killed bucket's requests are **re-enqueued idempotently** (same rid,
  same ``t_submit``, results recorded at most once) behind bounded
  exponential backoff with jitter; a request that exhausts its retries or
  its deadline lands in :attr:`ResilientServeEngine.dead_letters` instead
  of vanishing;
* under repeated faults or a modeled energy budget, the engine **degrades**
  to a pre-compiled lower-bit-width plan
  (:class:`repro.resilience.degrade.DegradePolicy`) — trading accuracy for
  forward progress exactly as the paper's low-bit operating points do.

LMs survive faults in ``launch/engine.ContinuousLMEngine``, which commits
decode epoch checkpoints and resumes from them after a kill.

The resilient engine is deliberately a *per-node* story (mesh=None only)
and dispatches buckets synchronously — recoverability instead of the base
engine's double-buffered overlap.
"""
from __future__ import annotations

import hashlib
import time

import jax
import numpy as np

from repro.launch.engine import Bucket, Result, ServeEngine
from .faults import (DEVICE_DROP, POWER_LOSS, SLOW_DISPATCH,
                     STAGING_CORRUPTION, DeviceDrop, FaultPlan, PowerLoss)

# logical work-clock charge of the staging hook, in dispatch units:
# staging is a host copy (cheap)
STAGING_DT = 0.25


class ResilientServeEngine(ServeEngine):
    """A :class:`ServeEngine` that survives an adversarial ``FaultPlan``.

    Parameters (beyond the base engine's)
    -------------------------------------
    fault_plan:      the seeded fault schedule (None -> fault-free, same
                     code path — the reference arm of bit-identity tests).
    max_retries:     kills a request survives before dead-lettering.
    backoff_base_s / backoff_max_s: exponential backoff bounds for
                     re-enqueued buckets (jittered; the engine's ``clock``
                     gates eligibility, so fake clocks stay deterministic).
    deadline_s:      per-request wall budget (submit -> dispatch start);
                     expired requests dead-letter with reason "deadline".
    degrade:         a :class:`repro.resilience.degrade.DegradePolicy`;
                     with ``fallbacks``, repeated faults or an exhausted
                     energy budget swap the runner to the next (lower-bit)
                     plan and reset the retry budget.  With the policy's
                     ``recover_after`` set, a streak of clean dispatches
                     re-arms the primary plan (``stats["recoveries"]``).
    fallbacks:       runners over pre-compiled degraded plans, best first.
    """

    def __init__(self, runner, *, fault_plan: FaultPlan | None = None,
                 max_retries: int = 3,
                 backoff_base_s: float = 0.01, backoff_max_s: float = 1.0,
                 deadline_s: float | None = None, degrade=None,
                 fallbacks=(), slow_dispatch_s: float = 0.0, seed: int = 0,
                 **kw):
        if kw.get("mesh") is not None:
            raise ValueError(
                "ResilientServeEngine is the per-node intermittency story "
                "(paper §II-B3): mesh sharding is not supported — shard "
                "above the engine, one resilient engine per node")
        super().__init__(runner, **kw)
        self.faults = fault_plan if fault_plan is not None else FaultPlan(None)
        self.max_retries = int(max_retries)
        self.backoff_base_s = backoff_base_s
        self.backoff_max_s = backoff_max_s
        self.deadline_s = deadline_s
        self.policy = degrade
        self.slow_dispatch_s = slow_dispatch_s
        self._runners = [runner, *fallbacks]
        self._active = 0
        # energy-weighted fault clock: MTBF is really mean-energy-between-
        # failures on a harvested supply, so a dispatch's fault exposure
        # scales with the active plan's energy per step.  1.0 for the
        # primary plan; degrading rescales by the fallback's relative
        # modeled energy — the causal mechanism by which the paper's
        # lower-bit operating points survive more brownouts (§II-B3)
        self._energy_scale = 1.0
        self._rng = np.random.RandomState(seed)
        self._attempts: dict[int, int] = {}
        self._retry: list[tuple[float, object]] = []   # (eligible_at, Request)
        self.dead_letters: dict[int, str] = {}
        self.result_runner: dict[int, int] = {}        # rid -> runner index
        self.stats.update(
            faults=0, power_losses=0, device_drops=0, slow_dispatches=0,
            staging_retries=0, retries=0, dead_lettered=0, degrades=0,
            recoveries=0, energy_pj=0.0)

    # -- queue side: retries are pre-admitted work --------------------------

    def _queued(self) -> int:
        return super()._queued() + len(self._retry)

    def _admit_retries(self, force: bool = False) -> None:
        """Move backoff-expired retries back into the batcher (original
        Request objects: same rid, same t_submit — idempotent)."""
        now = self.clock()
        still = []
        for eligible_at, req in self._retry:
            if force or eligible_at <= now:
                b = self.batcher.add(req, self.runner.shape_key(req.payload),
                                     now)
                if b is not None:
                    self._ready.append(b)
            else:
                still.append((eligible_at, req))
        self._retry = still

    def pump(self) -> None:
        self._admit_retries()
        super().pump()

    def drain(self) -> list[Result]:
        """Run to completion: every request either completes or
        dead-letters.  Closed-loop drain force-admits backoff'd retries
        (backoff paces the open-loop ``pump`` path; "drain now" means the
        caller is the clock).  Terminates because every kill increments an
        attempt counter bounded by ``max_retries``."""
        while True:
            self._admit_retries(force=True)
            self._flush_all()
            if not self._retry and not self.batcher.pending() \
                    and not self._ready:
                break
        out = [self._results[rid] for rid in sorted(self._results)]
        self._results.clear()
        return out

    # -- recovery ------------------------------------------------------------

    def _dead_letter(self, req, reason: str) -> None:
        if req.rid in self.dead_letters or req.rid in self._results:
            return
        self.dead_letters[req.rid] = reason
        self.stats["dead_lettered"] += 1
        self._attempts.pop(req.rid, None)

    def _requeue(self, bucket: Bucket) -> None:
        """Idempotent re-enqueue of a killed bucket: bounded retries,
        exponential backoff with jitter, dead-letter on exhaustion."""
        now = self.clock()
        for req in bucket.requests:
            a = self._attempts.get(req.rid, 0) + 1
            self._attempts[req.rid] = a
            if a > self.max_retries:
                self._dead_letter(req,
                                  f"retries exhausted ({self.max_retries})")
                continue
            delay = min(self.backoff_base_s * (1 << (a - 1)),
                        self.backoff_max_s)
            delay *= 0.5 + self._rng.uniform()          # jitter [0.5, 1.5)
            self._retry.append((now + delay, req))
            self.stats["retries"] += 1

    def _maybe_degrade(self) -> None:
        if self.policy is None or self._active + 1 >= len(self._runners):
            return
        if not self.policy.should_degrade():
            return
        old = self.runner
        self._active += 1
        self.runner = self._runners[self._active]
        self._energy_scale *= self._relative_energy(old, self.runner)
        self._params = jax.device_put(self.runner.params)
        self._attempts.clear()   # fresh retry budget at the new operating point
        self.policy.reset()
        self.stats["degrades"] += 1

    def _maybe_recover(self) -> None:
        """Re-arm the primary plan once fault pressure has subsided: the
        inverse of :meth:`_maybe_degrade`, gated by the policy's clean-
        dispatch streak.  Recovery jumps straight back to runner 0 (the
        best operating point — intermediate fallbacks only matter on the
        way *down*) and restores the unit energy scale that the degrades
        had discounted."""
        if self.policy is None or self._active == 0:
            return
        if not self.policy.should_recover():
            return
        self.runner = self._runners[0]
        self._active = 0
        self._energy_scale = 1.0
        self._params = jax.device_put(self.runner.params)
        self._attempts.clear()   # fresh retry budget at the restored point
        self.policy.reset()
        self.stats["recoveries"] += 1

    @staticmethod
    def _relative_energy(old, new) -> float:
        """new plan's modeled energy per step relative to old's (< 1 for a
        genuine bit-width downgrade; 1.0 when either lacks annotations)."""
        from repro.core.plan import plan_energy_pj

        def _e(r):
            plan = r.plan
            if plan is not None and hasattr(plan, "layers"):
                return plan_energy_pj(plan)
            return 0.0

        e_old, e_new = _e(old), _e(new)
        return e_new / e_old if e_old > 0 and e_new > 0 else 1.0

    # -- fault hooks ---------------------------------------------------------

    def _fault_gate(self, site: str, dt: float):
        """Poll the fault plan at one hook; kill-class events raise.

        ``dt`` is charged through the energy-weighted clock: the active
        plan's relative energy scales its exposure window."""
        ev = self.faults.poll(site, dt=dt * self._energy_scale)
        if ev is None:
            return None
        if ev.kind == SLOW_DISPATCH:
            self.stats["slow_dispatches"] += 1
            if self.slow_dispatch_s > 0:
                time.sleep(self.slow_dispatch_s)
            return ev
        if ev.kind in (POWER_LOSS, DEVICE_DROP):
            FaultPlan.raise_for(ev)
        return ev

    # -- device side: synchronous, recoverable dispatch ---------------------

    def _execute(self, buckets: list[Bucket]) -> None:
        for bucket in buckets:
            self._run_bucket(bucket)

    def _run_bucket(self, bucket: Bucket) -> None:
        now = self.clock()
        live = []
        for req in bucket.requests:
            if (self.deadline_s is not None
                    and now - req.t_submit > self.deadline_s):
                self._dead_letter(req, "deadline")
            else:
                live.append(req)
        if len(live) != len(bucket.requests):
            if not live:
                return
            bucket = Bucket(bucket.key, live)
        try:
            self._dispatch_bucket(bucket)
        except (PowerLoss, DeviceDrop) as f:
            self.stats["faults"] += 1
            self.stats["power_losses" if isinstance(f, PowerLoss)
                       else "device_drops"] += 1
            if self.policy is not None:
                self.policy.record_fault()
            self._requeue(bucket)
            self._maybe_degrade()

    def _dispatch_bucket(self, bucket: Bucket) -> None:
        padded = self._pad_to(len(bucket.requests))
        dev = self._stage_checked(bucket, padded)
        self._fault_gate("dispatch", dt=1.0)
        host = np.asarray(self._executable(bucket.key, padded)(self._params,
                                                              dev))
        self._record_results(bucket, padded, host)

    def _stage_checked(self, bucket: Bucket, padded: int):
        """Collate + host->device with corruption detection: a
        ``staging_corruption`` event flips bytes in the staged copy; the
        checksum taken at collate time catches it and the intact host
        payloads are restaged."""
        payloads = [r.payload for r in bucket.requests]
        batch = self.runner.collate(payloads, padded)
        checksum = hashlib.sha1(np.ascontiguousarray(batch)).hexdigest()
        ev = self.faults.poll("staging", dt=STAGING_DT * self._energy_scale)
        if ev is not None:
            if ev.kind == STAGING_CORRUPTION:
                corrupt = batch.copy()
                flat = corrupt.reshape(-1).view(np.uint8)
                flat[self._rng.randint(flat.size)] ^= 0xFF
                staged = corrupt
                if hashlib.sha1(np.ascontiguousarray(staged)).hexdigest() \
                        != checksum:
                    self.stats["staging_retries"] += 1
                    staged = self.runner.collate(payloads, padded)
                batch = staged
            else:
                FaultPlan.raise_for(ev)
        self.stats["put_chunks"] += 1
        return jax.device_put(batch)

    # -- harvest -------------------------------------------------------------

    def _record_results(self, bucket: Bucket, padded: int,
                        host: np.ndarray) -> None:
        n = len(bucket.requests)
        t_done = self.clock()
        for req, val in zip(bucket.requests, self.runner.split(host, n)):
            self._results[req.rid] = Result(req.rid, val, req.t_submit,
                                            t_done, n, padded)
            self._attempts.pop(req.rid, None)
            self.result_runner[req.rid] = self._active
        self.stats["dispatches"] += 1
        self.stats["requests"] += n
        self.stats["padded_rows"] += padded - n
        plan = self.runner.plan
        energy = 0.0
        if plan is not None and hasattr(plan, "layers"):
            from repro.core.plan import plan_energy_pj

            energy = plan_energy_pj(plan) * padded
            self.stats["energy_pj"] += energy
        if self.policy is not None:
            self.policy.record_dispatch(energy)
            self._maybe_degrade()
            self._maybe_recover()
