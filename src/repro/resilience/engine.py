"""Fault-surviving serve engine: epoch decode, recovery, degradation.

``ServeEngine`` (launch/engine.py) made many requests fast; this subclass
makes them survive the paper's operating environment — a power-intermittent
node (§II-B3) — without giving up the bit-identity contract:

* every dispatch is bracketed by :class:`repro.resilience.faults.FaultPlan`
  hook points (staging, prefill, per decode epoch, single-shot dispatch);
* the LM decode runs as K-step **epochs** (:class:`EpochLMRunner`) whose
  state commits through :class:`~repro.resilience.checkpoints.
  DecodeCheckpointer` after every epoch — the software NV-FA: a kill
  mid-decode loses at most one epoch, never the prefill or prior tokens;
* a killed bucket's requests are **re-enqueued idempotently** (same rid,
  same ``t_submit``, results recorded at most once) behind bounded
  exponential backoff with jitter; a request that exhausts its retries or
  its deadline lands in :attr:`ResilientServeEngine.dead_letters` instead
  of vanishing;
* under repeated faults or a modeled energy budget, the engine **degrades**
  to a pre-compiled lower-bit-width plan
  (:class:`repro.resilience.degrade.DegradePolicy`) — trading accuracy for
  forward progress exactly as the paper's low-bit operating points do.

The resilient engine is deliberately a *per-node* story (mesh=None only)
and dispatches buckets synchronously — recoverability instead of the base
engine's double-buffered overlap.  Forward-progress work accounting lives
in ``stats`` in logical decode steps, so a chaos run's efficiency is a
deterministic function of the fault seed and maps directly onto
``pim/intermittent.forward_progress`` (``benchmarks/bench_resilience.py``).
"""
from __future__ import annotations

import contextlib
import hashlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.engine import Bucket, LMRunner, Result, ServeEngine
from .checkpoints import DecodeCheckpointer
from .faults import (DEVICE_DROP, POWER_LOSS, SLOW_DISPATCH,
                     STAGING_CORRUPTION, DeviceDrop, FaultPlan, PowerLoss)

# logical work-clock charge (in decode-step units) for non-decode hooks:
# staging is a host copy (cheap), prefill one fused program over the prompt
STAGING_DT = 0.25
PREFILL_DT = 1.0


class EpochLMRunner(LMRunner):
    """LM runner whose decode is segmented into K-step checkpoint epochs.

    Instead of one fused prefill+scan program per bucket (``LMRunner``),
    the engine drives ``make_prefill_fn`` once and ``make_epoch_fn`` per
    epoch, committing state between epochs.  Each epoch is still a jitted
    ``lax.scan`` — the per-step dataflow is identical to ``launch/serve``'s
    one-trace decode, only the scan boundary moves — and only two epoch
    lengths ever compile (K and the tail remainder).

    ``epoch_steps`` is the checkpoint period: the paper's P, in decode
    steps.  Faulted-and-resumed output is bit-identical to a fault-free
    run *of this same runner* (the epoch boundary is a program boundary,
    so resume replays the exact program sequence on the exact state).
    """

    supports_epochs = True

    def __init__(self, params, cfg, *, new_tokens: int, epoch_steps: int = 4,
                 qmode: str = "serve", plan=None, model_plan=None):
        super().__init__(params, cfg, new_tokens=new_tokens, qmode=qmode,
                         plan=plan, model_plan=model_plan)
        if epoch_steps < 1:
            raise ValueError(f"epoch_steps must be >= 1, got {epoch_steps}")
        self.epoch_steps = int(epoch_steps)

    def epoch_schedule(self) -> tuple:
        """Decode-step counts per epoch: K, K, ..., remainder."""
        n, k = self.new_tokens - 1, self.epoch_steps
        return tuple([k] * (n // k) + ([n % k] if n % k else []))

    def _ctx(self):
        return (self.model_plan.activate() if self.model_plan is not None
                else contextlib.nullcontext())

    def make_prefill_fn(self, key):
        """(params, toks (B, S_p)) -> (grown cache, tok (B,1), pos)."""
        from repro.launch.serve import greedy_token, grow_cache
        from repro.models import transformer as T

        _, prompt_len, new_tokens = key
        cfg, plan, qmode = self.cfg, self.plan, self.qmode
        slots = prompt_len + new_tokens

        def fwd(params, toks):
            with self._ctx():
                logits, cache = T.prefill(params, cfg, plan, tokens=toks,
                                          qmode=qmode)
                cache = grow_cache(cache, prompt_len, slots)
                first = greedy_token(logits, cfg.vocab)
            return cache, first, jnp.asarray(prompt_len, jnp.int32)

        return fwd

    def make_epoch_fn(self, key, steps: int):
        """(params, cache, tok, pos) -> (cache, tok, pos, chunk (B, steps))."""
        from repro.launch.serve import make_decode_step

        cfg, plan, qmode = self.cfg, self.plan, self.qmode

        def fwd(params, cache, tok, pos):
            with self._ctx():
                step = make_decode_step(params, cfg, plan, qmode)
                (cache, tok, pos), toks = jax.lax.scan(
                    step, (cache, tok, pos), None, length=steps)
            return cache, tok, pos, toks[:, :, 0].T

        return fwd

    def decode_state_template(self, key, batch: int, emitted: int) -> dict:
        """Checkpoint-state structure rebuilt from config alone — nothing
        volatile survives a reboot, so restore cannot depend on any live
        cache object (shapes come from the stored arrays; the template
        supplies structure and dtypes)."""
        from repro.models import transformer as T

        _, prompt_len, new_tokens = key
        cache = T.init_cache(self.cfg, self.plan, batch,
                             prompt_len + new_tokens)
        return dict(cache=cache,
                    tok=np.zeros((batch, 1), np.int32),
                    pos=np.zeros((), np.int32),
                    toks=np.zeros((batch, emitted), np.int32))


class ResilientServeEngine(ServeEngine):
    """A :class:`ServeEngine` that survives an adversarial ``FaultPlan``.

    Parameters (beyond the base engine's)
    -------------------------------------
    fault_plan:      the seeded fault schedule (None -> fault-free, same
                     code path — the reference arm of bit-identity tests).
    checkpoint_dir:  where decode epoch checkpoints commit; None disables
                     micro-checkpointing (the volatile P=0 baseline: a kill
                     restarts the whole bucket from prefill).
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
                 checkpoint_dir: str | None = None, max_retries: int = 3,
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
        self.ckpt = (DecodeCheckpointer(checkpoint_dir)
                     if checkpoint_dir else None)
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
            recoveries=0,
            prefills=0, resumes=0, epochs=0, commits=0, commit_s=0.0,
            executed_steps=0, useful_steps=0, wasted_steps=0.0,
            energy_pj=0.0)

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
        survivors = []
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
            survivors.append(req)
        if self.ckpt is not None and len(survivors) != len(bucket.requests):
            # composition changed: the old tag can never be resumed
            self.ckpt.purge(self._bucket_tag(bucket))

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
        if self.ckpt is not None:
            # every outstanding checkpoint names the retired plan fingerprint
            self.ckpt.purge_all()

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
        if self.ckpt is not None:
            # outstanding checkpoints name the degraded plan fingerprint
            self.ckpt.purge_all()

    @staticmethod
    def _relative_energy(old, new) -> float:
        """new plan's modeled energy per step relative to old's (< 1 for a
        genuine bit-width downgrade; 1.0 when either lacks annotations)."""
        from repro.core.plan import plan_energy_pj

        def _e(r):
            plan = getattr(r, "model_plan", None) or getattr(r, "plan", None)
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
            self.stats["wasted_steps"] += ev.offset
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
            if self.ckpt is not None:
                self.ckpt.purge(self._bucket_tag(bucket))
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
        if getattr(self.runner, "supports_epochs", False):
            host = self._run_epochs(bucket, padded, dev)
        else:
            self._fault_gate("dispatch", dt=1.0)
            out = self._executable(bucket.key, padded)(self._params, dev)
            host = np.asarray(out)
            self.stats["executed_steps"] += 1
            self.stats["useful_steps"] += 1
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

    # -- epoch decode with micro-checkpoints --------------------------------

    def _bucket_tag(self, bucket: Bucket) -> str:
        fp = getattr(self.runner, "plan_fingerprint", lambda: None)()
        return DecodeCheckpointer.tag(
            (r.rid for r in bucket.requests), bucket.key, fp,
            getattr(self.runner, "epoch_steps", 0))

    def _prog(self, kind: str, key, padded: int, steps: int | None = None):
        fp = getattr(self.runner, "plan_fingerprint", lambda: None)()
        cache_key = ("resilient", kind, key, padded, steps, fp)
        if cache_key not in self._fns:
            if kind == "prefill":
                fn = self.runner.make_prefill_fn(key)
            else:
                fn = self.runner.make_epoch_fn(key, steps)
            self._fns[cache_key] = jax.jit(fn)
        return self._fns[cache_key]

    def _run_epochs(self, bucket: Bucket, padded: int, dev) -> np.ndarray:
        r = self.runner
        key = bucket.key
        schedule = r.epoch_schedule()
        tag = self._bucket_tag(bucket) if self.ckpt is not None else None
        start_epoch, state = 0, None
        if tag is not None:
            restored = self.ckpt.restore(
                tag, lambda emitted: r.decode_state_template(key, padded,
                                                             emitted))
            if restored is not None:
                committed, s = restored
                start_epoch = committed
                state = (s["cache"], s["tok"], s["pos"], s["toks"])
                self.stats["resumes"] += 1
        if state is None:
            self._fault_gate("prefill", dt=PREFILL_DT)
            cache, tok, pos = self._prog("prefill", key, padded)(self._params,
                                                                 dev)
            state = (cache, tok, pos, tok)
            self.stats["prefills"] += 1
            if tag is not None:
                self._commit(tag, 0, state)
        for e in range(start_epoch, len(schedule)):
            steps = schedule[e]
            self._fault_gate("decode", dt=float(steps))
            cache, tok, pos, toks = state
            cache, tok, pos, chunk = self._prog("epoch", key, padded,
                                                steps)(self._params, cache,
                                                       tok, pos)
            state = (cache, tok, pos, jnp.concatenate([toks, chunk], axis=1))
            self.stats["executed_steps"] += steps
            self.stats["epochs"] += 1
            if tag is not None:
                self._commit(tag, e + 1, state)
        host = np.asarray(state[3])
        self.stats["useful_steps"] += sum(schedule)
        if tag is not None:
            self.ckpt.purge(tag)
        return host

    def _commit(self, tag: str, epoch: int, state) -> None:
        cache, tok, pos, toks = state
        self.stats["commit_s"] += self.ckpt.commit(
            tag, epoch, dict(cache=cache, tok=tok, pos=pos, toks=toks),
            emitted=int(toks.shape[1]))
        self.stats["commits"] += 1

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
        plan = getattr(self.runner, "model_plan", None) \
            or getattr(self.runner, "plan", None)
        energy = 0.0
        if plan is not None and hasattr(plan, "layers"):
            from repro.core.plan import plan_energy_pj

            energy = plan_energy_pj(plan) * padded
            self.stats["energy_pj"] += energy
        if self.policy is not None:
            self.policy.record_dispatch(energy)
            self._maybe_degrade()
            self._maybe_recover()
