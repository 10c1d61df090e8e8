"""Chaos sweep: forward-progress efficiency on the REAL serving engine.

The analytic intermittency model (``pim/intermittent.forward_progress``,
paper Fig. 7) predicts how much useful work survives random power failures
as a function of MTBF and checkpoint period P.  This benchmark measures
the same quantity on the executing stack: an LM deployment from
``compiled.serve(resilience=...)`` — the continuous-batching engine —
serving generate requests under a seeded exponential
:class:`~repro.resilience.FaultPlan`, with decode epoch checkpoints every
K decode steps through the atomic checkpointer (K = P).  Both curves land
side by side in ``results/bench_resilience.json``.

Units: the engine's fault clock counts **dispatches** ("frames"): one per
prefill chunk and one per decode step.  A kill resumes from the last
commit (cold with P = 0), and the engine enqueues again, under its rid,
every request the restored schedule does not hold, so every prompt gets
one result.
Measured efficiency is the fault-free run's dispatches over total charged
work (dispatches executed, redone ones included, + the partial windows
the kills cut short + checkpoint writes priced in dispatch units, from
the measured commit/dispatch span-time ratio) —
``repro.fleet.measured_efficiency``.  The analytic arm runs
``forward_progress`` on the identical (MTBF, P) grid with the same
measured ``nv_write`` cost, over one wave of ``MAX_BATCH`` requests.

Hard assertions (the CI chaos gate, ``--fast``):
  * every request under chaos is bit-identical to the fault-free run;
  * no dead letters anywhere in the sweep;
  * at the HIGHEST fault rate, a bounded-retry CNN engine with a
    pre-compiled lower-bit fallback plan degrades instead of
    dead-lettering: the paper's accuracy-for-progress trade, executed.

Run standalone::

    PYTHONPATH=src python benchmarks/bench_resilience.py [--fast]

or via ``benchmarks/run.py`` (job name ``resilience``).
"""
from __future__ import annotations

import dataclasses
import json
import os
import shutil
import tempfile

import numpy as np

PROMPT_LEN = 8
NEW_TOKENS = 9            # 8 decode steps per request
MAX_BATCH = 4


def _build(fast: bool):
    import jax

    from repro import api
    from repro.configs import SINGLE, all_configs
    from repro.core.quant import PAPER_CONFIGS
    from repro.models import transformer as T
    from repro.models.cnn import init_cnn, svhn_cnn_spec

    cfg = dataclasses.replace(
        all_configs()["smollm-360m"].smoke(
            n_layers=2, d_model=64, n_heads=2, n_kv_heads=1, d_ff=128,
            vocab=64, head_dim=32),
        quant=PAPER_CONFIGS["w1a8"])
    params, _ = T.init_lm(jax.random.PRNGKey(0), cfg, SINGLE)
    lm = api.build(cfg, params=params).compile(batch_hints=(1, MAX_BATCH),
                                               prompt_len=PROMPT_LEN)
    n_req = 8 if fast else 16
    prompts = [np.random.RandomState(i).randint(0, cfg.vocab,
                                                size=(PROMPT_LEN,))
               .astype(np.int32) for i in range(n_req)]
    # the degrade arm: the svhn CNN at w1a8, with a cheaper w1a4 fallback
    spec = svhn_cnn_spec(8)
    cparams, _ = init_cnn(jax.random.PRNGKey(0), spec)
    cnn8, cnn4 = (api.build(spec, PAPER_CONFIGS[q], params=cparams,
                            img_hw=16).compile(batch_hints=(1, MAX_BATCH))
                  for q in ("w1a8", "w1a4"))
    imgs = [np.random.RandomState(i).uniform(size=(16, 16, 3))
            .astype(np.float32) for i in range(n_req)]
    return lm, prompts, cnn8, cnn4, imgs


def _engine(lm, k: int, ckdir: str, fault_plan=None):
    """A fresh resilient deployment: checkpoints every ``k`` decode steps,
    none for ``k == 0`` (the volatile P=0 baseline)."""
    from repro.resilience import ResilienceConfig

    return lm.serve(max_batch=MAX_BATCH, new_tokens=NEW_TOKENS,
                    resilience=ResilienceConfig(
                        fault_plan=fault_plan,
                        checkpoint_dir=ckdir if k else None,
                        epoch_steps=k or 1)).engine


def resilience_rows(fast: bool = False) -> list:
    from repro.fleet import measured_efficiency
    from repro.pim.intermittent import forward_progress
    from repro.resilience import DegradePolicy, FaultPlan, ResilienceConfig

    lm, prompts, cnn8, cnn4, imgs = _build(fast)
    n_waves = -(-len(prompts) // MAX_BATCH)
    mtbfs = (16.0, 48.0) if fast else (8.0, 16.0, 32.0, 64.0)
    periods = (0, 2, 4) if fast else (0, 1, 2, 4)
    root = tempfile.mkdtemp(prefix="bench_resilience_")
    rows = []
    mismatches = dead = 0
    try:
        # price one NV commit in dispatch units from a fault-free run's
        # spans (the same number feeds the analytic arm)
        eng = _engine(lm, 2, os.path.join(root, "price"))
        ref = [r.value for r in eng.serve(prompts)]   # compiles the programs
        useful = eng.stats["dispatches"]
        commits = eng.stats["commits"]
        rec = eng.record_spans()
        eng.serve(prompts)                        # warm: the priced run
        frames = useful // n_waves
        t_disp = sum(r["dt"] for n in ("lm.prefill_chunk", "lm.decode_step")
                     for r in rec.records.get(n, ()))
        t_commit = sum(r["dt"] for r in rec.records.get("lm.commit", ()))
        step_us = t_disp * 1e6 / useful
        nv_write_steps = (t_commit / commits) / (t_disp / useful)
        for k in periods:
            for mtbf in mtbfs:
                faults = FaultPlan(mtbf, seed=17)
                eng = _engine(lm, k, os.path.join(root, f"k{k}_{mtbf:g}"),
                              faults)
                got = [r.value for r in eng.serve(prompts)]
                bit_identical = (len(got) == len(ref) and all(
                    np.array_equal(g, r) for g, r in zip(got, ref)))
                mismatches += not bit_identical
                dead += len(eng.dead_letters)
                stats = dict(
                    eng.stats, useful_dispatches=useful,
                    wasted_steps=sum(e.offset for e in faults.log
                                     if e.kind in ("power_loss",
                                                   "device_drop")))
                measured = measured_efficiency(
                    stats, nv_write_steps if k else 0.0)
                # the measured arm is ONE seeded realization; the analytic
                # arm reports the model expectation (32 seeds) on the same
                # (MTBF, P, nv_write) point, over one wave of requests
                analytic = float(np.mean([
                    forward_progress(
                        n_frames=frames, frame_time_us=1.0, mtbf_us=mtbf,
                        checkpoint_period_frames=k,
                        nv_write_us=nv_write_steps if k else 0.0,
                        resume_us=1.0, seed=100 * i + 7)["efficiency"]
                    for i in range(32)]))
                rows.append(dict(
                    name=f"resilience_mtbf{mtbf:g}_k{k}", kind="chaos",
                    mtbf_steps=mtbf, checkpoint_period=k,
                    n_requests=len(prompts),
                    measured_efficiency=round(measured, 4),
                    analytic_efficiency=round(analytic, 4),
                    bit_identical=bit_identical,
                    dead_letters=len(eng.dead_letters),
                    power_losses=eng.stats["power_losses"],
                    commits=eng.stats["commits"],
                    dispatches=eng.stats["dispatches"],
                    useful_dispatches=useful,
                    wasted_steps=round(stats["wasted_steps"], 2)))

        # degraded-plan fallback on the CNN engine (the LM engine has no
        # degrade path), at the benchmark's highest fault rate: one fault
        # per image dispatch on average, harsher than any sweep cell.
        # After the degrade swap the cheaper w1a4 fallback sees a longer
        # energy-MTBF per dispatch and must keep serving with NO dead
        # letters
        worst = 1.0
        deg = cnn8.serve(resilience=ResilienceConfig(
            fault_plan=FaultPlan(worst, seed=23), max_retries=5,
            degrade=DegradePolicy(fault_window=4, fault_threshold=2)),
            fallback=cnn4, max_batch=1).engine
        res = deg.serve(imgs)
        rows.append(dict(
            name="resilience_degrade", kind="degrade", mtbf_steps=worst,
            n_requests=len(imgs), completed=len(res),
            degrades=deg.stats["degrades"], faults=deg.stats["faults"],
            dead_letters=len(deg.dead_letters),
            served_by_fallback=sum(v == 1
                                   for v in deg.result_runner.values()),
            energy_pj=round(deg.stats["energy_pj"], 1)))
        degrade_ok = (len(res) == len(imgs) and not deg.dead_letters
                      and deg.stats["degrades"] >= 1)
        rows.append(dict(
            name="resilience_summary", kind="summary",
            step_us=round(step_us, 2),
            nv_write_steps=round(nv_write_steps, 4),
            bit_identity_mismatches=mismatches,
            sweep_dead_letters=dead, degrade_ok=degrade_ok))
    finally:
        shutil.rmtree(root, ignore_errors=True)
    os.makedirs("results", exist_ok=True)
    with open("results/bench_resilience.json", "w") as f:
        json.dump(rows, f, indent=1, default=str)
    if fast and (mismatches or dead or not degrade_ok):
        raise SystemExit(
            f"chaos gate failed: {mismatches} bit-identity mismatches, "
            f"{dead} dead letters in sweep, degrade_ok={degrade_ok}")
    return rows


def main():
    import sys

    from repro.launch.jit_cache import enable_compile_cache

    enable_compile_cache()
    fast = "--fast" in sys.argv
    print("name,us_per_call,derived")
    for r in resilience_rows(fast=fast):
        us = r.get("measured_efficiency", r.get("degrades", 0))
        extra = {k: v for k, v in r.items() if k != "name"}
        print(f"{r['name']},{us},{json.dumps(extra)}")
    print("# full rows -> results/bench_resilience.json", file=sys.stderr)


if __name__ == "__main__":
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
    main()
