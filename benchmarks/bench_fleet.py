"""Fleet study: co-designed plans vs one-config-fits-all under harvest traces.

Simulates a heterogeneous fleet of energy-harvesting nodes (solar / RF /
thermal archetypes, ``repro.fleet.traces``) for one day each, prices every
node with its compiled plan's Table-II cost on its PIM target, and runs the
per-node co-design search (``repro.fleet.search``): pick each node's
(quant, target, checkpoint period) to maximize inferences/day subject to
its accuracy SLO.  Reported against the best single fleet-wide config.

Three CI gates (enforced in every mode; ``--fast`` shrinks the fleet):

  * determinism — the entire seeded study runs TWICE and the serialized
    aggregate reports must match bit-for-bit (same seed -> same bytes);
  * validation — one node's derived outage schedule replays through a REAL
    continuous LM engine (the resilient facade) and the simulator's
    engine-accounting mirror must agree: integer work counters exactly,
    float accounting within 1e-6 (the DESIGN.md §14 contract);
  * co-design win — aggregate inferences/day must beat the baseline while
    every node meets its SLO.

Run standalone::

    PYTHONPATH=src python benchmarks/bench_fleet.py [--fast]

or via ``benchmarks/run.py`` (job name ``fleet_study``).  Full results ->
``results/bench_fleet.json``.
"""
from __future__ import annotations

import json
import os
import shutil
import tempfile

SEED = 0          # fleet trace seed
SLO_SEED = 1      # per-node accuracy-SLO draw
RESUME_US = 26_000.0   # post-outage plan reload (cf. plan_resume_study)

# smoke-LM replay geometry
N_REQUESTS = 8
NEW_TOKENS = 7
EPOCH_STEPS = 2
MAX_BATCH = 4
VALIDATE_OUTAGES = 6
TOL = 1e-6


def _study(n_nodes: int):
    """One full seeded study; pure function of (n_nodes, SEED, SLO_SEED)."""
    from repro.fleet import (assign_slos, codesign, fleet_report,
                             frame_cost_table, generate_fleet, make_trace)

    specs = generate_fleet(n_nodes, seed=SEED)
    traces = [make_trace(s) for s in specs]
    slos = assign_slos(n_nodes, seed=SLO_SEED)
    costs = frame_cost_table()
    out = codesign(traces, slos, costs=costs,
                   node_kw=dict(resume_us=RESUME_US))
    results = out.pop("results")
    fleet = fleet_report(results, specs)
    report = dict(
        config=dict(n_nodes=n_nodes, seed=SEED, slo_seed=SLO_SEED,
                    resume_us=RESUME_US),
        fleet=fleet,
        codesign=dict(
            inferences_per_day=out["inferences_per_day"],
            baseline=out["baseline"],
            win_vs_baseline=out["win_vs_baseline"],
            slo_violations=out["slo_violations"],
            pareto=out["pareto"],
            candidates=out["candidates"]),
    )
    return report, specs, traces, out["assignments"], results


def _validate(traces, assignments, results):
    """Replay the busiest node's outage schedule through the live engine."""
    from repro.fleet import (NodeConfig, frame_cost_table, live_validation,
                             predict_engine_stats, rescale_outages,
                             simulate_node)
    from repro.resilience import FaultPlan

    # the node with the most outages gives the densest replay schedule
    idx = max(range(len(results)), key=lambda i: results[i]["failures"])
    a = assignments[idx]
    e, lat = frame_cost_table(quants=(a["quant"],),
                              targets=(a["target"],))[(a["quant"],
                                                       a["target"])]
    cfg = NodeConfig(node_id=a["node_id"], quant=a["quant"],
                     target=a["target"], period=a["period"],
                     frame_energy_uj=e, frame_time_us=lat,
                     resume_us=RESUME_US)
    r = simulate_node(traces[idx], cfg, collect_outages=VALIDATE_OUTAGES)
    outages = r["outage_frames"]
    # compress the day-scale schedule onto ~80% of the replay's fault-free
    # work so the kills land mid-decode, not all at t=0
    engine_work = 0.8 * predict_engine_stats(
        FaultPlan(None), n_requests=N_REQUESTS, new_tokens=NEW_TOKENS,
        epoch_steps=EPOCH_STEPS, max_batch=MAX_BATCH)["dispatches"]
    sched = (rescale_outages(outages, outages[-1], engine_work)
             if outages else [])
    ckdir = tempfile.mkdtemp(prefix="fleet_val_")
    try:
        v = live_validation(sched, checkpoint_dir=ckdir,
                            n_requests=N_REQUESTS, new_tokens=NEW_TOKENS,
                            epoch_steps=EPOCH_STEPS, max_batch=MAX_BATCH,
                            tol=TOL)
    finally:
        shutil.rmtree(ckdir, ignore_errors=True)
    v["node_id"] = a["node_id"]
    v["replayed_outages"] = len(sched)
    return v


def fleet_rows(fast: bool = False):
    n_nodes = 64 if fast else 1000
    report, specs, traces, assignments, results = _study(n_nodes)

    # determinism gate: same seed -> bit-for-bit identical report bytes
    report2 = _study(n_nodes)[0]
    blob = json.dumps(report, sort_keys=True)
    deterministic = blob == json.dumps(report2, sort_keys=True)
    report["determinism"] = dict(ok=deterministic, runs_compared=2)

    validation = _validate(traces, assignments, results)
    report["validation"] = validation
    report["assignments"] = assignments

    os.makedirs("results", exist_ok=True)
    with open("results/bench_fleet.json", "w") as f:
        json.dump(report, f, indent=1, default=str)

    cd, fl = report["codesign"], report["fleet"]
    rows = [dict(name="fleet_aggregate", **fl_no_arch(fl)),
            *[dict(name=f"fleet_{k}", **v)
              for k, v in sorted(fl.get("archetypes", {}).items())],
            dict(name="fleet_codesign",
                 inferences_per_day=cd["inferences_per_day"],
                 baseline_inferences_per_day=cd["baseline"][
                     "inferences_per_day"],
                 baseline=f"{cd['baseline']['quant']}/"
                          f"{cd['baseline']['target']}/"
                          f"P{cd['baseline']['period']}",
                 win_vs_baseline=round(cd["win_vs_baseline"], 4),
                 slo_violations=cd["slo_violations"],
                 pareto_points=len(cd["pareto"])),
            dict(name="fleet_validation", ok=validation["ok"],
                 node_id=validation["node_id"],
                 replayed_outages=validation["replayed_outages"],
                 efficiency_predicted=validation["efficiency_predicted"],
                 efficiency_measured=validation["efficiency_measured"],
                 tol=validation["tol"]),
            dict(name="fleet_determinism", ok=deterministic,
                 runs_compared=2)]

    gates = dict(determinism=deterministic, validation=validation["ok"],
                 win=cd["win_vs_baseline"] > 1.0,
                 slo=cd["slo_violations"] == 0)
    if not all(gates.values()):
        raise SystemExit(f"fleet gate failed: {gates}")
    return rows


def fl_no_arch(fl: dict) -> dict:
    return {k: v for k, v in fl.items() if k != "archetypes"}


def main():
    import sys

    from repro.launch.jit_cache import enable_compile_cache

    enable_compile_cache()
    fast = "--fast" in sys.argv
    print("name,us_per_call,derived")
    for r in fleet_rows(fast=fast):
        key = r.get("inferences_per_day", r.get("ok", 0))
        extra = {k: v for k, v in r.items() if k != "name"}
        print(f"{r['name']},{key},{json.dumps(extra)}")
    print("# full rows -> results/bench_fleet.json", file=sys.stderr)


if __name__ == "__main__":
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
    main()
