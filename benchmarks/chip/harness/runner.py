"""One run of one cell: set up, warm, measure, check, print one line.

The order inside a run:

1. find the cell, its configuration and traffic by name; refuse without
   the chips the cell asks for; turn on the compile cache in the checkout;
2. set-up (``setup_s`` counts it, from process start): weights from the
   seed, plan compile, engine, every shape the traffic dispatches, the
   open loop's warm-in under the window's own load, and a closed LM
   loop's first requests admitted and prefilled;
3. the window: ``--seconds`` of traffic; in a traced run the profiler
   records its last ``trace_s`` seconds, which the host-clock readers
   then leave out (the profiler slows the host several times over);
4. after it: ``memory_peak_bytes``, the metric readers, the system freed;
5. the check: a sample of what the window served against the plain
   reference (and, with ``--control 1``, the control's reading);
6. each compared number beside its limit on stderr, then the result line
   as the last line of stdout.
"""
from __future__ import annotations

import argparse
import gc
import importlib
import json
import os
import shutil
import sys
import time

from .common import (BenchError, CompileStats, Manifest, Run, enable_cache,
                     load_module, load_peaks, memory_peak_bytes,
                     require_devices)
from .trace import Trace, Tracer, host_spans
from .trace import load as load_trace


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def parse(argv) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="1: also read the control's number (not in "
                         "benchmark runs)")
    ap.add_argument("--rate", type=float, default=None,
                    help="override an open mix's arrival rate (knee sweeps)")
    ap.add_argument("--clients", type=int, default=None,
                    help="override a closed mix's callers (knee sweeps)")
    ap.add_argument("--dump", default=None,
                    help="directory for the run's records and trace summary")
    return ap.parse_args(argv)


def main(argv=None, *, t_process: float, manifest: str | None = None,
         require_tpu: bool = True, peaks: dict | None = None,
         fault=None) -> int:
    """``require_tpu=False``, ``peaks`` and ``fault`` exist for the tests:
    they run a tiny cell on the CPU, with a fault planted in the system."""
    args = parse(argv)
    try:
        result = _run(args, t_process, manifest, require_tpu, peaks, fault)
    except BenchError as e:
        log(f"bench: no result: {e}")
        return 1
    print(json.dumps(result), flush=True)
    return 0


def _run(args, t_process, manifest, require_tpu, peaks, fault) -> dict:
    from .common import REPO_ROOT

    m = Manifest.load(manifest or os.path.join(REPO_ROOT, "BENCHMARK.json"))
    cell = m.cell(args.workload)
    config = m.config(cell["config"])
    traffic = m.traffic(cell["traffic"])
    if args.rate is not None:
        traffic["rate_per_s"] = args.rate
    if args.clients is not None:
        traffic["clients"] = args.clients
    src = os.path.join(REPO_ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise BenchError(f"system under test not found at {src}")
    sys.path.insert(0, src)
    import jax

    if require_tpu:
        devices = require_devices(int(cell["chips"]))
        peaks = load_peaks(devices[0].device_kind)
    else:
        devices = jax.devices()[: int(cell["chips"])]
    cache = enable_cache(m.root)
    stats = CompileStats()
    driver = importlib.import_module(f"{__package__}.{config['driver']}")
    reference = load_module(m.find("reference", config["reference"], ".py"))
    trace = bool(args.trace)

    run = Run(cell, config, traffic, peaks=peaks)
    system = driver.System(config, traffic, args.seed, run, reference)
    if fault is not None:
        fault(system)
    trace_dir = os.path.join(os.environ.get("TMPDIR", "/tmp"),
                             f"bench-trace-{os.getpid()}")
    tracer = Tracer(trace, trace_dir)
    system.warm()
    setup_compile = stats.snapshot()
    log(f"bench: compile cache {cache}; set-up compiles "
        f"{setup_compile['compiles']} programs in "
        f"{setup_compile['compile_s']:.3f} s, "
        f"{setup_compile['cache_hits']} persistent-cache hits")

    warm_in = float(traffic.get("warm_in_s", 0.0))
    t_start = time.perf_counter()
    run.t0 = t_start + warm_in
    run.t1 = run.t0 + args.seconds
    tracer.t_from = run.t1 - float(traffic.get("trace_s", 3.0))
    run.tracer = tracer
    system.drive(t_start, run.t1, tracer)
    tracer.finish()
    run.setup_s = run.t0 - t_process
    run.tracer = None
    in_window = stats.compiles - setup_compile["compiles"]
    mem = memory_peak_bytes(devices)
    run.counters = system.counters()
    if trace:
        if tracer.t_start is None:
            raise BenchError("the window ended before the trace started")
        data = load_trace(trace_dir)
        data["host"] = host_spans(run.spans, tracer.t_start, data["window_ns"])
        run.trace = Trace(data)
        shutil.rmtree(trace_dir, ignore_errors=True)
        log(f"bench: traced window {run.trace.window_s:.6f} s on the "
            f"device, {tracer.t_stop - tracer.t_start:.6f} s on the host")
        # the profiler slows the host; host readers take the untraced part
        run.t1 = tracer.t_start
    run.find = m.find
    run.trace_span = (tracer.t_start, tracer.t_stop)

    metrics = {}
    for spec in m.metrics_for(cell["name"], trace):
        reader = load_module(m.find("metrics", spec["name"], ".py"))
        value = reader.read(run)
        if value is not None:
            metrics[spec["name"]] = dict(value=value, unit=spec["unit"])
    device = dict(platform=devices[0].platform, kind=devices[0].device_kind,
                  count=len(devices), memory_peak_bytes=mem)
    out = dict(attempted=0, failed=0, metrics=metrics, device=device)
    if trace:
        device.update(busy_s=run.trace.busy_s(), window_s=run.trace.window_s)
        out["breakdown"] = run.trace.breakdown()
    arrivals = getattr(system, "arrivals", None)
    gen = arrivals.report() if arrivals is not None else {}
    if args.dump:
        _dump(args.dump, run, gen)

    # the check, with the system's state freed first
    sample = system.sample(config["check"], args.seed)
    attempted = _attempted(run)
    failed = int(getattr(system, "nonfinite", 0))
    system.close()
    del system
    gc.collect()
    t_check = time.perf_counter()
    checked = driver.check(config, args.seed, sample, reference,
                           control=bool(args.control))
    checked["check_s"] = time.perf_counter() - t_check
    limits = config["check"]["limits"]
    checks = {k: dict(value=checked[k], limit=float(v))
              for k, v in limits.items()}
    correct = (bool(sample) and in_window == 0 and failed == 0
               and all(c["value"] <= c["limit"] for c in checks.values()))
    log(f"bench: window {args.seconds} s, set-up {run.setup_s:.3f} s, "
        f"{in_window} compiles inside the window, generator {gen}")
    log(f"bench: counters {json.dumps(run.counters, default=str)}")
    for k, v in checked.items():
        if k not in checks:
            log(f"bench: check {k} {v}")
    for k, c in checks.items():
        log(f"check: {k} {c['value']!r} limit {c['limit']!r}")
    out.update(correct=correct, attempted=attempted, failed=failed,
               generator=gen)
    for k, v in checked.items():
        if k.startswith("control_"):
            out[k] = v
    out["checks"] = checks
    return dict(correct=out.pop("correct"), **out)


def _attempted(run: Run) -> int:
    """Requests the window served: arrived by its end and not answered
    before its start (one whose answer never came back is charged by the
    latency metrics)."""
    def end(r):
        if r.get("t_done") is not None:
            return r["t_done"]
        return r["times"][-1] if r.get("times") else None

    return sum(1 for r in run.requests if r["t_arrive"] <= run.t1
               and (end(r) is None or end(r) >= run.t0))


def _dump(path: str, run: Run, gen: dict) -> None:
    os.makedirs(path, exist_ok=True)
    with open(os.path.join(path, "records.json"), "w") as f:
        json.dump(dict(t0=run.t0, t1=run.t1, setup_s=run.setup_s,
                       requests=run.requests, counters=run.counters,
                       generator=gen,
                       spans={k: v[:2000] for k, v in run.spans.items()}),
                  f, default=str)
    if run.trace is not None:
        with open(os.path.join(path, "trace.json"), "w") as f:
            json.dump(run.trace.data, f)
