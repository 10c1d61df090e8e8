"""Shared pieces of one run: the manifest, lookup by name, the device,
the compile cache, the host clock's bookkeeping, and tail arithmetic."""
from __future__ import annotations

import bisect
import dataclasses
import importlib.util
import json
import math
import os
import time
from typing import Any, Callable

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO_ROOT = os.path.dirname(os.path.dirname(BENCH_DIR))


class BenchError(RuntimeError):
    """A run that cannot produce a result (no chip, unknown name, ...)."""


# ---------------------------------------------------------------------------
# Manifest and lookup by name
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Manifest:
    root: str                  # directory that holds BENCHMARK.json
    data: dict

    @classmethod
    def load(cls, path: str) -> "Manifest":
        with open(path) as f:
            return cls(os.path.dirname(os.path.abspath(path)), json.load(f))

    def search_dirs(self) -> list[str]:
        """Benchmark directories to look names up in: the manifest's own
        ``paths`` first, then this harness's directory."""
        dirs = [os.path.join(self.root, p) for p in self.data.get("paths", [])]
        if BENCH_DIR not in dirs:
            dirs.append(BENCH_DIR)
        return dirs

    def find(self, kind: str, name: str, ext: str) -> str:
        for d in self.search_dirs():
            path = os.path.join(d, kind, name + ext)
            if os.path.isfile(path):
                return path
        raise BenchError(f"no {kind}/{name}{ext} under {self.search_dirs()}")

    def cell(self, name: str) -> dict:
        for w in self.data["workloads"]:
            if w["name"] == name:
                return w
        raise BenchError(f"no workload {name!r} in the manifest")

    def config(self, name: str) -> dict:
        for c in self.data["configs"]:
            if c["name"] == name:
                with open(os.path.join(self.root, c["file"])) as f:
                    return dict(json.load(f), name=name)
        raise BenchError(f"no config {name!r} in the manifest")

    def traffic(self, name: str) -> dict:
        with open(self.find("traffic", name, ".json")) as f:
            return dict(json.load(f), name=name)

    def metrics_for(self, cell: str, trace: bool) -> list[dict]:
        """The metrics this cell reports: its end-to-end ones untraced, its
        per-layer ones traced.  A metric without ``workloads`` belongs to
        every cell that reports the end-to-end metric it moves."""
        e2e = [m for m in self.data["end_to_end"]
               if "workloads" not in m or cell in m["workloads"]]
        if not trace:
            return e2e
        names = {m["name"] for m in e2e}
        return [m for m in self.data["per_layer"]
                if (cell in m["workloads"] if "workloads" in m
                    else m["moves"] in names)]


def load_module(path: str):
    """Import one file by path (metric readers and kernel files carry
    names with dots and dashes, which ``import`` cannot spell)."""
    name = "bench_" + os.path.relpath(path, BENCH_DIR).replace(
        os.sep, "_").replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_peaks(device_kind: str) -> dict:
    with open(os.path.join(BENCH_DIR, "roofline", "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table["devices"]:
        raise BenchError(f"no peaks for device kind {device_kind!r}; "
                         f"known: {sorted(table['devices'])}")
    return table["devices"][device_kind]


# ---------------------------------------------------------------------------
# Device, compile cache, compile statistics
# ---------------------------------------------------------------------------

def require_devices(chips: int):
    """The accelerator the cell asks for, or BenchError (no fallback)."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX platform is {devices[0].platform!r}")
    if len(devices) < chips:
        raise BenchError(f"cell needs {chips} chips, JAX sees {len(devices)}")
    return devices[:chips]


def enable_cache(root: str) -> str:
    """JAX's persistent compilation cache at a fixed directory inside the
    checkout, holding every program however fast it compiled."""
    import jax

    path = os.path.join(root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileStats:
    """Backend-compile seconds and persistent-cache hits, from JAX's own
    monitoring events (a copy of ``chip_smoke.CompileStats``)."""

    def __init__(self):
        import jax.monitoring as mon

        self.seconds, self.compiles, self.hits = 0.0, 0, 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.compiles += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snapshot(self) -> dict:
        return dict(compiles=self.compiles, compile_s=self.seconds,
                    cache_hits=self.hits)


def memory_peak_bytes(devices) -> int | None:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def seed32(seed: int, salt: int = 0) -> int:
    """A 32-bit key from any whole-number seed (driver seeds exceed 2**31)."""
    import numpy as np

    return int(np.random.SeedSequence([abs(int(seed)), salt]
                                      ).generate_state(1)[0])


# ---------------------------------------------------------------------------
# What a run hands to the metric readers
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Run:
    """Everything the readers may read.  Times are ``time.perf_counter``
    seconds; the window is ``[t0, t1]``."""

    cell: dict
    config: dict
    traffic: dict
    t0: float = 0.0
    t1: float = 0.0
    setup_s: float = 0.0
    requests: list = dataclasses.field(default_factory=list)
    spans: dict = dataclasses.field(default_factory=dict)
    counters: dict = dataclasses.field(default_factory=dict)
    trace: Any = None           # harness.trace.Trace in traced runs
    tracer: Any = None          # harness.trace.Tracer during the window
    peaks: dict = dataclasses.field(default_factory=dict)

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0

    def span(self, name: str, start: float, end: float, **info) -> None:
        self.spans.setdefault(name, []).append(dict(t=start, dt=end - start,
                                                    **info))


def record_engine_spans(run: Run, engine, tracer):
    """In a traced run, turn on the engine's own span recorder
    (``record_spans()``); returns a function that merges what it recorded
    into ``run.spans``, where the trace's host spans come from."""
    rec = engine.record_spans() if tracer.enabled else None

    def merge() -> None:
        if rec is not None:
            for name, recs in rec.records.items():
                run.spans.setdefault(name, []).extend(recs)

    return merge


def dispatched_batches(run: Run) -> list[int]:
    """The padded batch of each CNN bucket whose program ran inside the
    traced window.  Those are the buckets the engine dispatched
    (``serve.dispatch``, recorded in traced runs) between the window's
    two marker waits (``run.trace_span``): each device runs its programs
    in the order they were queued, so they run after the first marker and
    before the last.  A bucket's batch is that of the harness's
    ``collate`` that staged it, the last one before its dispatch."""
    lo, hi = run.trace_span
    staged = sorted((s["t"], s["batch"]) for s in run.spans.get("collate", []))
    starts = [t for t, _ in staged]
    out = []
    for d in run.spans.get("serve.dispatch", []):
        i = bisect.bisect_right(starts, d["t"])
        if lo <= d["t"] <= hi and i:
            out.append(staged[i - 1][1])
    return out


def timed(run: Run, name: str, fn: Callable, info: Callable | None = None):
    """Wrap ``fn`` so each call lands in ``run.spans[name]`` (host clock).
    Each call first lets the tracer start on time, however long the call
    into the system that contains it."""

    def wrapper(*args, **kwargs):
        t = time.perf_counter()
        if run.tracer is not None:
            run.tracer.poll(t)
        out = fn(*args, **kwargs)
        extra = info(*args, **kwargs) if info is not None else {}
        run.span(name, t, time.perf_counter(), **extra)
        return out

    return wrapper


class span:
    """A phase of the driver's loop (``submit``, ``pump``, ...), kept in
    ``run.spans`` while a trace is being taken, for naming idle gaps."""

    def __init__(self, run: Run, name: str):
        self.run, self.name = run, name

    def __enter__(self):
        self.t = time.perf_counter()

    def __exit__(self, *exc):
        tracer = self.run.tracer
        if tracer is not None and tracer.t_start is not None:
            self.run.span("phase:" + self.name, self.t, time.perf_counter())


# ---------------------------------------------------------------------------
# Tail and spread arithmetic
# ---------------------------------------------------------------------------

def percentile(xs, q: float) -> float | None:
    """Linear interpolation between closest ranks (numpy's default)."""
    xs = sorted(xs)
    if not xs:
        return None
    r = (len(xs) - 1) * q / 100.0
    lo = math.floor(r)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (r - lo)


def mean(xs) -> float | None:
    xs = list(xs)
    return sum(xs) / len(xs) if xs else None


def spread(values) -> float:
    """Interquartile distance over the median, as the bound rule reads it
    (``statistics.quantiles(values, n=4)``)."""
    import statistics

    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / med
