"""Profiler trace of the end of the window, and its reduction.

``Tracer`` starts JAX's profiler ``trace_s`` before the window's end and
stops it once the window is over, so that writing the trace out costs the
window nothing.  It starts between two calls into the system, or inside
one (the drivers poll it from their wrappers).  Only the device is
traced: host tracing slows the host path it would observe (the H2D
relayout of an image batch) about tenfold.  A tiny marker program runs
on the device right after the start and right before the stop, and the
host waits for each: the two executions' ends bound the traced window on
the device's clock, and the host's clock read as each wait returns
bounds it on the host's, so that both ends are taken alike (the stop's
marker runs after whatever the device still had queued).  The pair maps
the harness's own host spans (``run.spans``) onto the device's clock.

``load`` reads the ``.xplane.pb`` (``jax.profiler.ProfileData``) into a
plain dict, which is also the form of the small recorded trace the
tests read:

    {"window_ns": [start, end],              # device clock
     "ops": {device plane: [[name, start_ns, dur_ns], ...]},
     "host": [[span name, start_ns, dur_ns], ...]}

``ops`` keeps the device planes' line of single operations (``XLA
Ops``), whose union is the time the device was busy.
"""
from __future__ import annotations

import glob
import os
import time

OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
MARK = "bench_trace_mark"
CONTAINERS = ("while", "conditional", "call.")


def bench_trace_mark(x):
    return x + 1


class Tracer:
    """Made in set-up (the marker compiles then); ``t_from`` is set once
    the window's end is known."""

    def __init__(self, enabled: bool, trace_dir: str):
        self.enabled, self.dir = enabled, trace_dir
        self.t_from = float("inf")
        self.t_start = self.t_stop = None
        if enabled:
            import jax
            import jax.numpy as jnp

            self._mark_fn = jax.jit(bench_trace_mark)
            self._x = jnp.zeros((8, 128), jnp.float32)
            self._mark()                  # compiled in set-up, not in the trace

    def _mark(self) -> None:
        self._mark_fn(self._x).block_until_ready()

    def poll(self, now: float) -> None:
        if not self.enabled or self.t_start is not None or now < self.t_from:
            return
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._mark()
        self.t_start = time.perf_counter()

    def finish(self) -> None:
        if self.t_start is None or self.t_stop is not None:
            return
        import jax

        self._mark()
        self.t_stop = time.perf_counter()
        jax.profiler.stop_trace()


def load(trace_dir: str) -> dict:
    """The device ops of the traced window, bounded by the ends of the
    first and the last marker executions."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no trace under {trace_dir}")
    pd = ProfileData.from_file(files[-1])
    ops, marks = {}, []
    for plane in pd.planes:
        if not plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            if line.name == MODULE_LINE:
                marks += [(ev.start_ns, ev.start_ns + ev.duration_ns)
                          for ev in line.events if MARK in ev.name]
            elif line.name == OP_LINE:
                ops.setdefault(plane.name, []).extend(
                    [ev.name, ev.start_ns, ev.duration_ns]
                    for ev in line.events)
    if len(marks) < 2:
        raise ValueError(f"trace holds {len(marks)} window marks, not 2")
    marks.sort()
    return dict(window_ns=[marks[0][1], marks[-1][1]], ops=ops, host=[])


def host_spans(spans: dict, t_start: float, window_ns) -> list:
    """The harness's host spans inside the traced window, on the device's
    clock (the first marker's end is the host's ``t_start``)."""
    lo, hi = window_ns
    out = []
    for name, recs in spans.items():
        for r in recs:
            s = lo + (r["t"] - t_start) * 1e9
            if lo <= s <= hi:
                out.append([name.replace("phase:", ""), s, r["dt"] * 1e9])
    return sorted(out, key=lambda h: h[1])


# ---------------------------------------------------------------------------
# Reduction
# ---------------------------------------------------------------------------

def _union(intervals, lo: float, hi: float) -> list:
    """Merged [start, end) intervals clipped to [lo, hi]."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


class Trace:
    """Reductions of a loaded trace; every time in seconds."""

    def __init__(self, data: dict):
        self.data = data
        self.lo, self.hi = data["window_ns"]

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    def busy(self) -> dict:
        """Per device plane, the merged intervals in which an op ran."""
        return {plane: _union(((s, s + d) for _, s, d in evs), self.lo,
                              self.hi)
                for plane, evs in self.data["ops"].items()}

    def busy_s(self) -> float | None:
        """Busy seconds averaged over the devices that ran anything."""
        per = [sum(e - s for s, e in iv) * 1e-9
               for iv in self.busy().values() if iv]
        return sum(per) / len(per) if per else None

    def _events(self):
        for evs in self.data["ops"].values():
            for name, s, d in evs:
                if s >= self.lo and s + d <= self.hi:
                    yield name, s, d

    def matching(self, match) -> tuple[int, float]:
        """(calls, device seconds) of the ops ``match(name)`` takes; an op
        counts where it lies wholly inside the window."""
        n, t = 0, 0.0
        cache: dict = {}
        for name, _, d in self._events():
            ok = cache.get(name)
            if ok is None:
                ok = cache[name] = bool(match(name))
            if ok:
                n += 1
                t += d * 1e-9
        return n, t

    def breakdown(self, top: int = 10) -> dict:
        """The device ops that took most time, and the idle gaps by what
        the host was doing (the harness's spans over the gap's middle)."""
        per: dict = {}
        for name, _, d in self._events():
            short = name.split(" = ", 1)[0].lstrip("%")
            if short.startswith(CONTAINERS):
                continue              # their body's ops are listed too
            per[short] = per.get(short, 0.0) + d * 1e-9
        ops = sorted(per.items(), key=lambda kv: -kv[1])[:top]
        host = sorted((s, s + d, n) for n, s, d in self.data["host"])
        gaps: dict = {}
        for iv in self.busy().values():
            edges = [self.lo] + [x for s, e in iv for x in (s, e)] + [self.hi]
            for a, b in zip(edges[0::2], edges[1::2]):
                if b <= a:
                    continue
                mid = (a + b) / 2
                what = "no span"
                for s, e, n in host:
                    if s <= mid <= e:
                        what = n
                    elif s > mid:
                        break
                gaps[what] = gaps.get(what, 0.0) + (b - a) * 1e-9
        idle = sorted(gaps.items(), key=lambda kv: -kv[1])[:top]
        return dict(device_ops=[[n, t] for n, t in ops],
                    idle_gaps=[[n, t] for n, t in idle])
