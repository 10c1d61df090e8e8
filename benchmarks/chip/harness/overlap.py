"""Device idle time put down to what the host was doing, by overlap.

The engine's own spans (``serve.*``, from ``ServeEngine.record_spans``)
reach ``run.trace.data["host"]`` on the device's clock, as the harness's
spans do.  ``idle_in(run, name)`` is the share of the traced window in
which the device ran nothing while the host was inside a span of that
name: each idle interval counts by how much of it the spans cover, not
by which span holds its midpoint (``Trace.breakdown``'s rule, which
gives a whole gap to one span).
"""
from __future__ import annotations

from .trace import _union

# The host's and the device's marker-bounded windows may differ by this
# much before the two clocks count as misaligned.
CLOCK_SLACK_S = 1e-3


def _gaps(busy: list, lo: float, hi: float) -> list:
    """The complement of merged ``busy`` intervals inside [lo, hi]."""
    edges = [lo] + [x for s, e in busy for x in (s, e)] + [hi]
    return [[a, b] for a, b in zip(edges[0::2], edges[1::2]) if b > a]


def _overlap(a: list, b: list) -> float:
    """Total length common to two sorted lists of disjoint intervals."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            total += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_in(run, name: str) -> float | None:
    """% of the traced window idle on the device while the host was in a
    ``name`` span, averaged over the devices that ran anything.  None
    without a trace, without such spans (a program that records none), or
    when the host's window (``run.trace_span``) and the device's differ by
    more than ``CLOCK_SLACK_S``: a misaligned clock gives no number."""
    trace = run.trace
    if trace is None:
        return None
    t_start, t_stop = run.trace_span
    if abs((t_stop - t_start) - trace.window_s) > CLOCK_SLACK_S:
        return None
    host = _union(((s, s + d) for n, s, d in trace.data["host"] if n == name),
                  trace.lo, trace.hi)
    per = [_overlap(_gaps(iv, trace.lo, trace.hi), host)
           for iv in trace.busy().values() if iv]
    if not host or not per:
        return None
    return 100.0 * sum(per) / len(per) / (trace.hi - trace.lo)
