"""99th percentile of the gaps between a request's consecutive tokens,
over every gap that ends in the window."""
from harness.common import percentile


def read(run):
    gaps = [b - a for r in run.requests
            for a, b in zip(r["times"], r["times"][1:])
            if run.t0 <= b <= run.t1]
    v = percentile(gaps, 99)
    return None if v is None else v * 1e3
