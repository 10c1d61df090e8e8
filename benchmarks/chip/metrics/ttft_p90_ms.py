"""90th percentile of time to first token, from scheduled arrival, over
every request that arrived in the window; a request with no token by the
window's end enters with its wait so far."""
from harness.common import percentile


def read(run):
    ttft = [(r["times"][0] if r["times"] else run.t1) - r["t_arrive"]
            for r in run.requests if run.t0 <= r["t_arrive"] <= run.t1]
    v = percentile(ttft, 90)
    return None if v is None else v * 1e3
