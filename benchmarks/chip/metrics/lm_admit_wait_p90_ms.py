"""90th percentile of the wait for a slot (submit to admission, the
engine's own stamp), over requests that arrived in the window; one not
admitted by the window's end enters with its wait so far."""
from harness.common import percentile


def read(run):
    waits = [(r["t_admit"] if r["t_admit"] is not None
              and r["t_admit"] <= run.t1 else run.t1) - r["t_arrive"]
             for r in run.requests if run.t0 <= r["t_arrive"] <= run.t1]
    v = percentile(waits, 90)
    return None if v is None else v * 1e3
