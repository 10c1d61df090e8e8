"""99th percentile of image latency, scheduled arrival to logits on the
host, over every image that arrived in the window."""
from harness.common import percentile


def read(run):
    lat = [r["t_done"] - r["t_arrive"] for r in run.requests
           if run.t0 <= r["t_arrive"] <= run.t1]
    v = percentile(lat, 99)
    return None if v is None else v * 1e3
