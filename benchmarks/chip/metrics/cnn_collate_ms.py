"""Mean host time of the CNN runner's ``collate`` (stacking a bucket's
images into one batch) per bucket, in the window."""
from harness.common import mean


def read(run):
    v = mean(s["dt"] for s in run.spans.get("collate", [])
             if run.t0 <= s["t"] <= run.t1)
    return None if v is None else v * 1e3
