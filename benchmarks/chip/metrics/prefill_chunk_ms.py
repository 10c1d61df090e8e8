"""Mean host time of one ``(1, chunk)`` prefill step call in the window."""
from harness.common import mean


def read(run):
    v = mean(s["dt"] for s in run.spans.get("prefill_chunk", [])
             if run.t0 <= s["t"] <= run.t1)
    return None if v is None else v * 1e3
