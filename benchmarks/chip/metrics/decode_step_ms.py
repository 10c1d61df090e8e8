"""Mean host time of one ``(slots, 1)`` decode step call in the window,
the copy of its logits to the host included."""
from harness.common import mean


def read(run):
    v = mean(s["dt"] for s in run.spans.get("decode_step", [])
             if run.t0 <= s["t"] <= run.t1)
    return None if v is None else v * 1e3
