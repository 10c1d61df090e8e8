"""The decode step's share of the chip's peak: model operations of every
live row of the window's decode steps, at their peaks, over the steps'
summed host time."""
from harness.common import load_module


def read(run):
    steps = [s for s in run.spans.get("decode_step", [])
             if run.t0 <= s["t"] <= run.t1]
    if not steps:
        return None
    ops = load_module(run.find("roofline", "lm_ops", ".py"))
    model = run.config
    t_peak = sum(ops.token_time_at_peak(model, c, run.peaks)
                 for s in steps for c in s["ctx"])
    return 100.0 * t_peak / sum(s["dt"] for s in steps)
