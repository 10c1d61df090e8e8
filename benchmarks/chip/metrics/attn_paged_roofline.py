"""``attn_paged``'s share of its roofline: the least time of every call
in the traced window, for the live context of each row (not the page
pool), over the Pallas kernel's summed device time.  The XLA work the
wrapper does around the kernel (re-quantizing and transposing the pool)
is not attributed to it."""
from harness.common import load_module


def read(run):
    if run.trace is None:
        return None
    k = load_module(run.find("roofline", "attn_paged", ".py"))
    model = run.config
    lo, hi = run.trace_span
    least = sum(k.least_time(s["q"], s["ctx"], model, run.peaks)
                for name in ("prefill_chunk", "decode_step")
                for s in run.spans.get(name, []) if lo <= s["t"] <= hi)
    least *= model["num_hidden_layers"]
    calls, device_s = run.trace.matching(k.match)
    if not calls or least <= 0.0:
        return None
    return 100.0 * least / device_s
