"""``conv_implicit``'s share of its roofline: the least time of every call
the traced window dispatched (from each layer's shape and each bucket's
batch, at the peaks) over the kernel's summed device time in the trace."""
from harness.common import load_module


def read(run):
    if run.trace is None:
        return None
    k = load_module(run.find("roofline", "conv_implicit", ".py"))
    layers = load_module(run.find("roofline", "cnn_layers", ".py")).walk(
        run.config)
    engines = run.counters["engines"]
    lo, hi = run.trace_span
    least = 0.0
    for s in run.spans.get("collate", []):
        if lo <= s["t"] <= hi:
            b = s["batch"]
            least += sum(k.least_time(l, b, run.peaks) for l in layers
                         if engines[l["index"]].get(b) == k.ENGINE)
    calls, device_s = run.trace.matching(k.match)
    if not calls or least <= 0.0:
        return None
    return 100.0 * least / device_s
