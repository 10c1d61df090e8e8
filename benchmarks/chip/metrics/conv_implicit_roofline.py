"""``conv_implicit``'s share of its roofline: the least time of every call
of the buckets whose programs ran inside the traced window
(``dispatched_batches``; from each layer's shape and each bucket's batch,
at the peaks) over the kernel's summed device time in the trace.  On a
data mesh of ``chips`` devices each chip runs ``batch / chips`` rows of a
bucket, under the plan's verdict for that batch: the least time is that
of every chip's call, and the device time is summed over the chips'
planes."""
from harness.common import dispatched_batches, load_module


def read(run):
    if run.trace is None:
        return None
    k = load_module(run.find("roofline", "conv_implicit", ".py"))
    layers = load_module(run.find("roofline", "cnn_layers", ".py")).walk(
        run.config)
    engines = run.counters["engines"]
    chips = int(run.cell.get("chips", 1))
    least = 0.0
    for batch in dispatched_batches(run):
        b = batch // chips
        least += chips * sum(k.least_time(l, b, run.peaks) for l in layers
                             if engines[l["index"]].get(b) == k.ENGINE)
    calls, device_s = run.trace.matching(k.match)
    if not calls or least <= 0.0:
        return None
    return 100.0 * least / device_s
