"""``fused_qgemm``'s share of its roofline (the FC layers it serves): the
least time of every call of the buckets whose programs ran inside the
traced window over the kernel's summed device time in the trace.  On a
data mesh each of ``chips`` chips runs ``batch / chips`` rows of a bucket
and reads the weights itself, as ``conv_implicit_roofline`` counts it."""
from harness.common import dispatched_batches, load_module


def read(run):
    if run.trace is None:
        return None
    k = load_module(run.find("roofline", "fused_qgemm", ".py"))
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
