"""The whole CNN step's share of the chips' peak: images answered per
second in the window times the least time per image at one chip's peak
(quantized layers' ops at the int8 peak, full-precision layers' at the
bf16 peak), over the cell's number of chips."""
from harness.common import load_module


def read(run):
    n = sum(1 for r in run.requests if run.t0 <= r["t_done"] <= run.t1)
    if not n:
        return None
    layers = load_module(run.find("roofline", "cnn_layers", ".py"))
    t = sum(2.0 * l["macs"] / (run.peaks["bf16_flops"] if l["fp"]
                               else run.peaks["int8_ops"])
            for l in layers.walk(run.config))
    return 100.0 * n / run.window_s * t / int(run.cell.get("chips", 1))
