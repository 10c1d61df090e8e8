"""Share of the traced window in which the device is idle while the host
blocks on a bucket's output (the CNN engine's ``serve.wait``): time no
device op shows, such as a transfer still in flight or launch latency."""
from harness.overlap import idle_in


def read(run):
    return idle_in(run, "serve.wait")
