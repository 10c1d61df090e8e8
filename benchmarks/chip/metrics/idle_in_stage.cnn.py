"""Share of the traced window in which the device is idle while the host
stages a bucket (the CNN engine's ``serve.stage``: collate and
``device_put``), by interval overlap."""
from harness.overlap import idle_in


def read(run):
    return idle_in(run, "serve.stage")
