"""Mean host time of the CNN engine's ``serve.put`` span (the
``jax.device_put`` of one bucket's batch) per bucket, in the window.
Nothing without the engine's span recorder on."""
from harness.common import mean


def read(run):
    v = mean(s["dt"] for s in run.spans.get("serve.put", [])
             if run.t0 <= s["t"] <= run.t1)
    return None if v is None else v * 1e3
