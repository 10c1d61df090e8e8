"""Images answered inside the window over the window's length."""


def read(run):
    n = sum(1 for r in run.requests if run.t0 <= r["t_done"] <= run.t1)
    return n / run.window_s if n else None
