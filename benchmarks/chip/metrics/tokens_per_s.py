"""Tokens delivered inside the window (first tokens and decoded ones, each
when the ``pump()`` that produced it returned) over the window's length."""


def read(run):
    n = sum(1 for r in run.requests for t in r["times"]
            if run.t0 <= t <= run.t1)
    return n / run.window_s if n else None
