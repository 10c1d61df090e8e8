"""Set-up: process start to the window's first timed request (weights,
plan compile, engine, every shape warmed, an open loop's warm-in, a
closed LM loop's first requests prefilled)."""


def read(run):
    return run.setup_s
