"""Set-up: process start to the first timed request (load, weights,
programming, compilation or cache load, warm-up)."""


def read(run):
    return run.setup_s
