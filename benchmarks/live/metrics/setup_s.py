"""Set-up: process start to the window's start (loading, weights, warm-up
and, on a run that compiles, compilation)."""


def read(run):
    return run.setup_s
