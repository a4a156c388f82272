"""Set-up: process start to the window's start (weights, engine,
warm-up, and in a first run the compiles)."""


def read(run):
    return run.setup_s
