"""Set-up time: process start to the window's opening (imports, weights,
cost model, engine, warm-up, and in a cold run the compiles)."""


def read(run):
    return run.setup_s
