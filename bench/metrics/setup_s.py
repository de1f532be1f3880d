"""Set-up: from the process's start to the window's (loading, building and
compiling the engine, making the weights, and the warm-up)."""


def read(run):
    return run.setup_s
