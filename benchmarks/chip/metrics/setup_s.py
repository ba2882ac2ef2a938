"""Process start to window start: weights, gateway, compile or cache
load, and warm-up."""


def read(run):
    return run.setup_s
