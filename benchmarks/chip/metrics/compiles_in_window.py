"""Runtime layer: programs JAX lowered (compiled or loaded from the
persistent cache) inside the window, from a ``jax.monitoring`` listener.
Warm-up should leave none."""


def read(run):
    return run.win.programs
