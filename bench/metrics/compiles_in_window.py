"""XLA compiles (persistent-cache hits included) between the window's
open and close, from ``jax.monitoring`` backend-compile events."""


def read(run):
    return run.compiles_in_window
