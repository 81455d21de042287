"""Seconds from the start of the process to the window's open: imports,
the graph, the engine's tables and the warm-up batches with their
compiles."""


def read(run):
    return run.setup_s
