"""Share of the traced sub-window in which no operation ran on the
device: one minus the union of device event intervals."""


def read(run):
    t = run.trace
    if t is None or t.window_ns <= 0:
        return None
    return 100.0 * (1.0 - t.busy_ns / t.window_ns)
