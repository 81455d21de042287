"""Device program executions in the trace per ``execute`` call of the
traced sub-window."""


def read(run):
    t = run.trace
    if t is None or t.execute_spans == 0 or t.module_count == 0:
        return None
    return t.module_count / t.execute_spans
