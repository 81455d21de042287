"""Share of the traced sub-window the host spent outside ``execute``
calls: the engine's session loop, preparation and accounting, on the
proxy's host clock."""


def read(run):
    if run.trace_window is None:
        return None
    on, off = run.trace_window
    if off <= on:
        return None
    inside = sum(s.end - s.start for s in run.traced_steps())
    return 100.0 * (1.0 - inside / (off - on))
