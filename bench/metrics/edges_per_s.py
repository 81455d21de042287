"""Edges processed in the window, over the window's length (Graph500's
TEPS). A step's edges are the rise of its executor's ``edges_traversed()``
across the ``execute`` call; every finished query's sum is held to the
benchmark's own work count. The step in flight at the close counts with
the share of its duration that lies inside the window, so the rate has no
step-sized steps in it."""


def read(run):
    edges = 0.0
    for s in run.steps:
        if s.end <= run.close:
            edges += s.edges if s.end >= run.open else 0.0
        elif s.start < run.close:
            edges += s.edges * (run.close - s.start) / (s.end - s.start)
    return edges / run.seconds if edges > 0 else None
