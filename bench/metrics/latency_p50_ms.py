"""Median query latency in the window, over the same samples as the
latency tail: the window runs untraced in a ``--trace 1`` run too, whose
profiler trace is taken only after the close."""

import numpy as np


def read(run):
    lat = run.latencies_ms()
    return float(np.percentile(lat, 50)) if lat else None
