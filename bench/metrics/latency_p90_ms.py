"""90th percentile of query latency in the window: from the batch's
submission to the end of the query's last step, over every query that
finished inside the window (numpy's linear interpolation). The 90th and
not the 95th: a 51 s window finishes ~105 queries in this cell, and the
95th would have five samples beyond it."""

import numpy as np


def read(run):
    lat = run.latencies_ms()
    return float(np.percentile(lat, 90)) if lat else None
