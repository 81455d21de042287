"""Roofline share of the degree-count kernel (``degree_count_pallas``):
the least time the chip needs for the traced degree-count steps' work
(``harness.work.degree_count_needs``) over the device time of its
programs in the trace."""

from harness.peaks import roofline_share
from harness.work import degree_count_needs

PROGRAM = "degree_count"


def read(run):
    t = run.trace
    if t is None or not t.module_seen(PROGRAM):
        return None
    edges = sum(s.edges for s in run.traced_steps() if s.kind == "degree_count")
    counters = int(run.config["queries"]["degree_count"]["counters"])
    flops, nbytes = degree_count_needs(edges, counters, run.num_edges)
    return roofline_share(flops, nbytes, t.module_time_ns(PROGRAM) / 1e9, run.device_kind)
