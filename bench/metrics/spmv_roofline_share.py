"""Roofline share of the SpMV programs (``spmv_window``: the XLA gather
and the Pallas kernel): the least time the chip needs for the traced
PageRank-pull and BFS steps' work (``harness.work.spmv_needs``) over the
device time of the ``spmv_window`` programs in the trace."""

from harness.peaks import roofline_share
from harness.work import SPMV_KINDS, spmv_needs

PROGRAM = "spmv_window"


def read(run):
    t = run.trace
    if t is None or not t.module_seen(PROGRAM):
        return None
    edges = sum(s.edges for s in run.traced_steps() if s.kind in SPMV_KINDS)
    flops, nbytes = spmv_needs(edges, run.num_vertices, run.num_edges)
    return roofline_share(flops, nbytes, t.module_time_ns(PROGRAM) / 1e9, run.device_kind)
