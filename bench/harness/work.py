"""Operations and bytes a step needs, from counts alone, whatever
implementation computes it: removing padding raises a share instead of
hiding it.

* SpMV (PageRank-pull, BFS expansion): 12 B per edge traversed (source
  index, gathered value, target) and 8 B per target vertex (its sum read
  and written); 2 operations per edge. A step's targets are its share of
  the vertices, ``edges * V / E``: exact over a PageRank iteration, an
  average for a BFS expansion.
* Degree count: 8 B per edge (two endpoint ids) and 4 B per counter, the
  counters being the query's share ``edges / E`` of the array; 2
  operations (increments) per edge.
"""
from __future__ import annotations

SPMV_KINDS = ("pagerank_pull", "bfs")


def spmv_needs(edges: float, num_vertices: int, num_edges: int) -> tuple[float, float]:
    """``(operations, bytes)`` of an SpMV step that traversed ``edges``."""
    targets = edges * num_vertices / num_edges
    return 2.0 * edges, 12.0 * edges + 8.0 * targets


def degree_count_needs(edges: float, counters: int, num_edges: int) -> tuple[float, float]:
    """``(operations, bytes)`` of a degree-count step over ``edges`` edges."""
    return 2.0 * edges, 8.0 * edges + 4.0 * counters * edges / num_edges
