"""Plain references and the comparison that decides ``correct``.

Straightforward numpy over the benchmark's own edge list, importing nothing
of the program: BFS levels, PageRank power iteration in float64 (dangling
rank spread uniformly, each query's own damping), degree counts mod the
counter array. Each also gives the work count the engine's reported edges
are held to.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Graph:
    """The benchmark's host copy of the generated edge list."""

    src: np.ndarray
    dst: np.ndarray
    num_vertices: int

    def __post_init__(self) -> None:
        self.src = np.asarray(self.src, np.int64)
        self.dst = np.asarray(self.dst, np.int64)
        self.out_deg = np.bincount(self.src, minlength=self.num_vertices)

    @property
    def num_edges(self) -> int:
        return int(self.src.size)


def pagerank(g: Graph, damping: float, iters: int, dtype=np.float64) -> np.ndarray:
    """Power iteration from the uniform vector; dangling rank is spread over
    all vertices."""
    v = g.num_vertices
    dangling = g.out_deg == 0
    inv_deg = np.where(dangling, 0.0, 1.0 / np.maximum(g.out_deg, 1)).astype(dtype)
    rank = np.full(v, 1.0 / v, dtype)
    d = dtype(damping)
    for _ in range(iters):
        acc = np.bincount(g.dst, weights=(rank * inv_deg)[g.src], minlength=v).astype(dtype)
        rank = (1 - d) / v + d * (acc + rank[dangling].sum(dtype=dtype) / v)
    return rank


def bfs_levels(g: Graph, root: int) -> np.ndarray:
    """Level-synchronous top-down BFS; -1 for unreached vertices."""
    level = np.full(g.num_vertices, -1, np.int32)
    level[root] = 0
    frontier = np.zeros(g.num_vertices, bool)
    frontier[root] = True
    depth = 0
    while frontier.any():
        depth += 1
        touched = np.zeros(g.num_vertices, bool)
        touched[g.dst[frontier[g.src]]] = True
        new = touched & (level < 0)
        level[new] = depth
        frontier = new
    return level


def degree_counts(g: Graph, counters: int) -> np.ndarray:
    return (
        np.bincount(g.src % counters, minlength=counters)
        + np.bincount(g.dst % counters, minlength=counters)
    ).astype(np.int32)


def max_rel_err(got: np.ndarray, ref: np.ndarray) -> float:
    """Largest relative error over all vertices (ranks are > 0)."""
    got = np.asarray(got, np.float64)
    if got.shape != ref.shape:
        return float("inf")
    err = np.abs(got - ref) / np.abs(ref)
    return float(np.max(err)) if np.all(np.isfinite(err)) else float("inf")


class Checker:
    """Compares finished queries with the references and counts the work
    each should have reported. Degree counts are the same for every query
    of a graph, so they are computed once."""

    def __init__(self, g: Graph, queries_cfg: dict):
        self.g = g
        self.cfg = queries_cfg
        self._degrees: np.ndarray | None = None

    def work(self, kind: str, param, answer_ref=None) -> float:
        """Edges a query of this kind must report: PageRank iterations x E,
        BFS the out-degree sum of the vertices it reaches, degree count E."""
        if kind == "pagerank_pull":
            return float(self.cfg["pagerank_pull"]["iterations"] * self.g.num_edges)
        if kind == "bfs":
            return float(self.g.out_deg[answer_ref >= 0].sum())
        return float(self.g.num_edges)

    def reference(self, kind: str, param) -> np.ndarray:
        if kind == "pagerank_pull":
            return pagerank(self.g, param, self.cfg["pagerank_pull"]["iterations"])
        if kind == "bfs":
            return bfs_levels(self.g, param)
        if self._degrees is None:
            self._degrees = degree_counts(self.g, self.cfg["degree_count"]["counters"])
        return self._degrees

    def compare(self, kind: str, got, ref) -> float:
        """The compared number: max relative error for PageRank, the count
        of differing entries for the exact kinds."""
        if kind == "pagerank_pull":
            return max_rel_err(got, ref)
        got = np.asarray(got)
        if got.shape != ref.shape:
            return float(ref.size)
        return float(np.count_nonzero(got != ref))
