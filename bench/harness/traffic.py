"""Closed-loop batches of queries, drawn from the seed.

A traffic file gives ``sessions`` and a ``mix``: how many sessions of each
query kind a batch holds, in that order. A PageRank damping is drawn from
the run's seed, uniform over the configuration's range; a degree count
takes no parameter. A BFS root is uniform over the vertices of degree >= 1
(as Graph500 draws its roots), drawn in the configuration's structure from
its generator seed and mapped to the run's ids by the seed's relabelling:
every seed sends the same BFS work, in another vertex order. The warm-up
and the window draw from separate streams.
"""
from __future__ import annotations

import numpy as np

from .hooks import Query

KINDS = ("pagerank_pull", "bfs", "degree_count")


class QueryStream:
    def __init__(
        self, traffic: dict, config: dict, degree: np.ndarray, vertex_map: np.ndarray, seed: int, stream: int
    ):
        """``degree`` is over the run's vertex ids; ``vertex_map[v]`` is the
        run's id of structure vertex ``v`` (the generator's ``vertex_map``)."""
        self.kinds = [k for k, n in traffic["mix"].items() for _ in range(int(n))]
        unknown = set(self.kinds) - set(KINDS)
        if unknown:
            raise ValueError(f"unknown query kinds {sorted(unknown)} (known: {KINDS})")
        if len(self.kinds) != int(traffic["sessions"]):
            raise ValueError("the mix must fill every session")
        self.queries_cfg = config["queries"]
        self.vertex_map = vertex_map
        self.roots = np.flatnonzero(degree[vertex_map] > 0)  # structure ids
        self.rng = np.random.default_rng([seed, stream])
        self.root_rng = np.random.default_rng([int(config["structure_seed"]), stream])
        self.next_qid = 0

    def _param(self, kind: str):
        if kind == "pagerank_pull":
            lo, hi = self.queries_cfg["pagerank_pull"]["damping"]
            return float(self.rng.uniform(lo, hi))
        if kind == "bfs":
            return int(self.vertex_map[self.root_rng.choice(self.roots)])
        return None

    def batch(self) -> list[Query]:
        out = []
        for kind in self.kinds:
            out.append(Query(self.next_qid, kind, self._param(kind)))
            self.next_qid += 1
        return out
