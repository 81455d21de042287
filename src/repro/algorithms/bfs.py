"""Breadth-first search, top-down (the paper's data-driven algorithm).

Descriptor audit (repro.core.descriptors.BFS_TOP_DOWN): per frontier vertex we
read its CSR range (2 mem) and do loop bookkeeping (2 ops); per edge we load
the neighbour id and its visited flag (2 mem) + 1 compare; per found vertex a
CAS on the visited word (1 atomic) + 1 write of parent/queue slot.

Execution paths (§6: sequential / simple parallel / scheduler share one code
base, differing only in how the frontier is partitioned and combined):
  * single device — one edge-centric jitted program; package slot ranges
    arrive as traced scalars.
  * sharded (dry-run / TPU) — edges sharded over the device group;
    per-shard partial next-frontier masks combined with a max-psum (the
    TPU analogue of the CAS: conflict-free local scatter + explicit combine).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..core.descriptors import BFS_TOP_DOWN
from ..core.tracing import span
from ..graph.structure import Graph, GraphStats
from .common import EdgeArrays, compact_frontier, member_mask_from_slots, merge_ranges

NOT_VISITED = jnp.int32(-1)


# ---------------------------------------------------------------------------
# Pure reference (oracle for tests): plain jnp level-synchronous BFS.
# ---------------------------------------------------------------------------

def bfs_reference(graph: Graph, source: int, max_iters: int | None = None) -> np.ndarray:
    """Level array via dense edge-centric BFS (oracle; no scheduling)."""
    ea = EdgeArrays.from_graph(graph)
    v = ea.num_vertices
    level = np.full(v, -1, dtype=np.int32)
    level[source] = 0
    frontier = np.zeros(v, dtype=bool)
    frontier[source] = True
    src = np.asarray(ea.src)
    dst = np.asarray(ea.dst)
    depth = 0
    limit = max_iters or v
    while frontier.any() and depth < limit:
        depth += 1
        active = frontier[src]
        touched = np.zeros(v, dtype=bool)
        np.logical_or.at(touched, dst[active], True)
        new = touched & (level < 0)
        level[new] = depth
        frontier = new
    return level


# ---------------------------------------------------------------------------
# Jitted iteration kernels
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("num_vertices",))
def _expand_range(
    src: jnp.ndarray,
    dst: jnp.ndarray,
    visited: jnp.ndarray,       # [V] bool
    next_mask: jnp.ndarray,     # [V] bool accumulator
    frontier_list: jnp.ndarray, # [V] int32 padded
    n_frontier: jnp.ndarray,
    lo: jnp.ndarray,
    hi: jnp.ndarray,
    *,
    num_vertices: int,
):
    """Expand the frontier slots [lo, hi): mark unvisited out-neighbours."""
    member = member_mask_from_slots(frontier_list, n_frontier, lo, hi, num_vertices)
    active = member[src]                                   # [E]
    touched = (
        jnp.zeros((num_vertices,), dtype=bool).at[dst].max(active, mode="drop")
    )
    found = touched & ~visited
    edges = jnp.sum(active.astype(jnp.int32))
    return next_mask | found, edges


@partial(jax.jit, static_argnames=("num_vertices",))
def _commit(visited, next_mask, level, depth, *, num_vertices: int):
    level = jnp.where(next_mask, depth, level)
    visited = visited | next_mask
    frontier_list, n_frontier = compact_frontier(next_mask)
    return visited, level, frontier_list, n_frontier


# ---------------------------------------------------------------------------
# Executor (QueryExecutor protocol)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class BFSExecutor:
    graph: Graph
    source: int
    desc: Any = BFS_TOP_DOWN
    max_iters: int | None = None

    # kernel-lowering opt-in for core.backends.PallasBackend: frontier
    # expansion is an SpMV over the boolean semiring (count frontier parents
    # per target, threshold at > 0)
    pallas_lowering = "bfs"

    def __post_init__(self):
        self._ea = EdgeArrays.from_graph(self.graph)
        self._out_deg_host = np.asarray(self._ea.out_deg)

    # -- protocol ------------------------------------------------------
    def graph_stats(self) -> GraphStats:
        return self.graph.stats

    def start(self) -> None:
        v = self._ea.num_vertices
        self._visited = jnp.zeros((v,), dtype=bool).at[self.source].set(True)
        self._level = jnp.full((v,), -1, jnp.int32).at[self.source].set(0)
        self._next = jnp.zeros((v,), dtype=bool)
        self._frontier_list = jnp.full((v,), v, jnp.int32).at[0].set(self.source)
        self._n_frontier = jnp.int32(1)
        self._depth = 1
        self._edges = 0.0
        self._covered = 0
        self._frontier_host: np.ndarray | None = np.array([self.source])
        self._done = False

    def finished(self) -> bool:
        return self._done or (
            self.max_iters is not None and self._depth > self.max_iters
        )

    def frontier(self) -> tuple[int, np.ndarray | None, float]:
        if self._frontier_host is None:
            n = int(self._n_frontier)
            self._frontier_host = np.asarray(self._frontier_list)[:n]
        fl = self._frontier_host
        degrees = self._out_deg_host[fl] if fl.size else np.zeros(0, np.int64)
        unvisited = self.graph.stats.v_reach - float(jnp.sum(self._visited))
        return int(fl.size), degrees, max(unvisited, 0.0)

    def frontier_vertices(self) -> np.ndarray:
        """Compacted-frontier vertex ids — the locality-placement signal: a
        multi-domain engine bins these (degree-weighted) into graph shards
        to pick the domain this iteration's mass touches most."""
        if self._frontier_host is None:
            n = int(self._n_frontier)
            self._frontier_host = np.asarray(self._frontier_list)[:n]
        return self._frontier_host

    def run_packages(self, package_ids, packages, t: int, parallel: bool) -> None:
        """Expand the given packages (slot ranges of the compacted frontier).

        ``t``/``parallel`` select the modelled execution mode; on a single
        host device both modes run the same edge-centric program (the
        distinction drives the cost model and, on a real mesh, the shard_map
        path in repro.launch)."""
        ranges = merge_ranges(packages.bounds, package_ids)
        for lo, hi in ranges:
            self._next, edges = _expand_range(
                self._ea.src,
                self._ea.dst,
                self._visited,
                self._next,
                self._frontier_list,
                self._n_frontier,
                jnp.int32(lo),
                jnp.int32(hi),
                num_vertices=self._ea.num_vertices,
            )
            self._edges += float(edges)
            self._covered += hi - lo
        # the scheduler hands each package exactly once per iteration; once
        # the slot ranges cover the whole frontier, the iteration commits
        if self._covered >= int(self._n_frontier):
            self.end_iteration()

    def end_iteration(self) -> None:
        (
            self._visited,
            self._level,
            self._frontier_list,
            self._n_frontier,
        ) = _commit(
            self._visited,
            self._next,
            self._level,
            jnp.int32(self._depth),
            num_vertices=self._ea.num_vertices,
        )
        self._next = jnp.zeros_like(self._next)
        self._depth += 1
        self._covered = 0
        self._frontier_host = None
        with span("mq.sync"):
            empty = int(self._n_frontier) == 0
        if empty:
            self._done = True

    def edges_traversed(self) -> float:
        return self._edges

    def result(self) -> np.ndarray:
        return np.asarray(self._level)

    # -- execution-backend hooks (core.backends.PallasBackend) ----------
    def out_edges(self) -> tuple[np.ndarray, np.ndarray]:
        """(src, dst) host copies in out-edge order (the SpMV edge list)."""
        return np.asarray(self._ea.src), np.asarray(self._ea.dst)

    def frontier_slot_vertices(self, lo: int, hi: int) -> np.ndarray:
        """Vertex ids occupying compacted-frontier slots [lo, hi)."""
        if self._frontier_host is None:
            with span("mq.sync"):
                n = int(self._n_frontier)
                self._frontier_host = np.asarray(self._frontier_list)[:n]
        return self._frontier_host[lo:hi]

    def apply_expansion(self, counts: jnp.ndarray, lo: int, hi: int) -> None:
        """Fold a backend-computed parent count [V] for frontier slots
        [lo, hi) into the next-frontier mask — identical bookkeeping to
        ``run_packages`` on that slot range (``counts > 0`` is the touched
        set; edges = out-degrees of the expanded members)."""
        self._next = self._next | ((counts > 0) & ~self._visited)
        members = self.frontier_slot_vertices(lo, hi)
        if members.size:
            self._edges += float(self._out_deg_host[members].sum())
        self._covered += hi - lo
        if self._covered >= int(self._n_frontier):
            self.end_iteration()


# ---------------------------------------------------------------------------
# Direction-optimized BFS (beyond-paper: Beamer et al. [3], driven by the
# paper's own estimators)
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("num_vertices",))
def _expand_bottom_up(
    in_src: jnp.ndarray,   # [E] in-edge sources (per in-CSR order)
    in_dst: jnp.ndarray,   # [E] in-edge targets
    visited: jnp.ndarray,
    frontier_mask: jnp.ndarray,
    *,
    num_vertices: int,
):
    """Bottom-up step: every unvisited vertex scans its in-edges for a
    frontier parent — cheaper than top-down when the frontier is a large
    fraction of |V_reach| (each unvisited vertex stops at one hit; here,
    edge-vectorized: an in-edge contributes iff its source is in the
    frontier and its target unvisited)."""
    contributes = frontier_mask[in_src] & ~visited[in_dst]
    found = (
        jnp.zeros((num_vertices,), bool).at[in_dst].max(contributes, mode="drop")
    )
    edges = jnp.sum((~visited[in_dst]).astype(jnp.int32))  # in-edges scanned
    return found, edges


@dataclasses.dataclass
class DirectionOptimizedBFSExecutor(BFSExecutor):
    """BFS that switches top-down ↔ bottom-up per iteration using the
    §3.1 estimators: when the predicted touched set |U_j| exceeds
    ``switch_fraction``·|V_reach|, the bottom-up direction wins (fewer
    edge inspections). The estimator replaces Beamer's measured-frontier
    heuristic — preparation stays ahead of execution, as in the paper."""

    switch_fraction: float = 0.25
    # the direction switch lives inside run_packages; a kernel lowering that
    # bypasses it would silently disable bottom-up — opt out
    pallas_lowering = None

    def run_packages(self, package_ids, packages, t: int, parallel: bool) -> None:
        from ..core.estimators import TraversalEstimator

        est = TraversalEstimator(
            deg_mean=self.graph.stats.deg_out_mean,
            deg_max=self.graph.stats.deg_out_max,
            v_reach=self.graph.stats.v_reach,
        )
        fsize = int(self._n_frontier)
        touched = est.touched(fsize)
        if touched > self.switch_fraction * self.graph.stats.v_reach:
            # bottom-up consumes the whole frontier in one pass; package
            # ranges are irrelevant (every unvisited vertex is a work item)
            frontier_mask = (
                jnp.zeros((self._ea.num_vertices,), bool)
                .at[self._frontier_list]
                .set(
                    jnp.arange(self._frontier_list.shape[0]) < self._n_frontier,
                    mode="drop",
                )
            )
            found, edges = _expand_bottom_up(
                self._ea.in_src,
                self._ea.in_dst,
                self._visited,
                frontier_mask,
                num_vertices=self._ea.num_vertices,
            )
            self._next = self._next | found
            self._edges += float(edges)
            self._covered = int(self._n_frontier)
            self.end_iteration()
        else:
            super().run_packages(package_ids, packages, t, parallel)


def bfs_with_engine(graph: Graph, source: int, engine) -> np.ndarray:
    """Run one BFS query through a MultiQueryEngine-compatible loop."""
    ex = BFSExecutor(graph, source)
    from ..core.session import QueryRecord

    rec = QueryRecord(session=0, query=0, algorithm=ex.desc.name)
    engine.run_query(ex, rec)
    return ex.result()
