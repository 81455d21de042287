"""Pluggable execution substrate: where a schedule step's packages run.

Through PR 5 every package executed inline on the session engine's thread
(``executor.run_packages`` timed with ``perf_counter``) while the modeled
clock drove every scheduling decision — the Pallas kernels under
``repro.kernels`` sat unused by the engine. This module puts that seam
behind a protocol so the engine can dispatch the same :class:`ScheduleStep`
onto three substrates:

* :class:`ModeledBackend` (the default) — the query's compute still runs
  (executor state must advance: frontiers, convergence, edge counts), but
  nothing is wall-clock timed; ``execute`` *echoes the modeled step cost*
  as the measurement. The run is fully deterministic and the §4.4 feedback
  loop sees ratio-1.0 observations, i.e. the correction tables stay exactly
  neutral — byte-identical scheduling to the censor-neutralized engine of
  PR 5 on every gated modeled row.
* :class:`InlineBackend` — PR 5's timed path, extracted verbatim from the
  engine's ``_execute_step``: ``run_packages`` wrapped in
  ``perf_counter_ns``. Real host measurements flow into the feedback
  tables (and ``calibrate_from_runs`` can consume the accumulated
  (modeled, measured) pairs).
* :class:`PallasBackend` — lowers a package batch to a jitted
  SpMV / degree-count kernel call (``kernels/spmv``,
  ``kernels/degree_count``). The platform picks the mode: compiled Mosaic
  kernels on a TPU, the Pallas interpreter on ``cpu``; asking for the
  other one raises. Gang width maps to grid slices: the batch's tile
  range is cut into ``step.workers`` contiguous slices, one kernel launch
  each, run back to back, so the *measured* time is the serialized sum.
  Package ranges are padded to kernel tile boundaries and the
  out-of-range lanes masked off before the result is applied (unpadding),
  so results stay exact. Algorithms without a kernel lowering (PR-push)
  fall back to the inline path; ``PallasBackend.lowerings`` counts the
  lowering every prepared plan got, so a caller can tell a kernel-lowered
  query from one that ran inline.

The protocol splits *preparation* from *execution* deliberately:
``prepare`` may compile, build device tile tables, and warm the jit cache;
``execute`` measures steady-state kernel time only. The engine never times
``prepare``, so compilation cannot pollute the width-feedback EWMA's first
observation (the PR-5 inline path charged the first step with its jit
warm-up).
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import TYPE_CHECKING, Any, Protocol, runtime_checkable

import numpy as np

from .tracing import span

if TYPE_CHECKING:  # pragma: no cover - annotation-only imports (no cycles)
    from .autotuner import PreparedIteration
    from .scheduler import ScheduleStep
    from .session import QueryExecutor

# plans memoized per backend; small because at most one prep is live per
# executor at a time — the cap only bounds pathological executor churn
_PLAN_CACHE_CAP = 256


@dataclasses.dataclass(frozen=True)
class DevicePlan:
    """Backend-prepared execution state for one (executor, prep, shard) key.

    ``handle`` is backend-private (device tile tables, warm jitted callables,
    prefix sums for unpadding); the engine only ever passes the plan back to
    the backend that built it. ``shard`` is the locality-domain
    :class:`~..graph.partition.GraphShard` the plan was staged against
    (``None`` on a single-domain pool)."""

    executor: "QueryExecutor"
    prep: "PreparedIteration"
    handle: Any = None
    shard: Any = None


@runtime_checkable
class ExecutionBackend(Protocol):
    """Where a schedule step's packages execute.

    ``prepare`` is called (and memoized) before the first ``execute`` of an
    (executor, prep) pair and may be arbitrarily slow — compilation and
    device staging belong here, *outside* any measured window. A
    multi-domain engine additionally passes the ``shard`` its placement
    chose; the backend memoizes one plan per (prep, shard) so dispatch can
    run against shard-local device state. ``execute`` runs one step's
    package batch at the granted width and returns the measured nanoseconds
    that flow into records and the §4.4 feedback tables. ``modeled_ns`` is
    the engine's modeled cost for the step — substrates that do no
    wall-clock timing echo it back."""

    name: str

    def prepare(
        self, executor: "QueryExecutor", prep: "PreparedIteration", shard: Any = None
    ) -> DevicePlan:
        """Stage one (executor, prep[, shard]) key for execution (compile,
        build device tables, warm jit caches); memoized per key."""
        ...

    def execute(
        self, plan: DevicePlan, step: "ScheduleStep", modeled_ns: float = 0.0
    ) -> float:
        """Run one step's package batch; returns measured ns."""
        ...


def _run_inline(plan: DevicePlan, step: "ScheduleStep") -> None:
    """The shared inline execution body: the executor's own jitted compute."""
    parallel = step.mode == "parallel"
    plan.executor.run_packages(
        step.batch,
        plan.prep.packages,
        step.workers if parallel else 1,
        parallel=parallel,
    )


class _PlanMemo:
    """Per-backend (executor, prep, shard) → DevicePlan memo.

    Keyed by object ids but holding strong references through the stored
    plans, so a key can never be reused while its entry is alive. ``shard``
    joins the key so a session whose placement drifts across domains gets
    one plan per shard it executes against, not a single clobbered slot.
    Evicts FIFO past the cap — at most one prep is live per executor, so
    the cap is never reached by a well-behaved engine loop."""

    def __init__(self) -> None:
        self._plans: dict[tuple[int, int, int], DevicePlan] = {}

    def get(
        self, executor: "QueryExecutor", prep: "PreparedIteration", shard: Any = None
    ) -> DevicePlan | None:
        """The memoized plan for this exact (executor, prep, shard) key."""
        return self._plans.get(
            (id(executor), id(prep), id(shard) if shard is not None else 0)
        )

    def put(self, plan: DevicePlan) -> DevicePlan:
        """Memoize ``plan``; evicts the oldest entry past the cap."""
        key = (
            id(plan.executor),
            id(plan.prep),
            id(plan.shard) if plan.shard is not None else 0,
        )
        self._plans[key] = plan
        while len(self._plans) > _PLAN_CACHE_CAP:
            self._plans.pop(next(iter(self._plans)))
        return plan


class ModeledBackend:
    """Default substrate: advance the query, trust the modeled clock.

    ``run_packages`` still executes (the query's semantics — frontier
    expansion, convergence, edge counts — live there), but no wall-clock
    measurement is taken: ``execute`` returns the step's *modeled* cost as
    the measured time. Every (modeled, measured) pair the feedback loop
    sees is therefore exactly ratio 1.0, keeping all correction tables at
    their neutral fixed point — scheduling decisions are byte-identical to
    an engine with no feedback installed, and fully host-independent."""

    name = "modeled"

    def __init__(self) -> None:
        self._memo = _PlanMemo()

    def prepare(
        self, executor: "QueryExecutor", prep: "PreparedIteration", shard: Any = None
    ) -> DevicePlan:
        """No device staging needed; returns a bare (executor, prep) plan."""
        plan = self._memo.get(executor, prep, shard)
        if plan is None:
            plan = self._memo.put(DevicePlan(executor, prep, shard=shard))
        return plan

    def execute(
        self, plan: DevicePlan, step: "ScheduleStep", modeled_ns: float = 0.0
    ) -> float:
        """Run the packages inline, echo the modeled cost as measured."""
        _run_inline(plan, step)
        return float(modeled_ns)


class InlineBackend:
    """PR 5's measured path: time ``run_packages`` on this host.

    The first execution of a fresh jitted program still pays its
    compilation inside the measured window (there is no way to warm an
    executor's kernels without advancing its state); the backend seam at
    least guarantees *backend* preparation is never timed."""

    name = "inline"

    def __init__(self) -> None:
        self._memo = _PlanMemo()

    def prepare(
        self, executor: "QueryExecutor", prep: "PreparedIteration", shard: Any = None
    ) -> DevicePlan:
        """No device staging needed; returns a bare (executor, prep) plan."""
        plan = self._memo.get(executor, prep, shard)
        if plan is None:
            plan = self._memo.put(DevicePlan(executor, prep, shard=shard))
        return plan

    def execute(
        self, plan: DevicePlan, step: "ScheduleStep", modeled_ns: float = 0.0
    ) -> float:
        """Run the packages inline and return real wall nanoseconds."""
        t0 = time.perf_counter_ns()
        _run_inline(plan, step)
        return float(time.perf_counter_ns() - t0)


# ---------------------------------------------------------------------------
# Pallas substrate
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class _PallasHandle:
    """Device state one :class:`PallasBackend` plan executes against."""

    kind: str                      # "pr_pull" | "bfs" | "degree_count" | "inline"
    table: Any = None              # row-split dst-tiled COO (spmv kinds): a TileTable
    dst_tile: int = 0
    num_vertices: int = 0
    edge_prefix: np.ndarray | None = None  # [V+1] in-edges with dst < v (pr_pull)
    ids_pad: Any = None            # [2, E] endpoint ids mod C (degree_count)
    # shard-local dispatch (locality domains): the plan's shard covers dst
    # tiles [tile_lo, tile_hi) and shard_table holds that slab — ranges
    # inside it dispatch against the slab (what a domain's device would
    # actually hold), anything outside falls back to the full table so
    # results stay exact when a frontier drifts off its placed shard
    tile_lo: int = 0
    tile_hi: int = 0
    shard_table: Any = None


class PallasBackend:
    """Dispatch package batches onto the Pallas graph kernels.

    Lowerings (see the module docstring for the width → grid mapping and
    the padding/unpadding contract):

    * ``pagerank_pull`` — a package batch is a contiguous range of *target*
      vertices; the row-split dst-tiled COO built by
      ``kernels/spmv/ops.build_tiles`` is sliced to the rows of the tiles
      covering the range, the SpMV kernel aggregates each row into its tile
      on the one-hot path, and lanes outside the range are masked off before
      the partial is applied to the executor's accumulator.
    * ``bfs_top_down`` — frontier expansion *is* an SpMV over the boolean
      semiring: contributions are the indicator of the batch's frontier
      slots, the kernel counts per-target frontier parents over the
      dst-tiled out-edge list, and ``counts > 0 & ~visited`` is the found
      set (matches ``kernels/spmv/ref.py`` exactly on the counting level).
    * ``degree_count`` — a package batch is an edge range; its endpoint ids
      are padded to ``EDGE_BLOCK`` boundaries with the kernel's ``-1``
      sentinel and histogrammed by ``kernels/degree_count``.

    Anything without a lowering (PR-push's unsorted scatter) runs the
    inline path — the backend is a superset, never a restriction.
    ``lowerings`` counts prepared plans per lowering kind (``"pr_pull"``,
    ``"bfs"``, ``"degree_count"`` or ``"inline"``).

    ``interpret=None`` (default) follows the platform: compiled kernels on
    a TPU, the Pallas interpreter on ``cpu`` (numerically the real kernel,
    timed for real, just not TPU-fast). An explicit value that contradicts
    the platform raises: interpret mode on a TPU would silently measure the
    interpreter, and compiled kernels cannot execute on ``cpu``."""

    name = "pallas"

    def __init__(self, *, interpret: bool | None = None):
        from ..kernels.platform import resolve_interpret

        self.interpret = resolve_interpret(interpret, executing=True)
        self.lowerings: collections.Counter[str] = collections.Counter()
        self._memo = _PlanMemo()
        # graph-level device state, shared by every plan on the same graph:
        # raw tile tables under (gkey, "in"|"out"), and *whole warmed
        # handles* under (gkey, kind, shard_key) — the topology is staged
        # and the kernel warmed once per (graph, shard), so N concurrent
        # sessions (same or different algorithms, scan-shared gangs
        # included) load it once, not once per prep
        self._graph_tables: dict[tuple, _PallasHandle] = {}

    def _handle_key(self, executor: "QueryExecutor", kind: str, gkey, shard) -> tuple | None:
        """Shared-handle cache key: everything the staged device state
        depends on besides the graph itself. ``None`` when the lowering has
        no shareable state (inline fallback) or the graph has no identity."""
        if gkey is None:
            return None
        if kind == "pr_pull":
            skey = (
                (int(shard.v_lo), int(shard.v_hi)) if shard is not None else None
            )
            return (gkey, kind, skey)
        if kind == "bfs":
            return (gkey, kind, None)
        if kind == "degree_count":
            # ids_pad is reduced mod the counter-array size
            return (gkey, kind, int(executor.num_counters))
        return None

    # ------------------------------------------------------------ staging
    def _spmv_table(
        self, key: tuple, src: np.ndarray, dst: np.ndarray, num_vertices: int
    ) -> tuple[Any, int]:
        """Row-split dst-tiled table for one edge list, cached per graph+kind."""
        cached = self._graph_tables.get(key)
        if cached is not None:
            return cached.table, cached.dst_tile
        from ..kernels.spmv.ops import build_tiles
        from ..kernels.spmv.spmv import DST_TILE

        table = build_tiles(src, dst, num_vertices)
        self._graph_tables[key] = _PallasHandle(kind="tables", table=table, dst_tile=DST_TILE)
        return table, DST_TILE

    def staged_tables(self) -> list[tuple[str, tuple[int, ...], int]]:
        """``(direction, shape, bytes)`` of every staged row-split table:
        ``"in"`` serves PR-pull, ``"out"`` BFS; the shape is the source
        table's ``[rows, SUB_CHUNK]``, and bytes count the source and
        local-target tables and the row → tile map."""
        return [
            (key[-1], tuple(h.table.src.shape), h.table.nbytes)
            for key, h in self._graph_tables.items()
            if h.kind == "tables"
        ]

    def _warm_spmv(self, handle: _PallasHandle) -> None:
        """Trigger the kernel's compile/trace outside any measured window."""
        import jax
        import jax.numpy as jnp

        contrib = jnp.zeros((handle.num_vertices,), jnp.float32)
        jax.block_until_ready(self._spmv_range(handle, contrib, 0, 1, 1, 0, 0))

    def prepare(
        self, executor: "QueryExecutor", prep: "PreparedIteration", shard: Any = None
    ) -> DevicePlan:
        """Build (or reuse) device tile tables and warm the kernel; with a
        ``shard`` the pr_pull plan additionally stages the shard's dst-tile
        slab so dispatch against the placed domain touches only its slice."""
        plan = self._memo.get(executor, prep, shard)
        if plan is not None:
            return plan
        from .stealing import graph_identity

        gkey = graph_identity(executor)
        # executors opt into a kernel lowering explicitly (a subclass whose
        # run_packages carries extra semantics — direction-optimized BFS —
        # opts back out by clearing the attribute)
        kind = getattr(executor, "pallas_lowering", None)
        hkey = self._handle_key(executor, kind, gkey, shard) if kind else None
        if hkey is not None:
            shared = self._graph_tables.get(hkey)
            if shared is not None:
                # another session (or a previous prep of this one) already
                # staged and warmed this (graph, kind, shard) — reuse it
                self.lowerings[shared.kind] += 1
                return self._memo.put(
                    DevicePlan(executor, prep, shared, shard=shard)
                )
        handle: _PallasHandle
        if kind == "pr_pull":
            in_src, in_dst = executor.pull_edges()
            nv = int(executor.graph.num_vertices)
            table, tile = self._spmv_table((gkey, "in"), in_src, in_dst, nv)
            # in-edge list is sorted by target: a prefix sum of in-degrees
            # gives exact per-range edge counts without touching the device
            in_deg = np.bincount(in_dst, minlength=nv)
            prefix = np.concatenate([[0], np.cumsum(in_deg)])
            handle = _PallasHandle(
                kind="pr_pull",
                table=table,
                dst_tile=tile,
                num_vertices=nv,
                edge_prefix=prefix,
            )
            if shard is not None:
                # the shard's target vertices [v_lo, v_hi) cover dst tiles
                # [tile_lo, tile_hi); the slab is the shard-local device state
                handle.tile_lo = int(shard.v_lo) // tile
                handle.tile_hi = -(-int(shard.v_hi) // tile)
                handle.shard_table = table.slab(handle.tile_lo, handle.tile_hi)
            self._warm_spmv(handle)
        elif kind == "bfs":
            src, dst = executor.out_edges()
            nv = int(executor.graph.num_vertices)
            table, tile = self._spmv_table((gkey, "out"), src, dst, nv)
            handle = _PallasHandle(
                kind="bfs",
                table=table,
                dst_tile=tile,
                num_vertices=nv,
            )
            self._warm_spmv(handle)
        elif kind == "degree_count":
            import jax
            import jax.numpy as jnp

            from ..kernels.degree_count.degree_count import (
                COUNTER_TILE,
                EDGE_BLOCK,
                degree_count_pallas,
            )

            src, dst = executor.edge_endpoints()
            c = int(executor.num_counters)
            c_pad = -(-c // COUNTER_TILE) * COUNTER_TILE
            # endpoint ids in edge order, reduced mod the counter array; the
            # per-range slices are padded to EDGE_BLOCK with the kernel's -1
            # sentinel at execute time
            ids = np.stack([src % c, dst % c]).astype(np.int32)
            handle = _PallasHandle(
                kind="degree_count",
                num_vertices=c_pad,
                ids_pad=ids,
            )
            warm = np.full((EDGE_BLOCK,), -1, np.int32)
            jax.block_until_ready(
                degree_count_pallas(
                    jnp.asarray(warm), c_pad, interpret=self.interpret
                )
            )
        else:
            handle = _PallasHandle(kind="inline")
        if hkey is not None:
            self._graph_tables[hkey] = handle
        self.lowerings[handle.kind] += 1
        return self._memo.put(DevicePlan(executor, prep, handle, shard=shard))

    # ---------------------------------------------------------- execution
    def _grid_slices(self, t0: int, t1: int, workers: int) -> list[tuple[int, int]]:
        """Cut tile range [t0, t1) into ≤ ``workers`` contiguous grid slices.

        Each slice is one gang member's grid and one kernel launch; the
        launches run back to back (interpreted or compiled), so measured
        time reflects the serialized work."""
        n = t1 - t0
        w = max(min(int(workers), n), 1)
        bounds = np.linspace(t0, t1, w + 1).round().astype(int)
        return [(int(a), int(b)) for a, b in zip(bounds[:-1], bounds[1:]) if b > a]

    def _tile_slab(self, handle: _PallasHandle, a: int, b: int) -> Any:
        """The device table holding absolute dst tiles [a, b): the
        shard-local slab when the range lies inside the plan's shard (the
        common case under locality placement — the dispatch never touches
        other shards' tables), the full table otherwise (a drifted frontier
        stays exact)."""
        if handle.shard_table is not None and a >= handle.tile_lo and b <= handle.tile_hi:
            return handle.shard_table
        return handle.table

    def _spmv_range(
        self, handle: _PallasHandle, contrib, t0: int, t1: int, workers: int,
        lo: int, hi: int,
    ):
        """Aggregate dst tiles [t0, t1) at gang width ``workers``; returns
        the [V] per-target sums, zero outside targets [lo, hi) (unpadding).

        Window starts and bounds are traced and a window's row count follows
        its length (``TileTable.window_rows``), so each distinct slice
        length compiles once, however many ranges it serves. A launch's
        span carries the slots it gathers and the edges its tiles hold."""
        import jax.numpy as jnp

        from ..kernels.spmv.ops import spmv_window
        from ..kernels.spmv.spmv import SUB_CHUNK

        tile = handle.dst_tile
        out = jnp.zeros((handle.table.n_tiles * tile,), jnp.float32)
        for a, b in self._grid_slices(t0, t1, workers):
            table = self._tile_slab(handle, a, b)
            i, j = a - table.first_tile, b - table.first_tile
            rows = table.window_rows(b - a)
            edges = int(table.tile_edge_start[j] - table.tile_edge_start[i])
            with span("mq.launch", slots=rows * SUB_CHUNK, edges=edges):
                out = spmv_window(
                    out, table.src, table.dstl, contrib, int(table.tile_row_start[i]),
                    a * tile, lo, hi, row_tile=table.row_tile, n_tiles=b - a,
                    n_rows=rows, dst_tile=tile, interpret=self.interpret,
                )
        return out[: handle.num_vertices]

    def _ranges(self, plan: DevicePlan, step: "ScheduleStep") -> list[tuple[int, int]]:
        """The batch's contiguous frontier-slot ranges."""
        from ..algorithms.common import merge_ranges

        return merge_ranges(plan.prep.packages.bounds, step.batch)

    def _execute_pr_pull(
        self, plan: DevicePlan, step: "ScheduleStep"
    ) -> None:
        import jax

        h = plan.handle
        ex = plan.executor
        tile = h.dst_tile
        for lo, hi in self._ranges(plan, step):
            t0, t1 = lo // tile, -(-hi // tile)
            agg = self._spmv_range(h, ex.contrib, t0, t1, step.workers, lo, hi)
            edges = float(h.edge_prefix[hi] - h.edge_prefix[lo])
            with span("mq.sync"):
                jax.block_until_ready(agg)
            with span("mq.apply"):
                ex.apply_pull_aggregate(agg, lo, hi, edges)

    def _execute_bfs(self, plan: DevicePlan, step: "ScheduleStep") -> None:
        import jax
        import jax.numpy as jnp

        h = plan.handle
        ex = plan.executor
        n_tiles = h.table.n_tiles
        for lo, hi in self._ranges(plan, step):
            # the frontier indicator is built on the host: a device scatter
            # would compile once per distinct member count
            with span("mq.host_prep"):
                contrib = np.zeros((h.num_vertices,), np.float32)
                contrib[ex.frontier_slot_vertices(lo, hi)] = 1.0
                contrib = jnp.asarray(contrib)
            # members' out-neighbours may land in any target tile → full grid
            counts = self._spmv_range(
                h, contrib, 0, n_tiles, step.workers, 0, h.num_vertices
            )
            with span("mq.sync"):
                jax.block_until_ready(counts)
            with span("mq.apply"):
                ex.apply_expansion(counts, lo, hi)

    def _execute_degree_count(
        self, plan: DevicePlan, step: "ScheduleStep"
    ) -> None:
        import jax
        import jax.numpy as jnp

        from ..kernels.degree_count.degree_count import (
            EDGE_BLOCK,
            degree_count_pallas,
        )

        h = plan.handle
        ex = plan.executor
        for lo, hi in self._ranges(plan, step):
            # both endpoints of every edge in [lo, hi), padded to the
            # kernel's edge-block boundary with the -1 no-match sentinel
            with span("mq.host_prep"):
                ids = h.ids_pad[:, lo:hi].reshape(-1)
                total = np.zeros((h.num_vertices,), np.int32)
            for a, b in self._grid_slices(0, ids.size, step.workers):
                with span("mq.host_prep"):
                    chunk = ids[a:b]
                    pad = -(-chunk.size // EDGE_BLOCK) * EDGE_BLOCK
                    padded = np.full((pad,), -1, np.int32)
                    padded[: chunk.size] = chunk
                    padded = jnp.asarray(padded)
                with span("mq.launch"):
                    counts = degree_count_pallas(
                        padded, h.num_vertices, interpret=self.interpret
                    )
                with span("mq.sync"):
                    total += np.asarray(jax.block_until_ready(counts))
            with span("mq.apply"):
                ex.apply_counts(total[: int(ex.num_counters)], lo, hi)

    def execute(
        self, plan: DevicePlan, step: "ScheduleStep", modeled_ns: float = 0.0
    ) -> float:
        """Run one step's batch through the lowered kernel; returns real ns."""
        t0 = time.perf_counter_ns()
        kind = plan.handle.kind
        if kind == "pr_pull":
            self._execute_pr_pull(plan, step)
        elif kind == "bfs":
            self._execute_bfs(plan, step)
        elif kind == "degree_count":
            self._execute_degree_count(plan, step)
        else:
            _run_inline(plan, step)
        return float(time.perf_counter_ns() - t0)


_BACKENDS = {
    "modeled": ModeledBackend,
    "inline": InlineBackend,
    "pallas": PallasBackend,
}


def resolve_backend(spec: "ExecutionBackend | str | None") -> "ExecutionBackend":
    """Resolve a backend spec: an instance passes through, a name
    (``"modeled"`` | ``"inline"`` | ``"pallas"``) constructs the default
    instance, ``None`` means the modeled default."""
    if spec is None:
        return ModeledBackend()
    if isinstance(spec, str):
        try:
            return _BACKENDS[spec]()
        except KeyError:
            raise ValueError(
                f"unknown execution backend {spec!r} "
                f"(known: {sorted(_BACKENDS)})"
            ) from None
    if not isinstance(spec, ExecutionBackend):
        raise TypeError(f"not an ExecutionBackend: {spec!r}")
    return spec
