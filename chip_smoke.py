#!/usr/bin/env python3
"""Smoke run of the multi-query graph engine on one TPU chip.

Drives ``MultiQueryEngine.run_sessions`` through ``PallasBackend`` with the
compiled Pallas kernels, in this one process, on an RMAT graph at scale 20
(Graph500 Kronecker parameters A=0.57, B=C=0.19, edge factor 16: 1,048,576
vertices, 16,777,216 edges) made from a fixed seed:

* phase A, plain dispatch: 16 sessions x 1 query — 8 PageRank-pull
  (5 iterations, tol 0), 6 top-down BFS from distinct roots with out-degree
  > 0, and 2 degree counts over 65,536 counters;
* phase B, the same graph and backend with stealing and heterogeneous
  fusion on, so fused split-back and stolen batches reach the kernels too:
  8 PageRank-pull, 1 BFS, 7 degree counts. The BFS lowering runs the whole
  tile grid once per package range, and stealing cuts BFS iterations into
  many ranges: at scale 20 phase A's 6 BFS would make 2,338 whole-grid
  passes here, 1 BFS makes 105. Without a BFS nothing is stolen at
  scale 20.

Each phase checks every query against its pure reference (BFS levels and
degree counts exactly, PageRank within ``PR_RTOL``), that every prepared
plan was lowered to a kernel (none ran inline), and, in phase B, that
fusion and stealing happened. It prints wall seconds (work ending in
``block_until_ready``) and the compiles it triggered. Any failure raises.
The last line of standard output is one JSON object naming the device.

The script refuses every platform but ``tpu``. The persistent compile
cache goes where ``JAX_COMPILATION_CACHE_DIR`` says, else to ``.jax_cache/``
next to this file.

    python chip_smoke.py [--scale 20]
"""
from __future__ import annotations

import argparse
import collections
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
if not (ROOT / "src" / "repro").is_dir():
    sys.exit(f"chip_smoke: no src/repro next to {__file__}; run it from a checkout")
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.algorithms import (  # noqa: E402
    BFSExecutor,
    DegreeCountExecutor,
    PageRankExecutor,
    bfs_reference,
    degree_count_reference,
    pagerank_reference,
)
from repro.core import (  # noqa: E402
    XEON_E5_2660V4,
    EngineConfig,
    MultiQueryEngine,
    PallasBackend,
)
from repro.graph import rmat_graph  # noqa: E402
from repro.kernels.platform import enable_compile_cache  # noqa: E402

SEED = 3  # the graph and the BFS roots
SESSIONS = 16
# per phase: its engine config, and its sessions as (PageRank-pull, BFS,
# degree count)
PHASES = {
    "A": lambda backend: EngineConfig(backend=backend),
    "B": lambda backend: EngineConfig(backend=backend, steal=True, fuse=True, hetero_fuse=True),
}
MIXES = {"A": (8, 6, 2), "B": (8, 1, 7)}
PR_ITERS = 5
NUM_COUNTERS = 65_536
# float32 engine vs float64 reference after PR_ITERS iterations
PR_RTOL = 1e-4
LOWERED = ("pr_pull", "bfs", "degree_count")

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS = "/jax/compilation_cache/cache_misses"


class CompileCounter:
    """Counts XLA compiles (cache hits included) and persistent-cache
    hits and misses through ``jax.monitoring``."""

    def __init__(self) -> None:
        self.counts: collections.Counter[str] = collections.Counter()
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event: str, **_) -> None:
        if event in (_CACHE_HIT, _CACHE_MISS):
            self.counts[event] += 1

    def _duration(self, event: str, _secs: float, **_) -> None:
        if event == _BACKEND_COMPILE:
            self.counts[event] += 1

    def snapshot(self) -> dict[str, int]:
        return {
            "compiles": self.counts[_BACKEND_COMPILE],
            "cache_hits": self.counts[_CACHE_HIT],
            "cache_misses": self.counts[_CACHE_MISS],
        }


def device_info() -> dict:
    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind, "count": len(d)}


def build_graph(scale: int):
    t0 = time.perf_counter()
    graph = rmat_graph(scale, seed=SEED)
    gen_s = time.perf_counter() - t0
    print(
        f"graph: rmat scale={scale} seed={SEED} V={graph.num_vertices} "
        f"E={graph.num_edges} gen_seconds={gen_s:.3f}"
    )
    return graph


def print_staged_tables(backend: PallasBackend) -> None:
    """The row-split dst-tiled tables the backend staged on the device."""
    for direction, shape, nbytes in backend.staged_tables():
        print(f"tile tables {direction}: shape={shape} bytes={nbytes}")


class Workload:
    """The phase query mixes on one graph, with references computed once."""

    def __init__(self, graph):
        self.graph = graph
        deg = np.asarray(graph.out_degrees())
        rng = np.random.default_rng(SEED)
        n_roots = max(n_bfs for _, n_bfs, _ in MIXES.values())
        self.roots = [
            int(r) for r in rng.choice(np.flatnonzero(deg > 0), n_roots, replace=False)
        ]
        self._refs: dict = {}

    def query(self, mix: tuple[int, int, int], s: int) -> tuple:
        """Session ``s`` of ``mix``: ("pr",), ("bfs", root) or ("deg",)."""
        n_pr, n_bfs, _ = mix
        if s < n_pr:
            return ("pr",)
        if s < n_pr + n_bfs:
            return ("bfs", self.roots[s - n_pr])
        return ("deg",)

    def make(self, query: tuple):
        if query[0] == "pr":
            return PageRankExecutor(self.graph, mode="pull", max_iters=PR_ITERS, tol=0)
        if query[0] == "bfs":
            return BFSExecutor(self.graph, query[1])
        return DegreeCountExecutor(self.graph, num_counters=NUM_COUNTERS)

    def reference(self, query: tuple) -> np.ndarray:
        if query not in self._refs:
            g = self.graph
            if query[0] == "pr":
                self._refs[query] = pagerank_reference(g, iters=PR_ITERS)
            elif query[0] == "bfs":
                self._refs[query] = bfs_reference(g, query[1])
            else:
                self._refs[query] = degree_count_reference(
                    np.asarray(g.src), np.asarray(g.dst), NUM_COUNTERS
                )
        return self._refs[query]


def run_phase(label: str, work: Workload, backend: PallasBackend,
              counter: CompileCounter | None = None):
    """One phase: run its mix, check every result, every plan's lowering,
    and (for stealing/fusion configs) that those paths ran. Returns the
    engine report; raises on any failure."""
    mix = MIXES[label]
    config = PHASES[label](backend)
    queries = [work.query(mix, s) for s in range(SESSIONS)]
    made: dict[int, object] = {}

    def mk(s: int, q: int):
        made[s] = work.make(queries[s])
        return made[s]

    print(f"phase {label}: sessions (pagerank, bfs, degree_count)={mix}", flush=True)

    # the Xeon preset only drives the modeled scheduling clock, so decisions
    # match the CPU rehearsal; no calibration file is read from the host
    engine = MultiQueryEngine(
        XEON_E5_2660V4, pool_capacity=SESSIONS, policy="scheduler", calibration=None
    )
    lowered_before = collections.Counter(backend.lowerings)
    compiles_before = counter.snapshot() if counter else None
    t0 = time.perf_counter()
    report = engine.run_sessions(mk, sessions=SESSIONS, queries_per_session=1, config=config)
    # result() copies each query's device result to the host, so the window
    # ends only when the device has finished
    results = {s: ex.result() for s, ex in made.items()}
    wall = time.perf_counter() - t0

    failures = []
    if len(made) != SESSIONS:
        failures.append(f"{len(made)} of {SESSIONS} queries ran")
    pr_err = 0.0
    matched = 0
    for s, got in sorted(results.items()):
        ref = work.reference(queries[s])
        if queries[s][0] == "pr":
            err = float(np.max(np.abs(got - ref) / np.abs(ref)))
            pr_err = max(pr_err, err)
            ok = err <= PR_RTOL
        else:
            ok = got.shape == ref.shape and bool(np.array_equal(got, ref))
        matched += ok
        if not ok:
            failures.append(f"session {s} ({made[s].desc.name}) differs from its reference")
    lowered = backend.lowerings - lowered_before
    if lowered["inline"] or any(lowered[k] == 0 for k in LOWERED):
        failures.append(f"plan lowerings {dict(lowered)}: expected only {LOWERED}")
    fused, stolen = len(report.fusion_events), report.total_stolen
    if config.fuse or config.hetero_fuse:
        if fused == 0:
            failures.append("no fusion events")
    if config.steal and stolen == 0:
        failures.append("no stolen packages")

    print(
        f"phase {label}: queries={len(made)} matched_reference={matched} "
        f"pagerank_max_rel_err={pr_err:.3e} (rtol {PR_RTOL:g}) "
        f"plans={dict(sorted(lowered.items()))} fusion_events={fused} stolen={stolen}"
    )
    line = f"phase {label}: wall_seconds={wall:.3f}"
    if counter:
        now = counter.snapshot()
        line += " " + " ".join(f"{k}={now[k] - compiles_before[k]}" for k in now)
    print(line, flush=True)
    if failures:
        raise RuntimeError(f"phase {label} failed: " + "; ".join(failures))
    return report


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--scale", type=int, default=20, help="RMAT scale (log2 of V)")
    args = ap.parse_args(argv)

    info = device_info()
    print(f"device: platform={info['platform']} kind={info['kind']} count={info['count']}")
    if info["platform"] != "tpu":
        print(
            f"chip_smoke: needs a TPU, JAX platform is {info['platform']!r}; "
            "nothing was run",
            file=sys.stderr,
        )
        return 2
    cache_dir = enable_compile_cache(ROOT / ".jax_cache")
    print(f"compile cache: {cache_dir}")
    counter = CompileCounter()

    work = Workload(build_graph(args.scale))
    backend = PallasBackend()
    for label in PHASES:
        run_phase(label, work, backend, counter)
        print_staged_tables(backend)
    print(json.dumps({"ok": True, "device": info}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
