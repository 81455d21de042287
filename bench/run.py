#!/usr/bin/env python3
"""Run one benchmark cell once on the chips of this machine.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``) names a configuration, whose graph its
generator makes (``harness.graphs``: the Graph500 Kronecker generator unless
the configuration names one in ``bench/generators``), and a traffic mix of
closed-loop batches: one
``MultiQueryEngine.run_sessions`` call of ``sessions`` x 1 query through the
program's ``PallasBackend``, the next batch as soon as the last returns.

* Set-up (``setup_s``): imports, the graph made on the device from the
  configuration and ``--seed``, the engine's CSR and tile tables, and
  ``warmup_batches`` untimed batches of the cell's own mix, so that every
  shape the window uses is compiled before it.
* Window: ``--seconds`` of batches; a step counts when it ends inside it.
  The batch in flight at the close is finished untimed (at most
  ``DRAIN_S`` more) and checked like the rest.
* ``--trace 1``: the window runs untraced, as in ``--trace 0``, so the
  latency samples are the same kind. After the drain, set-up's warm-up
  batches run once more with the window's queries, every shape of them
  compiled, under a profiler trace of ``TRACE_S`` seconds started and
  stopped at step boundaries; the batch is cut where the trace stops and
  its queries are not checked. The trace gives the per-layer metrics;
  ``--trace 0`` prints the end-to-end metrics.
* Check: every finished query against the plain reference (PageRank
  vectors, BFS levels, degree counts), its reported edges against the
  benchmark's own work count, and every plan kernel-lowered.

The run refuses any platform but ``tpu`` and fewer chips than the cell
asks for: exit code 3, no result. The last line of standard output is one
JSON object; the numbers compared, each beside its limit, are the last
lines of standard error and the last key of that object.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
sys.path.insert(0, str(BENCH_DIR))

PLATFORMS = ("tpu",)
CACHE_DIR = BENCH_DIR / ".jax_cache"
DRAIN_S = 60.0      # the batch in flight at the close may run this much longer
TRACE_S = 4.0       # the traced sub-window after the close, to the next step boundary


class Refused(SystemExit):
    """No result: the machine or the checkout cannot run the cell."""

    def __init__(self, why: str):
        print(f"bench: {why}; nothing was run", file=sys.stderr)
        super().__init__(3)


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    when set, else a fixed directory in the checkout."""
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not env:
        jax.config.update("jax_compilation_cache_dir", os.fspath(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return env or os.fspath(CACHE_DIR)


def check_device(chips: int, platforms: tuple[str, ...]) -> dict:
    import jax

    devices = jax.devices()
    info = {"platform": devices[0].platform, "kind": devices[0].device_kind, "count": len(devices)}
    if info["platform"] not in platforms:
        raise Refused(f"needs a {' or '.join(platforms)} device, JAX platform is {info['platform']!r}")
    if info["count"] < chips:
        raise Refused(f"the cell needs {chips} chips, JAX sees {info['count']}")
    return info


class Run:
    """What a run saw: the metric readers read this."""

    def __init__(self, config: dict, seconds: float, device: dict, num_vertices: int, num_edges: int):
        self.config = config
        self.seconds = seconds
        self.device_kind = device["kind"]
        self.num_vertices = num_vertices
        self.num_edges = num_edges
        self.setup_s = 0.0
        self.open = self.close = 0.0
        self.steps = []
        self.queries = []
        self.compiles_in_window = 0
        self.trace = None            # harness.trace.TraceSummary
        self.trace_window = None     # (on, off) on the host clock

    def window_steps(self):
        return [s for s in self.steps if self.open <= s.end <= self.close]

    def traced_steps(self):
        if self.trace_window is None:
            return []
        on, off = self.trace_window
        return [s for s in self.steps if s.start >= on and s.end <= off]

    def latencies_ms(self) -> list[float]:
        """Submission to the end of the last step, of every query that
        finished inside the window."""
        return [
            1e3 * (q.finished - q.submitted)
            for q in self.queries
            if q.finished is not None and self.open <= q.finished <= self.close
        ]


class TraceTaken(Exception):
    """Raised at the step boundary where the trace stops: the batch being
    traced is cut there."""


class Tracer:
    """Starts and stops the profiler at step boundaries."""

    def __init__(self, proxy, seconds: float):
        self.proxy = proxy
        self.seconds = seconds
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        self.on = self.off = None

    def boundary(self, now: float) -> None:
        import jax

        if self.on is None:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0  # host spans only, no Python frames
            jax.profiler.start_trace(self.dir, profiler_options=options)
            self.proxy.annotate = True
            self.on = self.proxy.clock()
        elif self.on is not None and self.off is None and now >= self.on + self.seconds:
            self.stop(now)
            raise TraceTaken

    def stop(self, now: float) -> None:
        import jax

        if self.on is None or self.off is not None:
            return
        self.proxy.close_spans()
        self.proxy.annotate = False
        self.off = now
        jax.profiler.stop_trace()

    def summary(self):
        from harness import trace

        try:
            if self.on is None:
                return None
            return trace.reduce(trace.load(self.dir))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def make_executor_for(graph, config: dict, pagerank_iterations: int | None = None):
    """The executor of each query kind; ``pagerank_iterations`` overrides
    the configuration's count (the warm-up's shorter queries)."""
    from repro.algorithms import BFSExecutor, DegreeCountExecutor, PageRankExecutor

    qcfg = config["queries"]

    def make(q):
        if q.kind == "pagerank_pull":
            pr = qcfg["pagerank_pull"]
            iters = int(pagerank_iterations or pr["iterations"])
            return PageRankExecutor(graph, mode="pull", damping=q.param, max_iters=iters, tol=float(pr["tol"]))
        if q.kind == "bfs":
            return BFSExecutor(graph, q.param)
        return DegreeCountExecutor(graph, num_counters=int(qcfg["degree_count"]["counters"]))

    return make


def check(run: Run, checker, lowerings) -> tuple[dict, int, int]:
    """``({name: [value, limit]}, attempted, failed)`` over every query the
    window's batches submitted."""
    limits = run.config["limits"]
    worst = {"pagerank_pull": 0.0, "bfs": 0.0, "degree_count": 0.0}
    edge_mismatch = unfinished = failed = 0
    for q in run.queries:
        if q.finished is None:
            unfinished += 1
            failed += 1
            continue
        ref = checker.reference(q.kind, q.param)
        value = checker.compare(q.kind, q.result, ref)
        worst[q.kind] = max(worst[q.kind], value)
        work = checker.work(q.kind, q.param, ref)
        edges_ok = q.edges == work
        edge_mismatch += not edges_ok
        limit = limits["pagerank_max_rel_err"] if q.kind == "pagerank_pull" else 0
        failed += not (edges_ok and value <= limit)
    kinds = {q.kind for q in run.queries}
    out = {}
    if "pagerank_pull" in kinds:
        out["pagerank_max_rel_err"] = [worst["pagerank_pull"], limits["pagerank_max_rel_err"]]
    if "bfs" in kinds:
        out["bfs_level_mismatches"] = [worst["bfs"], 0]
    if "degree_count" in kinds:
        out["degree_count_mismatches"] = [worst["degree_count"], 0]
    out["edge_count_mismatches"] = [edge_mismatch, 0]
    out["inline_plans"] = [lowerings.get("inline", 0), 0]
    out["unfinished"] = [unfinished, 0]
    out["queries_checked_at_least"] = [len(run.queries) - unfinished, 1]
    return out, len(run.queries), failed


def passes(checks: dict) -> bool:
    return all(
        (v >= lim) if name.endswith("_at_least") else (v <= lim)
        for name, (v, lim) in checks.items()
    )


def main(
    argv: list[str] | None = None,
    *,
    root: Path = ROOT,
    platforms: tuple[str, ...] = PLATFORMS,
    bench_dir: Path = BENCH_DIR,
) -> int:
    """One run of a cell; ``bench_dir`` is where its traffic, generator and
    metric files are found."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    root = Path(root)
    if not (root / "src" / "repro").is_dir():
        raise Refused(f"no system under test at {root / 'src' / 'repro'}")
    sys.path.insert(0, str(root / "src"))

    from harness.manifest import load_cell, read_metrics

    cell = load_cell(root, args.workload, bench_dir)
    device = check_device(cell.chips, platforms)
    enable_compile_cache()

    import jax

    from harness import graphs, reference
    from harness.hooks import CompileCounter, Overdue, TimedBackend
    from harness.traffic import QueryStream
    from repro.core import PRESETS, EngineConfig, MultiQueryEngine, PallasBackend
    from repro.graph.structure import build_graph

    counter = CompileCounter()
    config, traffic = cell.config, cell.traffic
    generator = graphs.load(config, bench_dir)
    src, dst = generator.edges(config, args.seed)
    nv = generator.num_vertices(config)
    host_graph = reference.Graph(src, dst, nv)
    vmap = generator.vertex_map(config, args.seed)
    graph = build_graph(src, dst, nv, name=cell.config_name)
    run = Run(config, args.seconds, device, nv, host_graph.num_edges)

    sessions = int(traffic["sessions"])
    ecfg = config["engine"]
    engine = MultiQueryEngine(
        PRESETS[ecfg["hardware"]], pool_capacity=sessions, policy=ecfg["policy"], calibration=None
    )
    inner = PallasBackend()
    proxy = TimedBackend(inner)
    run_config = EngineConfig(backend=proxy, **ecfg.get("run_config", {}))
    make = make_executor_for(graph, config)

    def run_batch(queries, make=make) -> None:
        t = proxy.clock()
        for q in queries:
            q.submitted = t
        engine.run_sessions(
            proxy.factory(queries, make), sessions=sessions, queries_per_session=1, config=run_config
        )

    # warm-up: batches of the cell's own mix, whose PageRank queries may run
    # fewer iterations where every iteration takes the same shapes
    warmup = traffic.get("warmup", {})
    warm_make = make_executor_for(graph, config, warmup.get("pagerank_iterations"))
    warm = QueryStream(traffic, config, host_graph.out_deg, vmap, args.seed, stream=1)
    warm_batches = int(warmup.get("batches", 1))
    for _ in range(warm_batches):
        run_batch(warm.batch(), warm_make)

    stream = QueryStream(traffic, config, host_graph.out_deg, vmap, args.seed, stream=0)
    at_open = counter.snapshot()
    compiles = {"open": at_open["compiles"]}
    run.open = proxy.clock()
    run.setup_s = run.open - T_START
    run.close = run.open + args.seconds
    proxy.steps.clear()

    def boundary(now: float) -> None:
        if now >= run.close and "close" not in compiles:
            compiles["close"] = counter.snapshot()["compiles"]

    proxy.on_boundary = boundary
    proxy.deadline = run.close + DRAIN_S
    try:
        while proxy.clock() < run.close:
            batch = stream.batch()
            run.queries.extend(batch)
            run_batch(batch)
    except Overdue as e:
        print(f"bench: {e}", file=sys.stderr)
    run.compiles_in_window = compiles.get("close", counter.snapshot()["compiles"]) - compiles["open"]
    drain_s = max([s.end - run.close for s in proxy.steps] + [0.0])
    if args.trace:
        # the trace: set-up's warm-up batches once more, every shape of
        # which is compiled, with the window's queries (a warm-up's shorter
        # PageRank would start a query, and pay its host set-up, every
        # step), cut at the first step boundary TRACE_S in
        tracer = Tracer(proxy, min(TRACE_S, args.seconds / 2))
        proxy.on_boundary = tracer.boundary
        proxy.deadline = None
        replay = QueryStream(traffic, config, host_graph.out_deg, vmap, args.seed, stream=1)
        try:
            for _ in range(warm_batches):
                run_batch(replay.batch())
        except TraceTaken:
            pass
        tracer.stop(proxy.clock())
        run.trace_window = (tracer.on, tracer.off) if tracer.on is not None else None
        run.trace = tracer.summary()
    proxy.on_boundary = None
    run.steps = list(proxy.steps)

    stats = jax.devices()[0].memory_stats() or {}
    device["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))
    lowerings = dict(inner.lowerings)
    del engine, proxy, inner, graph, make
    gc.collect()

    t_ref = time.perf_counter()
    checker = reference.Checker(host_graph, config["queries"])
    checks, attempted, failed = check(run, checker, lowerings)
    correct = passes(checks)
    print(
        f"bench: setup_s={run.setup_s:.3f} setup_compiles={at_open['compiles']} "
        f"setup_cache_hits={at_open['cache_hits']} setup_cache_misses={at_open['cache_misses']} "
        f"window_steps={len(run.window_steps())} "
        f"finished_in_window={len(run.latencies_ms())} compiles_in_window={run.compiles_in_window} "
        f"drain_s={drain_s:.3f} "
        f"reference_s={time.perf_counter() - t_ref:.3f} compiled_in_window="
        f"{[name for t, name in counter.compiled if run.open <= t <= run.close]}",
        file=sys.stderr,
    )

    metrics = read_metrics(cell.per_layer if args.trace else cell.end_to_end, run, bench_dir)
    result = {"correct": correct, "attempted": attempted, "failed": failed, "metrics": metrics, "device": device}
    if run.trace is not None:
        from harness.trace import breakdown

        device["busy_s"] = run.trace.busy_ns / 1e9
        device["window_s"] = run.trace.window_ns / 1e9
        result["breakdown"] = breakdown(run.trace)
    result["checks"] = {name: {"value": v, "limit": lim} for name, (v, lim) in checks.items()}
    for name, (v, lim) in checks.items():
        print(f"check {name} {v!r} limit {lim!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
