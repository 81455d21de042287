"""The engine's ``mq.*`` spans (``repro.core.tracing``) under a live
profiler, read back from the ``.xplane.pb``, and their reduction against
device intervals (``tools/trace_spans.py``) on synthetic lists."""
import bisect

import jax
import numpy as np
import pytest

from repro.algorithms import BFSExecutor, DegreeCountExecutor, PageRankExecutor
from repro.core import EngineConfig, MultiQueryEngine, PallasBackend, XEON_E5_2660V4
from repro.core.tracing import SPANS
from repro.graph import rmat_graph
from tools import trace_spans

INSIDE_DISPATCH = ("mq.sync", "mq.launch", "mq.host_prep", "mq.apply")


class _CountingBackend:
    """Passes every call to a ``PallasBackend`` and counts ``execute``."""

    def __init__(self, inner):
        self.inner = inner
        self.name = inner.name
        self.executes = 0

    def prepare(self, executor, prep, shard=None):
        if shard is None:
            return self.inner.prepare(executor, prep)
        return self.inner.prepare(executor, prep, shard)

    def execute(self, plan, step, modeled_ns=0.0):
        self.executes += 1
        return self.inner.execute(plan, step, modeled_ns=modeled_ns)


@pytest.fixture(scope="module")
def graph():
    return rmat_graph(10, seed=3)


def _run(graph, trace_dir=None):
    """PageRank-pull, BFS from the largest hub and a degree count, as three
    concurrent sessions through the interpreted Pallas backend."""
    root = int(np.argmax(np.asarray(graph.out_degrees())))
    kinds = [
        lambda: PageRankExecutor(graph, mode="pull", max_iters=2, tol=0),
        lambda: BFSExecutor(graph, root),
        lambda: DegreeCountExecutor(graph, num_counters=256),
    ]
    made = []

    def make(session, _query):
        made.append(kinds[session]())
        return made[-1]

    backend = _CountingBackend(PallasBackend(interpret=True))
    engine = MultiQueryEngine(XEON_E5_2660V4, pool_capacity=4, policy="scheduler")
    cfg = EngineConfig(backend=backend)
    if trace_dir is None:
        report = engine.run_sessions(make, sessions=3, queries_per_session=1, config=cfg)
    else:
        with jax.profiler.trace(str(trace_dir)):
            report = engine.run_sessions(make, sessions=3, queries_per_session=1, config=cfg)
    return report, backend, made


@pytest.fixture(scope="module")
def traced(graph, tmp_path_factory):
    trace_dir = tmp_path_factory.mktemp("trace")
    report, backend, made = _run(graph, trace_dir)
    return report, backend, made, trace_spans.load(str(trace_dir))


def test_every_span_on_the_path_appears(traced):
    *_, tr = traced
    assert {name for name, _, _ in tr.spans} == set(SPANS)


def test_backend_spans_nest_inside_dispatch(traced):
    *_, tr = traced
    dispatch = sorted((s, e) for name, s, e in tr.spans if name == "mq.dispatch")
    starts = [s for s, _ in dispatch]
    inner = [(s, e) for name, s, e in tr.spans if name in INSIDE_DISPATCH]
    assert inner
    for s, e in inner:
        i = bisect.bisect_right(starts, s) - 1
        assert i >= 0 and e <= dispatch[i][1], (s, e)


def test_one_dispatch_span_per_execute(traced):
    _, backend, _, tr = traced
    assert backend.executes > 3
    assert sum(name == "mq.dispatch" for name, _, _ in tr.spans) == backend.executes


def test_spans_name_their_session_and_query(traced):
    *_, tr = traced
    named = [(n, a) for (n, _, _), a in zip(tr.spans, tr.args) if n in ("mq.query_start", "mq.dispatch")]
    assert all({"session", "query"} <= set(a) for _, a in named)
    assert {(a["session"], a["query"]) for n, a in named if n == "mq.query_start"} == {(0, 0), (1, 0), (2, 0)}


def test_spmv_launches_carry_their_slots_and_edges(graph, traced):
    """Every SpMV launch says how many table slots it gathers and how many
    edges its tiles hold; degree-count launches gather none."""
    *_, tr = traced
    launches = [a for (n, _, _), a in zip(tr.spans, tr.args) if n == "mq.launch"]
    spmv = [a for a in launches if "slots" in a]
    assert spmv and len(spmv) < len(launches)
    for a in spmv:
        assert a["slots"] % 512 == 0 and 0 <= a["edges"] <= a["slots"]
    assert sum(a["edges"] for a in spmv) > 0
    assert trace_spans.reduce(tr)["metrics"]["gather_slots_per_edge"] >= 1


def test_tracing_changes_no_decision(graph, traced):
    """The same run with the profiler off gives the same report: edges,
    iterations, modeled times, decision traces and answers."""
    traced_report, _, traced_made, _ = traced
    report, _, made = _run(graph)

    def decisions(rep):
        return [
            (r.session, r.query, r.algorithm, r.edges, r.iterations, r.parallel_iterations,
             r.modeled_ns, r.submitted_ns, r.started_ns, r.finished_ns, r.traces)
            for r in rep.records
        ]

    assert decisions(report) == decisions(traced_report)
    assert report.makespan_modeled_ns == traced_report.makespan_modeled_ns
    for a, b in zip(made, traced_made):
        np.testing.assert_array_equal(a.result(), b.result())


# ---------------- the reduction, on synthetic lists ----------------

def _synthetic():
    # harness spans tile the window [0, 150): two steps, the second cut by
    # the trace's stop before its dispatch span closed. The program's spans
    # on one thread: a dispatch holding a launch, a sync and an apply, then
    # the step's accounting, a query start and the next preparation
    spans = [
        ("mq.dispatch", 0, 100),
        ("mq.launch", 10, 20),
        ("mq.sync", 20, 60),
        ("mq.apply", 60, 70),
        ("mq.account", 100, 110),
        ("mq.query_start", 112, 118),
        ("mq.prepare", 120, 130),
        ("mq.decide", 130, 134),
    ]
    harness = [("execute:bfs", 0, 95), ("engine", 95, 140), ("execute:bfs", 140, 150)]
    device = [(10, 40), (60, 62), (200, 210)]   # the last one lies outside
    return trace_spans.Trace(spans, harness, device)


def test_idle_goes_to_the_innermost_open_span():
    r = trace_spans.reduce(_synthetic())
    assert r["window_s"] == pytest.approx(150e-9)
    assert r["busy_s"] == pytest.approx(32e-9)
    idle = {n: s * 1e9 for n, s in r["idle_by_span"].items()}
    # 0-10 and 70-100 dispatch, 40-60 sync, 62-70 apply, 100-110 account,
    # 112-118 query start, 120-130 prepare, 130-134 decide; the rest none
    assert idle == pytest.approx({
        "mq.dispatch": 40, "mq.sync": 20, "mq.apply": 8, "mq.account": 10,
        "mq.query_start": 6, "mq.prepare": 10, "mq.decide": 4, "none": 20,
    })
    assert r["idle_attributed"] == pytest.approx(1 - 20 / 118)


def test_self_time_counts_and_the_four_numbers():
    r = trace_spans.reduce(_synthetic())
    prog = {n: (v["count"], v["total_s"] * 1e9, v["self_s"] * 1e9) for n, v in r["program"].items()}
    assert prog["mq.dispatch"] == pytest.approx((1, 100, 40))
    assert prog["mq.sync"] == pytest.approx((1, 40, 40))
    m = r["metrics"]
    assert m["query_start_ms"] == pytest.approx(6e-6)
    assert m["schedule_ms_per_step"] == pytest.approx(12e-6)   # 10 + 10 + 4 ns over two steps
    assert m["syncs_per_step"] == 0.5
    assert m["dispatch_idle_share"] == pytest.approx(100 * 68 / 150)
    assert m["device_idle_share"] == pytest.approx(100 * 118 / 150)
    assert m["spans_per_step"] == 4


def test_backend_spans_count_as_dispatch_where_the_trace_lost_their_parent():
    tr = _synthetic()
    tr.spans = [sp for sp in tr.spans if sp[0] != "mq.dispatch"]
    r = trace_spans.reduce(tr)
    # 40-60 sync and 62-70 apply; the dispatch's own 40 ns is now none
    assert r["metrics"]["dispatch_idle_share"] == pytest.approx(100 * 28 / 150)
    assert r["idle_by_span"]["none"] * 1e9 == pytest.approx(60)


def test_a_span_that_outlives_its_parent_is_cut_at_the_parent_end():
    segs = trace_spans.segments([("mq.dispatch", 0, 10), ("mq.sync", 5, 20), ("mq.account", 12, 14)])
    assert segs == [
        (0, 5, ("mq.dispatch",)),
        (5, 10, ("mq.dispatch", "mq.sync")),
        (12, 14, ("mq.account",)),
    ]


@pytest.mark.parametrize(
    "launches, expect",
    [
        ([(10, {"slots": 1024, "edges": 1000}), (60, {"slots": 512, "edges": 200})], 1536 / 1200),
        ([(10, {"slots": 1024, "edges": 1000}), (160, {"slots": 512, "edges": 1})], 1024 / 1000),
        ([(10, {}), (20, {"slots": 512, "edges": 512})], 1.0),   # a degree-count launch
        ([(10, {})], None),
    ],
    ids=["two-launches", "one-past-the-window", "no-args", "nothing-gathered"],
)
def test_gather_slots_per_edge(launches, expect):
    spans = [("mq.dispatch", 0, 150)] + [("mq.launch", s, s + 5) for s, _ in launches]
    args = [{}] + [a for _, a in launches]
    tr = trace_spans.Trace(spans, [("execute:bfs", 0, 150)], [(0, 150)], args)
    got = trace_spans.reduce(tr)["metrics"]["gather_slots_per_edge"]
    assert got == (None if expect is None else pytest.approx(expect))


@pytest.mark.parametrize(
    "spans, harness, expect",
    [
        ([], [("engine", 0, 10)], None),                          # no program spans
        ([("mq.prepare", 0, 10)], [], {"syncs_per_step": None}),  # no dispatch, window from the spans
    ],
)
def test_nothing_to_read(spans, harness, expect):
    r = trace_spans.reduce(trace_spans.Trace(spans, harness, []))
    if expect is None:
        assert r is None
    else:
        assert r["window_s"] == pytest.approx(10e-9)
        assert all(r["metrics"][k] == v for k, v in expect.items())
