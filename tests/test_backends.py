"""ExecutionBackend seam (core/backends.py): resolve/memoize semantics, the
ModeledBackend's byte-identical modeled-echo, PallasBackend lowerings against
the pure algorithm references, measured-time flow into CostFeedback through
every dispatch path (plain step, fused split-back, stolen batch), the
prepare-vs-execute measurement split, and the EngineConfig kwarg
deprecation."""
import time

import numpy as np
import pytest

from repro.algorithms import (
    BFSExecutor,
    DegreeCountExecutor,
    PageRankExecutor,
    bfs_reference,
    degree_count_reference,
    pagerank_reference,
)
from repro.core import (
    CostFeedback,
    DevicePlan,
    EngineConfig,
    ExecutionBackend,
    FusionConfig,
    InlineBackend,
    ModeledBackend,
    MultiQueryEngine,
    PallasBackend,
    QueryRecord,
    XEON_E5_2660V4,
    resolve_backend,
)
from repro.graph import rmat_graph


def _engine(backend=None, **kw):
    return MultiQueryEngine(
        XEON_E5_2660V4, policy="scheduler", backend=backend, **kw
    )


def _run_one(eng, ex):
    rec = QueryRecord(0, 0, ex.desc.name)
    eng.run_query(ex, rec)
    return rec


def _mixed_mk(graph):
    deg = np.asarray(graph.out_degrees())
    hubs = np.argsort(-deg)

    def mk(s, q):
        if s == 0:
            return PageRankExecutor(graph, mode="pull", max_iters=3, tol=0)
        return BFSExecutor(graph, int(hubs[s % 4]))

    return mk


# ---------------- resolve + memoization ----------------

def test_resolve_backend_specs():
    assert isinstance(resolve_backend(None), ModeledBackend)
    assert isinstance(resolve_backend("modeled"), ModeledBackend)
    assert isinstance(resolve_backend("inline"), InlineBackend)
    assert isinstance(resolve_backend("pallas"), PallasBackend)
    inst = InlineBackend()
    assert resolve_backend(inst) is inst
    with pytest.raises(ValueError, match="unknown execution backend"):
        resolve_backend("gpu")
    with pytest.raises(TypeError):
        resolve_backend(42)


def test_backends_satisfy_protocol():
    for b in (ModeledBackend(), InlineBackend(), PallasBackend(interpret=True)):
        assert isinstance(b, ExecutionBackend)


def test_prepare_is_memoized_per_executor_prep_pair(small_rmat):
    backend = ModeledBackend()
    ex = PageRankExecutor(small_rmat, mode="pull", max_iters=2, tol=0)
    ex.start()
    prep = object()  # backends key plans by identity, never inspect prep here
    plan = backend.prepare(ex, prep)
    assert backend.prepare(ex, prep) is plan
    assert backend.prepare(ex, object()) is not plan


# ---------------- modeled echo ----------------

def test_modeled_backend_echoes_modeled_cost(small_rmat):
    """The default substrate takes no wall measurement: every record's
    measured time equals its modeled time exactly."""
    eng = _engine("modeled")
    rec = _run_one(eng, PageRankExecutor(small_rmat, mode="pull", max_iters=3, tol=0))
    assert rec.modeled_ns > 0
    assert rec.measured_ns == rec.modeled_ns


def test_modeled_scheduling_identical_across_substrates(small_rmat):
    """Without feedback the engine schedules on the modeled clock alone, so
    modeled traces are identical whichever substrate executed the packages."""
    reps = {}
    for backend in ("modeled", "inline"):
        eng = _engine(backend)
        reps[backend] = eng.run_sessions(
            _mixed_mk(small_rmat),
            sessions=4,
            queries_per_session=1,
            config=EngineConfig(steal=True, fuse=True, fusion=FusionConfig(hold_ns=2e4)),
        )
    a, b = reps["modeled"], reps["inline"]
    assert [r.modeled_ns for r in a.records] == [r.modeled_ns for r in b.records]
    assert [r.traces for r in a.records] == [r.traces for r in b.records]
    assert a.makespan_modeled_ns == b.makespan_modeled_ns


def test_modeled_echo_keeps_feedback_neutral(small_rmat):
    """The echo makes every (modeled, measured) pair ratio-1.0, so an
    installed feedback loop stays at its neutral fixed point and scheduling
    matches an engine with no feedback at all — the property that keeps the
    gated modeled benchmark rows host-independent."""
    fb = CostFeedback()
    cfg = EngineConfig(
        steal=True, fuse=True, fusion=FusionConfig(hold_ns=2e4), width_feedback=True
    )
    eng_fb = _engine("modeled", feedback=fb)
    rep_fb = eng_fb.run_sessions(
        _mixed_mk(small_rmat), sessions=4, queries_per_session=1, config=cfg
    )
    assert fb.observations > 0 and fb.width_observations > 0
    for (algo, par) in list(fb._log_corr):
        assert fb.correction(algo, par) == pytest.approx(1.0)
    for (algo, w) in list(fb._log_width):
        assert fb.correction(algo, w >= 2, width=w) == pytest.approx(1.0)
        assert fb.width_ratio(algo, w) == pytest.approx(1.0)

    eng_none = _engine("modeled")
    rep_none = eng_none.run_sessions(
        _mixed_mk(small_rmat), sessions=4, queries_per_session=1, config=cfg
    )
    assert [r.modeled_ns for r in rep_fb.records] == [
        r.modeled_ns for r in rep_none.records
    ]
    assert rep_fb.makespan_modeled_ns == rep_none.makespan_modeled_ns


# ---------------- pallas lowerings vs pure references ----------------

@pytest.fixture(scope="module")
def pallas_graph():
    return rmat_graph(10, seed=3)


def test_pallas_pagerank_pull_matches_reference(pallas_graph):
    iters = 5
    ref = pagerank_reference(pallas_graph, iters=iters)
    eng = _engine(PallasBackend(interpret=True))
    ex = PageRankExecutor(pallas_graph, mode="pull", max_iters=iters, tol=0)
    rec = _run_one(eng, ex)
    np.testing.assert_allclose(ex.result(), ref, rtol=2e-4, atol=1e-8)
    assert rec.edges == pytest.approx(pallas_graph.num_edges * iters)
    assert rec.measured_ns > 0  # real kernel wall time, not an echo


def test_pallas_bfs_matches_reference(pallas_graph):
    deg = np.asarray(pallas_graph.out_degrees())
    src = int(np.argmax(deg))
    eng = _engine(PallasBackend(interpret=True))
    ex = BFSExecutor(pallas_graph, src)
    _run_one(eng, ex)
    assert np.array_equal(ex.result(), bfs_reference(pallas_graph, src))


def test_pallas_degree_count_matches_reference(pallas_graph):
    eng = _engine(PallasBackend(interpret=True))
    ex = DegreeCountExecutor(pallas_graph)
    _run_one(eng, ex)
    ref = degree_count_reference(
        np.asarray(pallas_graph.src), np.asarray(pallas_graph.dst), ex.num_counters
    )
    assert np.array_equal(ex.result(), ref)


def test_pallas_falls_back_inline_without_lowering(pallas_graph):
    """PR-push has no kernel lowering (unsorted scatter) — the backend runs
    it on the inline path, says so in ``lowerings``, and the result still
    matches the oracle."""
    iters = 5
    backend = PallasBackend(interpret=True)
    ex = PageRankExecutor(pallas_graph, mode="push", max_iters=iters, tol=0)
    _run_one(_engine(backend), ex)
    np.testing.assert_allclose(
        ex.result(), pagerank_reference(pallas_graph, iters=iters),
        rtol=2e-4, atol=1e-8,
    )
    assert set(backend.lowerings) == {"inline"}


def test_pallas_lowerings_name_the_kernel_each_plan_got(pallas_graph):
    backend = PallasBackend(interpret=True)
    eng = _engine(backend)
    _run_one(eng, PageRankExecutor(pallas_graph, mode="pull", max_iters=2, tol=0))
    _run_one(eng, DegreeCountExecutor(pallas_graph))
    assert backend.lowerings["pr_pull"] > 0 and backend.lowerings["degree_count"] > 0
    assert backend.lowerings["inline"] == 0


def test_pallas_staged_tables_report_what_is_on_the_device(pallas_graph):
    """PR-pull stages the in-edge tables, BFS the out-edge tables, once per
    graph however many plans share them; bytes cover both tables."""
    backend = PallasBackend(interpret=True)
    eng = _engine(backend)
    assert backend.staged_tables() == []
    _run_one(eng, PageRankExecutor(pallas_graph, mode="pull", max_iters=2, tol=0))
    _run_one(eng, PageRankExecutor(pallas_graph, mode="pull", max_iters=2, tol=0))
    _run_one(eng, BFSExecutor(pallas_graph, 0))
    staged = backend.staged_tables()
    assert sorted(d for d, _, _ in staged) == ["in", "out"]
    e, n_tiles = pallas_graph.num_edges, -(-pallas_graph.num_vertices // 512)
    for _, (rows, width), nbytes in staged:
        # rows of 512 slots: every edge, at most 511 padding slots per tile,
        # then up to 7 padding rows; bytes: two [rows, 512] int32 tables and
        # the [rows] int32 row -> tile map
        assert width == 512 and rows % 8 == 0
        assert e <= rows * width <= e + n_tiles * 511 + 7 * 512
        assert nbytes == 2 * rows * width * 4 + rows * 4


def test_pallas_shard_slab_matches_the_full_table(medium_rmat):
    """Under locality domains a PR-pull plan stages its shard's rows of the
    row-split table; a range inside the shard dispatches against that slab
    and gives the full table's sums at every gang width."""
    import jax.numpy as jnp

    from repro.graph.partition import GraphPartition
    from repro.kernels.spmv import spmv_ref

    backend = PallasBackend(interpret=True)
    ex = PageRankExecutor(medium_rmat, mode="pull", max_iters=2, tol=0)
    ex.start()
    shard = GraphPartition.build(medium_rmat, 2).shards[1]
    local = backend.prepare(ex, object(), shard).handle
    full = backend.prepare(ex, object()).handle
    a, b = local.tile_lo, local.tile_hi
    assert 0 < a < b == full.table.n_tiles
    assert local.shard_table.src.shape[0] < full.table.src.shape[0]
    assert backend._tile_slab(local, a, b) is local.shard_table
    assert backend._tile_slab(full, a, b) is full.table

    nv = medium_rmat.num_vertices
    contrib = jnp.asarray(np.random.default_rng(5).normal(size=nv).astype(np.float32))
    src, dst = ex.pull_edges()
    ref = np.asarray(spmv_ref(jnp.asarray(src), jnp.asarray(dst), contrib, nv))
    lo, hi = int(shard.v_lo), int(shard.v_hi)
    for workers in (1, 3):
        got = np.asarray(backend._spmv_range(local, contrib, a, b, workers, lo, hi))
        want = np.asarray(backend._spmv_range(full, contrib, a, b, workers, lo, hi))
        np.testing.assert_array_equal(got, want)
        np.testing.assert_allclose(got[lo:hi], ref[lo:hi], rtol=1e-5, atol=1e-5)
        assert not got[:lo].any() and not got[hi:].any()


def test_pallas_interpret_mode_follows_the_platform(monkeypatch):
    """On cpu the default is the interpreter and compiled kernels are
    refused; on a TPU the default is compiled and the interpreter refused."""
    import jax

    from repro.kernels.platform import resolve_interpret

    assert PallasBackend().interpret is True
    with pytest.raises(ValueError, match="cpu"):
        PallasBackend(interpret=False)
    # kernel entry points may still be lowered for a described TPU from cpu
    assert resolve_interpret(False) is False

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert resolve_interpret(None) is False
    assert PallasBackend().interpret is False
    with pytest.raises(ValueError, match="tpu"):
        PallasBackend(interpret=True)


def test_pallas_results_stable_across_gang_widths(pallas_graph):
    """The width → grid-slice mapping is a performance knob, not a semantic
    one: single-query (wide gang) and a contended 4-session run (narrow,
    stolen, re-sliced gangs) produce identical PageRank ranks."""
    iters = 3
    solo = _engine(PallasBackend(interpret=True))
    ex_solo = PageRankExecutor(pallas_graph, mode="pull", max_iters=iters, tol=0)
    _run_one(solo, ex_solo)

    made = []

    def mk(s, q):
        ex = PageRankExecutor(pallas_graph, mode="pull", max_iters=iters, tol=0)
        made.append(ex)
        return ex

    eng = MultiQueryEngine(
        XEON_E5_2660V4,
        pool_capacity=4,
        policy="scheduler",
        backend=PallasBackend(interpret=True),
    )
    eng.run_sessions(
        mk, sessions=4, queries_per_session=1, config=EngineConfig(steal=True)
    )
    for ex in made:
        np.testing.assert_allclose(ex.result(), ex_solo.result(), rtol=1e-6)


# ---------------- measured time reaches the feedback loop ----------------

def _skew_mk(graph):
    """fig14's shape: 1 heavy PageRank + short BFS thief fodder."""
    deg = np.asarray(graph.out_degrees())
    hubs = np.argsort(-deg)

    def mk(s, q):
        if s == 0:
            return PageRankExecutor(graph, mode="pull", max_iters=6, tol=0)
        return BFSExecutor(graph, int(hubs[s % 8]))

    return mk


def test_backend_measurements_reach_feedback_stolen_path(medium_rmat):
    """Stolen batches route the backend's measured ns into the §4.4 tables
    exactly like plain steps."""
    fb = CostFeedback()
    eng = MultiQueryEngine(
        XEON_E5_2660V4,
        pool_capacity=16,
        policy="scheduler",
        feedback=fb,
        backend="inline",
    )
    rep = eng.run_sessions(
        _skew_mk(medium_rmat),
        sessions=8,
        queries_per_session=1,
        config=EngineConfig(steal=True, width_feedback=True),
    )
    assert rep.total_stolen > 0
    assert fb.observations == sum(r.iterations for r in rep.records)
    assert fb.width_observations > 0
    # real host measurements: the records cannot all be exact modeled echoes
    assert any(r.measured_ns != r.modeled_ns for r in rep.records)


def test_backend_measurements_reach_feedback_fused_path(medium_rmat):
    """Fused split-back shares carry the backend's measured ns into the
    member records and the width table."""
    fb = CostFeedback()
    eng = MultiQueryEngine(
        XEON_E5_2660V4,
        pool_capacity=8,
        policy="scheduler",
        feedback=fb,
        backend="inline",
    )
    rep = eng.run_sessions(
        lambda s, q: PageRankExecutor(medium_rmat, mode="pull", max_iters=3, tol=0),
        sessions=4,
        queries_per_session=1,
        config=EngineConfig(fuse=True, width_feedback=True),
    )
    assert rep.total_fused > 0
    assert fb.width_observations > 0
    assert all(r.measured_ns > 0 for r in rep.records)


def test_pallas_measurements_populate_width_table(pallas_graph):
    """Acceptance: pallas-measured kernel times land in the width-keyed
    feedback table."""
    fb = CostFeedback()
    eng = MultiQueryEngine(
        XEON_E5_2660V4,
        pool_capacity=8,
        policy="scheduler",
        feedback=fb,
        backend=PallasBackend(interpret=True),
    )
    rep = eng.run_sessions(
        _mixed_mk(pallas_graph),
        sessions=2,
        queries_per_session=1,
        config=EngineConfig(steal=True, width_feedback=True),
    )
    assert fb.width_observations > 0
    assert all(r.measured_ns > 0 for r in rep.records)


# ---------------- prepare is outside the measured window ----------------

class _SlowPrepareStub:
    """Stub substrate whose preparation (compilation stand-in) is ~100x the
    cost of an execute; execute reports a fixed 7 ns."""

    name = "slow-prepare-stub"

    def __init__(self):
        self.prepare_calls = 0
        self.execute_calls = 0

    def prepare(self, executor, prep):
        self.prepare_calls += 1
        time.sleep(0.002)  # ~2e6 ns: dwarfs every reported execute
        return DevicePlan(executor, prep)

    def execute(self, plan, step, modeled_ns=0.0):
        self.execute_calls += 1
        plan.executor.run_packages(
            step.batch,
            plan.prep.packages,
            step.workers if step.mode == "parallel" else 1,
            parallel=step.mode == "parallel",
        )
        return 7.0


def test_prepare_cost_never_pollutes_measured_time(small_rmat):
    """Regression for the PR-5 inline path charging jit warm-up to the first
    measured step: the engine must take the backend's reported execute time
    verbatim, so a 100x-slower prepare leaves every step at exactly 7 ns."""
    stub = _SlowPrepareStub()
    eng = _engine(stub)
    rec = _run_one(
        eng, PageRankExecutor(small_rmat, mode="pull", max_iters=3, tol=0)
    )
    assert stub.prepare_calls > 0 and stub.execute_calls > 0
    assert rec.measured_ns == pytest.approx(7.0 * stub.execute_calls)


def test_custom_backend_instance_via_engine_config(small_rmat):
    """EngineConfig.backend accepts an instance, scoped to that run: the
    engine's default backend is restored afterwards."""
    stub = _SlowPrepareStub()
    eng = _engine("modeled")
    default = eng.backend
    rep = eng.run_sessions(
        _mixed_mk(small_rmat),
        sessions=2,
        queries_per_session=1,
        config=EngineConfig(backend=stub),
    )
    assert stub.execute_calls > 0
    # every booked measurement is a multiple of the stub's fixed 7 ns —
    # nothing else (prepare, engine-side timing) leaked into the numbers
    for r in rep.records:
        assert r.measured_ns > 0
        assert r.measured_ns % 7.0 == pytest.approx(0.0, abs=1e-9)
    assert eng.backend is default


# ---------------- config-only surface ----------------

def test_run_sessions_rejects_legacy_kwargs(small_rmat):
    """The PR-6 one-release keyword shim is gone: the individual feature
    keywords are plain unknown arguments now, not a deprecation path."""
    eng = _engine()
    with pytest.raises(TypeError):
        eng.run_sessions(
            _mixed_mk(small_rmat), sessions=2, queries_per_session=1, steal=True
        )
    # the consolidated surface is unaffected
    rep = eng.run_sessions(
        _mixed_mk(small_rmat), sessions=2, queries_per_session=1,
        config=EngineConfig(steal=True),
    )
    assert len(rep.records) == 2
