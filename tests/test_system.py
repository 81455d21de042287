"""End-to-end behaviour tests for the paper's system: concurrent sessions
through the full engine, contention-driven selective sequential execution,
and multi-device sharded execution parity (subprocess with forced devices)."""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from repro.algorithms import BFSExecutor, PageRankExecutor
from repro.core import MultiQueryEngine, XEON_E5_2660V4


def test_concurrent_sessions_report(medium_rmat):
    eng = MultiQueryEngine(XEON_E5_2660V4, policy="scheduler")

    def mk(s, q):
        return BFSExecutor(medium_rmat, (s * 37 + q) % medium_rmat.num_vertices)

    rep = eng.run_sessions(mk, sessions=4, queries_per_session=2)
    assert len(rep.records) == 8
    assert rep.total_edges > 0
    assert rep.throughput_modeled() > 0
    assert rep.makespan_modeled_ns > 0


def test_contention_forces_sequential(medium_rmat):
    """With many sessions on few workers, grants shrink below T_min and the
    engine runs iterations sequentially (the paper's §4.3 behaviour).

    The pool is odd-sized so partial grants (granted=1 < T_min) actually
    occur: since the zero-grant fix, a session granted nothing stalls instead
    of phantom-grinding, and on a pool of 2 with T_min=2 every woken session
    takes both workers and runs parallel."""
    eng = MultiQueryEngine(XEON_E5_2660V4, pool_capacity=3, policy="scheduler")

    def mk(s, q):
        return PageRankExecutor(medium_rmat, mode="pull", max_iters=3, tol=0)

    rep = eng.run_sessions(mk, sessions=6, queries_per_session=1)
    par_iters = sum(r.parallel_iterations for r in rep.records)
    iters = sum(r.iterations for r in rep.records)
    assert par_iters < iters  # at least some selective sequential execution


def test_throughput_scales_with_sessions(medium_rmat):
    """Sequential-policy throughput grows with session count (paper Fig. 10:
    'performance of sequential is usually scaling linearly with concurrency')."""
    def mk(s, q):
        return PageRankExecutor(medium_rmat, mode="pull", max_iters=3, tol=0)

    peps = []
    for sessions in (1, 4):
        eng = MultiQueryEngine(XEON_E5_2660V4, policy="sequential")
        rep = eng.run_sessions(mk, sessions=sessions, queries_per_session=1)
        peps.append(rep.throughput_modeled())
    assert peps[1] > 2.0 * peps[0]


def test_pool_never_leaks(medium_rmat):
    eng = MultiQueryEngine(XEON_E5_2660V4, policy="scheduler")

    def mk(s, q):
        return BFSExecutor(medium_rmat, s + q)

    eng.run_sessions(mk, sessions=3, queries_per_session=2)
    assert eng.pool.available == eng.pool.capacity


@pytest.mark.slow
def test_sharded_execution_parity_subprocess(tmp_path):
    """8 forced host devices: a (4,2) mesh BFS-expansion step must equal the
    single-device result — proves the distributed data path is coherent."""
    script = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np, json
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.graph import rmat_graph
        from repro.algorithms import bfs_reference

        g = rmat_graph(10, seed=3)
        V = g.num_vertices
        E = g.num_edges
        mesh = jax.make_mesh((4, 2), ("data", "model"))
        src = jnp.asarray(g.src); dst = jnp.asarray(g.dst)
        esh = NamedSharding(mesh, P(("data", "model")))
        vsh = NamedSharding(mesh, P())
        src = jax.device_put(src, esh); dst = jax.device_put(dst, esh)

        @jax.jit
        def expand(visited, frontier):
            active = frontier.at[src].get(out_sharding=esh)
            # scatter-max takes no out_sharding: set True at active targets
            touched = jnp.zeros((V,), jnp.bool_).at[jnp.where(active, dst, V)].set(
                True, mode="drop", out_sharding=vsh
            )
            new = touched & ~visited
            return visited | new, new

        visited = jnp.zeros((V,), bool).at[5].set(True)
        frontier = jnp.zeros((V,), bool).at[5].set(True)
        visited = jax.device_put(visited, vsh); frontier = jax.device_put(frontier, vsh)
        level = np.full(V, -1); level[5] = 0
        depth = 0
        with jax.set_mesh(mesh):
            while bool(frontier.any()):
                depth += 1
                visited, frontier = expand(visited, frontier)
                level[np.asarray(frontier)] = depth
        ref = bfs_reference(g, 5)
        assert np.array_equal(level, ref), "sharded BFS != reference"
        print(json.dumps({"ok": True, "devices": len(jax.devices())}))
        """
    )
    p = tmp_path / "sharded_bfs.py"
    p.write_text(script)
    # a CPU-only child on forced host devices: it never loads the TPU
    # runtime, so it cannot contend with a parent that holds the chip
    r = subprocess.run(
        [sys.executable, str(p)],
        capture_output=True, text=True,
        env={**os.environ, "PYTHONPATH": "src", "JAX_PLATFORMS": "cpu"},
        cwd=str(Path(__file__).resolve().parent.parent),
        timeout=300,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["ok"] and out["devices"] == 8
