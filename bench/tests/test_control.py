"""The control: the PageRank reference in bfloat16 fails the limit that
the program's float32 results pass, on three seeds, both on its own and
put in the program's place inside a run."""
import json

import numpy as np
import pytest

from conftest import BENCH_DIR, MANIFEST, TINY_CELLS, config_file, run_cell

SMALL_K = 12  # each configuration runs at its generator's small(cfg, SMALL_K)
CONFIGS = [c["name"] for c in MANIFEST["configs"]]
PAGERANK_CELLS = sorted(
    cell for cell, traffic in TINY_CELLS.items()
    if "pagerank_pull" in json.loads((BENCH_DIR / "traffic" / f"{traffic}.json").read_text())["mix"]
)


@pytest.mark.parametrize("config_name", CONFIGS)
def test_control_fails_the_limit(config_name):
    from harness import graphs, reference
    from harness.control import control_errors

    config = config_file(config_name)
    generator = graphs.load(config)
    config = generator.small(config, SMALL_K)
    limit = config["limits"]["pagerank_max_rel_err"]
    for seed in (3, 2**31 + 1, 2**33 + 9):
        g = reference.Graph(*generator.edges(config, seed), generator.num_vertices(config))
        assert min(control_errors(g, [0.80, 0.85, 0.90], 5)) > 3 * limit


@pytest.mark.parametrize("workload", PAGERANK_CELLS)
def test_control_in_the_programs_place_makes_the_run_incorrect(tiny_root, capsys, monkeypatch, workload):
    """Every PageRank answer the run checks is the bfloat16 power iteration
    at the query's own damping and iteration count: ``correct`` is false,
    and ``pagerank_max_rel_err`` is the check that fails."""
    import jax.numpy as jnp
    import run

    from harness import graphs, reference
    from harness.control import pagerank_lowp
    from repro.algorithms import PageRankExecutor

    seed = 2**31 + 29
    config = json.loads((tiny_root / "tiny.json").read_text())
    generator = graphs.load(config)
    g = reference.Graph(*generator.edges(config, seed), generator.num_vertices(config))
    src, dst, out_deg = (jnp.asarray(a, jnp.int32) for a in (g.src, g.dst, g.out_deg))

    def lowp_result(self):
        rank = pagerank_lowp(
            src, dst, out_deg, jnp.float32(self.damping), num_vertices=g.num_vertices, iters=self.max_iters
        )
        return np.asarray(rank.astype(jnp.float32))

    monkeypatch.setattr(PageRankExecutor, "result", lowp_result)
    rc, result, err = run_cell(tiny_root, workload, capsys, seed=seed, seconds=1.0)
    assert rc == 0, err
    assert result["correct"] is False
    failing = [n for n, c in result["checks"].items() if not run.passes({n: (c["value"], c["limit"])})]
    assert failing == ["pagerank_max_rel_err"], result["checks"]
