"""The control: the PageRank reference in bfloat16 fails the limit that
the program's float32 results pass, on three seeds, both on its own and
put in the program's place inside a run."""
import json

import numpy as np
import pytest

from conftest import BENCH_DIR, REPO, run_cell

SCALE = 12
CONFIGS = [c["name"] for c in json.loads((REPO / "BENCHMARK.json").read_text())["configs"]]


@pytest.mark.parametrize("config_name", CONFIGS)
def test_control_fails_the_limit(config_name):
    from harness import reference
    from harness.control import control_errors
    from harness.kronecker import kronecker_edges

    config = json.loads((BENCH_DIR / "configs" / f"{config_name}.json").read_text())
    config["SCALE"] = SCALE
    limit = config["limits"]["pagerank_max_rel_err"]
    for seed in (3, 2**31 + 1, 2**33 + 9):
        g = reference.Graph(*kronecker_edges(config, seed), 1 << SCALE)
        assert min(control_errors(g, [0.80, 0.85, 0.90], 5)) > 3 * limit


@pytest.mark.parametrize("traffic", ["pr-4s", "mix-16s"])
def test_control_in_the_programs_place_makes_the_run_incorrect(tiny_root, capsys, monkeypatch, traffic):
    """Every PageRank answer the run checks is the bfloat16 power iteration
    at the query's own damping and iteration count: ``correct`` is false,
    and ``pagerank_max_rel_err`` is the check that fails."""
    import jax.numpy as jnp
    import run

    from harness import reference
    from harness.control import pagerank_lowp
    from harness.kronecker import kronecker_edges
    from repro.algorithms import PageRankExecutor

    seed = 2**31 + 29
    config = json.loads((tiny_root / "tiny.json").read_text())
    g = reference.Graph(*kronecker_edges(config, seed), 1 << int(config["SCALE"]))
    src, dst, out_deg = (jnp.asarray(a, jnp.int32) for a in (g.src, g.dst, g.out_deg))

    def lowp_result(self):
        rank = pagerank_lowp(
            src, dst, out_deg, jnp.float32(self.damping), num_vertices=g.num_vertices, iters=self.max_iters
        )
        return np.asarray(rank.astype(jnp.float32))

    monkeypatch.setattr(PageRankExecutor, "result", lowp_result)
    rc, result, err = run_cell(tiny_root, f"tiny.{traffic}", capsys, seed=seed, seconds=1.0)
    assert rc == 0, err
    assert result["correct"] is False
    failing = [n for n, c in result["checks"].items() if not run.passes({n: (c["value"], c["limit"])})]
    assert failing == ["pagerank_max_rel_err"], result["checks"]
