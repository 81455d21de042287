"""The generator hook (``harness.graphs``): a configuration without a
``generator`` key gets exactly the Kronecker inputs it had before the hook,
and a generator that is a new file alone runs a cell through ``run.main``."""
import hashlib
import json

import numpy as np
import pytest

from conftest import MANIFEST, REPO, add_grid_generator, config_file, grid_config, run_cell

CONFIGS = [c["name"] for c in MANIFEST["configs"]]
SEEDS = (11, 2**31 + 5, 2**33 + 3)
# sha256 over (src, dst, vertex map) as int32 for SEEDS, of both
# configurations at SCALE 10, as kronecker_edges and vertex_map made them
# before configurations could name a generator
KRONECKER_S10_SHA256 = "0f5e2a83efedd05c59148144e9c8fe5d95f99281ee03f3fb0cc827423ef32e88"


@pytest.mark.parametrize("config_name", CONFIGS)
def test_default_generator_makes_the_kronecker_inputs(config_name):
    """Edges, vertex map, vertex count, BFS roots and dampings through
    ``graphs.load`` equal ``kronecker_edges``, ``vertex_map`` and ``1 <<
    SCALE`` called directly, on three seeds at SCALE 10."""
    from harness import graphs, kronecker, reference
    from harness.kronecker import kronecker_edges, vertex_map
    from harness.traffic import QueryStream

    config = config_file(config_name)
    assert "generator" not in config
    generator = graphs.load(config)
    assert generator is kronecker
    small = generator.small(config, 10)
    assert small == dict(config, SCALE=10)
    traffic = json.loads((REPO / "bench" / "traffic" / "mix-16s.json").read_text())
    digest = hashlib.sha256()
    for seed in SEEDS:
        src, dst = generator.edges(small, seed)
        want_src, want_dst = kronecker_edges(small, seed)
        np.testing.assert_array_equal(src, want_src)
        np.testing.assert_array_equal(dst, want_dst)
        assert src.dtype == dst.dtype == np.int32
        vmap = generator.vertex_map(small, seed)
        np.testing.assert_array_equal(vmap, vertex_map(small, seed))
        nv = generator.num_vertices(small)
        assert nv == 1 << 10
        for a in (src, dst, vmap):
            digest.update(np.ascontiguousarray(a, np.int32).tobytes())
        for stream in (0, 1):
            got = QueryStream(traffic, small, reference.Graph(src, dst, nv).out_deg, vmap, seed, stream)
            want = QueryStream(
                traffic, small, reference.Graph(want_src, want_dst, 1 << 10).out_deg,
                vertex_map(small, seed), seed, stream,
            )
            for _ in range(3):
                assert [(q.kind, q.param) for q in got.batch()] == [(q.kind, q.param) for q in want.batch()]
    assert digest.hexdigest() == KRONECKER_S10_SHA256


def test_a_cell_on_a_new_generator_runs_and_is_correct(tmp_path, bench_copy, capsys):
    """A configuration naming the toy grid generator, with the generator, a
    mix and the configuration added as new files only, runs ``run.main``
    on the CPU with ``correct`` true: PageRank, BFS across a 32 x 32 grid
    (up to 62 levels) and a degree count."""
    add_grid_generator(bench_copy)
    (tmp_path / "src").symlink_to(REPO / "src")
    (tmp_path / "grid32.json").write_text(json.dumps(grid_config()))
    (bench_copy / "traffic" / "mix-3s.json").write_text(json.dumps({
        "name": "mix-3s", "loop": "closed", "sessions": 3,
        "mix": {"pagerank_pull": 1, "bfs": 1, "degree_count": 1}, "warmup": {"batches": 1},
    }))
    manifest = json.loads(json.dumps(MANIFEST))
    manifest["configs"] = [dict(manifest["configs"][-1], name="grid32", file="grid32.json", reduced=[])]
    manifest["workloads"] = [
        {"name": "grid32.mix-3s", "config": "grid32", "traffic": "mix-3s", "chips": 1, "why": "x"}
    ]
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        m["workloads"] = ["grid32.mix-3s"]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))

    rc, result, err = run_cell(tmp_path, "grid32.mix-3s", capsys, seed=2**31 + 41, seconds=2.0, bench_dir=bench_copy)
    assert rc == 0, err
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["attempted"] >= 3
    assert set(result["checks"]) >= {"pagerank_max_rel_err", "bfs_level_mismatches", "degree_count_mismatches"}
    assert result["metrics"]["edges_per_s"]["value"] > 0
