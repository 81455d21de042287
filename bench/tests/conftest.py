"""The benchmark's own tests, outside the repository's tier-1 suite:

    python -m pytest bench/tests

They run on the CPU at a tiny size with interpreted kernels, and steer the
harness's device check themselves."""
import json
import os
import shutil
import sys
import tempfile
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# compiles of the tests stay out of the checkout's cache
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", tempfile.mkdtemp(prefix="bench-tests-cache-"))

import pytest  # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent.parent
REPO = BENCH_DIR.parent
sys.path.insert(0, str(BENCH_DIR))

TINY_K = 10  # the tiny graph has about 2**TINY_K vertices
MANIFEST = json.loads((REPO / "BENCHMARK.json").read_text())


def tiny_cell(workload: dict) -> str:
    """A cell's name in the tiny root: by its configuration and traffic."""
    return f"tiny.{workload['config']}.{workload['traffic']}"


# tiny cell -> its traffic, one for each cell of BENCHMARK.json
TINY_CELLS = {tiny_cell(w): w["traffic"] for w in MANIFEST["workloads"]}


def config_file(name: str) -> dict:
    entry = next(c for c in MANIFEST["configs"] if c["name"] == name)
    return json.loads((REPO / entry["file"]).read_text())


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory) -> Path:
    """A checkout-shaped directory: the system under test, and a
    ``BENCHMARK.json`` whose cells run the real mixes on a tiny graph made
    by the Kronecker generator, from the last configuration that uses it,
    at ``small(cfg, TINY_K)``."""
    from harness import kronecker

    root = tmp_path_factory.mktemp("tiny")
    (root / "src").symlink_to(REPO / "src")
    manifest = json.loads(json.dumps(MANIFEST))
    entry = [c for c in manifest["configs"] if "generator" not in config_file(c["name"])][-1]
    config = kronecker.small(config_file(entry["name"]), TINY_K)
    config["name"] = "tiny"
    (root / "tiny.json").write_text(json.dumps(config))
    rename = {w["name"]: tiny_cell(w) for w in manifest["workloads"]}
    manifest["configs"] = [dict(entry, name="tiny", file="tiny.json")]
    manifest["workloads"] = [
        dict(w, name=rename[w["name"]], config="tiny") for w in manifest["workloads"]
    ]
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [rename[w] for w in m["workloads"]]
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    return root


def run_cell(
    root: Path, workload: str, capsys, seed: int = 5, seconds: float = 2.0, trace: int = 0, bench_dir: Path = BENCH_DIR
):
    """``run.main`` on the CPU; returns (exit code, last stdout line as
    JSON or None, stderr)."""
    import run

    rc = run.main(
        ["--workload", workload, "--seed", str(seed), "--seconds", str(seconds), "--trace", str(trace)],
        root=root,
        platforms=("cpu",),
        bench_dir=bench_dir,
    )
    out, err = capsys.readouterr()
    lines = out.strip().splitlines()
    return rc, (json.loads(lines[-1]) if lines else None), err


@pytest.fixture
def bench_copy(tmp_path) -> Path:
    """A copy of the benchmark directory to add files to."""
    dst = tmp_path / "bench"
    shutil.copytree(BENCH_DIR, dst, ignore=shutil.ignore_patterns(".jax_cache", "__pycache__", "tests"))
    return dst


# a toy generator: a rows x cols grid, undirected, whose vertices the seed
# relabels by a random permutation
GRID_GENERATOR = '''
import numpy as np


def num_vertices(cfg):
    return int(cfg["rows"]) * int(cfg["cols"])


def vertex_map(cfg, seed):
    return np.random.default_rng(seed).permutation(num_vertices(cfg)).astype(np.int32)


def edges(cfg, seed):
    ids = np.arange(num_vertices(cfg)).reshape(int(cfg["rows"]), int(cfg["cols"]))
    a = np.concatenate([ids[:, :-1].ravel(), ids[:-1, :].ravel()])
    b = np.concatenate([ids[:, 1:].ravel(), ids[1:, :].ravel()])
    vmap = vertex_map(cfg, seed)
    return vmap[np.concatenate([a, b])], vmap[np.concatenate([b, a])]


def small(cfg, k):
    return dict(cfg, rows=1 << (k // 2), cols=1 << (k - k // 2))
'''


def grid_config() -> dict:
    """A configuration on the toy generator: a Kronecker one's queries,
    engine and limits, with the Kronecker keys replaced by the grid's."""
    config = config_file(MANIFEST["configs"][-1]["name"])
    for key in ("SCALE", "edgefactor", "A", "B", "C", "reduced", "assumed"):
        config.pop(key, None)
    config.update(name="grid32", generator="grid", rows=32, cols=32)
    return config


def add_grid_generator(bench_copy) -> None:
    (bench_copy / "generators").mkdir(exist_ok=True)
    (bench_copy / "generators" / "grid.py").write_text(GRID_GENERATOR)
