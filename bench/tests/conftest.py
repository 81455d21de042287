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

TINY_SCALE = 10


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory) -> Path:
    """A checkout-shaped directory: the system under test, and a
    ``BENCHMARK.json`` whose cells run the real mixes on a SCALE-10 graph."""
    root = tmp_path_factory.mktemp("tiny")
    (root / "src").symlink_to(REPO / "src")
    manifest = json.loads((REPO / "BENCHMARK.json").read_text())
    config = json.loads((REPO / manifest["configs"][-1]["file"]).read_text())
    config.update(name="tiny", SCALE=TINY_SCALE)
    (root / "tiny.json").write_text(json.dumps(config))
    rename = {w["name"]: f"tiny.{w['traffic']}" for w in manifest["workloads"]}
    manifest["configs"] = [dict(manifest["configs"][0], name="tiny", file="tiny.json")]
    manifest["workloads"] = [
        dict(w, name=rename[w["name"]], config="tiny") for w in manifest["workloads"]
    ]
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [rename[w] for w in m["workloads"]]
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    return root


def run_cell(root: Path, workload: str, capsys, seed: int = 5, seconds: float = 2.0, trace: int = 0):
    """``run.main`` on the CPU; returns (exit code, last stdout line as
    JSON or None, stderr)."""
    import run

    rc = run.main(
        ["--workload", workload, "--seed", str(seed), "--seconds", str(seconds), "--trace", str(trace)],
        root=root,
        platforms=("cpu",),
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
