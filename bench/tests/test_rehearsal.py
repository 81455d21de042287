"""A CPU rehearsal of ``run.py`` on the tiny graph with interpreted kernels."""
import json

import pytest

from conftest import TINY_CELLS, run_cell


@pytest.mark.parametrize("workload", sorted(TINY_CELLS))
@pytest.mark.parametrize("trace", [0, 1])
def test_cell_runs_and_is_correct(tiny_root, capsys, workload, trace):
    # a window that finishes whole batches of the undirected graph's BFS
    # on a loaded CPU, so the latency tail has samples
    rc, result, err = run_cell(tiny_root, workload, capsys, seed=2**31 + 17, seconds=6.0, trace=trace)
    assert rc == 0, err
    assert list(result)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(result)[-1] == "checks"
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["device"]["platform"] == "cpu"
    manifest = json.loads((tiny_root / "BENCHMARK.json").read_text())
    group = manifest["per_layer" if trace else "end_to_end"]
    expected = {m["name"] for m in group if workload in m.get("workloads", [workload])}
    # the CPU trace has no device plane, so the trace readers find nothing
    assert set(result["metrics"]) <= expected
    if not trace:
        assert set(result["metrics"]) == expected
        assert all(m["value"] > 0 for m in result["metrics"].values())
    for name, check in result["checks"].items():
        assert f"check {name} " in err
    assert err.strip().splitlines()[-1].startswith("check ")


def test_same_seed_same_inputs(tiny_root):
    import run  # noqa: F401  (puts the harness on the path)
    from harness import graphs

    config = json.loads((tiny_root / "tiny.json").read_text())
    edges = graphs.load(config).edges
    a = edges(config, 2**33 + 5)
    b = edges(config, 2**33 + 5)
    c = edges(config, 2**33 + 6)
    assert all((x == y).all() for x, y in zip(a, b))
    assert not (a[0] == c[0]).all()


def test_refuses_a_platform_that_is_not_a_tpu(tiny_root, capsys):
    import run

    with pytest.raises(SystemExit) as exc:
        run.main(["--workload", sorted(TINY_CELLS)[0], "--seed", "1", "--seconds", "1"], root=tiny_root)
    assert exc.value.code == 3
    out, err = capsys.readouterr()
    assert out == "" and "nothing was run" in err


def test_refuses_a_directory_without_the_system(tmp_path, capsys):
    import run

    (tmp_path / "BENCHMARK.json").write_text("{}")
    with pytest.raises(SystemExit) as exc:
        run.main(["--workload", "x", "--seed", "1", "--seconds", "1"], root=tmp_path, platforms=("cpu",))
    assert exc.value.code == 3
    assert capsys.readouterr().out == ""
