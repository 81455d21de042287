"""``BENCHMARK.json`` against the benchmark's contract, and the harness
finding a configuration, mix or metric that is new files alone."""
import json
import re

import pytest

from conftest import BENCH_DIR, REPO

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    return json.loads((REPO / "BENCHMARK.json").read_text())


def test_top_level_keys(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert manifest["paths"] == ["bench"]
    assert manifest["command"][1] == "bench/run.py"
    assert 1 <= manifest["run_seconds"] <= 51 and isinstance(manifest["run_seconds"], int)


def test_names_and_units(manifest):
    metrics = manifest["end_to_end"] + manifest["per_layer"]
    for entry in manifest["configs"] + manifest["workloads"] + metrics:
        assert NAME.match(entry["name"]), entry["name"]
    for m in metrics:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for c in manifest["configs"]:
        assert all(NAME.match(k) for k in c["reduced"])
    for text in [w["why"] for w in manifest["workloads"]] + [c["why"] for c in manifest["configs"]]:
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text
    names = [e["name"] for e in metrics]
    assert len(names) == len(set(names))


def test_every_cell_has_its_files(manifest):
    configs = {c["name"]: c for c in manifest["configs"]}
    for w in manifest["workloads"]:
        assert w["chips"] in (1, 4)
        assert (REPO / configs[w["config"]]["file"]).is_file()
        assert (BENCH_DIR / "traffic" / f"{w['traffic']}.json").is_file()
    for c in manifest["configs"]:
        data = json.loads((REPO / c["file"]).read_text())
        assert data["name"] == c["name"]
        assert set(c["reduced"]) == set(data["reduced"])
        assert any(w["config"] == c["name"] for w in manifest["workloads"])
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert (BENCH_DIR / "metrics" / f"{m['name']}.py").is_file(), m["name"]


def test_bounds(manifest):
    for m in manifest["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25


def test_every_cell_reports_enough(manifest):
    cells = [w["name"] for w in manifest["workloads"]]

    def reported(m, cell):
        return cell in m.get("workloads", cells)

    for cell in cells:
        e2e = [m["name"] for m in manifest["end_to_end"] if reported(m, cell)]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert any(reported(m, cell) for m in manifest["per_layer"])


def test_per_layer_metrics_move_what_their_cells_report(manifest):
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    cells = [w["name"] for w in manifest["workloads"]]
    for m in manifest["per_layer"]:
        assert m["moves"] in e2e
        assert m["workloads"], m["name"]
        for cell in m["workloads"]:
            assert cell in cells
            assert cell in e2e[m["moves"]].get("workloads", cells), (m["name"], cell)


def test_new_config_mix_and_metric_are_picked_up_from_new_files(tmp_path, bench_copy):
    """A new configuration, traffic mix and per-layer metric, added as new
    files and new manifest entries only, are found by name."""
    from harness.manifest import load_cell, read_metrics

    root = tmp_path
    manifest = json.loads((REPO / "BENCHMARK.json").read_text())
    config = json.loads((REPO / manifest["configs"][-1]["file"]).read_text())
    config["name"] = "g500-s14"
    (bench_copy / "configs" / "g500-s14.json").write_text(json.dumps(config))
    (bench_copy / "traffic" / "pr-1s.json").write_text(
        json.dumps({"name": "pr-1s", "sessions": 1, "mix": {"pagerank_pull": 1}})
    )
    (bench_copy / "metrics" / "steps_in_window.py").write_text(
        "def read(run):\n    return len(run.window_steps())\n"
    )
    manifest["configs"].append(dict(manifest["configs"][1], name="g500-s14", file="bench/configs/g500-s14.json"))
    manifest["workloads"].append({"name": "g500-s14.pr-1s", "config": "g500-s14", "traffic": "pr-1s", "chips": 1, "why": "x"})
    manifest["per_layer"].append({
        "name": "steps_in_window", "unit": "steps", "better": "higher", "source": "host_clock",
        "layer": "engine host loop", "moves": "edges_per_s", "workloads": ["g500-s14.pr-1s"],
    })
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))

    cell = load_cell(root, "g500-s14.pr-1s", bench_dir=bench_copy)
    assert cell.config["name"] == "g500-s14"
    assert cell.traffic["mix"] == {"pagerank_pull": 1}
    assert [m.name for m in cell.per_layer] == ["steps_in_window"]
    assert [m.name for m in cell.end_to_end] == ["edges_per_s", "setup_s"]

    class FakeRun:
        def window_steps(self):
            return [1, 2, 3]

    assert read_metrics(cell.per_layer, FakeRun(), bench_dir=bench_copy) == {
        "steps_in_window": {"value": 3.0, "unit": "steps"}
    }
