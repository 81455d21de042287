"""The reduction from trace events to numbers, on a synthetic list."""
import pytest

from harness import trace


def _events():
    # host spans tile [0, 100): execute 0-40, engine 40-50, execute 50-90, engine 90-100
    host = [("execute:bfs", 0, 40), ("engine", 40, 10), ("execute:pagerank_pull", 50, 40), ("engine", 90, 10)]
    modules = [
        ("jit_spmv_window(1)", 5, 20),      # 5-25
        ("jit_add", 25, 5),                 # 25-30
        ("jit_spmv_window(1)", 55, 30),     # 55-85
        ("jit_degree_count_pallas", 120, 5),  # outside the window
    ]
    ops = [
        ("%fusion = f32[40]{0:T(1024)} fusion(f32[8]{0} %a)", 5, 15),
        ("%spmv.1 = f32[4,8]{1,0:T(8,128)} custom-call(f32[40]{0} %r)", 20, 5),
        ("add", 25, 5),
        ("%fusion = f32[40]{0:T(1024)} fusion(f32[8]{0} %a)", 55, 25),
        ("%spmv.1 = f32[4,8]{1,0:T(8,128)} custom-call(f32[40]{0} %r)", 80, 5),
    ]
    return trace.TraceEvents(modules=modules, ops=ops, host=host, devices=1)


def test_reduce_busy_idle_and_programs():
    s = trace.reduce(_events())
    assert s.window_ns == 100
    assert s.busy_ns == 25 + 30          # 5-30 and 55-85
    assert s.module_count == 3
    assert s.module_time_ns("spmv_window") == 50
    assert not s.module_seen("degree_count")
    assert s.execute_spans == 2
    # gaps: 0-5 (execute:bfs), 30-55 (engine 40-50 covers 10 of 25, execute:bfs 10,
    # execute:pagerank_pull 5), 85-100 (engine 10 of 15)
    assert sorted(g[1] for g in s.gaps) == [5, 15, 25]
    named = dict((round(ns), name) for name, ns in s.gaps)
    assert named[5] == "execute:bfs"
    assert named[15] == "engine"


def test_breakdown_lists_ops_and_gaps_in_seconds():
    b = trace.breakdown(trace.reduce(_events()))
    assert b["device_ops"][0] == ["jit_spmv_window: %fusion f32[40]", pytest.approx(40e-9)]
    assert ["jit_add: add", pytest.approx(5e-9)] in b["device_ops"]
    assert len(b["idle_gaps"]) == 3 and b["idle_gaps"][0][1] == pytest.approx(25e-9)


def test_only_planes_with_programs_count_as_chips(tmp_path):
    ev = _events()
    ev.devices = 1
    s = trace.reduce(ev)
    assert 100 * (1 - s.busy_ns / s.window_ns) == pytest.approx(45.0)


def test_nothing_to_read_gives_none():
    assert trace.reduce(trace.TraceEvents([], [], [("engine", 0, 5)])) is None
    assert trace.reduce(trace.TraceEvents([("m", 0, 1)], [], [])) is None
