"""From a profiler trace to numbers: device busy time, programs by name,
idle gaps named by the host span they fell in.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote into plain
lists of ``(name, start_ns, duration_ns)``; ``reduce`` works on those lists
alone, so it is checked on a synthetic event list. Device events are the
``XLA Modules`` (one per program execution) and ``XLA Ops`` lines of every
``/device:`` plane; host spans are the harness's ``engine`` and
``execute:<kind>`` annotations. The traced window runs from the first host
span's start to the last one's end: tracing starts and stops at step
boundaries, so the spans tile it.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from collections import defaultdict

MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"


@dataclasses.dataclass
class TraceEvents:
    modules: list[tuple[str, float, float]]     # device programs, per plane
    ops: list[tuple[str, float, float]]         # device ops
    host: list[tuple[str, float, float]]        # engine / execute:<kind>
    devices: int = 1


def _is_host_span(name: str) -> bool:
    return name == "engine" or name.startswith("execute:")


def load(trace_dir: str) -> TraceEvents:
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    ev = TraceEvents([], [], [], devices=0)
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            seen = len(ev.modules) + len(ev.ops)
            for line in plane.lines:
                into = {MODULES_LINE: ev.modules, OPS_LINE: ev.ops}.get(line.name)
                if into is not None:
                    into.extend((e.name, e.start_ns, e.duration_ns) for e in line.events)
            # planes without programs (a runtime's custom plane) hold no chip
            ev.devices += len(ev.modules) + len(ev.ops) > seen
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                ev.host.extend(
                    (e.name, e.start_ns, e.duration_ns)
                    for e in line.events
                    if _is_host_span(e.name)
                )
    ev.devices = max(ev.devices, 1)
    return ev


def _program(name: str) -> str:
    """``jit_spmv_window(6015231107290252590)`` -> ``jit_spmv_window``."""
    return name.split("(", 1)[0]


def _op(name: str) -> str:
    """An HLO op's name and result shape, without layouts:
    ``%fusion = f32[40632320]{0:T(1024)} fusion(...)`` -> ``%fusion f32[40632320]``."""
    m = re.match(r"(\S+) = (\(?[^ ]+)", name)
    if m is None:
        return name[:120]
    return f"{m.group(1)} {re.sub(r'\{[^}]*\}', '', m.group(2))}"[:120]


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


@dataclasses.dataclass
class TraceSummary:
    window_ns: float
    busy_ns: float                     # union of device intervals, per device
    module_count: int
    module_ns: dict[str, float]        # device time per program name
    op_ns: dict[str, float]            # device time per op name
    gaps: list[tuple[str, float]]      # idle gaps (host span, ns), longest first
    execute_spans: int

    def module_time_ns(self, fragment: str) -> float:
        """Device time of the programs whose name holds ``fragment``."""
        return sum(t for name, t in self.module_ns.items() if fragment in name)

    def module_seen(self, fragment: str) -> bool:
        return any(fragment in name for name in self.module_ns)


def reduce(ev: TraceEvents) -> TraceSummary | None:
    """``None`` when the trace holds no host span or no device event."""
    if not ev.host or not (ev.modules or ev.ops):
        return None
    w0 = min(s for _, s, _ in ev.host)
    w1 = max(s + d for _, s, d in ev.host)
    clipped = [
        (max(s, w0), min(s + d, w1))
        for _, s, d in ev.modules + ev.ops
        if s < w1 and s + d > w0
    ]
    busy = _union(clipped)
    busy_ns = sum(b - a for a, b in busy) / ev.devices
    module_ns: dict[str, float] = defaultdict(float)
    count = 0
    for name, s, d in ev.modules:
        if w0 <= s < w1:
            module_ns[name] += d
            count += 1
    # ops by program: the module event that holds each op's start
    mods = sorted((s, s + d, name) for name, s, d in ev.modules)
    mod_starts = [s for s, _, _ in mods]
    op_ns: dict[str, float] = defaultdict(float)
    for name, s, d in ev.ops:
        if w0 <= s < w1:
            i = bisect.bisect_right(mod_starts, s) - 1
            program = _program(mods[i][2]) if i >= 0 and s < mods[i][1] else "?"
            op_ns[f"{program}: {_op(name)}"] += d
    # idle gaps: the window minus the busy union, each named by the host
    # span that overlaps it most
    gaps = []
    edge = w0
    for a, b in busy + [(w1, w1)]:
        if a > edge:
            gaps.append((edge, a))
        edge = max(edge, b)
    host = sorted((s, s + d, name) for name, s, d in ev.host)
    starts = [s for s, _, _ in host]
    longest = 0.0
    for s, e, _ in host:
        longest = max(longest, e - s)
    named = []
    for a, b in gaps:
        best, best_overlap = "untraced", 0.0
        first = bisect.bisect_left(starts, a - longest)
        for i in range(first, len(host)):
            s, e, name = host[i]
            if s >= b:
                break
            overlap = min(b, e) - max(a, s)
            if overlap > best_overlap:
                best, best_overlap = name, overlap
        named.append((best, b - a))
    named.sort(key=lambda g: -g[1])
    return TraceSummary(
        window_ns=w1 - w0,
        busy_ns=busy_ns,
        module_count=count,
        module_ns=dict(module_ns),
        op_ns=dict(op_ns),
        gaps=named,
        execute_spans=sum(name.startswith("execute:") for name, _, _ in ev.host),
    )


def breakdown(summary: TraceSummary, top: int = 10) -> dict:
    ops = sorted(summary.op_ns.items(), key=lambda kv: -kv[1])[:top]
    return {
        "device_ops": [[name, ns / 1e9] for name, ns in ops],
        "idle_gaps": [[name, ns / 1e9] for name, ns in summary.gaps[:top]],
    }
