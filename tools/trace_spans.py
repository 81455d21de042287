#!/usr/bin/env python3
"""Where the device's idle time goes, by the engine's own spans.

The engine marks its layers with ``mq.*`` spans (``repro.core.tracing``) on
the profiler's clock. This tool reads them out of a ``.xplane.pb`` beside
the device's programs and reduces them:

* ``program``: per span name, the count that starts in the window, the
  total time and the self time (time in which it is the innermost open span);
* ``idle_by_span``: device-idle time by the innermost open span, ``none``
  where no span is open;
* the numbers the benchmark's per-layer metrics would read:
  ``query_start_ms`` (mean ``mq.query_start``), ``schedule_ms_per_step``
  (self time of ``mq.prepare``, ``mq.decide`` and ``mq.account`` per
  step), ``syncs_per_step`` (``mq.sync`` per step), ``dispatch_idle_share``
  (device-idle time under ``mq.dispatch`` or the backend spans inside it,
  over the window, %), beside ``device_idle_share`` and ``spans_per_step``;
* ``gather_slots_per_edge``: the table slots the SpMV launches gathered
  over the edges their tiles hold (the ``slots``/``edges`` args of each
  ``mq.launch`` that starts in the window): 1 where no slot is padding.

The window is the benchmark's: from the first to the last harness span
(``engine``, ``execute:<kind>``), or the program spans' extent in a trace
without them. A step is one harness ``execute:<kind>`` span, or one
``mq.dispatch`` without them. The benchmark starts and stops the profiler
inside ``execute``, so the first step's ``mq.dispatch`` opened before the
trace did and is missing from it; its backend spans are there, and count
as dispatch time.

    python tools/trace_spans.py run --keep DIR -- --workload <cell> --seed <n> --seconds <s>
    python tools/trace_spans.py report DIR

``run`` runs a cell as ``bench/run.py --trace 1`` does (same replay, same
traced sub-window), keeps the profiler's files in ``DIR``, prints the
reduction and what one span costs with the profiler off and on, and writes
both to ``DIR/report.json``. ``report`` reduces a kept trace again.
"""
from __future__ import annotations

import argparse
import dataclasses
import glob
import json
import os
import shutil
import sys
import tempfile
import time
from collections import defaultdict
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
PREFIX = "mq."
DEVICE_LINES = ("XLA Modules", "XLA Ops")
SCHEDULE = ("mq.prepare", "mq.decide", "mq.account")
DISPATCH = ("mq.dispatch", "mq.host_prep", "mq.launch", "mq.sync", "mq.apply")


@dataclasses.dataclass
class Trace:
    spans: list[tuple[str, float, float]]    # mq.* (name, start, end), one thread
    harness: list[tuple[str, float, float]]  # engine / execute:<kind>
    device: list[tuple[float, float]]        # device programs and ops
    args: list[dict] = dataclasses.field(default_factory=list)  # per span


def load(trace_dir: str) -> Trace:
    """The newest ``.xplane.pb`` under ``trace_dir``; program spans from the
    host thread that holds the most of them."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    tr = Trace([], [], [])
    best: list = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name in DEVICE_LINES:
                    tr.device.extend((e.start_ns, e.start_ns + e.duration_ns) for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                mine = []
                for e in line.events:
                    if e.name.startswith(PREFIX):
                        mine.append((e.name, e.start_ns, e.start_ns + e.duration_ns, {k: v for k, v in e.stats}))
                    elif e.name == "engine" or e.name.startswith("execute:"):
                        tr.harness.append((e.name, e.start_ns, e.start_ns + e.duration_ns))
                if len(mine) > len(best):
                    best = mine
    tr.spans = [(n, s, e) for n, s, e, _ in best]
    tr.args = [a for *_, a in best]
    return tr


def _union(intervals):
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def segments(spans):
    """``(start, end, path)`` pieces of time over which the same spans are
    open, ``path`` outermost first. Spans of one thread nest; one that
    outlives its parent is cut at the parent's end."""
    out = []
    stack: list[tuple[float, str]] = []   # (end, name), innermost last
    t = 0.0

    def pop_until(x):
        nonlocal t
        while stack and stack[-1][0] <= x:
            end = stack[-1][0]
            if end > t:
                out.append((t, end, tuple(n for _, n in stack)))
                t = end
            stack.pop()

    for name, s, e in sorted(spans, key=lambda x: (x[1], -x[2])):
        pop_until(s)
        if stack and s > t:
            out.append((t, s, tuple(n for _, n in stack)))
        t = s
        stack.append((min(e, stack[-1][0]) if stack else e, name))
    pop_until(float("inf"))
    return out


def reduce(tr: Trace) -> dict | None:
    """The reduction over the window; ``None`` without program spans."""
    if not tr.spans:
        return None
    edges = tr.harness or tr.spans
    w0, w1 = min(s for _, s, _ in edges), max(e for _, _, e in edges)
    window = w1 - w0
    busy = _union([(max(a, w0), min(b, w1)) for a, b in tr.device if a < w1 and b > w0])
    gaps, edge = [], w0
    for a, b in busy + [(w1, w1)]:
        if a > edge:
            gaps.append((edge, a))
        edge = max(edge, b)
    segs = [(max(a, w0), min(b, w1), p) for a, b, p in segments(tr.spans) if a < w1 and b > w0]

    count: dict[str, int] = defaultdict(int)
    total: dict[str, float] = defaultdict(float)
    for name, s, e in tr.spans:
        if w0 <= s < w1:
            count[name] += 1
            total[name] += min(e, w1) - s
    self_ns: dict[str, float] = defaultdict(float)
    for a, b, path in segs:
        self_ns[path[-1]] += b - a

    idle: dict[str, float] = defaultdict(float)
    under_dispatch = 0.0
    j = 0
    for a, b in gaps:
        while j < len(segs) and segs[j][1] <= a:
            j += 1
        covered = 0.0
        k = j
        while k < len(segs) and segs[k][0] < b:
            s, e, path = segs[k]
            overlap = min(b, e) - max(a, s)
            if overlap > 0:
                idle[path[-1]] += overlap
                covered += overlap
                under_dispatch += overlap if any(n in DISPATCH for n in path) else 0.0
            k += 1
        if b - a > covered:
            idle["none"] += b - a - covered

    executes = sum(n.startswith("execute:") and w0 <= s < w1 for n, s, _ in tr.harness)
    steps = executes or count.get("mq.dispatch", 0)
    idle_ns = sum(b - a for a, b in gaps)

    def per_step(x):
        return x / steps if steps else None

    slots = gathered = 0
    for (name, s, _), a in zip(tr.spans, tr.args):
        if name == "mq.launch" and w0 <= s < w1 and "slots" in a:
            slots += a["slots"]
            gathered += a["edges"]

    starts = count.get("mq.query_start", 0)
    sched = sum(self_ns.get(n, 0.0) for n in SCHEDULE)
    return {
        "window_s": window / 1e9,
        "busy_s": (window - idle_ns) / 1e9,
        "metrics": {
            "query_start_ms": total["mq.query_start"] / starts / 1e6 if starts else None,
            "schedule_ms_per_step": per_step(sched / 1e6),
            "syncs_per_step": per_step(count.get("mq.sync", 0)),
            "dispatch_idle_share": 100.0 * under_dispatch / window if window else None,
            "device_idle_share": 100.0 * idle_ns / window if window else None,
            "spans_per_step": per_step(sum(count.values())),
            "gather_slots_per_edge": slots / gathered if gathered else None,
        },
        "program": {
            n: {"count": count.get(n, 0), "total_s": total.get(n, 0.0) / 1e9, "self_s": self_ns.get(n, 0.0) / 1e9}
            for n in sorted(set(count) | set(self_ns))
        },
        "idle_by_span": {n: ns / 1e9 for n, ns in sorted(idle.items(), key=lambda kv: -kv[1])},
        "idle_attributed": 1.0 - idle.get("none", 0.0) / idle_ns if idle_ns else None,
    }


def span_cost(n: int = 100_000) -> dict:
    """Microseconds per ``span`` (with ``session``/``query`` args) with the
    profiler off and on."""
    import jax

    from repro.core.tracing import span

    def per_span_us() -> float:
        t = time.perf_counter()
        for _ in range(n):
            with span("mq.sync", session=1, query=2):
                pass
        return (time.perf_counter() - t) / n * 1e6

    off = per_span_us()
    scratch = tempfile.mkdtemp(prefix="span-cost-")
    try:
        with jax.profiler.trace(scratch):
            on = per_span_us()
    finally:
        shutil.rmtree(scratch, ignore_errors=True)
    return {"span_us_off": off, "span_us_on": on}


def run_cell(bench_argv: list[str], keep: str) -> int:
    """``bench/run.py --trace 1`` with its profiler files kept in ``keep``."""
    sys.path.insert(0, str(ROOT / "bench"))
    import run as bench_run

    class KeepingTracer(bench_run.Tracer):
        def summary(self):
            if self.on is not None:
                shutil.copytree(self.dir, keep, dirs_exist_ok=True)
            return super().summary()

    bench_run.Tracer = KeepingTracer
    return bench_run.main(bench_argv + ["--trace", "1"], root=ROOT)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    sub = ap.add_subparsers(dest="cmd", required=True)
    r = sub.add_parser("run")
    r.add_argument("--keep", required=True)
    r.add_argument("bench", nargs=argparse.REMAINDER)
    sub.add_parser("report").add_argument("dir")
    args = ap.parse_args(argv)
    if args.cmd == "run":
        rc = run_cell([a for a in args.bench if a != "--"], args.keep)
        if rc:
            return rc
        out = {"reduction": reduce(load(args.keep)), "cost": span_cost()}
        Path(args.keep, "report.json").write_text(json.dumps(out, indent=1))
    else:
        out = {"reduction": reduce(load(args.dir))}
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.path.insert(0, str(ROOT / "src"))
    sys.exit(main())
