"""What the harness owns around the engine: a timing proxy for the
execution backend, the query factory handed to ``run_sessions``, and a
compile counter.

The engine is observed only through these. The proxy satisfies the
program's ``ExecutionBackend`` protocol, times every ``execute`` call on the
host clock (each ends in ``block_until_ready`` inside the backend), reads the
executor's ``edges_traversed()`` before and after it, and notes when a query
finished. With tracing on it writes host spans on the profiler's clock:
``execute:<kind>`` around each call and ``engine`` between calls.
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Any, Callable

import jax

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS = "/jax/compilation_cache/cache_misses"


class CompileCounter:
    """Counts XLA compiles (persistent-cache hits included) and cache hits
    and misses through ``jax.monitoring``; ``compiled`` keeps each compile's
    host time and function name."""

    def __init__(self) -> None:
        self.counts: collections.Counter[str] = collections.Counter()
        self.compiled: list[tuple[float, str]] = []
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event: str, **_) -> None:
        if event in (_CACHE_HIT, _CACHE_MISS):
            self.counts[event] += 1

    def _duration(self, event: str, _secs: float, fun_name: str = "?", **_) -> None:
        if event == _BACKEND_COMPILE:
            self.counts[event] += 1
            self.compiled.append((time.perf_counter(), fun_name))

    def snapshot(self) -> dict[str, int]:
        return {
            "compiles": self.counts[_BACKEND_COMPILE],
            "cache_hits": self.counts[_CACHE_HIT],
            "cache_misses": self.counts[_CACHE_MISS],
        }


@dataclasses.dataclass
class Query:
    """One submitted query: its parameters, and what the run saw of it."""

    qid: int
    kind: str                 # "pagerank_pull" | "bfs" | "degree_count"
    param: Any                # damping, root, or None
    submitted: float = 0.0    # host clock: the batch's run_sessions call
    executor: Any = None
    edges: float = 0.0        # sum of edges_traversed() rises over its steps
    finished: float | None = None   # host clock: end of its last step
    result: Any = None        # host copy of the answer


@dataclasses.dataclass(frozen=True)
class Step:
    """One ``execute`` call: host start and end, the query, its edges."""

    start: float
    end: float
    qid: int
    kind: str
    edges: float


class Overdue(RuntimeError):
    """Raised inside the engine loop when a batch runs past its deadline."""


class TimedBackend:
    """``ExecutionBackend`` proxy that times and records every step."""

    def __init__(self, inner: Any, clock: Callable[[], float] = time.perf_counter):
        self.inner = inner
        self.name = inner.name
        self.clock = clock
        self.steps: list[Step] = []
        self.by_executor: dict[int, Query] = {}
        self.deadline: float | None = None
        # step-boundary hook: called with the clock before each execute (the
        # traced run starts and stops the profiler there)
        self.on_boundary: Callable[[float], None] | None = None
        self.annotate = False
        self._engine_span: Any = None

    def prepare(self, executor, prep, shard=None):
        if shard is None:
            return self.inner.prepare(executor, prep)
        return self.inner.prepare(executor, prep, shard)

    def execute(self, plan, step, modeled_ns: float = 0.0) -> float:
        now = self.clock()
        if self.deadline is not None and now > self.deadline:
            raise Overdue(f"batch still running {now - self.deadline:.1f} s past its deadline")
        if self.on_boundary is not None:
            self.on_boundary(now)
        q = self.by_executor[id(plan.executor)]
        if self._engine_span is not None:
            self._engine_span.__exit__(None, None, None)
            self._engine_span = None
        before = float(plan.executor.edges_traversed())
        t0 = self.clock()
        if self.annotate:
            with jax.profiler.TraceAnnotation(f"execute:{q.kind}"):
                ns = self.inner.execute(plan, step, modeled_ns=modeled_ns)
        else:
            ns = self.inner.execute(plan, step, modeled_ns=modeled_ns)
        t1 = self.clock()
        rise = float(plan.executor.edges_traversed()) - before
        self.steps.append(Step(t0, t1, q.qid, q.kind, rise))
        q.edges += rise
        if plan.executor.finished() and q.finished is None:
            q.finished = t1
            q.result = plan.executor.result()
            del self.by_executor[id(plan.executor)]
            q.executor = None
        if self.annotate:
            self._engine_span = jax.profiler.TraceAnnotation("engine")
            self._engine_span.__enter__()
        return ns

    def close_spans(self) -> None:
        if self._engine_span is not None:
            self._engine_span.__exit__(None, None, None)
            self._engine_span = None

    def factory(self, queries: list[Query], make: Callable[[Query], Any]):
        """The ``make_executor(session, query)`` for one batch: session ``s``
        runs ``queries[s]``."""

        def make_executor(session: int, _query: int):
            q = queries[session]
            q.executor = make(q)
            self.by_executor[id(q.executor)] = q
            return q.executor

        return make_executor
