"""Spans on the profiler's clock, inside the engine and its backend.

``span(name, **args)`` is a ``jax.profiler.TraceAnnotation``: with no
profiler session it costs one short-lived object; under ``jax.profiler.trace``
it lands in the same ``.xplane.pb`` as the device's ``XLA Modules`` and
``XLA Ops``, on their clock, so each device idle gap can be put down to the
innermost span open over it. Spans carry ``session=`` and ``query=`` where
the caller knows them; a span's parent is the span enclosing it on the same
thread. What each name covers is listed in ``docs/ARCHITECTURE.md``
("Tracing").
"""
from __future__ import annotations

import jax

SPANS = (
    "mq.query_start",  # make_executor + start() + the query's record
    "mq.prepare",      # frontier read -> sample -> estimate -> cost -> bounds -> package, placement
    "mq.decide",       # policy bounds, run install, the scheduler's next step
    "mq.dispatch",     # one ExecutionBackend prepare (memoized) + execute
    "mq.account",      # iteration and width bookkeeping, pool samples, wake-ups
    "mq.host_prep",    # host work that feeds a launch: indicators, padding, uploads
    "mq.launch",       # enqueueing one kernel program
    "mq.sync",         # one host wait on a device value
    "mq.apply",        # folding a result into the executor
)


def span(name: str, **args) -> jax.profiler.TraceAnnotation:
    """A host span ``name`` with ``args`` (``session=``, ``query=``...)."""
    return jax.profiler.TraceAnnotation(name, **args)
