"""Activation-sharding context: models call ``constrain(x, logical_axes)``
at their hot intermediates; when a mesh context is active the call becomes a
``with_sharding_constraint`` under the rule table, otherwise it is a no-op
(single-device tests/benchmarks never pay for it).
"""
from __future__ import annotations

import contextlib
import contextvars
from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding

from .rules import default_rules, spec_for

_ACTIVE: contextvars.ContextVar[tuple[Mesh, dict] | None] = contextvars.ContextVar(
    "repro_sharding_ctx", default=None
)


@contextlib.contextmanager
def activation_sharding(mesh: Mesh, rules: dict | None = None):
    token = _ACTIVE.set((mesh, rules or default_rules(mesh)))
    try:
        yield
    finally:
        _ACTIVE.reset(token)


def active() -> tuple[Mesh, dict] | None:
    return _ACTIVE.get()


def sharding_for(axes: tuple | None, shape: tuple[int, ...]) -> NamedSharding | None:
    """The active context's sharding for an array of ``shape`` with these
    logical axes; ``None`` outside a context (single device)."""
    ctx = _ACTIVE.get()
    if ctx is None or axes is None:
        return None
    mesh, rules = ctx
    return NamedSharding(mesh, spec_for(tuple(axes), shape, mesh, rules))


def constrain(x: Any, axes: tuple | None):
    sharding = sharding_for(axes, x.shape)
    if sharding is None:
        return x
    return jax.lax.with_sharding_constraint(x, sharding)


# --- scan unrolling for dry-run cost accounting ---------------------------
# XLA's cost_analysis counts a while-loop body ONCE regardless of trip count.
# The dry-run therefore lowers tiny-depth analysis variants with scans fully
# unrolled (trip counts 1 and 2) and scales the per-layer delta analytically.
_UNROLL: contextvars.ContextVar[bool] = contextvars.ContextVar(
    "repro_scan_unroll", default=False
)


@contextlib.contextmanager
def unrolled_scans():
    token = _UNROLL.set(True)
    try:
        yield
    finally:
        _UNROLL.reset(token)


def scan_unroll() -> bool:
    """Pass as lax.scan(..., unroll=scan_unroll())."""
    return _UNROLL.get()
