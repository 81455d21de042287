"""The control for ``correct``: the PageRank reference put in the
program's place and computed one precision below the configuration's
float32, in bfloat16, on the device. Its compared number (the largest
relative error against the float64 reference) has to fail the limit."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import reference


@functools.partial(jax.jit, static_argnames=("num_vertices", "iters"))
def pagerank_lowp(src, dst, out_deg, damping, *, num_vertices: int, iters: int):
    """Power iteration with ranks, contributions and sums in bfloat16."""
    dt = jnp.bfloat16
    dangling = out_deg == 0
    inv_deg = jnp.where(dangling, 0.0, 1.0 / jnp.maximum(out_deg, 1)).astype(dt)
    rank = jnp.full((num_vertices,), 1.0 / num_vertices, dt)
    d = damping.astype(dt)
    for _ in range(iters):
        acc = jax.ops.segment_sum((rank * inv_deg)[src], dst, num_segments=num_vertices)
        lost = jnp.sum(jnp.where(dangling, rank, jnp.zeros_like(rank)))
        rank = ((1 - d) / num_vertices + d * (acc + lost / num_vertices)).astype(dt)
    return rank


def control_errors(g: reference.Graph, dampings, iters: int) -> list[float]:
    """The control's compared number for each damping."""
    src = jnp.asarray(g.src, jnp.int32)
    dst = jnp.asarray(g.dst, jnp.int32)
    out_deg = jnp.asarray(g.out_deg, jnp.int32)
    errs = []
    for d in dampings:
        low = pagerank_lowp(
            src, dst, out_deg, jnp.float32(d), num_vertices=g.num_vertices, iters=iters
        )
        ref = reference.pagerank(g, d, iters)
        errs.append(reference.max_rel_err(np.asarray(low.astype(jnp.float32)), ref))
    return errs
