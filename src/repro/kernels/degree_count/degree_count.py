"""Pallas TPU kernel: degree count (vertex-ID histogram) — the paper's §5.1
calibration/reference algorithm.

CPU original: fetch-and-add atomics on a shared counter array, 16k-edge work
packages. TPU adaptation (DESIGN.md §2): atomics do not exist — each grid
step turns a 16k-edge block into a one-hot comparison tile and reduces it on
the VPU/MXU, accumulating *conflict-free* partial counters in VMEM; cross-
block combination happens through the sequential grid revisiting the same
output tile (and across devices via an explicit psum in ops.py).

Tiling:
  grid = (num_counter_tiles, num_edge_blocks)
  ids block:     [EDGE_BLOCK]            (VMEM, revisited per counter tile)
  counters tile: [COUNTER_TILE]          (VMEM accumulator, int32)

Inside a step a loop walks the id block SUB_BLOCK ids at a time, so one
compare is [SUB_BLOCK, COUNTER_TILE], not [EDGE_BLOCK, COUNTER_TILE]
(128 MiB of int32 if it were ever stored). Mosaic unrolls a compare over
vector registers, so kernel code grows with it: with the whole 16k-id block
in one compare the v5e kernel was ~1.6 MB of code and took ~10 s to compile
per distinct ids length; the loop brings that to ~0.14 MB and ~0.4 s (TPU
compiler, v5e target, no chip attached).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..platform import resolve_interpret

EDGE_BLOCK = 16 * 1024   # the paper's work-package grain (§5.1)
COUNTER_TILE = 2048
SUB_BLOCK = 1024         # ids per one-hot compare: one (8, 128) int32 tile


def _degree_count_kernel(ids_ref, out_ref, *, counter_tile: int, sub_block: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    i = pl.program_id(0)
    base = i * counter_tile
    lanes = base + jax.lax.broadcasted_iota(jnp.int32, (counter_tile,), 0)

    def count(k, acc):
        start = pl.multiple_of(k * sub_block, sub_block)
        ids = ids_ref[pl.ds(start, sub_block)]           # [SUB_BLOCK] int32
        # one-hot compare + reduce: [SUB_BLOCK, C_TILE] -> [C_TILE]
        onehot = (ids[:, None] == lanes[None, :]).astype(jnp.int32)
        return acc + jnp.sum(onehot, axis=0)

    steps = ids_ref.shape[0] // sub_block
    out_ref[...] += jax.lax.fori_loop(0, steps, count, jnp.zeros_like(lanes))


# jitted so an eager call reuses the traced kernel: a bare pallas_call
# traces the kernel again on every call (~0.25 s of host time on a v5e host)
@functools.partial(
    jax.jit, static_argnames=("num_counters", "edge_block", "counter_tile", "interpret")
)
def degree_count_pallas(
    ids: jnp.ndarray,
    num_counters: int,
    *,
    edge_block: int = EDGE_BLOCK,
    counter_tile: int = COUNTER_TILE,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Histogram of ``ids`` (already reduced mod num_counters by the caller).

    ids: [E] int32, padded with -1 (never matches a lane).
    Returns counts [num_counters] int32. ``interpret=None`` lets the
    platform decide at trace time (``kernels.platform.resolve_interpret``)."""
    e = ids.shape[0]
    sub_block = min(SUB_BLOCK, edge_block)
    assert e % edge_block == 0, "pad ids to a multiple of edge_block"
    assert edge_block % sub_block == 0, "edge_block must be a multiple of SUB_BLOCK"
    assert num_counters % counter_tile == 0, "pad counters to tile multiple"
    grid = (num_counters // counter_tile, e // edge_block)
    return pl.pallas_call(
        functools.partial(
            _degree_count_kernel, counter_tile=counter_tile, sub_block=sub_block
        ),
        grid=grid,
        in_specs=[pl.BlockSpec((edge_block,), lambda i, j: (j,))],
        out_specs=pl.BlockSpec((counter_tile,), lambda i, j: (i,)),
        out_shape=jax.ShapeDtypeStruct((num_counters,), jnp.int32),
        interpret=resolve_interpret(interpret),
        name="degree_count",
    )(ids)
