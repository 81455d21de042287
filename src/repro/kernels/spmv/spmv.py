"""Pallas TPU kernel: tiled SpMV for PageRank-pull / GNN sum-aggregation.

Format: *row-split dst-tiled COO* built by ops.py — edges sorted by target
vertex and bucketed into tiles of DST_TILE consecutive targets; each tile's
edges are cut into rows of SUB_CHUNK slots, a tile with ``c`` edges taking
``max(ceil(c / SUB_CHUNK), 1)`` rows, in tile order. Only the tail of each
tile's last row is padding. Per tile,

    out[d] = Σ_{edges e in tile, dst_local(e)=d} contrib[src(e)]

is a one-hot(dst_local) reduction of the gathered contributions — no
scatter conflicts (each target tile is owned by the rows that hold its
edges; pull = owner-computes, the paper's no-atomics path).

Split of the work:

* the jitted wrapper gathers ``contrib[src_rows]`` in XLA, straight from
  HBM, so no kernel block holds the whole contribution vector;
* the kernel reduces the gathered ``[R, SUB_CHUNK]`` values against the
  ``dstl`` one-hot on the VPU. ``grid = (R / ROW_BLOCK,)``: a step takes
  ROW_BLOCK rows (the (8, 128) block rule), and a scalar-prefetched row →
  tile map says which tile's sums each row adds into; rows whose tile is
  negative add nothing. The largest live value is one
  ``[DST_TILE, SUB_CHUNK]`` compare (1 MiB).

The lane-axis reduction leaves a row's sums as a column, so the window's
output stays in VMEM for the whole grid as ``[n_tiles / 128, DST_TILE,
128]``, one lane per tile, and the wrapper transposes it back to
``[n_tiles, DST_TILE]``. Sums are exact float32 (order differs from a
sequential segment-sum only).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..platform import resolve_interpret

DST_TILE = 512
SUB_CHUNK = 512    # edge slots per row of the table (its width)
ROW_BLOCK = 8      # rows per grid step: the sublane tile
TILE_LANES = 128   # tiles per output block: one lane each


def _spmv_kernel(tile_ref, vals_ref, dstl_ref, out_ref, *, dst_tile: int):
    g = pl.program_id(0)

    @pl.when(g == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    width = vals_ref.shape[1]
    targets = jax.lax.broadcasted_iota(jnp.int32, (dst_tile, width), 0)
    lanes = jax.lax.broadcasted_iota(jnp.int32, (dst_tile, TILE_LANES), 1)
    for r in range(ROW_BLOCK):
        t = tile_ref[g * ROW_BLOCK + r]

        @pl.when(t >= 0)
        def _row():
            hit = dstl_ref[r : r + 1, :] == targets          # [DST_TILE, W]
            vals = jnp.where(hit, vals_ref[r : r + 1, :], 0.0)
            col = jnp.sum(vals, axis=1, keepdims=True)       # [DST_TILE, 1]
            blk = t // TILE_LANES
            out_ref[blk] += jnp.where(lanes == t % TILE_LANES, col, 0.0)


@functools.partial(jax.jit, static_argnames=("n_tiles", "dst_tile", "interpret"))
def spmv_pallas(
    src_rows: jnp.ndarray,      # [R, W] int32
    dstl_rows: jnp.ndarray,     # [R, W] int32 (local ids, pad -1)
    row_tile: jnp.ndarray,      # [R] int32: output tile of each row, < 0 for none
    contrib: jnp.ndarray,       # [V] f32
    *,
    n_tiles: int,
    dst_tile: int = DST_TILE,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Per-tile sums ``[n_tiles, dst_tile]``: row ``r`` adds into tile
    ``row_tile[r]``, which must be below ``n_tiles``; ``interpret=None``
    lets the platform decide at trace time
    (``kernels.platform.resolve_interpret``)."""
    rows, width = src_rows.shape
    assert rows % ROW_BLOCK == 0, "pad rows to a multiple of ROW_BLOCK (build_tiles)"
    vals = contrib[src_rows]                                 # XLA gather [R, W]
    blocks = -(-n_tiles // TILE_LANES)
    out_shape = (blocks, dst_tile, TILE_LANES)
    out = pl.pallas_call(
        functools.partial(_spmv_kernel, dst_tile=dst_tile),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(rows // ROW_BLOCK,),
            in_specs=[
                pl.BlockSpec((ROW_BLOCK, width), lambda g, tiles: (g, 0)),
                pl.BlockSpec((ROW_BLOCK, width), lambda g, tiles: (g, 0)),
            ],
            out_specs=pl.BlockSpec(out_shape, lambda g, tiles: (0, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct(out_shape, contrib.dtype),
        interpret=resolve_interpret(interpret),
        name="spmv_tiles",
    )(row_tile.astype(jnp.int32), vals, dstl_rows)
    return out.transpose(0, 2, 1).reshape(-1, dst_tile)[:n_tiles]
