"""Pallas TPU kernel: tiled SpMV for PageRank-pull / GNN sum-aggregation.

Format: *dst-tiled COO* built by ops.py — edges sorted by target vertex and
bucketed into tiles of DST_TILE consecutive targets; each tile's edge chunk
is padded to a common CHUNK length (ELL-by-tile). Per tile,

    out[d] = Σ_{edges e in tile, dst_local(e)=d} contrib[src(e)]

is a one-hot(dst_local) reduction of the gathered contributions — no
scatter conflicts (each target tile is owned by exactly one grid row;
pull = owner-computes, the paper's no-atomics path).

Split of the work:

* the jitted wrapper gathers ``contrib[src_chunks]`` in XLA, straight from
  HBM, so no kernel block holds the whole contribution vector;
* the kernel reduces the gathered ``[T, CHUNK]`` values against the
  ``dstl`` one-hot on the VPU. ``grid = (T / TILE_ROWS, CHUNK / SUB_CHUNK)``:
  a step takes TILE_ROWS tiles × SUB_CHUNK edge slots (the (8, 128) block
  rule), and the chunk axis revisits the same output block, accumulating.
  The largest live value is one ``[DST_TILE, SUB_CHUNK]`` compare (1 MiB).

The lane-axis reduction leaves each tile's sums as a column, so the kernel
writes a ``[T / TILE_ROWS, DST_TILE, TILE_ROWS]`` block layout and the
wrapper transposes it back to ``[T, DST_TILE]``. Sums are exact float32
(order differs from a sequential segment-sum only).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..platform import resolve_interpret

DST_TILE = 512
TILE_ROWS = 8      # dst tiles per grid step: the sublane tile
SUB_CHUNK = 512    # edge slots per grid step; build_tiles pads CHUNK to it


def _spmv_kernel(vals_ref, dstl_ref, out_ref, *, dst_tile: int):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    sub = vals_ref.shape[1]
    targets = jax.lax.broadcasted_iota(jnp.int32, (dst_tile, sub), 0)
    for r in range(vals_ref.shape[0]):
        hit = dstl_ref[r : r + 1, :] == targets              # [DST_TILE, SUB]
        vals = jnp.where(hit, vals_ref[r : r + 1, :], 0.0)
        out_ref[0, :, r : r + 1] += jnp.sum(vals, axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("dst_tile", "interpret"))
def spmv_pallas(
    src_chunks: jnp.ndarray,    # [n_tiles, CHUNK] int32
    dstl_chunks: jnp.ndarray,   # [n_tiles, CHUNK] int32 (local ids, pad -1)
    contrib: jnp.ndarray,       # [V] f32
    *,
    dst_tile: int = DST_TILE,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Per-tile sums ``[n_tiles, dst_tile]``; ``interpret=None`` lets the
    platform decide at trace time (``kernels.platform.resolve_interpret``)."""
    n_tiles, chunk = src_chunks.shape
    sub = min(SUB_CHUNK, chunk)
    assert chunk % sub == 0, "pad CHUNK to a multiple of SUB_CHUNK (build_tiles)"
    vals = contrib[src_chunks]                               # XLA gather [T, C]
    pad = -n_tiles % TILE_ROWS
    if pad:
        vals = jnp.pad(vals, ((0, pad), (0, 0)))
        dstl_chunks = jnp.pad(dstl_chunks, ((0, pad), (0, 0)), constant_values=-1)
    groups = (n_tiles + pad) // TILE_ROWS
    out = pl.pallas_call(
        functools.partial(_spmv_kernel, dst_tile=dst_tile),
        grid=(groups, chunk // sub),
        in_specs=[
            pl.BlockSpec((TILE_ROWS, sub), lambda i, j: (i, j)),
            pl.BlockSpec((TILE_ROWS, sub), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((1, dst_tile, TILE_ROWS), lambda i, j: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((groups, dst_tile, TILE_ROWS), contrib.dtype),
        interpret=resolve_interpret(interpret),
        name="spmv_tiles",
    )(vals, dstl_chunks)
    return out.transpose(0, 2, 1).reshape(-1, dst_tile)[:n_tiles]
