"""Jit'd wrapper + dst-tiled COO format builder (host-side, numpy)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .spmv import DST_TILE, SUB_CHUNK, spmv_pallas


def build_tiles(
    src, dst, num_vertices: int, *, dst_tile: int = DST_TILE, chunk_multiple: int = SUB_CHUNK
):
    """Sort edges by dst and bucket into per-dst-tile padded chunks.

    Returns (src_chunks [T, C], dstl_chunks [T, C], padded_v). Pad source id
    0 with local dst -1 (matches no lane)."""
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    v_pad = ((num_vertices + dst_tile - 1) // dst_tile) * dst_tile
    n_tiles = v_pad // dst_tile
    order = np.argsort(dst, kind="stable")
    src_s, dst_s = src[order], dst[order]
    tile_of = dst_s // dst_tile
    counts = np.bincount(tile_of, minlength=n_tiles)
    chunk = int(max(counts.max() if counts.size else 1, 1))
    chunk = ((chunk + chunk_multiple - 1) // chunk_multiple) * chunk_multiple
    src_chunks = np.zeros((n_tiles, chunk), np.int32)
    dstl_chunks = np.full((n_tiles, chunk), -1, np.int32)
    starts = np.concatenate([[0], np.cumsum(counts)])
    for t in range(n_tiles):
        lo, hi = starts[t], starts[t + 1]
        k = hi - lo
        src_chunks[t, :k] = src_s[lo:hi]
        dstl_chunks[t, :k] = dst_s[lo:hi] - t * dst_tile
    return jnp.asarray(src_chunks), jnp.asarray(dstl_chunks), v_pad


@functools.partial(jax.jit, static_argnames=("num_vertices", "interpret"))
def spmv(src_chunks, dstl_chunks, contrib, num_vertices: int, *, interpret: bool | None = None):
    """contrib [V] -> aggregated [num_vertices] (PR-pull inner product)."""
    out_tiles = spmv_pallas(src_chunks, dstl_chunks, contrib, interpret=interpret)
    return out_tiles.reshape(-1)[:num_vertices]


@functools.partial(jax.jit, static_argnames=("n_tiles", "dst_tile", "interpret"))
def spmv_window(
    out, src_chunks, dstl_chunks, contrib, row, base, lo, hi,
    *, n_tiles: int, dst_tile: int = DST_TILE, interpret: bool | None = None,
):
    """``out`` [V_pad] with the sums of ``n_tiles`` tiles written at vertex
    ``base``: the tiles are chunk-table rows ``row`` on, and targets outside
    ``[lo, hi)`` get 0.

    ``row``, ``base``, ``lo`` and ``hi`` are traced, so one compile serves
    every window of a length; ``base + n_tiles * dst_tile`` must not pass
    ``out``'s end (tile-aligned windows of a ``build_tiles`` table never do)."""
    src = jax.lax.dynamic_slice_in_dim(src_chunks, row, n_tiles)
    dstl = jax.lax.dynamic_slice_in_dim(dstl_chunks, row, n_tiles)
    flat = spmv_pallas(src, dstl, contrib, dst_tile=dst_tile, interpret=interpret).reshape(-1)
    ids = base + jnp.arange(flat.shape[0], dtype=jnp.int32)
    flat = jnp.where((ids >= lo) & (ids < hi), flat, 0.0)
    return jax.lax.dynamic_update_slice_in_dim(out, flat.astype(out.dtype), base, 0)
