"""Jit'd wrappers + row-split dst-tiled COO format builder (host-side, numpy)."""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from .spmv import DST_TILE, ROW_BLOCK, SUB_CHUNK, spmv_pallas


@dataclasses.dataclass
class TileTable:
    """A graph's edges as rows of ``SUB_CHUNK`` slots, in dst-tile order.

    Tile ``t`` (targets ``[t * dst_tile, (t + 1) * dst_tile)``, counted from
    ``first_tile``) holds rows ``[tile_row_start[t], tile_row_start[t + 1])``
    and edges ``[tile_edge_start[t], tile_edge_start[t + 1])``. Padding slots
    hold source 0 and local target -1 (matches no lane); the rows past the
    last tile, up to a multiple of ``ROW_BLOCK``, are padding too, with
    ``row_tile`` past every tile."""

    src: jax.Array                  # [R, SUB_CHUNK] int32 source ids
    dstl: jax.Array                 # [R, SUB_CHUNK] int32 target within the tile
    row_tile: jax.Array             # [R] int32 absolute tile of each row
    tile_row_start: np.ndarray      # [T + 1] host prefix sum of rows per tile
    tile_edge_start: np.ndarray     # [T + 1] host prefix sum of edges per tile
    first_tile: int = 0
    _window_rows: dict = dataclasses.field(default_factory=dict, repr=False)

    @property
    def n_tiles(self) -> int:
        return len(self.tile_row_start) - 1

    @property
    def nbytes(self) -> int:
        return self.src.nbytes + self.dstl.nbytes + self.row_tile.nbytes

    def window_rows(self, n_tiles: int) -> int:
        """Rows a window of ``n_tiles`` tiles reads: the most that any such
        window of this table holds, rounded up to ``ROW_BLOCK``, so the
        window's shape depends on its length alone."""
        rows = self._window_rows.get(n_tiles)
        if rows is None:
            trs = self.tile_row_start
            most = int((trs[n_tiles:] - trs[:-n_tiles]).max())
            rows = self._window_rows[n_tiles] = -(-most // ROW_BLOCK) * ROW_BLOCK
        return rows

    def slab(self, t0: int, t1: int) -> "TileTable":
        """The table of absolute tiles ``[t0, t1)`` alone, as a device that
        holds only those tiles would; row tiles stay absolute."""
        a, b = t0 - self.first_tile, t1 - self.first_tile
        r0, r1 = int(self.tile_row_start[a]), int(self.tile_row_start[b])
        pad = -(r1 - r0) % ROW_BLOCK
        return TileTable(
            src=jnp.pad(self.src[r0:r1], ((0, pad), (0, 0))),
            dstl=jnp.pad(self.dstl[r0:r1], ((0, pad), (0, 0)), constant_values=-1),
            row_tile=jnp.pad(self.row_tile[r0:r1], (0, pad), constant_values=t1),
            tile_row_start=self.tile_row_start[a : b + 1] - r0,
            tile_edge_start=self.tile_edge_start[a : b + 1] - self.tile_edge_start[a],
            first_tile=t0,
        )


def build_tiles(src, dst, num_vertices: int, *, dst_tile: int = DST_TILE) -> TileTable:
    """Sort edges by dst, bucket them into dst tiles and cut each tile into
    rows of ``SUB_CHUNK`` slots: a tile with ``c`` edges gets
    ``max(ceil(c / SUB_CHUNK), 1)`` rows."""
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    n_tiles = -(-num_vertices // dst_tile)
    order = np.argsort(dst, kind="stable")
    src_s, dst_s = src[order], dst[order]
    tile_of = dst_s // dst_tile
    counts = np.bincount(tile_of, minlength=n_tiles)
    rows = np.maximum(-(-counts // SUB_CHUNK), 1)
    row_start = np.concatenate([[0], np.cumsum(rows)])
    edge_start = np.concatenate([[0], np.cumsum(counts)])
    n_rows = -(-int(row_start[-1]) // ROW_BLOCK) * ROW_BLOCK
    # an edge's slot: its tile's first slot plus its rank inside the tile
    slot = row_start[tile_of] * SUB_CHUNK + np.arange(dst_s.size) - edge_start[tile_of]
    src_rows = np.zeros(n_rows * SUB_CHUNK, np.int32)
    dstl_rows = np.full(n_rows * SUB_CHUNK, -1, np.int32)
    src_rows[slot] = src_s
    dstl_rows[slot] = dst_s - tile_of * dst_tile
    row_tile = np.full(n_rows, n_tiles, np.int32)
    row_tile[: row_start[-1]] = np.repeat(np.arange(n_tiles, dtype=np.int32), rows)
    return TileTable(
        src=jnp.asarray(src_rows.reshape(n_rows, SUB_CHUNK)),
        dstl=jnp.asarray(dstl_rows.reshape(n_rows, SUB_CHUNK)),
        row_tile=jnp.asarray(row_tile),
        tile_row_start=row_start,
        tile_edge_start=edge_start,
    )


@functools.partial(jax.jit, static_argnames=("num_vertices", "interpret"))
def spmv(src_rows, dstl_rows, row_tile, contrib, num_vertices: int, *, interpret: bool | None = None):
    """contrib [V] -> aggregated [num_vertices] (PR-pull inner product) over
    a whole ``build_tiles`` table."""
    n_tiles = -(-num_vertices // DST_TILE)
    tiles = jnp.where(row_tile < n_tiles, row_tile, -1)
    out_tiles = spmv_pallas(src_rows, dstl_rows, tiles, contrib, n_tiles=n_tiles, interpret=interpret)
    return out_tiles.reshape(-1)[:num_vertices]


@functools.partial(jax.jit, static_argnames=("n_tiles", "n_rows", "dst_tile", "interpret"))
def spmv_window(
    out, src_rows, dstl_rows, contrib, row, base, lo, hi,
    *, row_tile, n_tiles: int, n_rows: int, dst_tile: int = DST_TILE,
    interpret: bool | None = None,
):
    """``out`` [V_pad] with the sums of ``n_tiles`` tiles written at vertex
    ``base``: the tiles' rows are table rows ``row`` on, and targets outside
    ``[lo, hi)`` get 0.

    The window reads ``n_rows`` rows (``TileTable.window_rows``) and keeps
    those whose ``row_tile`` lies in the window's tiles ``[base / dst_tile,
    base / dst_tile + n_tiles)``: rows past the window, and rows before
    ``row`` where ``dynamic_slice`` clamps a start near the table's end, add
    nothing. ``row``, ``base``, ``lo`` and ``hi`` are traced, so one compile
    serves every window of a length; ``base`` is tile-aligned and ``base +
    n_tiles * dst_tile`` must not pass ``out``'s end (tile-aligned windows
    of a ``build_tiles`` table never do)."""
    src = jax.lax.dynamic_slice_in_dim(src_rows, row, n_rows)
    dstl = jax.lax.dynamic_slice_in_dim(dstl_rows, row, n_rows)
    tiles = jax.lax.dynamic_slice_in_dim(row_tile, row, n_rows) - base // dst_tile
    tiles = jnp.where(tiles < n_tiles, tiles, -1)   # the kernel skips tiles < 0
    flat = spmv_pallas(
        src, dstl, tiles, contrib, n_tiles=n_tiles, dst_tile=dst_tile, interpret=interpret
    ).reshape(-1)
    ids = base + jnp.arange(flat.shape[0], dtype=jnp.int32)
    flat = jnp.where((ids >= lo) & (ids < hi), flat, 0.0)
    return jax.lax.dynamic_update_slice_in_dim(out, flat.astype(out.dtype), base, 0)
