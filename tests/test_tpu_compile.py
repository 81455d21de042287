"""The kernels PallasBackend dispatches compile for a TPU v5e.

Each test lowers a kernel for one chip of a described ``v5e:2x2`` topology
and compiles it with the TPU compiler; no chip is attached, nothing runs.
That catches what the Pallas interpreter never checks: block shapes that
break the (8, 128) tiling rule, in-kernel ops Mosaic cannot lower, and
blocks that overflow VMEM, and more scalar prefetch than SMEM holds.
Shapes: the row-split table of the benchmark's undirected Graph500 SCALE-20
graph (2,048 dst tiles in 66,561 rows of 512 slots, padded to 66,568; a
512-tile window reads at most 16,987 rows, one tile at most 308, each
rounded up to 16,992 and 312), a ragged slice as the backend cuts them,
and a small graph.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU runtime, and every test worker imports
this file.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.degree_count.degree_count import EDGE_BLOCK, degree_count_pallas
from repro.kernels.spmv.ops import spmv_window
from repro.kernels.spmv.spmv import DST_TILE, ROW_BLOCK, SUB_CHUNK, spmv_pallas

SCALE20_V = 1 << 20
SCALE20_ROWS = 66_561
SCALE20_WINDOW_ROWS = {1: 308, 512: 16_987}


def _rows(rows: int) -> int:
    """Rows as ``build_tiles`` and ``TileTable.window_rows`` round them."""
    return -(-rows // ROW_BLOCK) * ROW_BLOCK


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else the compiler logs to /tmp
        try:
            yield topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to an enabled persistent
    cache but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize(
    "n_tiles,rows,num_vertices",
    [
        (SCALE20_V // DST_TILE, _rows(SCALE20_ROWS), SCALE20_V),   # the whole table
        (13, 32, 13 * DST_TILE),
        (2, 8, 1024),
    ],
    ids=["scale20", "ragged-slice", "small"],
)
def test_spmv_compiles_for_v5e(one_chip, no_persistent_cache, n_tiles, rows, num_vertices):
    compiled = _compile(
        functools.partial(spmv_pallas, n_tiles=n_tiles, interpret=False),
        one_chip,
        ((rows, SUB_CHUNK), jnp.int32),
        ((rows, SUB_CHUNK), jnp.int32),
        ((rows,), jnp.int32),
        ((num_vertices,), jnp.float32),
    )
    assert "tpu_custom_call" in compiled.as_text()
    out = compiled.out_info
    assert out.shape == (n_tiles, DST_TILE) and out.dtype == jnp.float32


@pytest.mark.parametrize("n_tiles", [1, 512])
def test_spmv_window_compiles_for_v5e(one_chip, no_persistent_cache, n_tiles):
    """The windowed call PallasBackend dispatches, over the whole scale-20
    table: row slices from a traced row, the row → tile map as scalar
    prefetch, the kernel, the masked write-back."""
    rows = _rows(SCALE20_ROWS)
    scalar = ((), jnp.int32)
    window = functools.partial(
        spmv_window, n_tiles=n_tiles, n_rows=_rows(SCALE20_WINDOW_ROWS[n_tiles]), interpret=False
    )
    compiled = _compile(
        lambda out, src, dstl, contrib, row, base, lo, hi, row_tile: window(
            out, src, dstl, contrib, row, base, lo, hi, row_tile=row_tile
        ),
        one_chip,
        ((SCALE20_V,), jnp.float32),
        ((rows, SUB_CHUNK), jnp.int32),
        ((rows, SUB_CHUNK), jnp.int32),
        ((SCALE20_V,), jnp.float32),
        scalar, scalar, scalar, scalar,
        ((rows,), jnp.int32),
    )
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.out_info.shape == (SCALE20_V,)


def test_degree_count_compiles_for_v5e(one_chip, no_persistent_cache):
    compiled = _compile(
        functools.partial(degree_count_pallas, num_counters=65_536, interpret=False),
        one_chip,
        ((EDGE_BLOCK * 8,), jnp.int32),
    )
    assert "tpu_custom_call" in compiled.as_text()
