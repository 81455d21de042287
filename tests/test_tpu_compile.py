"""The kernels PallasBackend dispatches compile for a TPU v5e.

Each test lowers a kernel for one chip of a described ``v5e:2x2`` topology
and compiles it with the TPU compiler; no chip is attached, nothing runs.
That catches what the Pallas interpreter never checks: block shapes that
break the (8, 128) tiling rule, in-kernel ops Mosaic cannot lower, and
blocks that overflow VMEM. Shapes: the scale-20 RMAT graph ``chip_smoke.py``
runs (2,048 dst tiles; its largest tile holds 82,982 edges), a ragged slice
as the backend cuts them, and a small graph.

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
from repro.kernels.spmv.spmv import DST_TILE, SUB_CHUNK, spmv_pallas

SCALE20_V = 1 << 20
SCALE20_MAX_TILE_EDGES = 82_982


def _chunk(max_tile_edges: int) -> int:
    """CHUNK as ``build_tiles`` pads it."""
    return -(-max_tile_edges // SUB_CHUNK) * SUB_CHUNK


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
    "n_tiles,chunk,num_vertices",
    [
        (SCALE20_V // DST_TILE, _chunk(SCALE20_MAX_TILE_EDGES), SCALE20_V),
        (13, _chunk(700), 13 * DST_TILE),
        (2, _chunk(100), 1024),
    ],
    ids=["scale20", "ragged-slice", "small"],
)
def test_spmv_compiles_for_v5e(one_chip, no_persistent_cache, n_tiles, chunk, num_vertices):
    compiled = _compile(
        functools.partial(spmv_pallas, interpret=False),
        one_chip,
        ((n_tiles, chunk), jnp.int32),
        ((n_tiles, chunk), jnp.int32),
        ((num_vertices,), jnp.float32),
    )
    assert "tpu_custom_call" in compiled.as_text()
    out = compiled.out_info
    assert out.shape == (n_tiles, DST_TILE) and out.dtype == jnp.float32


@pytest.mark.parametrize("n_tiles", [2, 512])
def test_spmv_window_compiles_for_v5e(one_chip, no_persistent_cache, n_tiles):
    """The windowed call PallasBackend dispatches, over the whole scale-20
    tables: a slice from a traced row, the kernel, the masked write-back."""
    t, chunk = SCALE20_V // DST_TILE, _chunk(SCALE20_MAX_TILE_EDGES)
    scalar = ((), jnp.int32)
    compiled = _compile(
        functools.partial(spmv_window, n_tiles=n_tiles, interpret=False),
        one_chip,
        ((SCALE20_V,), jnp.float32),
        ((t, chunk), jnp.int32),
        ((t, chunk), jnp.int32),
        ((SCALE20_V,), jnp.float32),
        scalar, scalar, scalar, scalar,
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
