"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, interpret mode
(asked for explicitly: it is what the kernels resolve to on the CPU)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.attention import attention_ref, flash_attention, flash_attention_pallas
from repro.kernels.degree_count import degree_count, degree_count_ref
from repro.kernels.embedding_bag import embedding_bag, embedding_bag_ref
from repro.kernels.scoring import score_topk, scoring_pallas, scoring_ref, topk_ref
from repro.kernels.spmv import build_tiles, spmv, spmv_ref
from repro.kernels.spmv.ops import spmv_window
from repro.kernels.spmv.spmv import DST_TILE, SUB_CHUNK


# ---------------- degree count ----------------

@pytest.mark.parametrize("v,e", [(100, 1000), (3000, 40000), (2048, 16384), (5000, 100_000)])
def test_degree_count_shapes(v, e, rng):
    src = jnp.asarray(rng.integers(0, v, e), jnp.int32)
    dst = jnp.asarray(rng.integers(0, v, e), jnp.int32)
    out = degree_count(src, dst, v, interpret=True)
    ref = degree_count_ref(jnp.concatenate([src, dst]) % v, v)
    assert jnp.array_equal(out, ref)
    assert int(out.sum()) == 2 * e


def test_degree_count_modular(rng):
    """Counter array smaller than the id space (Eq. 11: M varies freely)."""
    ids = rng.integers(0, 100_000, 5000)
    out = degree_count(
        jnp.asarray(ids, jnp.int32), jnp.asarray(ids, jnp.int32), 257, interpret=True
    )
    ref = degree_count_ref(jnp.asarray(ids % 257, jnp.int32), 257) * 2
    assert jnp.array_equal(out, ref)


# ---------------- spmv ----------------

@pytest.mark.parametrize("v,e", [(100, 500), (2000, 30000), (513, 7000)])
@pytest.mark.parametrize("dtype", [jnp.float32])
def test_spmv_shapes(v, e, dtype, rng):
    src = rng.integers(0, v, e)
    dst = rng.integers(0, v, e)
    contrib = jnp.asarray(rng.normal(size=v).astype(dtype))
    t = build_tiles(src, dst, v)
    out = spmv(t.src, t.dstl, t.row_tile, contrib, v, interpret=True)
    ref = spmv_ref(jnp.asarray(src, jnp.int32), jnp.asarray(dst, jnp.int32), contrib, v)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


def test_spmv_empty_rows(rng):
    v = 600
    src = rng.integers(0, v, 100)
    dst = np.full(100, 3)  # everything lands on one vertex
    contrib = jnp.ones(v, jnp.float32)
    t = build_tiles(src, dst, v)
    out = spmv(t.src, t.dstl, t.row_tile, contrib, v, interpret=True)
    assert float(out[3]) == pytest.approx(100.0)
    assert float(out.sum()) == pytest.approx(100.0)


def _row_graph(case, rng):
    """``(src, dst, V)`` with the tile sizes a row-split case needs: 6
    tiles of 512 targets (130 for "wide"), 5,000 random edges, and per
    case a hub tile whose edges fill several rows or an empty tile."""
    v = (130 if case == "wide" else 6) * DST_TILE
    src = rng.integers(0, v, 5000)
    dst = rng.integers(0, v, 5000)
    if case in ("hub", "wide"):
        # 3,000 more edges: 6+ rows against 1-2 elsewhere, in tile 2 of 6
        # or in the last of 130, past a 128-tile window from tile 0
        hub = 2 if case == "hub" else 129
        src = np.concatenate([src, rng.integers(0, v, 3000)])
        dst = np.concatenate([dst, rng.integers(hub * DST_TILE, (hub + 1) * DST_TILE, 3000)])
    elif case == "empty-tile":
        keep = dst // DST_TILE != 3
        src, dst = src[keep], dst[keep]
    return src, dst, v


@pytest.mark.parametrize(
    "case,a,n_tiles,clamped",
    [
        ("hub", 2, 1, False),          # the hub tile alone, split over several rows
        ("hub", 1, 3, False),          # windows at offsets and lengths around it,
        ("hub", 0, 6, False),          # reading rows past their last tile
        ("hub", 5, 1, False),
        ("hub", 3, 2, True),           # rows past the table's end: the start clamps
        ("hub", 4, 2, True),
        ("empty-tile", 3, 1, False),   # a tile with no edges has one empty row
        ("empty-tile", 2, 3, False),
        ("wide", 0, 128, False),       # rows past the window's 128 output lanes
    ],
)
def test_spmv_window_row_table(case, a, n_tiles, clamped, rng):
    src, dst, v = _row_graph(case, rng)
    t = build_tiles(src, dst, v)
    contrib = jnp.asarray(rng.normal(size=v).astype(np.float32))
    ref = np.asarray(spmv_ref(jnp.asarray(src, jnp.int32), jnp.asarray(dst, jnp.int32), contrib, v))
    rows = t.window_rows(n_tiles)
    row = int(t.tile_row_start[a])
    assert row + rows >= int(t.tile_row_start[a + n_tiles])
    assert (row + rows > t.src.shape[0]) == clamped
    lo, hi = a * DST_TILE + 5, (a + n_tiles) * DST_TILE - 7
    out = spmv_window(
        jnp.full((v,), 9.0, jnp.float32), t.src, t.dstl, contrib, row, a * DST_TILE, lo, hi,
        row_tile=t.row_tile, n_tiles=n_tiles, n_rows=rows, interpret=True,
    )
    want = np.full(v, 9.0, np.float32)
    want[a * DST_TILE : (a + n_tiles) * DST_TILE] = 0.0
    want[lo:hi] = ref[lo:hi]
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("case", ["hub", "empty-tile", "one-vertex"])
def test_row_table_slots_are_bounded(case, rng):
    """Rows never hold more than ``E + T * (SUB_CHUNK - 1)`` slots, each tile
    at least one row, and every edge sits in its tile's rows."""
    if case == "one-vertex":
        src, dst, v = rng.integers(0, 600, 2000), np.full(2000, 3), 600
    else:
        src, dst, v = _row_graph(case, rng)
    t = build_tiles(src, dst, v)
    trs = t.tile_row_start
    slots = int(trs[-1]) * SUB_CHUNK
    assert slots <= len(src) + t.n_tiles * (SUB_CHUNK - 1)
    assert (np.diff(trs) >= 1).all() and t.src.shape[0] % 8 == 0
    edges = np.bincount(np.asarray(dst) // DST_TILE, minlength=t.n_tiles)
    assert np.array_equal(np.diff(t.tile_edge_start), edges)
    row_tile = np.asarray(t.row_tile)
    assert np.array_equal(row_tile[: trs[-1]], np.repeat(np.arange(t.n_tiles), np.diff(trs)))
    assert (row_tile[trs[-1] :] == t.n_tiles).all()
    dstl = np.asarray(t.dstl)
    assert int((dstl >= 0).sum()) == len(src)
    for tile in range(t.n_tiles):
        block = dstl[trs[tile] : trs[tile + 1]]
        assert int((block >= 0).sum()) == edges[tile]


# ---------------- scoring ----------------

@pytest.mark.parametrize("b,n,d", [(1, 4096, 64), (4, 5000, 32), (8, 2048, 128)])
def test_scoring_topk(b, n, d, rng):
    q = jnp.asarray(rng.normal(size=(b, d)).astype(np.float32))
    c = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    v, i = score_topk(q, c, k=16)
    rv, ri = topk_ref(q, c, 16)
    np.testing.assert_allclose(v, rv, rtol=1e-5, atol=1e-5)
    assert jnp.array_equal(i, ri)


def test_scoring_matmul_only(rng):
    q = jnp.asarray(rng.normal(size=(2, 16)).astype(np.float32))
    c = jnp.asarray(rng.normal(size=(4096, 16)).astype(np.float32))
    out = scoring_pallas(q, c)
    np.testing.assert_allclose(out, scoring_ref(q, c), rtol=1e-5, atol=1e-5)


# ---------------- embedding bag ----------------

@pytest.mark.parametrize("v,d,n,b", [(500, 32, 200, 16), (100, 8, 50, 7), (1000, 64, 400, 32)])
def test_embedding_bag_shapes(v, d, n, b, rng):
    table = jnp.asarray(rng.normal(size=(v, d)).astype(np.float32))
    ids = jnp.asarray(rng.integers(0, v, n), jnp.int32)
    segs = jnp.asarray(rng.integers(0, b, n), jnp.int32)
    out = embedding_bag(table, ids, segs, b)
    ref = embedding_bag_ref(table, ids, segs, jnp.ones(n), b)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


def test_embedding_bag_empty_bags_zero(rng):
    table = jnp.asarray(rng.normal(size=(50, 8)).astype(np.float32))
    ids = jnp.asarray([1, 2], jnp.int32)
    segs = jnp.asarray([0, 0], jnp.int32)
    out = embedding_bag(table, ids, segs, 5)
    assert jnp.allclose(out[1:], 0.0)


def test_embedding_bag_weighted(rng):
    table = jnp.asarray(rng.normal(size=(200, 16)).astype(np.float32))
    ids = jnp.asarray(rng.integers(0, 200, 64), jnp.int32)
    segs = jnp.asarray(rng.integers(0, 8, 64), jnp.int32)
    w = jnp.asarray(rng.normal(size=64).astype(np.float32))
    out = embedding_bag(table, ids, segs, 8, weights=w)
    ref = embedding_bag_ref(table, ids, segs, w, 8)
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-5)


# ---------------- flash attention ----------------

@pytest.mark.parametrize("s,d,bq,bk", [(128, 32, 32, 32), (256, 64, 64, 32), (256, 32, 128, 64)])
def test_flash_attention_shapes(s, d, bq, bk, rng):
    q = jnp.asarray(rng.normal(size=(2, s, d)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(2, s, d)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(2, s, d)).astype(np.float32))
    out = flash_attention_pallas(q, k, v, block_q=bq, block_k=bk)
    np.testing.assert_allclose(out, attention_ref(q, k, v), rtol=2e-5, atol=2e-5)


def test_flash_attention_bshd_wrapper(rng):
    b, s, h, d = 2, 128, 4, 32
    mk = lambda: jnp.asarray(rng.normal(size=(b, s, h, d)).astype(np.float32))
    q, k, v = mk(), mk(), mk()
    out = flash_attention(q, k, v, block_q=64, block_k=64)
    fold = lambda x: x.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    ref = attention_ref(fold(q), fold(k), fold(v)).reshape(b, h, s, d).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)


def test_flash_matches_blocked_jax_twin(rng):
    """Kernel and the pure-JAX blocked attention share their math."""
    from repro.layers.attention import blocked_causal_attention

    q = jnp.asarray(rng.normal(size=(2, 128, 32)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(2, 128, 32)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(2, 128, 32)).astype(np.float32))
    out = flash_attention_pallas(q, k, v, block_q=32, block_k=32)
    tw = blocked_causal_attention(
        q[:, :, None, :], k[:, :, None, :], v[:, :, None, :], block_kv=32
    )[:, :, 0, :]
    np.testing.assert_allclose(out, tw, rtol=2e-5, atol=2e-5)
