"""The BFS reference over the out-CSR against the whole-edge-list mask BFS
it replaced, kept here as the oracle."""
import numpy as np
import pytest

from conftest import MANIFEST, config_file

SMALL_K = 12
CONFIGS = [c["name"] for c in MANIFEST["configs"]]


def mask_bfs_levels(g, root: int) -> np.ndarray:
    """Level-synchronous top-down BFS that masks the whole edge list once
    per level; -1 for unreached vertices."""
    level = np.full(g.num_vertices, -1, np.int32)
    level[root] = 0
    frontier = np.zeros(g.num_vertices, bool)
    frontier[root] = True
    depth = 0
    while frontier.any():
        depth += 1
        touched = np.zeros(g.num_vertices, bool)
        touched[g.dst[frontier[g.src]]] = True
        new = touched & (level < 0)
        level[new] = depth
        frontier = new
    return level


def _same_levels(g, roots) -> None:
    from harness import reference

    for root in roots:
        got = reference.bfs_levels(g, int(root))
        want = mask_bfs_levels(g, int(root))
        assert got.dtype == want.dtype and got.shape == want.shape
        np.testing.assert_array_equal(got, want, err_msg=f"root {root}")


@pytest.mark.parametrize("config_name", CONFIGS)
def test_equals_the_mask_bfs_on_each_configuration(config_name):
    """20 roots, drawn as the traffic draws them (degree >= 1), and one
    vertex without edges, on each configuration at its generator's small
    size."""
    from harness import graphs, reference

    config = config_file(config_name)
    generator = graphs.load(config)
    config = generator.small(config, SMALL_K)
    g = reference.Graph(*generator.edges(config, 2**31 + 7), generator.num_vertices(config))
    rng = np.random.default_rng(4)
    roots = list(rng.choice(np.flatnonzero(g.out_deg > 0), 20, replace=False))
    roots += list(np.flatnonzero(g.out_deg == 0)[:1])
    _same_levels(g, roots)


def test_equals_the_mask_bfs_on_a_long_path():
    """An undirected path of 1,200 vertices, in shuffled ids and edge order:
    1,199 levels from an end, 600 from the middle."""
    from harness import reference

    n = 1200
    ids = np.random.default_rng(1).permutation(n)
    a, b = ids[:-1], ids[1:]
    order = np.random.default_rng(2).permutation(2 * (n - 1))
    g = reference.Graph(np.concatenate([a, b])[order], np.concatenate([b, a])[order], n)
    levels = reference.bfs_levels(g, int(ids[0]))
    assert levels.max() == n - 1
    _same_levels(g, [ids[0], ids[n // 2]])


def test_equals_the_mask_bfs_with_unreached_vertices():
    """Two components, isolated vertices, a one-way edge out of the root's
    component, a one-way edge into it, self-loops and duplicate edges."""
    from harness import reference

    src = np.array([0, 1, 1, 2, 2, 2, 5, 6, 7, 3, 9, 4, 4])
    dst = np.array([1, 0, 2, 1, 2, 2, 6, 5, 5, 8, 0, 4, 4])
    g = reference.Graph(src, dst, 12)
    levels = reference.bfs_levels(g, 0)
    np.testing.assert_array_equal(levels, [0, 1, 2, -1, -1, -1, -1, -1, -1, -1, -1, -1])
    _same_levels(g, range(12))


def test_out_csr_lists_each_vertexs_out_edges():
    from harness import reference

    rng = np.random.default_rng(3)
    src, dst = rng.integers(0, 50, 400), rng.integers(0, 50, 400)
    g = reference.Graph(src, dst, 60)
    offsets, targets = g.out_csr()
    assert offsets[0] == 0 and offsets[-1] == src.size
    for v in range(60):
        assert list(targets[offsets[v]:offsets[v + 1]]) == list(dst[src == v])
