"""The Graph500 Kronecker edge generator, made on the device from a seed.

A copy of the specification's reference generator (Graph500 specification,
section "Kronecker generator"): every edge descends SCALE levels of the
2x2 initiator [[A, B], [C, 1-A-B-C]], choosing its source bit with
probability 1-(A+B) and its target bit conditionally on it; vertex labels
are then permuted. The spec's graph is undirected, so every generated edge
is fed in both directions: ``2 * edgefactor * 2**SCALE`` directed edges,
self-loops and duplicates kept (a self-loop appears twice). The list stays
in the generator's order, which the engine sorts away (the spec's final
shuffle of the list is left out).

The configuration fixes the generator's own seed, so every run holds the
same structure; the run's ``--seed`` only relabels the vertices. The
relabelling swaps the two halves of every aligned dyadic block
of vertex ids at random (a random automorphism of the binary tree over the
ids), so it maps every aligned block of 2**k ids onto another for every k:
however the engine cuts the vertex range into power-of-two tiles, each
seed gives it the same multiset of tile sizes, in another order, and the
same work.

One jitted call makes the list on the device, so set-up pays one compile,
cached, and no host loop; only the vertex permutation of the structure is
drawn on the host.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any non-negative seed, 64-bit ones included: the low
    32 bits make the key and the high bits are folded into it."""
    if seed < 0:
        raise ValueError(f"seed must be non-negative, got {seed}")
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


@functools.partial(jax.jit, static_argnames=("scale", "edgefactor"))
def _edges(structure_key, key, vperm, a, b, c, *, scale: int, edgefactor: int):
    m = edgefactor << scale
    ab = a + b
    c_norm = c / (1.0 - ab)
    a_norm = a / ab

    def level(ib, ij):
        u = jax.random.uniform(jax.random.fold_in(structure_key, ib), (2, m))
        ii_bit = u[0] > ab
        jj_bit = u[1] > jnp.where(ii_bit, c_norm, a_norm)
        return ij | (jnp.stack([ii_bit, jj_bit]).astype(jnp.int32) << ib)

    ij = jax.lax.fori_loop(0, scale, level, jnp.zeros((2, m), jnp.int32))
    ij = jnp.concatenate([ij, ij[::-1]], axis=1)  # both directions: undirected
    return _tree_relabel(key, vperm[ij], scale)


def _tree_relabel(key, ids, scale: int):
    """Apply one random automorphism of the binary tree over ``scale``-bit
    vertex ids: at each node, swap its two subtrees with probability 1/2."""
    flips = jax.random.bernoulli(key, 0.5, ((1 << scale) - 1,)).astype(jnp.int32)

    def level(lv, out):
        node = (1 << lv) - 1 + (ids >> (scale - lv))
        return out ^ (flips[node] << (scale - 1 - lv))

    return jax.lax.fori_loop(0, scale, level, ids)


_relabel = jax.jit(_tree_relabel, static_argnames=("scale",))


def vertex_map(cfg: dict, seed: int) -> np.ndarray:
    """``map[v]``: the id that ``seed``'s relabelling gives vertex ``v`` of
    the configuration's structure."""
    scale = int(cfg["SCALE"])
    return np.asarray(_relabel(seed_key(seed), jnp.arange(1 << scale, dtype=jnp.int32), scale=scale))


def kronecker_edges(cfg: dict, seed: int) -> tuple[np.ndarray, np.ndarray]:
    """``(src, dst)`` int32 host arrays of ``2 * edgefactor * 2**SCALE``
    directed edges, both directions of each generated one: the
    configuration's generator (``SCALE``, ``edgefactor``, ``A``, ``B``,
    ``C``, ``structure_seed``), relabelled by ``seed``."""
    scale = int(cfg["SCALE"])
    structure_seed = int(cfg["structure_seed"])
    # the spec's random vertex permutation, on the host: a sort-based
    # permutation on the device takes ~40 s to compile for a v5e
    vperm = np.random.default_rng(structure_seed).permutation(1 << scale).astype(np.int32)
    ij = np.asarray(
        _edges(
            seed_key(structure_seed), seed_key(seed), vperm,
            cfg["A"], cfg["B"], cfg["C"],
            scale=scale, edgefactor=int(cfg["edgefactor"]),
        )
    )
    return ij[0], ij[1]
