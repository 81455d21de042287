"""A configuration's graph generator, found by name.

A configuration may name its generator with the key ``"generator"``: the
module ``bench/generators/<name>.py``, loaded by path. Without the key the
generator is the Graph500 Kronecker one (``harness/kronecker.py``). A
generator module gives:

* ``edges(cfg, seed) -> (src, dst)``: int32 host arrays of the directed
  edge list, both directions of every edge of an undirected graph;
* ``num_vertices(cfg) -> int``;
* ``vertex_map(cfg, seed) -> ndarray``: ``map[v]`` is the id that ``seed``'s
  relabelling gives vertex ``v`` of the configuration's structure. Every
  seed has to give the same work, in another vertex order (see
  ``traffic.py``, which draws BFS roots in the structure);
* ``small(cfg, k) -> cfg``: the same generator at about ``2**k`` vertices,
  for the CPU tests.

A generator's configuration keeps a ``structure_seed``: ``QueryStream``
draws BFS roots with it.
"""
from __future__ import annotations

from pathlib import Path
from types import ModuleType

from . import kronecker
from .manifest import BENCH_DIR, load_module


def load(config: dict, bench_dir: Path = BENCH_DIR) -> ModuleType:
    """The generator module of ``config``."""
    name = config.get("generator")
    return kronecker if name is None else load_module("generators", name, bench_dir)
