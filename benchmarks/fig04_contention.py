"""Fig. 4: mean update time as a function of counter array size.

Measured: the degree-count reference (scatter-add) and the Pallas kernel
(interpret mode) on this host, counter sizes sweeping the cache hierarchy.
Derived: ns/update. The paper's observation to reproduce: update time grows
~log(M) and is a function of M, not of graph size."""
import jax.numpy as jnp
import numpy as np

from repro.kernels.degree_count import degree_count
from .common import Row, time_call


def run() -> list[Row]:
    rng = np.random.default_rng(0)
    e = 1 << 16
    src = jnp.asarray(rng.integers(0, 1 << 30, e), jnp.int32)
    dst = jnp.asarray(rng.integers(0, 1 << 30, e), jnp.int32)
    # interpret-mode kernel is Python-per-grid-step: tiny sweep only
    ek = 1 << 13
    srck, dstk = src[:ek], dst[:ek]
    rows: list[Row] = []
    for log_c in (10, 12, 14, 16, 18, 20):
        n_counters = 1 << log_c
        import jax

        @jax.jit
        def ref_run():
            ids = jnp.concatenate([src, dst]) % n_counters
            return jnp.zeros((n_counters,), jnp.int32).at[ids].add(1)

        us = time_call(lambda: ref_run().block_until_ready())
        rows.append((f"fig04/scatter_add/M={n_counters*4}B", us, us * 1e3 / (2 * e)))
        if log_c <= 12:
            usk = time_call(
                lambda: degree_count(srck, dstk, n_counters).block_until_ready(),
                repeats=1, warmup=1,
            )
            rows.append((f"fig04/pallas_interp/M={n_counters*4}B", usk, usk * 1e3 / (2 * ek)))
    return rows
