"""The paper's own workload as an 11th config: a sharded PageRank-pull
iteration + BFS frontier expansion over an RMAT-scale graph, distributed
edge-parallel over the mesh (the graph-engine data path the scheduler
controls). Dry-run-only at full scale (V=2^26, E=2^30).

Gathers and scatters with edge-sharded operands spell out their output
sharding (``sharding_for``): an explicit-axis mesh refuses to infer it."""
import jax
import jax.numpy as jnp

from ..launch.steps import CellProgram
from ..sharding.context import sharding_for

ARCH_ID = "paper-graph-engine"
FAMILY = "graph"
SHAPES = ["pr_iteration", "bfs_expand"]

V = 1 << 26
E = 1 << 30

def make_cell(shape: str, **_):
    if shape == "pr_iteration":
        def step(src, dst, rank, out_deg):
            contrib = jnp.where(out_deg > 0, rank / jnp.maximum(out_deg, 1), 0.0)
            vals = contrib.at[src].get(out_sharding=sharding_for(("edges",), src.shape))
            acc = jnp.zeros((V,), vals.dtype).at[dst].add(
                vals, out_sharding=sharding_for(("nodes",), (V,))
            )
            return 0.15 / V + 0.85 * acc

        args = (
            jax.ShapeDtypeStruct((E,), jnp.int32),
            jax.ShapeDtypeStruct((E,), jnp.int32),
            jax.ShapeDtypeStruct((V,), jnp.float32),
            jax.ShapeDtypeStruct((V,), jnp.int32),
        )
        axes = (("edges",), ("edges",), ("nodes",), ("nodes",))
        return CellProgram(
            name=f"{ARCH_ID}:{shape}", kind="serve", step_fn=step,
            abstract_args=args, axes_trees=axes,
            meta=dict(model_flops=2.0 * E, n_edges=E, n_nodes=V),
        )

    def step(src, dst, visited, frontier):
        active = frontier.at[src].get(out_sharding=sharding_for(("edges",), src.shape))
        # the bool OR of a scatter-max, as a set of True at active edges'
        # targets: in JAX 0.9 `.at[].max` takes no out_sharding ("unexpected
        # keyword argument 'out_sharding'") and without it the scatter raises
        # ShardingTypeError ("out sharding could not be resolved
        # unambiguously"); inactive edges index past V and are dropped
        touched = jnp.zeros((V,), jnp.bool_).at[jnp.where(active, dst, V)].set(
            True, mode="drop", out_sharding=sharding_for(("nodes",), (V,))
        )
        new = touched & ~visited
        return visited | new, new

    args = (
        jax.ShapeDtypeStruct((E,), jnp.int32),
        jax.ShapeDtypeStruct((E,), jnp.int32),
        jax.ShapeDtypeStruct((V,), jnp.bool_),
        jax.ShapeDtypeStruct((V,), jnp.bool_),
    )
    axes = (("edges",), ("edges",), ("nodes",), ("nodes",))
    return CellProgram(
        name=f"{ARCH_ID}:{shape}", kind="serve", step_fn=step,
        abstract_args=args, axes_trees=axes,
        meta=dict(model_flops=1.0 * E, n_edges=E, n_nodes=V),
    )
