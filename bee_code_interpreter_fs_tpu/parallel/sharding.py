"""Sharding helpers: PartitionSpec plumbing over named meshes.

Thin on purpose — NamedSharding + jit's in_shardings/out_shardings IS the
TPU-native distribution mechanism; there is nothing to hand-schedule. These
helpers only remove the boilerplate of pairing a mesh with pytrees of
PartitionSpecs.
"""

from __future__ import annotations

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def named_sharding(mesh: Mesh, *spec) -> NamedSharding:
    """`named_sharding(mesh, "dp", None)` -> NamedSharding(mesh, P("dp", None))."""
    return NamedSharding(mesh, P(*spec))


def job_sharding(mesh: Mesh, axis: str = "jobs") -> NamedSharding:
    """Layout for a stacked batch of independent small jobs: a
    ``[n_jobs, ...]`` operand array split along the mesh's job axis, one
    job's block per device. This is the fused-dispatch half of the batched
    execution lanes — ``shard_map`` over a 1-axis job mesh runs every
    job's block on its own chip in ONE XLA program (see the
    ``batched_dispatch`` pre-warm kernel in ``services/compile_cache.py``).
    """
    return NamedSharding(mesh, P(axis))


def shard_pytree(mesh: Mesh, tree, specs):
    """Device-put a pytree with a matching pytree of PartitionSpecs.

    `specs` may be a single PartitionSpec (applied to every leaf) or a pytree
    with the same structure as `tree`.
    """
    if isinstance(specs, P):
        return jax.device_put(tree, NamedSharding(mesh, specs))
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        tree,
        specs,
        is_leaf=lambda x: x is None,
    )
