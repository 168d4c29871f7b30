"""Device mesh + sharding layout for the document axis.

The workload's data-parallel axis is documents (SURVEY.md §2.9): every
kernel state/op array has a leading [B] docs dimension and no cross-document
dataflow, so sharding B over a 1-D mesh scales merge throughput linearly
over ICI with zero collectives on the merge path. Multi-host: the same
spec over a process-spanning mesh; DCN carries only host→device op streams
(server/shuttle), not inter-chip traffic.

Metrics aggregation (ops/sec counters, queue depths) uses psum over the
docs axis — the only collective in the system.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

DOCS_AXIS = "docs"


def make_mesh(devices=None) -> Mesh:
    """1-D mesh over all (or given) devices, named by the docs axis."""
    if devices is None:
        devices = jax.devices()
    return Mesh(np.asarray(devices), (DOCS_AXIS,))


def doc_sharding(mesh: Mesh) -> NamedSharding:
    """Sharding for [B, ...] arrays: batch split over the mesh."""
    return NamedSharding(mesh, PartitionSpec(DOCS_AXIS))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec())


def shard_state(tree, mesh: Mesh):
    """Place a kernel state/op pytree with the docs axis sharded. Scalars
    and [B]-leading arrays alike shard on dim 0 (every leaf carries B)."""
    sharding = doc_sharding(mesh)
    return jax.device_put(tree, sharding)


def doc_count_for_mesh(mesh: Mesh, per_device: int) -> int:
    return mesh.devices.size * per_device


def aggregate_metrics(mesh: Mesh, tree):
    """All-reduce [B]-leading metric leaves over the docs axis via psum.

    The one collective in the system: per-shard partial sums of each metric
    (ops sequenced, queue depth, ...) are psum'ed across the mesh so every
    device — and the host — sees the global totals. The merge path itself
    stays collective-free (reference analog: per-lambda metric counters
    aggregated off the hot path, services-core/src/metricClient.ts).
    """
    import jax.numpy as jnp

    leaves, treedef = jax.tree_util.tree_flatten(tree)

    def local_reduce(*xs):
        return tuple(
            jax.lax.psum(jnp.sum(x, axis=0), DOCS_AXIS) for x in xs)

    fn = jax.shard_map(
        local_reduce, mesh=mesh,
        in_specs=tuple(PartitionSpec(DOCS_AXIS) for _ in leaves),
        out_specs=tuple(PartitionSpec() for _ in leaves))
    return jax.tree_util.tree_unflatten(treedef, fn(*leaves))
