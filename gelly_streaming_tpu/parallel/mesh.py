"""Device mesh construction and sharding helpers.

The reference's parallelism is implicit in Flink: operator parallelism plus
Netty shuffles (SURVEY.md §2.5-2.6). Here parallelism is explicit and
declarative: a ``jax.sharding.Mesh`` over TPU chips with named axes, and
shardings annotated on edge blocks / vertex tables; XLA inserts the ICI
collectives.

Axis conventions used throughout the framework:

- ``"edges"`` — the data-parallel axis: edge blocks are split along their
  capacity dimension (the analog of the reference's edge-partition
  data-parallelism, ``SummaryBulkAggregation.java:76-80``).
- ``"vertices"`` — the state-parallel axis: a ``vcap``-sized vertex table
  is split along dimension 0 in contiguous blocks, one block a chip
  (:func:`vertex_sharding`; owner of row ``i`` is ``i // (vcap / shards)``,
  both powers of two). Today the CC pointer forest is the one table that
  is sharded this way (``summaries/forest.py``); every other vertex table
  is replicated, and an aggregation without a sharded carry refuses a
  mesh whose ``vertices`` axis is above 1.
- ``"model"`` — feature/model parallel axis for the GNN layers (tensor
  parallelism over the feature dimension); unused (size 1) for the pure
  analytics workloads.

On a single chip all axes have size 1 and everything degenerates gracefully.
Multi-chip testing runs on a virtual CPU mesh
(``XLA_FLAGS=--xla_force_host_platform_device_count=8``) — the moral
equivalent of the reference's in-process Flink mini-cluster
(SURVEY.md §4).
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

EDGE_AXIS = "edges"
VERTEX_AXIS = "vertices"
MODEL_AXIS = "model"


def make_mesh(
    n_edge_shards: Optional[int] = None,
    n_model_shards: int = 1,
    devices: Optional[Sequence[jax.Device]] = None,
    n_vertex_shards: int = 1,
) -> Mesh:
    """Build a 3-D (edges, vertices, model) mesh over the available
    devices. ``n_edge_shards=None`` takes every device the other two
    axes leave."""
    devs = list(devices) if devices is not None else jax.devices()
    if n_vertex_shards < 1 or n_vertex_shards & (n_vertex_shards - 1):
        raise ValueError(
            f"n_vertex_shards must be a power of two, got {n_vertex_shards} "
            "(a vcap-sized table is a power of two rows, split in equal "
            "contiguous blocks)"
        )
    if n_edge_shards is None:
        n_edge_shards = len(devs) // (n_vertex_shards * n_model_shards)
    n = n_edge_shards * n_vertex_shards * n_model_shards
    if n < 1 or n > len(devs):
        raise ValueError(
            f"requested {n} devices "
            f"({n_edge_shards}x{n_vertex_shards}x{n_model_shards}) "
            f"but only {len(devs)} available"
        )
    grid = np.asarray(devs[:n]).reshape(
        n_edge_shards, n_vertex_shards, n_model_shards
    )
    return Mesh(grid, (EDGE_AXIS, VERTEX_AXIS, MODEL_AXIS))


def edge_sharding(mesh: Mesh) -> NamedSharding:
    """Sharding for EdgeBlock arrays: split capacity across the edge axis."""
    return NamedSharding(mesh, P(EDGE_AXIS))


def replicated(mesh: Mesh) -> NamedSharding:
    """Fully-replicated sharding (window-sized arrays, summaries, every
    vertex table but a vertex-sharded forest)."""
    return NamedSharding(mesh, P())


def vertex_shards(mesh: Optional[Mesh]) -> int:
    """Size of the ``vertices`` axis (1 for no mesh, or a mesh without it)."""
    if mesh is None:
        return 1
    return int(mesh.shape.get(VERTEX_AXIS, 1))


def vertex_sharding(mesh: Mesh) -> NamedSharding:
    """Sharding of a ``vcap``-sized table: dimension 0 in contiguous
    blocks over the ``vertices`` axis, replicated over the others."""
    return NamedSharding(mesh, P(VERTEX_AXIS))


def table_vertex_shards(table) -> int:
    """How many ``vertices`` shards a table is laid out in: 1 for a host
    array, a single-device array or a replicated one."""
    spec = getattr(getattr(table, "sharding", None), "spec", None)
    if not spec or spec[0] != VERTEX_AXIS:
        return 1
    return vertex_shards(table.sharding.mesh)


def shard_block_spec():
    """PartitionSpec pytree for an EdgeBlock (all leaf arrays edge-sharded)."""
    from ..core.edgeblock import EdgeBlock  # local import to avoid cycle

    return EdgeBlock(src=P(EDGE_AXIS), dst=P(EDGE_AXIS), val=P(EDGE_AXIS), mask=P(EDGE_AXIS), n_vertices=0)
