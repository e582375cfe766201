"""Collective-communication layer: the TPU-native Flink shuffle.

The reference has zero transport code of its own — all communication is
implicit in Flink dataflow edges over Netty TCP (SURVEY.md §2.6): hash
shuffles (``keyBy``), broadcast, gather-to-one (``timeWindowAll`` /
``setParallelism(1)``), and the tree-reduce topology built by re-keying
(``SummaryTreeReduce.java:95-123``).

This module is the explicit equivalent over ICI, built on ``shard_map`` +
XLA collectives. Mapping (reference -> here):

- flat global reduce (``timeWindowAll().reduce`` + parallelism-1 ``Merger``,
  ``SummaryBulkAggregation.java:81-83``)  ->  :func:`all_reduce` (psum/pmin/
  pmax over a mesh axis; every shard gets the result — strictly stronger
  than the reference's single-task funnel).
- tree reduce (``SummaryTreeReduce.enhance()``)  ->  :func:`tree_all_reduce`,
  a log2(p) ``ppermute`` butterfly provided for topology parity/testing; on
  real ICI the flat collective is already ring/tree-optimal, so the engine
  uses :func:`all_reduce` by default.
- broadcast (``edges.broadcast()``, ``BroadcastTriangleCount.java:42``) ->
  replication (no sharding) or :func:`all_gather`.
- hash shuffle (``keyBy``)  ->  deterministic host-side bucketing by compact
  vertex id (VertexDict) — data is *placed* correctly instead of shuffled.

All functions take an ``axis_name`` and must run inside ``shard_map`` (or any
SPMD context where the axis is bound).
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh


def shard_map(f, mesh: Mesh, in_specs, out_specs, check_vma: bool = False):
    """Thin wrapper over ``jax.shard_map`` with relaxed
    varying-manual-axes checks."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=check_vma,
    )


# --------------------------------------------------------------------------- #
# Flat collectives (P3 / P5 in SURVEY.md §2.5)
# --------------------------------------------------------------------------- #
def all_reduce(x: Any, axis_name: str, op: str = "sum") -> Any:
    """All-reduce a pytree across a mesh axis (sum/min/max)."""
    if op == "sum":
        return lax.psum(x, axis_name)
    if op == "min":
        return lax.pmin(x, axis_name)
    if op == "max":
        return lax.pmax(x, axis_name)
    raise ValueError(f"unknown all_reduce op {op!r}")


def all_gather(x: Any, axis_name: str, axis: int = 0, tiled: bool = False) -> Any:
    return lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def axis_index(axis_name: str) -> jax.Array:
    return lax.axis_index(axis_name)


def axis_size(axis_name: str) -> int:
    return lax.axis_size(axis_name)


# --------------------------------------------------------------------------- #
# Tree reduction (P4): ppermute butterfly, parity with SummaryTreeReduce
# --------------------------------------------------------------------------- #
def stacked_reduce(stacked: Any, n: int, combine: Callable[[Any, Any], Any]) -> Any:
    """Log-depth fold of ``n`` stacked partials (leading axis) with an
    arbitrary pytree ``combine`` — the bulk engine's cross-shard merge
    (``SummaryBulkAggregation``'s timeWindowAll-gather analog). Handles
    odd counts by carrying the tail partial into the next level."""
    while n > 1:
        half = n // 2
        lo = jax.tree.map(lambda x: x[:half], stacked)
        hi = jax.tree.map(lambda x: x[half: 2 * half], stacked)
        merged = jax.vmap(combine)(lo, hi)
        if n % 2:
            stacked = jax.tree.map(
                lambda m, x: jnp.concatenate([m, x[2 * half: n]]),
                merged,
                stacked,
            )
            n = half + 1
        else:
            stacked = merged
            n = half
    return jax.tree.map(lambda x: x[0], stacked)


def validate_tree_degree(n_shards: int, degree: int) -> None:
    """The degree-d butterfly needs the axis size to be a power of the
    degree; callable eagerly (stream setup) so a misconfiguration fails
    before any window runs, whichever carry ends up executing."""
    if degree < 2:
        raise ValueError(f"tree_all_reduce degree must be >= 2, got {degree}")
    total = 1
    while total < n_shards:
        total *= degree
    if total != n_shards:
        raise ValueError(
            f"tree_all_reduce requires the axis size ({n_shards}) to be a "
            f"power of the tree degree ({degree}); use degree=2 for "
            "power-of-two meshes"
        )


def resolve_tree_degree(n_shards: int, degree: int) -> int:
    """Effective butterfly fan-in for this mesh: ``degree`` when the
    axis size is a power of it, else 2 (which fits every power-of-two
    mesh) with a warning.

    In the reference ``degree`` configures the partial-aggregation
    PARALLELISM (``setParallelism(degree)``) while ``enhance()``'s
    fan-in is fixed at 2 — a non-conforming degree there degrades with a
    warning rather than failing. The butterfly generalizes degree into a
    true fan-in, so a degree the mesh cannot honor degrades the same
    way: warn, run the degree-2 butterfly. ``degree < 2`` still raises
    (no meaningful fallback)."""
    if degree < 2:
        raise ValueError(f"tree_all_reduce degree must be >= 2, got {degree}")
    total = 1
    while total < n_shards:
        total *= degree
    if total == n_shards:
        return degree
    import warnings

    warnings.warn(
        f"tree degree {degree} does not fit the {n_shards}-shard edge "
        "axis (axis size must be a power of the degree); falling back "
        "to the degree-2 butterfly",
        stacklevel=2,
    )
    return 2


def tree_all_reduce(
    x: Any,
    axis_name: str,
    combine: Callable[[Any, Any], Any],
    n_shards: int,
    degree: int = 2,
) -> Any:
    """Butterfly all-reduce with an arbitrary combine fn and fan-in
    ``degree``.

    The reference's ``SummaryTreeReduce.enhance()`` repeatedly reduces
    parallelism by its tree degree and combines partials
    (``SummaryTreeReduce.java:95-123``). The ICI-native equivalent is a
    degree-d butterfly: at round r the shards split into groups of
    ``degree`` (stride ``degree**r``); every shard ppermute-receives the
    other ``degree - 1`` members' partials and folds them in — after
    ``log_degree(p)`` rounds *every* shard holds the global combine.
    ``degree=2`` is the classic recursive-doubling exchange; higher
    degrees trade fewer rounds (less latency-bound collective setup) for
    more sequential combines per round.

    ``combine`` may be any associative+commutative pytree merge (not just
    an elementwise monoid) — commutativity is required because each shard
    folds partials in its own arrival order (the degree-2 case already
    relied on this: shard i computes combine(x_i, x_j) while shard j
    computes combine(x_j, x_i)).

    ``n_shards`` must be a power of ``degree`` (the mesh axis size).
    """
    validate_tree_degree(n_shards, degree)
    group = 1
    while group < n_shards:
        span = group * degree
        # permute the ROUND-START partial each exchange: accumulating
        # into the permute source would ship partially-combined values
        # on the second and later exchanges of a round
        x0 = x
        for j in range(1, degree):
            # shard i = hi*span + pos*group + lo receives the partial of
            # the group member at position (pos - j) mod degree
            perm = []
            for i in range(n_shards):
                hi, rem = divmod(i, span)
                pos, lo = divmod(rem, group)
                dst = hi * span + ((pos + j) % degree) * group + lo
                perm.append((i, dst))
            partner = jax.tree.map(
                lambda leaf: lax.ppermute(leaf, axis_name, perm), x0
            )
            x = combine(x, partner)
        group = span
    return x


# --------------------------------------------------------------------------- #
# Sharded segment reduction: the engine's cross-shard combine primitive
# --------------------------------------------------------------------------- #
def sharded_segment_min(
    values: jax.Array,
    segment_ids: jax.Array,
    num_segments: int,
    axis_name: str,
) -> jax.Array:
    """Per-shard scatter-min over a replicated vertex table, then pmin.

    The building block of the distributed aggregate path: each shard folds its
    slice of the edge block into a local V-sized table, and one ICI all-reduce
    replaces the reference's keyBy + timeWindowAll funnel.
    """
    local = jax.ops.segment_min(values, segment_ids, num_segments=num_segments)
    return lax.pmin(local, axis_name)


def sharded_segment_sum(
    values: jax.Array,
    segment_ids: jax.Array,
    num_segments: int,
    axis_name: str,
) -> jax.Array:
    local = jax.ops.segment_sum(values, segment_ids, num_segments=num_segments)
    return lax.psum(local, axis_name)
