"""Segment reductions: the TPU replacement for Flink's per-key window state.

Every neighborhood aggregation in the reference is a per-key stateful fold:
``keyBy(vertex)`` then fold/reduce/apply over the window's records
(``SnapshotStream.java:61-181``). On TPU the same computation is a *segment
reduction* over a sorted-or-scattered edge block: vertex id = segment id,
edge value = element. Three tiers, fastest first:

1. :func:`segment_reduce` — recognized monoids (sum/min/max/prod) lower to
   ``jax.ops.segment_*`` (XLA scatter-reduce; no sort needed).
2. :func:`segmented_reduce_generic` — arbitrary *associative* combine, via a
   segmented ``lax.associative_scan`` over edges sorted by segment (the
   classic (flag, value) trick). Parallel depth O(log E).
   :func:`segmented_reduce_lanes` is its window-sized form: the result
   stays on the sorted lanes, so nothing has ``num_segments`` rows.
3. :func:`segmented_fold` — arbitrary (possibly non-associative) fold in
   arrival order, via ``lax.scan`` over the sorted edges. Sequential in E but
   fully compiled; mirrors the reference's per-record ``EdgesFold`` exactly
   (``EdgesFold.java:33-47``). Prefer tiers 1-2 for throughput.

All functions take padded blocks (mask-aware) and a static ``num_segments``.
"""

from __future__ import annotations

from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
from jax import lax

_INT_MAX = jnp.iinfo(jnp.int32).max

_MONOIDS = {
    "sum": jax.ops.segment_sum,
    "min": jax.ops.segment_min,
    "max": jax.ops.segment_max,
    "prod": jax.ops.segment_prod,
}


def segment_reduce(
    values: jax.Array,
    segment_ids: jax.Array,
    mask: jax.Array,
    num_segments: int,
    op: str = "sum",
) -> jax.Array:
    """Masked monoid segment reduction (tier 1).

    Padding rows are routed to a sentinel segment (``num_segments``) so they
    never contribute. Empty segments hold whatever ``jax.ops.segment_*``
    produces for them — callers must gate on a count/nonempty mask.
    """
    ids = jnp.where(mask, segment_ids, num_segments)
    out = _MONOIDS[op](values, ids, num_segments=num_segments + 1)
    return out[:num_segments]


def segment_count(segment_ids: jax.Array, mask: jax.Array, num_segments: int) -> jax.Array:
    """Per-segment element count (degree computation)."""
    ones = mask.astype(jnp.int32)
    ids = jnp.where(mask, segment_ids, num_segments)
    return jax.ops.segment_sum(ones, ids, num_segments=num_segments + 1)[:num_segments]


# --------------------------------------------------------------------------- #
# Sorting edges by segment (shared by tiers 2-3 and CSR building)
# --------------------------------------------------------------------------- #
def sort_by_segment(
    segment_ids: jax.Array, mask: jax.Array, *arrays: jax.Array
) -> Tuple[jax.Array, ...]:
    """Stable-sort edge arrays by (masked) segment id.

    Padding gets the sentinel id ``INT_MAX`` so it sorts last; arrival order
    within a segment is preserved (stable), which is what makes tier-3 folds
    match the reference's per-record processing order.

    Returns ``(sorted_ids, sorted_mask, *sorted_arrays)``.
    """
    ids = jnp.where(mask, segment_ids, _INT_MAX)
    leaves, treedef = jax.tree.flatten(arrays)
    if all(leaf.shape == ids.shape for leaf in leaves):
        # lanes of one rank ride the sort as operands: one variadic
        # ``lax.sort`` where an argsort is followed by a gather a column
        # (on the v5e, 2^17 lanes and two columns: 0.23 ms against 3.95,
        # a gather costs 7 ns a lane there; PERF.md section 6, PR 34)
        out = lax.sort((ids, *leaves), num_keys=1, is_stable=True)
        # the padding is what sorted under the sentinel
        return (out[0], out[0] != _INT_MAX) + tuple(
            jax.tree.unflatten(treedef, out[1:]))
    order = jnp.argsort(ids, stable=True)
    return (ids[order], mask[order]) + tuple(
        jax.tree.map(lambda a: a[order], arr) for arr in arrays
    )


def _segment_last_index(sorted_ids: jax.Array, num_segments: int) -> Tuple[jax.Array, jax.Array]:
    """For each segment: index of its last element, and whether it is nonempty."""
    seg = jnp.arange(num_segments, dtype=sorted_ids.dtype)
    right = jnp.searchsorted(sorted_ids, seg, side="right")
    left = jnp.searchsorted(sorted_ids, seg, side="left")
    nonempty = right > left
    last = jnp.clip(right - 1, 0, sorted_ids.shape[0] - 1)
    return last, nonempty


def segmented_reduce_lanes(
    values: Any,
    segment_ids: jax.Array,
    mask: jax.Array,
    combine: Callable[[Any, Any], Any],
    scope: str = "segment",
) -> Tuple[jax.Array, Any, jax.Array]:
    """The window-sized form of :func:`segmented_reduce_generic`: no
    operand or result has a row per segment, so the cost follows the
    lanes and not the id space.

    Returns ``(sorted_ids, scanned, last)`` over the lanes stable-sorted
    by segment: ``scanned[i]`` is the reduction of lane ``i``'s segment
    up to and including lane ``i`` (arrival order kept), and ``last``
    marks the one lane of every non-padding segment that holds the
    segment's whole reduction. Padding lanes sort to the end under the
    id ``INT_MAX`` and are never ``last``.

    Mechanism: sort by segment (named scope ``<scope>.sort``), then the
    standard segmented-scan construction (``<scope>.scan``) — carry
    (start_flag, value) pairs through ``lax.associative_scan`` where a
    start flag blocks combination across the boundary. This keeps
    arbitrary ``EdgesReduce`` UDFs (``EdgesReduce.java:31-44``) fully
    parallel on the VPU.
    """
    with jax.named_scope(f"{scope}.sort"):
        sorted_ids, sorted_mask, sorted_vals = sort_by_segment(
            segment_ids, mask, values
        )
    with jax.named_scope(f"{scope}.scan"):
        change = sorted_ids[1:] != sorted_ids[:-1]
        edge = jnp.ones(1, bool)
        starts = jnp.concatenate([edge, change])

        def scan_op(a, b):
            fa, va = a
            fb, vb = b
            merged = combine(va, vb)
            v = jax.tree.map(
                lambda m, y: jnp.where(_bcast(fb, y), y, m), merged, vb
            )
            return fa | fb, v

        _, scanned = lax.associative_scan(scan_op, (starts, sorted_vals))
        last = jnp.concatenate([change, edge]) & sorted_mask
    return sorted_ids, scanned, last


def segmented_reduce_generic(
    values: Any,
    segment_ids: jax.Array,
    mask: jax.Array,
    num_segments: int,
    combine: Callable[[Any, Any], Any],
) -> Tuple[Any, jax.Array]:
    """Arbitrary associative segmented reduction (tier 2).

    ``combine(a, b) -> c`` must be associative over the value pytree.
    Returns ``(per_segment_result, nonempty_mask)``, one row a segment;
    rows of empty segments are whatever the scan produced and must be
    gated by ``nonempty_mask``. The sort and the scan are
    :func:`segmented_reduce_lanes`'; a caller whose segments are an id
    space far larger than its lanes takes that form.
    """
    sorted_ids, scanned, _ = segmented_reduce_lanes(
        values, segment_ids, mask, combine
    )
    last, nonempty = _segment_last_index(sorted_ids, num_segments)
    result = jax.tree.map(lambda a: a[last], scanned)
    return result, nonempty


def segmented_fold(
    init: Any,
    fold_fn: Callable[[Any, jax.Array, jax.Array, jax.Array], Any],
    segment_ids: jax.Array,
    neighbor_ids: jax.Array,
    values: Any,
    mask: jax.Array,
    num_segments: int,
    id_of_segment: jax.Array | None = None,
    id_of_neighbor: jax.Array | None = None,
) -> Tuple[Any, jax.Array]:
    """Arbitrary per-edge fold in arrival order (tier 3).

    ``fold_fn(accum, vertex_id, neighbor_id, edge_value) -> accum`` is the
    exact TPU analog of ``EdgesFold.foldEdges`` (``EdgesFold.java:33-47``).
    ``id_of_segment``/``id_of_neighbor`` optionally map compact indices back
    to raw vertex ids (int32 lookup tables) so UDFs observe the same ids the
    reference would.

    .. warning:: **Cost model — prefer tiers 1-2 at scale.** Arrival-order
       semantics with an arbitrary (possibly non-associative) ``fold_fn``
       force a SEQUENTIAL ``lax.scan`` over the whole window: per-window
       depth is the edge count, so throughput is the per-edge scan-step
       rate regardless of window size — far below the scatter tiers
       (``bench.py --all`` times it as ``segmented_fold_eps``; not
       measured on the current code).
       Use it only when the fold is genuinely order-dependent and
       non-associative, exactly like the reference's sequential
       ``EdgesFold``. Otherwise:

       * tier 1 — ``reduce_on_edges("sum"|"min"|"max")``: one XLA
         scatter-reduce, no sort;
       * tier 2 — ``reduce_on_edges(callable)``: any ASSOCIATIVE combine
         via segmented associative scan (log-depth);
       * order-dependent but associative-after-keying folds can usually
         be re-expressed as a tier-2 reduce over (timestamp, value)
         pairs.

    Returns ``(per_segment_accum, nonempty_mask)``.
    """
    sorted_ids, sorted_mask, sorted_nbr, sorted_vals = sort_by_segment(
        segment_ids, mask, neighbor_ids, values
    )
    starts = jnp.concatenate([jnp.ones(1, bool), sorted_ids[1:] != sorted_ids[:-1]])

    def step(carry, x):
        accum = carry
        sid, is_start, valid, nbr, val = x
        base = jax.tree.map(
            lambda i, a: jnp.where(_bcast(is_start, a), i, a), init, accum
        )
        vid = sid if id_of_segment is None else id_of_segment[jnp.clip(sid, 0, id_of_segment.shape[0] - 1)]
        nid = nbr if id_of_neighbor is None else id_of_neighbor[nbr]
        new = fold_fn(base, vid, nid, val)
        accum = jax.tree.map(
            lambda n, a: jnp.where(_bcast(valid, a), n, a), new, base
        )
        return accum, accum

    init_c = jax.tree.map(lambda i: jnp.asarray(i), init)
    _, outs = lax.scan(step, init_c, (sorted_ids, starts, sorted_mask, sorted_nbr, sorted_vals))
    last, nonempty = _segment_last_index(sorted_ids, num_segments)
    result = jax.tree.map(lambda a: a[last], outs)
    return result, nonempty


def _bcast(flag: jax.Array, like: jax.Array) -> jax.Array:
    """Broadcast a scalar/vector bool flag against a value of any rank."""
    extra = like.ndim - flag.ndim
    if extra > 0:
        flag = flag.reshape(flag.shape + (1,) * extra)
    return flag
