"""Triangle-counting kernels: sorted-adjacency intersection on dense rows.

TPU-native replacement for the reference's two triangle paths:

- ``example/WindowTriangles.java:86-139`` materializes O(Σdeg²) wedge
  candidates per window and joins them against real edges — a blowup
  SURVEY.md §7 explicitly avoids. Here a window's triangles are counted by
  intersecting the sorted neighbor rows of each edge's endpoints
  (:func:`window_triangle_count`): O(E·D·logD) dense vector work.
- ``example/ExactTriangleCount.java:74-116`` pairs per-edge neighborhood
  snapshots in keyed state so each triangle is counted exactly once, when its
  last edge arrives. The TPU form (:func:`packed_triangle_update` over the
  :func:`merge_packed_adjacency`-carried sorted adjacency) keeps an
  *arrival rank* per accumulated edge and counts, for each new edge, common
  neighbors whose two closing edges both have smaller rank — the same
  "closed by the final edge" semantics, batched per window, with O(E)
  carried memory and per-query enumeration bounded by the min-degree
  endpoint's class.

The window kernel takes dense ``[V, D]`` neighbor matrices (see
``ops/csr.py``); the streaming kernels work on the packed sorted columns.
Invalid slots hold +INT_MAX everywhere so binary search never matches
them.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from .csr import build_csr, dense_neighbors

_BIG = jnp.iinfo(jnp.int32).max


def canonicalize(src: jax.Array, dst: jax.Array, mask: jax.Array):
    """(min,max) edge ordering, self-loops masked off
    (``ExactTriangleCount.java:136-146`` ProjectCanonicalEdges)."""
    u = jnp.minimum(src, dst)
    v = jnp.maximum(src, dst)
    return u, v, mask & (u != v)


def dedup_canonical(u: jax.Array, v: jax.Array, mask: jax.Array, num_vertices: int):
    """Mask duplicate canonical edges within a block. Returns (u, v, mask)
    with duplicates masked off. Two-key ``lax.sort`` — no composite int64
    key, which would overflow with x64 disabled."""
    del num_vertices
    iota = jnp.arange(u.shape[0], dtype=jnp.int32)
    u_m = jnp.where(mask, u, _BIG)
    v_m = jnp.where(mask, v, _BIG)
    su, sv, si = jax.lax.sort((u_m, v_m, iota), num_keys=2)
    first = jnp.concatenate(
        [jnp.ones(1, bool), (su[1:] != su[:-1]) | (sv[1:] != sv[:-1])]
    )
    keep = jnp.zeros_like(mask).at[si].set(first)
    return u, v, mask & keep


def _row_membership(rows_a: jax.Array, rows_b: jax.Array):
    """For each element of rows_a[i], its position and presence in rows_b[i].

    Both inputs ``[E, D]`` with rows sorted ascending. Returns (pos, found);
    +INT_MAX sentinels never count as found.
    """

    def one(a, b):
        pos = jnp.searchsorted(b, a)
        pos_c = jnp.clip(pos, 0, b.shape[0] - 1)
        found = (b[pos_c] == a) & (a != _BIG)
        return pos_c, found

    return jax.vmap(one)(rows_a, rows_b)


def window_triangle_count(
    src: jax.Array,
    dst: jax.Array,
    mask: jax.Array,
    num_vertices: int,
    max_degree: int,
    edge_chunk: int = 1 << 16,
) -> Tuple[jax.Array, jax.Array]:
    """Exact triangle count of one window's edge block, degree-oriented.

    Edges are oriented from lexicographically-smaller ``(degree, id)`` to
    larger, and each edge intersects the *out*-neighbor rows of its
    endpoints — the standard forward-counting orientation. Two wins over
    intersecting full neighborhoods: each triangle is counted exactly once
    (no /3), and row width is bounded by the max out-degree, which is at
    most ~sqrt(2E) for ANY degree distribution — a Zipf hub no longer
    inflates the dense rows (the reference's wedge generation has the same
    O(Σdeg²) hub blowup this avoids, ``WindowTriangles.java:86-114``).

    ``max_degree`` must cover the max *oriented out-degree* (callers bucket
    it host-side). The [E, D] membership intermediates are processed in
    ``edge_chunk`` slices via ``lax.scan`` to bound peak memory.

    Returns ``(total, per_vertex[V])``; ``per_vertex[w]`` = number of window
    triangles containing ``w``.
    """
    a, b, m, ids = _oriented_rows(src, dst, mask, num_vertices, max_degree)
    return _membership_pass(ids, a, b, m, num_vertices, edge_chunk)


def _oriented_rows(src, dst, mask, num_vertices: int, max_degree: int):
    """Shared prep of the window kernel: canonical dedup'd edges oriented
    low->high (degree, id) plus the sorted dense out-neighbor rows."""
    u, v, m = canonicalize(src, dst, mask)
    u, v, m = dedup_canonical(u, v, m, num_vertices)
    mi = m.astype(jnp.int32)
    deg = jnp.zeros(num_vertices, jnp.int32).at[u].add(mi).at[v].add(mi)
    # orient a -> b where (deg, id) of a < of b
    du, dv = deg[u], deg[v]
    swap = (dv < du) | ((dv == du) & (v < u))
    a = jnp.where(swap, v, u)
    b = jnp.where(swap, u, v)
    # out-neighbor rows sorted by id (invalid slots +INT_MAX)
    zeros = jnp.zeros_like(a)
    csr = build_csr(a, b, zeros, m, num_vertices)
    nbr_mat, _, valid = dense_neighbors(csr, max_degree)
    ids = jnp.sort(jnp.where(valid, nbr_mat, _BIG), axis=1)
    return a, b, m, ids


def _membership_pass(ids, a, b, m, num_vertices: int, edge_chunk: int):
    """Membership counting over (a, b) edge slices against the replicated
    ``ids`` rows; [E, D] intermediates bounded by ``edge_chunk`` scan."""
    E = a.shape[0]
    pad_to = -(-E // edge_chunk) * edge_chunk
    ap = jnp.concatenate([a, jnp.zeros(pad_to - E, a.dtype)])
    bp = jnp.concatenate([b, jnp.zeros(pad_to - E, b.dtype)])
    mp = jnp.concatenate([m, jnp.zeros(pad_to - E, bool)])
    n_chunks = pad_to // edge_chunk
    ac = ap.reshape(n_chunks, edge_chunk)
    bc = bp.reshape(n_chunks, edge_chunk)
    mc = mp.reshape(n_chunks, edge_chunk)

    def chunk_step(carry, x):
        counts, total = carry
        a_i, b_i, m_i = x
        rows_a = jnp.where(m_i[:, None], ids[a_i], _BIG)
        rows_b = ids[b_i]
        _, found = _row_membership(rows_a, rows_b)
        c = found.sum(axis=1).astype(jnp.int32)
        w_ids = jnp.where(found, rows_a, 0)
        counts = counts.at[w_ids.reshape(-1)].add(
            found.reshape(-1).astype(jnp.int32)
        )
        cm = jnp.where(m_i, c, 0)
        counts = counts.at[a_i].add(cm).at[b_i].add(cm)
        return (counts, total + cm.sum()), None

    init = (jnp.zeros(num_vertices, jnp.int32), jnp.int32(0))
    (per_vertex, total), _ = jax.lax.scan(chunk_step, init, (ac, bc, mc))
    return total, per_vertex


def window_triangle_count_sharded(
    src: jax.Array,
    dst: jax.Array,
    mask: jax.Array,
    num_vertices: int,
    max_degree: int,
    mesh,
    edge_chunk: int = 1 << 13,
) -> Tuple[jax.Array, jax.Array]:
    """Edge-sharded :func:`window_triangle_count` (SURVEY §2.5 P1 + P3).

    The prep (canonicalize/dedup/orient/row build) is replicated — it
    needs the whole window and is O(E log E) sort work; the membership
    pass (the O(E*D) dominant cost) splits over the mesh's ``"edges"``
    axis with the dense rows replicated, and the per-vertex counts and
    total ``psum`` back over ICI. Deterministic: per-shard counting is
    order-independent integer adds. The block capacity (a power of two)
    must divide by the edge-axis size.
    """
    from jax.sharding import PartitionSpec as P

    from ..parallel import comm
    from ..parallel.mesh import EDGE_AXIS

    a, b, m, ids = _oriented_rows(src, dst, mask, num_vertices, max_degree)

    def shard_fn(ids_r, a_s, b_s, m_s):
        total, counts = _membership_pass(
            ids_r, a_s, b_s, m_s, num_vertices, edge_chunk
        )
        return (
            jax.lax.psum(total, EDGE_AXIS),
            jax.lax.psum(counts, EDGE_AXIS),
        )

    return comm.shard_map(
        shard_fn,
        mesh,
        in_specs=(P(None, None), P(EDGE_AXIS), P(EDGE_AXIS), P(EDGE_AXIS)),
        out_specs=(P(), P()),
    )(ids, a, b, m)


def ranged_searchsorted(arr, lo, hi, x, *, side: str = "left", steps: int = 32):
    """Elementwise binary search of ``x`` within ``arr[lo:hi)`` (each
    element has its own range; ``arr`` ascending within every range).
    Returns the leftmost (``side='left'``) or rightmost insertion
    position. Fixed ``steps`` iterations (covers arrays up to 2^steps)."""
    right = side == "right"

    def body(_, c):
        lo, hi = c
        mid = (lo + hi) >> 1
        mid_c = jnp.clip(mid, 0, arr.shape[0] - 1)
        v = arr[mid_c]
        go_right = (v <= x) if right else (v < x)
        go_right = go_right & (lo < hi)
        return jnp.where(go_right, mid + 1, lo), jnp.where(
            lo < hi, jnp.where(go_right, hi, mid), hi
        )

    lo, hi = jax.lax.fori_loop(0, steps, body, (lo, hi))
    return lo


def _count_composite(sv, sn, v, n, side: str):
    """How many (sv, sn) pairs (sorted, sentinel-padded) compare
    less [or less-or-equal for side='right'] than each (v, n) query —
    the composite-key searchsorted, in pure int32."""
    lt = jnp.searchsorted(sv, v, side="left").astype(jnp.int32)
    hi = jnp.searchsorted(sv, v, side="right").astype(jnp.int32)
    within = ranged_searchsorted(sn, lt, hi, n, side=side)
    return within


def merge_packed_adjacency(pv, pn, pr, new_v, new_n, new_r, n_new):
    """Merge sorted new (vertex, nbr, rank) entries into the packed sorted
    adjacency — a composite-key merge path (two-level searchsorted +
    scatter), not a re-sort of the accumulated arrays; per-window work is
    O(total) data movement but only O(log) comparisons per element, all
    int32 (no 64-bit key packing).

    Both inputs sorted by (vertex, nbr) with +INT32_MAX sentinel padding
    in the vertex column; real keys must be disjoint (callers dedup).
    Output arrays keep the callers' pre-grown capacity = len(pv).
    """
    cap = pv.shape[0]
    ncap = new_v.shape[0]
    pos_old = jnp.arange(cap, dtype=jnp.int32) + _count_composite(
        new_v, new_n, pv, pn, side="left"
    )
    pos_new = jnp.arange(ncap, dtype=jnp.int32) + _count_composite(
        pv, pn, new_v, new_n, side="right"
    )
    pos_old = jnp.where(pv == _BIG, cap, pos_old)
    pos_new = jnp.where(jnp.arange(ncap) < n_new, pos_new, cap)
    out_v = jnp.full(cap, _BIG, jnp.int32)
    out_n = jnp.zeros(cap, jnp.int32)
    out_r = jnp.zeros(cap, jnp.int32)
    out_v = out_v.at[pos_old].set(pv, mode="drop").at[pos_new].set(new_v, mode="drop")
    out_n = out_n.at[pos_old].set(pn, mode="drop").at[pos_new].set(new_n, mode="drop")
    out_r = out_r.at[pos_old].set(pr, mode="drop").at[pos_new].set(new_r, mode="drop")
    return out_v, out_n, out_r


def prepare_packed_window(
    pv, pn, pr, src, dst, mask, rank0, num_vertices: int,
    search_steps: int = 32,
):
    """One-dispatch window prep for streaming exact triangles: canonicalize
    the window's raw edges, drop self-loops, dedup in-window, reject edges
    already present in the packed adjacency (ranged binary search), sort
    the survivors' two directed entries, merge them into the packed
    columns, and rebuild the row pointer — entirely on device.

    The previous design did the dedup (np.unique + hash set) and the
    entry sort (np.lexsort) on the host: ~220 ms per 256k-edge window,
    which WAS the system rate (round-3 profile). Returns
    ``(pv, pn, pr, row_ptr, qu, qv, qrank, qmask)`` where the q-arrays
    are the accepted query edges aligned with the input slots.
    """
    n = src.shape[0]
    u, v, m = canonicalize(src, dst, mask)
    u, v, m = dedup_canonical(u, v, m, num_vertices)
    # cross-window duplicates: is (u, v) already a packed row of u?
    row_ptr0 = jnp.searchsorted(
        pv, jnp.arange(num_vertices + 1, dtype=jnp.int32)
    ).astype(jnp.int32)
    uc = jnp.clip(u, 0, num_vertices - 1)
    lo = row_ptr0[uc]
    hi = row_ptr0[uc + 1]
    pos = ranged_searchsorted(pn, lo, hi, v, steps=search_steps)
    pos_c = jnp.clip(pos, 0, pn.shape[0] - 1)
    dup = (pos < hi) & (pn[pos_c] == v)
    m = m & ~dup
    qrank = rank0 + jnp.arange(n, dtype=jnp.int32)
    # both directed entries of every accepted edge; rejected slots become
    # +INT32_MAX sentinels and sort to the tail
    pv_new = jnp.concatenate([jnp.where(m, u, _BIG), jnp.where(m, v, _BIG)])
    pn_new = jnp.concatenate([jnp.where(m, v, 0), jnp.where(m, u, 0)])
    pr_new = jnp.concatenate([jnp.where(m, qrank, 0)] * 2)
    spv, spn, spr = jax.lax.sort((pv_new, pn_new, pr_new), num_keys=2)
    n_new = 2 * m.sum().astype(jnp.int32)
    pv2, pn2, pr2 = merge_packed_adjacency(pv, pn, pr, spv, spn, spr, n_new)
    row_ptr = jnp.searchsorted(
        pv2, jnp.arange(num_vertices + 1, dtype=jnp.int32)
    ).astype(jnp.int32)
    return pv2, pn2, pr2, row_ptr, u, v, qrank, m


# --------------------------------------------------------------------- #
# Shared packed-adjacency carry helpers (used by the streaming triangle
# pipeline AND the k=2 device spanner — one implementation of the growth,
# host-side build, class-binning, and recompile-avoidance policies).
# --------------------------------------------------------------------- #

def grow_packed_columns(pv, pn, pr, need: int, minimum: int = 8):
    """Grow (or create) packed (vertex, nbr, rank) columns to a pow2
    bucket covering ``need`` entries — appending +INT32_MAX vertex
    sentinels keeps the sort order."""
    from ..core.edgeblock import bucket_capacity

    cap = bucket_capacity(max(need, minimum))
    if pv is None:
        return (
            jnp.full(cap, _BIG, jnp.int32),
            jnp.zeros(cap, jnp.int32),
            jnp.zeros(cap, jnp.int32),
        )
    old = pv.shape[0]
    if cap <= old:
        return pv, pn, pr
    return (
        jnp.concatenate([pv, jnp.full(cap - old, _BIG, jnp.int32)]),
        jnp.concatenate([pn, jnp.zeros(cap - old, jnp.int32)]),
        jnp.concatenate([pr, jnp.zeros(cap - old, jnp.int32)]),
    )


def build_sorted_directed(u, v, ranks=None, cap=None):
    """Host-side build of both directed entries of canonical edges,
    (vertex, nbr)-lexsorted and sentinel-padded: the merge input format
    of :func:`merge_packed_adjacency`. Returns numpy
    ``(pv, pn, pr, n_new)``."""
    import numpy as _np

    from ..core.edgeblock import bucket_capacity

    pv_new = _np.concatenate([u, v])
    pn_new = _np.concatenate([v, u])
    if ranks is None:
        pr_new = _np.zeros(len(pv_new), _np.int32)
    else:
        pr_new = _np.concatenate([ranks, ranks])
    order = _np.lexsort((pn_new, pv_new))
    n_new = len(pv_new)
    ncap = cap if cap is not None else bucket_capacity(n_new, minimum=16)
    pvp = _np.full(ncap, _np.iinfo(_np.int32).max, _np.int32)
    pnp = _np.zeros(ncap, _np.int32)
    prp = _np.zeros(ncap, _np.int32)
    pvp[:n_new] = pv_new[order]
    pnp[:n_new] = pn_new[order]
    prp[:n_new] = pr_new[order]
    return pvp, pnp, prp, n_new


#: min-degree classes coarsen by powers of this factor: a handful of
#: dispatches per window (each pays its launch overhead) for at most
#: CLASS_FACTOR x enumeration-width waste in a class
CLASS_FACTOR = 4

#: [chunk, width] int32 entries budget for dense enumeration blocks
ENUM_BUDGET = 1 << 24  # 64 MB


def degree_class_plan(mindeg, class_factor: int = CLASS_FACTOR,
                      enum_budget: int = ENUM_BUDGET):
    """Group query indices into coarse min-degree classes.

    Yields ``(width, sel, tcap, chunk)`` per class: ``sel`` the query
    indices (numpy int32), ``tcap`` their pow2 padding, ``chunk`` the
    scan slice keeping [chunk, width] within ``enum_budget``.
    """
    import numpy as _np

    from ..core.edgeblock import bucket_capacity

    fbits = int(class_factor).bit_length() - 1
    exp = _np.ceil(
        _np.log2(_np.maximum(_np.maximum(mindeg, 16), 1)) / fbits
    ).astype(_np.int64)
    classes = _np.int64(1) << (exp * fbits)
    for c in _np.unique(classes):
        sel = _np.nonzero(classes == c)[0].astype(_np.int32)
        tcap = bucket_capacity(len(sel), minimum=16)
        chunk = min(tcap, bucket_capacity(max(enum_budget // int(c), 16)))
        yield int(c), sel, tcap, int(chunk)


def chunked_class_scan(body_fn, carry, sel, chunk: int):
    """Scan one degree class's padded selection (``-1`` padding) in
    ``chunk`` slices: ``body_fn(carry, sel_slice) -> carry``. The shared
    scaffold of the per-class query kernels (triangle counting, spanner
    common-neighbor tests) — bounds the [chunk, width] enumeration block
    instead of materializing the whole class at once. ``sel`` length and
    ``chunk`` are both powers of two, so the reshape is exact."""
    sel_r = sel.reshape(sel.shape[0] // chunk, chunk)
    out, _ = jax.lax.scan(lambda c, s: (body_fn(c, s), None), carry, sel_r)
    return out


def sticky_search_steps(current: int, max_degree: int) -> int:
    """Monotone, 8-quantized binary-search step count covering the
    longest adjacency row: at most a few distinct jit signatures over a
    stream's lifetime (each recompile costs ~20-40 s through the remote
    compiler), instead of churning every time the max degree crosses a
    pow2 bucket."""
    from ..core.edgeblock import bucket_capacity

    needed = max(4, int(bucket_capacity(max(int(max_degree), 1))).bit_length())
    return max(current, ((needed + 7) // 8) * 8)


def packed_common_neighbor_exists(
    pn, row_ptr, qu, qv, qmask, enum_width: int, search_steps: int = 32,
):
    """For each query pair (qu, qv): do their packed-adjacency rows share
    a neighbor? The k=2 reachability primitive of the device spanner —
    common-neighbor existence over the same packed sorted adjacency the
    triangle pipeline carries, with per-class dense enumeration rows (the
    caller groups queries by min-degree class). No [B, V] frontier."""
    d_u = row_ptr[qu + 1] - row_ptr[qu]
    d_v = row_ptr[qv + 1] - row_ptr[qv]
    take_u = d_u <= d_v
    small = jnp.where(take_u, qu, qv)
    big = jnp.where(take_u, qv, qu)
    idx = row_ptr[small][:, None] + jnp.arange(enum_width)[None, :]
    valid = (
        qmask[:, None]
        & (jnp.arange(enum_width)[None, :] < jnp.minimum(d_u, d_v)[:, None])
    )
    idx = jnp.clip(idx, 0, pn.shape[0] - 1)
    w = pn[idx]
    lo = jnp.broadcast_to(row_ptr[big][:, None], w.shape)
    hi = jnp.broadcast_to(row_ptr[big + 1][:, None], w.shape)
    pos = ranged_searchsorted(pn, lo, hi, w, steps=search_steps)
    pos_c = jnp.clip(pos, 0, pn.shape[0] - 1)
    found = valid & (pos < hi) & (pn[pos_c] == w)
    return found.any(axis=1)


def packed_triangle_update(
    pn, pr, row_ptr,
    qu, qv, qrank, qmask,
    counts,
    enum_width: int,
    search_steps: int = 32,
):
    """Count triangles closed by query edges against a PACKED adjacency.

    ``pn``/``pr``: neighbor/rank columns of the packed (vertex, nbr)-sorted
    adjacency; ``row_ptr[v]`` the start of v's run. Each query edge
    enumerates the neighborhood of its SMALLER-degree endpoint (the caller
    groups queries into ``enum_width`` degree classes, so dense enumeration
    rows are only as wide as each class — no hub sizes anyone else's rows;
    memory is O(E) total) and checks each candidate w against the larger
    endpoint's run with a ranged binary search, under the closed-by-last-
    edge rank rule: both closing edges strictly earlier than the query.
    Returns ``(counts, delta)``.
    """
    d_u = row_ptr[qu + 1] - row_ptr[qu]
    d_v = row_ptr[qv + 1] - row_ptr[qv]
    take_u = d_u <= d_v
    small = jnp.where(take_u, qu, qv)
    big = jnp.where(take_u, qv, qu)
    idx = row_ptr[small][:, None] + jnp.arange(enum_width)[None, :]
    valid = (
        qmask[:, None]
        & (jnp.arange(enum_width)[None, :] < jnp.minimum(d_u, d_v)[:, None])
    )
    idx = jnp.clip(idx, 0, pn.shape[0] - 1)
    w = pn[idx]
    wr = pr[idx]
    lo = jnp.broadcast_to(row_ptr[big][:, None], w.shape)
    hi = jnp.broadcast_to(row_ptr[big + 1][:, None], w.shape)
    pos = ranged_searchsorted(pn, lo, hi, w, steps=search_steps)
    pos_c = jnp.clip(pos, 0, pn.shape[0] - 1)
    found = (pos < hi) & (pn[pos_c] == w)
    r = qrank[:, None]
    match = valid & found & (wr < r) & (pr[pos_c] < r)
    c = match.sum(axis=1).astype(jnp.int32)
    w_ids = jnp.where(match, w, 0)
    counts = counts.at[w_ids.reshape(-1)].add(match.reshape(-1).astype(jnp.int32))
    cm = jnp.where(qmask, c, 0)
    counts = counts.at[qu].add(cm).at[qv].add(cm)
    return counts, cm.sum().astype(jnp.int32)


