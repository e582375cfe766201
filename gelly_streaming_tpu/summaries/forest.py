"""Window-local fold over a lazily-canonicalized forest carry: the one
fold under connected components and the signed cover.

The dense-label engine (``summaries/labels.py``) pays O(vcap) work per
window — init_labels + full-table fixpoint + combine — even when the
window touches <=2W vertices. That is the wrong cost shape vs the
reference, whose per-partition fold touches only the window's edges
(``SummaryBulkAggregation.java:76-80``). Here the carried summary is a
**pointer forest** ``canon[vcap]`` (int32, ``canon[v] <= v``, acyclic by
the strictly-decreasing min-root invariant) that is only *canonicalized*
— chains collapsed to flat labels — at emission or checkpoint time.
Per window, every kernel is sized by the window, not the vertex space:

1. The HOST computes the window's touched set beside the stream (unique
   endpoints of the cached pre-padding columns, order unspecified — the
   novelty-shadow pattern: zero device->host reads in the producer loop)
   and renumbers the window's edges into local indices ``[0, T)``
   (:func:`pad_window`; :func:`pad_group` for K windows in one dispatch).
2. The DEVICE chases the touched vertices' pointers to their current
   roots (``chase_roots``: a ``lax.while_loop`` that carries each
   lane's next pointer, so a round costs ONE O(T) gather out of the
   table and the loop's condition none; chains only pass through
   former roots, and touched vertices are fully path-compressed every
   window).
3. One scatter-min into a vcap-sized scratch gives every touched lane
   the representative (min lane) of its "same current root" group. The
   window's edges are relabelled through it, once, and a min-label
   fixpoint over the **local** T-sized table (exactly the dense
   kernel's hook+shortcut, on a table the size of the window) joins the
   representatives: connected components of the window's quotient
   graph, W lanes a round. Every lane reads its label back through its
   representative (:func:`_make_local_fixpoint`).
4. A pair of masked scatters re-roots the old roots (and the touched
   vertices, for path compression) to the merged component's min root.

Steps 2 to 4 are written ONCE: :func:`window_body` (``chase_and_group``,
the local fixpoint of :func:`_make_local_fixpoint`, ``commit_roots``)
and, for K windows fused, :func:`group_body` (one chase, a scan of
fixpoints, one re-rooting). CC's jitted programs are those bodies as
they are. The signed cover's (``summaries/candidates.py``) are the same
bodies over the ``2*vcap`` cover id space: the lanes doubled, an edge
mask over its pad rows, and a conflict latch read off the new roots.
All four programs live in one bounded cache (:func:`cached_step`).

The remaining vcap-sized costs are the functional scatter's buffer copy
(which is also what keeps per-window emissions valid snapshots — the
pre-scatter buffer stays alive for any lazy emission holding it), the
step-2 scratch and the three table-sized scatters. What each phase
costs on the chip, per cell, is measured and kept in ``PERF.md``
section 5 (the rates in ``PERF_LEDGER.jsonl``); no CPU timing says
anything about it.

Tracing (``obs/trace.py``): the device phases are ``jax.named_scope``s
inside the jitted step, shared by the per-window and the superbatch
steps of both carries (CC and the signed cover) — ``forest.chase``
(step 2's pointer chase), ``forest.group`` (its vcap-sized same-root
scratch), ``forest.fixpoint`` (step 3) and inside it
``forest.contract`` (its once-a-step part: the endpoints relabelled,
the labels read back), ``forest.commit`` (step 4), inside group and
commit ``forest.sort`` (the sort ahead of each table-sized scatter)
and, on the cover, ``forest.latch``. A device
trace carries the scope in
the ``tf_op`` stat of each ``XLA Ops`` event's metadata; the jitted
programs keep the name ``jit_step``. On the host one window is the span ``forest.window`` with
the children ``forest.prep`` (step 1 and the padding) and
``forest.dispatch`` (uploads and the jit call: enqueue time).

Vertex-sharded layout (``parallel/mesh.py``'s ``vertices`` axis above
1): the SAME step over a table of which every chip holds one
contiguous block of rows. Every access to the ``vcap``-sized table goes
through one pair of primitives, :class:`TableOps` ``gather`` and
``scatter``; with no axis they are ``table[idx]`` and, behind a sort
of the (row, value) lanes by row (scope ``forest.sort``),
``table.at[idx].set/min(mode="drop", indices_are_sorted=True)``; with
the axis the whole step runs under ``shard_map``, the lanes are
replicated, the owner of a row answers a gather and one all-reduce
(scope ``forest.exchange``) makes the lanes whole, and a scatter sorts
and applies the lanes this chip owns with no exchange. The host then
also emits ``forest.place`` (child of
``forest.window``: the window's columns placed on every chip) and the
attributes ``shards`` and ``owner_max_share`` on ``forest.window``.

Reference parity: this is the ``UpdateCC``/``CombineCC`` pair of
``library/ConnectedComponents.java:83-126`` with the DisjointSet's
pointer forest kept on device and its find-with-path-compression
vectorized over the window's touched set.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from ..core.edgeblock import bucket_capacity
from ..obs import trace as _trace
from ..parallel import comm
from ..parallel.mesh import (
    EDGE_AXIS,
    VERTEX_AXIS,
    replicated,
    vertex_sharding,
    vertex_shards,
)
from .labels import _propagate

_I32_MAX = jnp.iinfo(jnp.int32).max

#: every jitted forest program of the process, CC's and the cover's, per
#: window and per group: keyed ``(kind, tcap, wcap, vcap, ...)``; bounded
#: FIFO like the engine's step cache (each signature costs seconds of
#: compilation).
_STEP_CACHE: dict = {}
_STEP_CACHE_MAX = 32


def cached_step(key, build):
    """The jitted program under ``key``; built by ``build()`` and kept,
    the oldest entry making room, if it is not there yet."""
    fn = _STEP_CACHE.get(key)
    if fn is None:
        fn = build()
        if len(_STEP_CACHE) >= _STEP_CACHE_MAX:
            _STEP_CACHE.pop(next(iter(_STEP_CACHE)))
        _STEP_CACHE[key] = fn
    return fn


def _table_combine(tcap: int):
    """Merge two local label tables over the same touched set: the
    union's constraints are exactly the pointer edges of both tables
    (``labels.label_combine`` on plain arrays)."""
    iota = jnp.arange(tcap, dtype=jnp.int32)

    def combine(a, b):
        u = jnp.concatenate([iota, iota])
        w = jnp.concatenate([a, b])
        return _propagate(
            jnp.minimum(a, b), u, w, jnp.ones(2 * tcap, bool)
        )

    return combine


class TableOps:
    """The one pair of primitives through which a step reads and writes
    a ``vcap``-sized table: a gather of lanes and a masked scatter of
    lanes (``set`` or ``min``).

    ``TableOps(vcap)`` is the whole table on one chip: ``table[idx]``
    and ``table.at[idx].set/min(val, mode="drop")`` over lanes sorted by
    row.

    Every scatter goes out sorted: the (row, value) pairs are sorted by
    row, then value (``lax.sort``, under the named scope
    ``forest.sort``), and the scatter says ``indices_are_sorted``. On
    the chip a table-sized scatter of unsorted lanes costs 91 ns a lane
    and one of sorted lanes 17 ns; the sort, in fast memory, under a
    microsecond a thousand lanes. The table that comes out is the same
    on every row: ``min`` does not care for order, and the callers'
    ``set`` writes one value to a row however often the row repeats.
    ``unique_indices`` is NOT said: an old root repeats once per touched
    member, and the pads all sit on one sentinel.

    ``TableOps(vcap, shards, exchange)`` is one chip's block of a table
    split over the ``vertices`` axis, and is used INSIDE ``shard_map``
    (:func:`sharded_table_fn`): ``table`` is the local block of
    ``rows = vcap / shards`` rows starting at ``axis_index * rows``,
    lanes (``idx``, ``val``) are replicated. A gather reads the lanes
    this chip owns and 0 elsewhere, and ONE all-reduce (a sum: every
    row has exactly one owner) under the named scope ``exchange`` makes
    the lanes whole on every chip. A scatter applies the lanes this
    chip owns and drops the rest at the local sentinel ``rows``, where
    they sort to the end; the callers' whole-table sentinel ``vcap`` is
    nobody's row, so it drops everywhere. No exchange: every chip sees,
    and sorts, every lane."""

    __slots__ = ("rows", "shards", "exchange")

    def __init__(self, vcap: int, shards: int = 1,
                 exchange: str = "forest.exchange"):
        if vcap % shards:
            raise ValueError(
                f"a table of {vcap} rows does not split in {shards} "
                "equal blocks"
            )
        self.rows = vcap // shards
        self.shards = shards
        self.exchange = exchange

    def _local(self, idx):
        """``(mine, off)``: which lanes this chip owns and their row in
        its block."""
        off = idx - lax.axis_index(VERTEX_AXIS).astype(idx.dtype) * self.rows
        return (off >= 0) & (off < self.rows), off

    def full(self, fill):
        """This chip's block of a fresh int32 table."""
        return jnp.full(self.rows, fill, jnp.int32)

    def gather(self, table, idx):
        if self.shards == 1:
            return table[idx]
        mine, off = self._local(idx)
        got = jnp.where(mine, table[jnp.where(mine, off, 0)], 0)
        with jax.named_scope(self.exchange):
            return lax.psum(got, VERTEX_AXIS)

    def scatter(self, table, idx, val, op: str = "set"):
        if self.shards > 1:
            mine, off = self._local(idx)
            idx = jnp.where(mine, off, self.rows)
        # dropped lanes carry a sentinel at or past ``rows`` and sort to
        # the end; the second key makes the sorted lanes independent of
        # the order they came in
        with jax.named_scope("forest.sort"):
            idx, val = lax.sort((idx, val), num_keys=2)
        at = table.at[idx]
        return (at.min if op == "min" else at.set)(
            val, mode="drop", indices_are_sorted=True
        )


def sharded_table_fn(fn, mesh, n_lanes: int, table_out: bool):
    """``fn(table, *lanes)`` under ``shard_map`` over the ``vertices``
    axis: the table argument (and the result, if ``table_out``) is split
    by rows, the ``n_lanes`` lane arguments (and a lane result) are
    replicated."""
    return comm.shard_map(
        fn, mesh, (P(VERTEX_AXIS),) + (P(),) * n_lanes,
        P(VERTEX_AXIS) if table_out else P(),
    )


def vertex_layout(mesh, superbatch: bool = False) -> int:
    """Shards of the ``vertices`` axis a forest step is laid out in (1:
    the whole table on every chip of ``mesh``). Refuses, in one place,
    what the sharded layout cannot do yet."""
    shards = vertex_shards(mesh)
    if shards == 1:
        return 1
    if mesh.shape.get(EDGE_AXIS, 1) > 1:
        raise NotImplementedError(
            f"mesh {dict(mesh.shape)}: the vertex-sharded forest step runs "
            "its window-sized fixpoint whole on every chip; splitting the "
            "edge columns over an `edges` axis above 1 at the same time "
            "is not built (make_mesh(n_edge_shards=1, n_vertex_shards=...))"
        )
    if superbatch:
        raise NotImplementedError(
            "the superbatch forest step (_forest_superbatch_fn) and its "
            "ForestReplay read the whole table on one chip; under a "
            "`vertices` axis above 1 run superbatch=1"
        )
    return shards


def chase_roots(canon, r0, tab: TableOps = None):
    """Follow every lane of ``r0`` along ``canon`` to its root: the one
    pointer chase of the repo (the forest steps and the serving tier's
    ``_batch_roots`` call it), over ``tab``'s layout of the table.

    The loop carries ``(r, nxt)`` with ``nxt == canon[r]``, so its
    condition is an elementwise compare and a reduce over the lanes and
    its body holds the round's only gather out of the table: a chain of
    depth ``d`` costs ``1 + d`` gathers where testing ``canon[r] != r``
    and then stepping ``r = canon[r]`` cost ``1 + 2d`` (a gather costs
    per lane on the chip, 17 ns, not per byte). Same roots after the
    same number of trips. Read-only on ``canon``, so chains are static
    during the chase; roots satisfy ``canon[r] == r`` and chains
    strictly decrease (min-root invariant), so the loop terminates.
    """
    tab = tab or TableOps(canon.shape[0])
    r, _nxt = lax.while_loop(
        lambda c: jnp.any(c[1] != c[0]),
        lambda c: (c[1], tab.gather(canon, c[1])),
        (r0, tab.gather(canon, r0)),
    )
    return r


def chase_and_group(canon, tid, tmask, tcap: int, vcap: int,
                    tab: TableOps = None):
    """Shared forest-step front half (CC + signed-cover carries).

    1. Chase touched pointers to their current roots
       (:func:`chase_roots`). Padding lanes chase from 0, which is
       always self-rooted (canon[0] <= 0).
    2. "Same current root" constraints: scatter-min each lane's local
       index into a vcap scratch keyed by root, so every lane learns
       its group's representative lane — a memset, a scatter and a
       gather. The scatter's lanes are sorted by root first
       (:meth:`TableOps.scatter`: 17 against 91 ns a lane on the chip).
       ``rep_i`` stands for lane i's whole group; pads self-loop.

    Returns ``(r, v2, key_, iota)``: current roots per lane, each
    lane's group's representative (a depth-1 forest: ``v2[v2] == v2``),
    the root-value keys (+inf on pads), and the lane iota.
    ``tab`` is the table's layout (default: whole, on one chip); the
    scratch is laid out like the table.
    """
    tab = tab or TableOps(vcap)
    with jax.named_scope("forest.chase"):
        r = chase_roots(
            canon, jnp.where(tmask, tab.gather(canon, tid), 0), tab
        )
    with jax.named_scope("forest.group"):
        iota = jnp.arange(tcap, dtype=jnp.int32)
        sid_r = jnp.where(tmask, r, vcap)
        scratch = tab.scatter(
            tab.full(_I32_MAX), sid_r,
            jnp.where(tmask, iota, _I32_MAX), "min",
        )
        rep = tab.gather(scratch, jnp.where(tmask, r, 0))
        v2 = jnp.where(tmask, rep, iota)
        key_ = jnp.where(tmask, r, _I32_MAX)
    return r, v2, key_, iota


def commit_roots(canon, local, key_, r, tid, tmask, tcap: int, vcap: int,
                 tab: TableOps = None):
    """Shared forest-step back half: the merged component's new root is
    the min of its members' old roots (each old root is the min id of
    its old component, so the min over merged roots is the min id of the
    merged component); re-root the old roots and path-compress the
    touched lanes (pads dropped). Returns ``(canon, nr)`` — ``nr`` is
    each lane's final root value (the cover carry's conflict latch reads
    it)."""
    with jax.named_scope("forest.commit"):
        nr = new_roots(local, key_, tcap)
        canon = reroot(canon, nr, r, tid, tmask, vcap, tab)
    return canon, nr


def new_roots(local, key_, tcap: int):
    """Each lane's merged component's min old root (callers name the
    scope: ``forest.commit``)."""
    minr = jnp.full(tcap, _I32_MAX, jnp.int32).at[local].min(key_)
    return minr[local]


def reroot(canon, nr, r, tid, tmask, vcap: int, tab: TableOps = None):
    """The masked scatter pair of the commit: old roots, then the
    touched lanes (path compression); pads drop at index ``vcap``."""
    tab = tab or TableOps(vcap)
    sid_r = jnp.where(tmask, r, vcap)
    canon = tab.scatter(canon, sid_r, nr)
    tid_s = jnp.where(tmask, tid, vcap)
    return tab.scatter(canon, tid_s, nr)


def _make_local_fixpoint(tcap: int, mesh=None, tree: bool = False,
                         degree: int = 2):
    """The T-sized local min-label fixpoint of every forest program, CC's
    and the cover's, per window and per group: ``fixpoint(seed, lu, lv,
    targets, emask=None)`` is connected components of the window's
    QUOTIENT graph. ``targets`` maps every lane to the representative of
    its same-root group, so the groups are folded into their
    representatives once, before the rounds (scope ``forest.contract``):
    the edge rows are relabelled ``(targets[lu], targets[lv])``,
    ``_propagate`` runs over those ``wcap`` rows alone, and every lane
    reads its label back through its representative, ``lab[targets]``.
    The same partition and the same label on every lane as with the
    pointer edges ``(i, targets[i])`` carried through every round as
    edges, at ``wcap`` lanes a round and not ``wcap + tcap``, and in no
    more rounds (group mates are one node from the start).

    PRECONDITION, met by both callers and not checked in the jitted
    step: ``targets[targets] == targets`` (a depth-1 forest), and
    ``seed[targets[i]]`` is the label lane ``i`` enters with. The
    per-window body seeds from the lane iota and targets each group's
    min lane (pads self-loop); the group body seeds from, and targets,
    the carried label table, flat at convergence.

    With no ``emask`` every row counts: lu/lv pads are (0,0) self-loops.
    A carry whose id space gives those a meaning (the cover) masks its
    rows, and a masked row stays masked whatever its relabelled
    endpoints are.

    Under a mesh this is the engine's per-shard-fold +
    cross-shard-combine shape on WINDOW-SIZED tables: each shard folds
    its slice of the edge columns (``targets`` replicates) and expands
    to a whole label table, then the label tables merge through the
    bulk stack or the ppermute butterfly. The vcap-sized carry never
    crosses the mesh."""
    if mesh is not None:
        p = mesh.shape[EDGE_AXIS]
        combine = _table_combine(tcap)

    def fixpoint(seed, lu, lv, targets, emask=None):
        def fold(lu_s, lv_s, em_s=None):
            with jax.named_scope("forest.contract"):
                u, w = targets[lu_s], targets[lv_s]
            m = jnp.ones(u.shape[0], bool) if em_s is None else em_s
            lab = _propagate(seed, u, w, m)
            with jax.named_scope("forest.contract"):
                return lab[targets]

        cols = (lu, lv) if emask is None else (lu, lv, emask)
        if mesh is None:
            return fold(*cols)

        def shard_fn(*cols_s):
            lab = fold(*cols_s)
            if tree:
                return comm.tree_all_reduce(
                    lab, EDGE_AXIS, combine, p, degree=degree
                )
            return lab[None]

        out = comm.shard_map(
            shard_fn, mesh, (P(EDGE_AXIS),) * len(cols),
            P() if tree else P(EDGE_AXIS),
        )(*cols)
        return out if tree else comm.stacked_reduce(out, p, combine)

    return fixpoint


def window_body(tcap: int, vcap: int, tab: TableOps, fixpoint):
    """THE forest fold of one window, over ``tab``'s layout of a
    ``vcap``-row table: ``body(canon, tid, tmask, lu, lv, emask=None) ->
    (canon, nr)`` is :func:`chase_and_group`, the local ``fixpoint``
    (:func:`_make_local_fixpoint`; scope ``forest.fixpoint``) seeded
    from the lane iota with each lane's same-root group's min lane as
    targets, and :func:`commit_roots`. CC's step is this body and
    returns ``canon``; the cover's doubles the lanes, masks its pad rows
    and reads its latch off ``nr`` (``candidates.py``)."""

    def body(canon, tid, tmask, lu, lv, emask=None):
        r, v2, key_, lanes = chase_and_group(
            canon, tid, tmask, tcap, vcap, tab
        )
        with jax.named_scope("forest.fixpoint"):
            local = fixpoint(lanes, lu, lv, v2, emask)
        return commit_roots(
            canon, local, key_, r, tid, tmask, tcap, vcap, tab
        )

    return body


def group_body(tcap: int, vcap: int, fixpoint):
    """THE forest fold of K windows in one dispatch, GROUP-LOCAL:
    ``body(canon, tid, tmask, lu, lv, emask=None) -> (canon, r, nr_s)``
    with ``lu, lv`` (and ``emask``) ``[k, wcap]`` over the GROUP's
    touched lanes.

    The naive fusion — scanning the per-window body with the vcap-sized
    canon as the carry — still pays vcap-sized work per window (XLA
    materializes carry updates, and the group-rep scratch memset is
    vcap-wide), which is exactly the cost shape the forest carry exists
    to avoid. This body instead hoists ALL vcap-sized work to the group
    boundary:

    1. ONE root chase + same-root grouping over the group's union
       touched set (``chase_and_group`` — one vcap scratch memset per
       GROUP, not per window);
    2. a ``lax.scan`` over the K windows whose carry is only the
       T-sized local label table: window k folds its edge columns into
       the carried table (the seeded ``fixpoint``) and emits
       ``nr_k[lane] = min pre-group root value of lane's merged group``
       — the per-window new-root assignment, [k, tcap];
    3. ONE masked scatter pair re-roots the old roots and
       path-compresses the whole touched set with the final window's
       assignment.

    Sequential window semantics are preserved by the carried table
    (window k sees every merge from windows < k); per-window canon
    snapshots are recovered lazily from ``(r, nr_k)`` by
    :class:`ForestReplay` — value-identical under resolution to the
    per-window path's canon (pointer SHAPE may differ: the fused commit
    path-compresses the group's touched set once at the end, which
    changes no root assignment). The cover reads its per-window latches
    off ``nr_s`` (``candidates.py``).

    The callers do NOT donate the input canon: the pre-group buffer
    backs the group's lazy emissions — the one vcap-copy per GROUP
    replaces the per-window path's copy per WINDOW."""

    def body(canon, tid, tmask, lu, lv, emask=None):
        r, v2, key_, _lanes = chase_and_group(canon, tid, tmask, tcap, vcap)

        def fold(lab, cols):
            lu_k, lv_k, *em_k = cols
            with jax.named_scope("forest.fixpoint"):
                lab = fixpoint(lab, lu_k, lv_k, lab, *em_k)
            with jax.named_scope("forest.commit"):
                return lab, new_roots(lab, key_, tcap)

        # v2 maps each lane to the MIN lane of its pre-group root group:
        # a depth-1 min-rooted pointer forest, i.e. already a valid
        # label table encoding the group constraints — the scan's seed
        _lab_end, nr_s = lax.scan(
            fold, v2, (lu, lv) if emask is None else (lu, lv, emask)
        )
        with jax.named_scope("forest.commit"):
            canon = reroot(canon, nr_s[-1], r, tid, tmask, vcap)
        return canon, r, nr_s

    return body


def _forest_step_fn(tcap: int, wcap: int, vcap: int, mesh=None,
                    tree: bool = False, degree: int = 2):
    """CC's jitted per-window program: :func:`window_body`, returning
    the table (under ``shard_map`` when the table is split by rows)."""

    def build():
        shards = vertex_layout(mesh)
        # under the vertices layout the edges axis is 1 and every chip
        # runs the window-sized fixpoint whole, as one chip does
        body = window_body(
            tcap, vcap, TableOps(vcap, shards),
            _make_local_fixpoint(
                tcap, mesh if shards == 1 else None, tree, degree
            ),
        )

        def step(canon, tid, tmask, lu, lv):
            return body(canon, tid, tmask, lu, lv)[0]

        if shards > 1:
            step = sharded_table_fn(step, mesh, 4, table_out=True)
        return jax.jit(step)

    return cached_step(("cc", tcap, wcap, vcap, mesh, tree, degree), build)


def _forest_superbatch_fn(tcap: int, wcap: int, vcap: int, k: int,
                          mesh=None, tree: bool = False, degree: int = 2):
    """CC's jitted group program: :func:`group_body` as it is."""

    def build():
        vertex_layout(mesh, superbatch=True)
        body = group_body(
            tcap, vcap, _make_local_fixpoint(tcap, mesh, tree, degree)
        )

        # a program is named after its callable, and every forest
        # program is ``jit_step`` (the device trace's readers look for it)
        def step(canon, tid, tmask, lu, lv):
            return body(canon, tid, tmask, lu, lv)

        return jax.jit(step)

    return cached_step(
        ("cc-group", tcap, wcap, vcap, k, mesh, tree, degree), build
    )


def _own_rows(rows: int):
    """Global ids of this chip's block (inside ``shard_map``)."""
    return lax.axis_index(VERTEX_AXIS).astype(jnp.int32) * rows + jnp.arange(
        rows, dtype=jnp.int32
    )


def init_forest(vcap: int, mesh=None) -> jax.Array:
    """Fresh forest: every vertex self-rooted. Under a ``vertices`` axis
    every chip builds its own block in place."""
    shards = vertex_shards(mesh)
    if shards == 1:
        return jnp.arange(vcap, dtype=jnp.int32)
    return jax.jit(comm.shard_map(
        lambda: _own_rows(vcap // shards), mesh, (), P(VERTEX_AXIS)
    ))()


def grow_forest(canon: jax.Array, new_vcap: int, mesh=None) -> jax.Array:
    """The forest at ``new_vcap`` rows, the new ones self-rooted. Under
    a ``vertices`` axis the blocks grow by ``g = new / old``, so the new
    block ``k`` holds the old blocks ``k*g .. k*g + g - 1``: each old
    block travels once, chip to chip, and no chip ever holds more than
    its new block and its old one."""
    old = canon.shape[0]
    if new_vcap <= old:
        return canon
    shards = vertex_shards(mesh)
    if shards == 1:
        return jnp.concatenate(
            [canon, jnp.arange(old, new_vcap, dtype=jnp.int32)]
        )
    g, rows = new_vcap // old, old // shards
    slots = min(g, shards)

    def grow(local):
        parts = [
            lax.ppermute(
                local, VERTEX_AXIS,
                [(j, j // g) for j in range(shards) if j % g == t],
            )
            for t in range(slots)
        ]
        parts.append(jnp.zeros(g * rows - slots * rows, jnp.int32))
        own = _own_rows(g * rows)
        return jnp.where(own < old, jnp.concatenate(parts), own)

    return jax.jit(sharded_table_fn(grow, mesh, 0, table_out=True))(canon)


class WindowPrep:
    """Reusable host scratch for the per-window touched-set + local
    renumbering. Native single pass when the toolchain is available
    (``native.NativeWindowPrep``: epoch-stamped, ~10-15 ms/1M-edge
    window); numpy bitmap + LUT fallback (~50 ms — still 13x faster than
    the ``np.unique`` + ``searchsorted`` it replaced, whose binary
    search is cache-miss bound). Touched-id ORDER differs between the
    two (arrival vs sorted) — the device kernels index by position, not
    value, so both are valid; emission/checkpoint never depend on it."""

    __slots__ = ("bm", "lut", "_native")

    def __init__(self):
        self.bm = np.zeros(0, bool)
        self.lut = np.zeros(0, np.int32)
        try:
            from .. import native

            self._native = native.NativeWindowPrep()
        except RuntimeError:
            # no toolchain: the numpy twin below; native.build_error()
            # keeps the compiler's message for callers that must refuse it
            self._native = None

    def prep(self, src_h, dst_h, vcap: int):
        """-> (tids unique endpoints, lu, lv local indices)."""
        if self._native is not None:
            return self._native.run(src_h, dst_h, vcap)
        if len(self.bm) < vcap:
            self.bm = np.zeros(vcap, bool)
            self.lut = np.zeros(vcap, np.int32)
        bm = self.bm
        bm[src_h] = True
        bm[dst_h] = True
        tids = np.nonzero(bm[:vcap])[0].astype(np.int32)
        bm[tids] = False  # restore the scratch without an O(V) clear
        self.lut[tids] = np.arange(len(tids), dtype=np.int32)
        return tids, self.lut[src_h], self.lut[dst_h]


def _touched_bucket(tids):
    """``(tcap, tid, tmask)``: touched ids in their pow2 bucket, the
    pad lanes masked."""
    t = len(tids)
    tcap = bucket_capacity(t, minimum=8)
    tid = np.zeros(tcap, np.int32)
    tid[:t] = tids
    tmask = np.zeros(tcap, bool)
    tmask[:t] = True
    return tcap, tid, tmask


def pad_window(prep, src_h, dst_h, vcap: int, wmin: int = 8):
    """Shared host prep + pow2 bucket padding for the window-local steps
    (CC forest + signed-cover): returns ``(tids, tcap, wcap, tid, tmask,
    lu, lv)`` with the touched bucket masked and the edge columns
    zero-padded (pad rows are (0,0) self-loops; carries whose space
    makes those meaningful — the cover — add their own edge mask)."""
    n = len(src_h)
    with _trace.span("forest.prep"):
        tids, lu_r, lv_r = prep.prep(src_h, dst_h, vcap)
        tcap, tid, tmask = _touched_bucket(tids)
        wcap = bucket_capacity(n, minimum=wmin)
        lu = np.zeros(wcap, np.int32)
        lv = np.zeros(wcap, np.int32)
        lu[:n] = lu_r
        lv[:n] = lv_r
    return tids, tcap, wcap, tid, tmask, lu, lv


def pad_group(prep, windows, vcap: int, wmin: int = 8):
    """:func:`pad_window` for a GROUP of K windows (``(src_h, dst_h)``
    host column pairs) folded in one dispatch, shared by CC's and the
    cover's group drivers: returns ``(win_tids, tcap, wcap, tid, tmask,
    lu, lv, lens)``. Two prep passes through the same per-stream
    :class:`WindowPrep` scratch: (a) one per window for the PER-WINDOW
    touched ids ``win_tids`` (the first-seen log advances in window
    order), (b) one over the group's concatenated columns for the GROUP
    touched set ``tid, tmask`` and the group-local edge renumbering
    ``lu, lv`` ``[k, wcap]`` — the lane space the device scan's carried
    label table lives in. All K windows pad to the group's bucketed
    caps, so a stream hits O(log^2 x distinct-k) jit signatures;
    padding lanes are inert in every kernel (pads chase from 0 and
    scatter-drop) and pad rows are (0,0) self-loops, which the cover
    masks by the windows' lengths ``lens``."""
    if prep is None:
        raise ValueError(
            "a group fold requires a per-stream WindowPrep (see "
            "forest_window)"
        )
    _e = np.zeros(0, np.int32)
    win_tids = [
        prep.prep(s, d, vcap)[0] if len(s) else _e for s, d in windows
    ]
    lens = np.asarray([len(s) for s, _ in windows], np.int64)
    tids_g, lu_all, lv_all = _e, _e, _e
    if lens.sum():
        tids_g, lu_all, lv_all = prep.prep(
            np.concatenate([s for s, _ in windows]),
            np.concatenate([d for _, d in windows]), vcap,
        )
    tcap, tid, tmask = _touched_bucket(tids_g)
    wcap = bucket_capacity(int(lens.max(initial=0)), minimum=wmin)
    # the concatenated columns back into their windows' rows
    rows = np.arange(wcap) < lens[:, None]
    lu = np.zeros(rows.shape, np.int32)
    lv = np.zeros(rows.shape, np.int32)
    lu[rows] = lu_all
    lv[rows] = lv_all
    return win_tids, tcap, wcap, tid, tmask, lu, lv, lens


def window_span(n: int):
    """The ``forest.window`` span of one per-window fold (CC and cover
    share the name, as they share :func:`pad_window`)."""
    return _trace.span("forest.window", {"edges": n} if _trace.on() else None)


def note_buckets(sp, tids, tcap: int, wcap: int) -> None:
    if sp.recording:
        sp.set(touched=len(tids), tcap=tcap, wcap=wcap)


def note_owners(sp, tids, vcap: int, shards: int) -> None:
    """On ``forest.window`` under a ``vertices`` axis: ``shards`` and
    ``owner_max_share``, the largest owner's share of the window's
    touched ids (what an exchange that routes each lane to its owner
    alone would have to live with; 1/shards when balanced)."""
    if sp.recording and len(tids):
        owners = np.bincount(tids // (vcap // shards), minlength=shards)
        sp.set(shards=shards,
               owner_max_share=float(owners.max()) / len(tids))


def forest_window(
    canon: jax.Array,
    src_h: np.ndarray,
    dst_h: np.ndarray,
    vcap: int,
    prep: WindowPrep,
    mesh=None,
    tree: bool = False,
    degree: int = 2,
) -> Tuple[jax.Array, np.ndarray]:
    """Fold one window (host compact-id columns) into the forest.

    ``prep`` is REQUIRED: it is the reusable per-stream scratch (native
    wprep handle + vcap-sized table) — constructing one per window would
    silently re-allocate all of it, defeating the class's design
    (round-5 advisor finding 4). Callers hold one WindowPrep per stream.

    Returns ``(new_canon, touched_ids)`` where ``touched_ids`` holds the
    window's unique endpoints (ORDER UNSPECIFIED: arrival order from the
    native prep, sorted from the numpy fallback — every consumer indexes
    by position or treats them as a set) — the caller maintains the host
    first-seen log for emission. All device inputs are bucketed to
    powers of two so a stream hits O(log^2) jit signatures.
    """
    if prep is None:
        raise ValueError(
            "forest_window requires a per-stream WindowPrep (its scratch "
            "is reusable by design; allocating one per window would "
            "silently re-create the native handle and vcap-sized table)"
        )
    n = len(src_h)
    if n == 0:
        return canon, np.zeros(0, np.int32)
    wmin = 8
    if mesh is not None:
        # the sharded columns must divide by the axis size; passing it as
        # the bucket minimum keeps every bucket divisible for ANY axis
        # width (the edgeblock.py convention), not just powers of two
        wmin = max(wmin, mesh.shape[EDGE_AXIS])
    shards = vertex_shards(mesh)
    with window_span(n) as sp:
        tids, tcap, wcap, tid, tmask, lu, lv = pad_window(
            prep, src_h, dst_h, vcap, wmin
        )
        note_buckets(sp, tids, tcap, wcap)
        cols = (tid, tmask, lu, lv)
        if shards > 1:
            note_owners(sp, tids, vcap, shards)
            with _trace.span("forest.place"):
                # every chip sees every lane: one copy of the window's
                # columns a chip
                cols = jax.device_put(cols, replicated(mesh))
        with _trace.span("forest.dispatch"):
            step = _forest_step_fn(tcap, wcap, vcap, mesh, tree, degree)
            canon = step(canon, *(jnp.asarray(c) for c in cols))
    return canon, tids


class ForestReplay:
    """Lazy mid-group canon reconstruction for superbatch emissions.

    A superbatch dispatch materializes only the FINAL canon plus the
    group's per-window new-root assignments (``nr``, device ``[k, tcap]``)
    over the group-shared touched lanes (host ``tid``/``tmask``, device
    old roots ``r``). A window-k emission that is actually read rebuilds
    that window's canon on host: copy the pre-group base and apply
    window k's assignment to the old roots and the touched set — the
    same scatter pair the fused commit runs with the last window's
    assignment, so the reconstruction resolves identically to the
    per-window path's canon. Unread emissions cost nothing; the delta
    download happens once per group on first read.
    """

    __slots__ = ("_base", "_tid", "_tmask", "_r_dev", "_nr_dev",
                 "_base_np", "_r", "_nr")

    def __init__(self, base_canon, tid: np.ndarray, tmask: np.ndarray,
                 r_dev, nr_stack):
        self._base = base_canon  # device buffer, pre-group (not donated)
        self._tid = tid          # host [tcap]
        self._tmask = tmask      # host [tcap]
        self._r_dev = r_dev      # device [tcap]
        self._nr_dev = nr_stack  # device [k, tcap]
        self._base_np = None
        self._r = None
        self._nr = None

    def canon_np(self, k: int) -> np.ndarray:
        """Host canon after window ``k`` of the group (a private copy)."""
        if self._r is None:
            self._r = np.asarray(self._r_dev)
            self._nr = np.asarray(self._nr_dev)
            self._base_np = np.asarray(self._base)
        canon = self._base_np.copy()
        m = self._tmask
        canon[self._r[m]] = self._nr[k][m]
        canon[self._tid[m]] = self._nr[k][m]
        return canon


def forest_superbatch(
    canon: jax.Array,
    windows,
    vcap: int,
    prep: WindowPrep,
    mesh=None,
    tree: bool = False,
    degree: int = 2,
) -> Tuple[jax.Array, list, "ForestReplay"]:
    """Fold K windows (list of host ``(src_h, dst_h)`` column pairs)
    into the forest as ONE fused group-local dispatch
    (:func:`pad_group`, then :func:`group_body`).

    Returns ``(new_canon, [touched_ids per window], replay)`` — the
    caller feeds ``touched_ids`` to its first-seen log in window order
    and hands ``replay`` to the group's lazy emissions.
    """
    # the sharded columns must divide by the axis size (forest_window)
    wmin = 8 if mesh is None else max(8, mesh.shape[EDGE_AXIS])
    win_tids, tcap, wcap, tid, tmask, lu, lv, _lens = pad_group(
        prep, windows, vcap, wmin
    )
    step = _forest_superbatch_fn(
        tcap, wcap, vcap, len(windows), mesh, tree, degree
    )
    new_canon, r_dev, nr_s = step(
        canon, *(jnp.asarray(c) for c in (tid, tmask, lu, lv))
    )
    return new_canon, win_tids, ForestReplay(canon, tid, tmask, r_dev, nr_s)


class MirrorReplay:
    """Lazy mid-group canon reconstruction for HOST-carry superbatches.

    The host union-find computes each window's re-rooting delta
    ``(idx, val)`` on host anyway; the superbatch path defers the device
    mirror to ONE batched scatter per group, so mid-group canons exist
    only as these host deltas. Reconstruction is cumulative (deltas
    apply in window order); sequential reads advance incrementally, a
    backward read restarts from the pre-group base. The base device
    buffer downloads once, lazily.
    """

    __slots__ = ("_base", "_deltas", "_canon", "_upto")

    def __init__(self, base_canon, deltas):
        self._base = base_canon  # device buffer, pre-group
        # [(touched, roots, changed, changed_roots) per window]
        self._deltas = deltas
        self._canon = None
        self._upto = -1

    def canon_np(self, k: int) -> np.ndarray:
        """Host canon after window ``k`` of the group (a private copy)."""
        if self._canon is None or k < self._upto:
            self._canon = np.asarray(self._base).copy()
            self._upto = -1
        for j in range(self._upto + 1, k + 1):
            t, r, c, cr = self._deltas[j]
            self._canon[t] = r
            self._canon[c] = cr
        self._upto = k
        return self._canon.copy()


#: device-mirror scatter for the host carry (jit re-specializes per
#: (ncap, vcap) shape pair automatically)
_mirror_jit = jax.jit(lambda c, i, v: c.at[i].set(v, mode="drop"))


def mirror_update(
    canon: jax.Array, idx_np: np.ndarray, val_np: np.ndarray, vcap: int
) -> jax.Array:
    """Apply a host-computed re-rooting to the device pointer-forest
    mirror: one masked scatter (pads dropped at index ``vcap``)."""
    n = len(idx_np)
    if n == 0:
        return canon
    ncap = bucket_capacity(n, minimum=8)
    idx = np.full(ncap, vcap, np.int64)
    val = np.zeros(ncap, np.int32)
    idx[:n] = idx_np
    val[:n] = val_np
    return _mirror_jit(canon, jnp.asarray(idx), jnp.asarray(val))


def resolve_flat(canon: jax.Array) -> jax.Array:
    """Canonicalize the forest to flat labels ON DEVICE (checkpoint /
    mode-switch sync point): pointer-jumping doubles chain shortcuts per
    pass, so depth is log2 of the longest chain."""

    def body(lab):
        return lab[lab]

    return lax.while_loop(
        lambda lab: jnp.any(lab[lab] != lab), body, canon
    )


def resolve_flat_host(canon_np: np.ndarray) -> np.ndarray:
    """Host-side canonicalization (emission materialization path)."""
    lab = canon_np
    while True:
        nxt = lab[lab]
        if np.array_equal(nxt, lab):
            return lab
        lab = nxt


def fold_edges_host(canon_np: np.ndarray, src: np.ndarray,
                    dst: np.ndarray) -> np.ndarray:
    """Fold ONE edge-column group into a host forest table, returning a
    fully-canonical min-rooted flat table (``out[v] <= v``, depth 1).

    The host analog of the group-fold window step: min-label hooking
    over the group's edges alternated with :func:`resolve_flat_host`
    pointer jumping until fixpoint — every pass is whole-array numpy,
    never a per-edge Python loop. Monotone (labels only decrease), so
    it terminates; the result's components are exactly the input
    table's components unioned with the group's edges. Callers pass
    MANY windows' (or many shards') columns concatenated as one group —
    one fold call for the whole group is the group-fold shape."""
    lab = resolve_flat_host(np.asarray(canon_np))
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    if len(src) == 0:
        return lab
    lab = lab.copy()
    while True:
        lo = np.minimum(lab[src], lab[dst])
        before = lab
        lab = lab.copy()
        # hook both endpoints' current ROOTS down to the edge minimum;
        # the flat invariant between passes makes lab[src] the root
        np.minimum.at(lab, before[src], lo)
        np.minimum.at(lab, before[dst], lo)
        lab = resolve_flat_host(lab)
        if np.array_equal(lab, before):
            return lab


def fold_into_forest_host(canon_np: np.ndarray, src: np.ndarray,
                          dst: np.ndarray) -> np.ndarray:
    """Fold a SMALL edge group into a BIG flat table without paying the
    whole-table fixpoint per pass (ISSUE 18's per-pane fold shape: a
    few thousand edges against a table of a million rows, where
    :func:`fold_edges_host`'s resolve-per-pass iterations are all
    table scans).

    Union happens at ROOT granularity: the group's edges project to
    edges between current component roots, those roots compact to a
    dense local id space, the local forest folds with
    :func:`fold_edges_host` (tiny arrays, same fixpoint), and ONE
    whole-table mapping pass rewrites every vertex whose root merged.
    Roots are min vertex ids and the local fold picks the min local
    index — which is the min root under the sorted compaction — so the
    result is byte-identical to ``fold_edges_host(canon_np, src, dst)``
    (the oracle contract), at O(group·fixpoint + table) instead of
    O(table·fixpoint)."""
    lab = resolve_flat_host(np.asarray(canon_np))
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    if len(src) == 0:
        return lab
    rs, rd = lab[src], lab[dst]
    roots = np.unique(np.concatenate([rs, rd]))
    if len(roots) < 2:
        return lab
    local = fold_edges_host(
        np.arange(len(roots), dtype=np.int64),
        np.searchsorted(roots, rs),
        np.searchsorted(roots, rd),
    )
    newroot = roots[local]
    if np.array_equal(newroot, roots):
        return lab  # the group united nothing new
    # one table pass: a scatter/gather translation table (root ->
    # merged root, identity elsewhere) beats a binary search per row
    trans = np.arange(len(lab), dtype=np.int64)
    trans[roots] = newroot
    return trans[lab]


def merge_forest_tables_host(tables) -> np.ndarray:
    """Cross-shard union step: merge N same-length forest tables into
    one canonical table whose components are the components of the
    UNION of the inputs' edge sets.

    Each input forest IS a spanning structure of its own components
    (edges ``(i, t[i])`` where ``t[i] != i``), so concatenating every
    table's non-trivial pointer edges into ONE group and folding them
    with :func:`fold_edges_host` yields exactly the union connectivity
    — the scatter-gather merge a sharded serving router performs, in
    one group-fold call rather than N incremental ones."""
    tables = [np.asarray(t) for t in tables]
    if not tables:
        raise ValueError("merge_forest_tables_host needs >= 1 table")
    n = len(tables[0])
    for t in tables:
        if len(t) != n:
            raise ValueError(
                f"forest tables disagree on length: {len(t)} != {n}"
            )
    srcs, dsts = [], []
    for t in tables:
        i = np.nonzero(t != np.arange(len(t), dtype=t.dtype))[0]
        srcs.append(i.astype(np.int64))
        dsts.append(t[i].astype(np.int64))
    return fold_edges_host(
        np.arange(n, dtype=np.int32),
        np.concatenate(srcs) if srcs else np.zeros(0, np.int64),
        np.concatenate(dsts) if dsts else np.zeros(0, np.int64),
    )


def apply_forest_delta_host(lab: np.ndarray, sizes: np.ndarray,
                            src: np.ndarray, dst: np.ndarray) -> np.ndarray:
    """Incremental counterpart to :func:`merge_forest_tables_host`:
    union a SMALL batch of delta edges into an existing canonical host
    forest IN PLACE, O(changed rows * alpha) instead of O(forest).

    ``lab`` is a min-rooted pointer table (``lab[v] <= v``; flat or the
    output of earlier delta applications) and ``sizes`` the per-dense-id
    member counts of its roots; both are mutated. Unions hook the LARGER
    root under the smaller (min-label discipline), so the invariant —
    and therefore agreement with a from-scratch
    :func:`merge_forest_tables_host` rebuild after
    :func:`resolve_flat_host` — is preserved exactly. Path-halving on
    the find walks keeps amortized chains near-flat between full
    rebuilds.

    Returns the dense ids of every root that participated in an
    EFFECTIVE union (winners and absorbed alike; empty when no edge
    changed connectivity) — the selective cache-invalidation signal the
    sharded router keys on: a cached answer whose roots are disjoint
    from this set provably kept its components untouched."""
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    if len(src) != len(dst):
        raise ValueError(
            f"delta columns disagree on length: {len(src)} != {len(dst)}"
        )
    touched = set()
    for a, b in zip(src.tolist(), dst.tolist()):
        ra = a
        while lab[ra] != ra:
            lab[ra] = lab[lab[ra]]  # path halving
            ra = int(lab[ra])
        rb = b
        while lab[rb] != rb:
            lab[rb] = lab[lab[rb]]
            rb = int(lab[rb])
        if ra == rb:
            continue
        if rb < ra:
            ra, rb = rb, ra
        lab[rb] = ra
        sizes[ra] += sizes[rb]
        touched.add(ra)
        touched.add(rb)
    if not touched:
        return np.zeros(0, np.int64)
    return np.fromiter(touched, np.int64, len(touched))


def repair_forest_host(
    lab: np.ndarray,
    expired_src: np.ndarray,
    expired_dst: np.ndarray,
    surviving_src: np.ndarray,
    surviving_dst: np.ndarray,
):
    """Decremental counterpart to :func:`apply_forest_delta_host`: REPAIR
    a host forest after a batch of edges EXPIRED (event-time retraction,
    ISSUE 18), rebuilding ONLY the affected components.

    Union-find supports cheap union but not cheap deletion; the repair
    rule this repo uses is bounded recompute from the carried table: the
    components the expired edges touched (their roots in ``lab``) are
    reset to singletons, and exactly the SURVIVING edges incident to
    those components are re-folded through :func:`fold_edges_host` — one
    group-fold call over the suspect subgraph, never the whole stream.
    An edge's endpoints always share a component, so membership of ONE
    endpoint in an affected component selects precisely the suspect
    edges.

    ``lab`` is a canonical forest table (any pointer depth; resolved
    here). ``surviving_src``/``surviving_dst`` are the live edge
    multiset AFTER the expiry (callers keep per-pane columns, so this is
    a concatenation of the surviving panes' views, not a recompute).
    Returns ``(new_lab, stats)`` where ``new_lab`` is fully-canonical
    min-rooted flat (byte-identical to a from-scratch
    :func:`fold_edges_host` over the surviving multiset — the oracle
    contract ``tests/test_eventtime.py`` pins) and ``stats`` records the
    bounded-recompute evidence: affected roots/members and re-folded
    edge count (the retraction-vs-rebuild ratio ``bench.py --eventtime``
    commits)."""
    lab = resolve_flat_host(np.asarray(lab))
    expired_src = np.asarray(expired_src, np.int64)
    expired_dst = np.asarray(expired_dst, np.int64)
    surviving_src = np.asarray(surviving_src, np.int64)
    surviving_dst = np.asarray(surviving_dst, np.int64)
    if len(expired_src) != len(expired_dst):
        raise ValueError(
            f"expired columns disagree on length: "
            f"{len(expired_src)} != {len(expired_dst)}"
        )
    if len(surviving_src) != len(surviving_dst):
        raise ValueError(
            f"surviving columns disagree on length: "
            f"{len(surviving_src)} != {len(surviving_dst)}"
        )
    stats = {"roots": 0, "members": 0, "refolded": 0,
             "surviving": int(len(surviving_src))}
    if len(expired_src) == 0:
        return lab, stats
    roots = np.unique(
        np.concatenate([lab[expired_src], lab[expired_dst]])
    )
    # membership via a scatter bitmap (roots are vertex ids, so the
    # bitmap is table-sized): one gather instead of isin's sort
    root_hit = np.zeros(len(lab), bool)
    root_hit[roots] = True
    affected = root_hit[lab]
    members = np.nonzero(affected)[0]
    out = lab.copy()
    out[members] = members.astype(out.dtype)
    if len(surviving_src):
        suspect = affected[surviving_src]
        s = surviving_src[suspect]
        d = surviving_dst[suspect]
        stats["refolded"] = int(len(s))
        if len(s):
            out = fold_into_forest_host(out, s, d)
    stats["roots"] = int(len(roots))
    stats["members"] = int(len(members))
    return out, stats


class TouchLog:
    """Append-only first-seen log of touched compact ids.

    The host computes the touched set per window anyway (it builds the
    local renumbering), so first-seen tracking costs one vectorized
    bitmap lookup — the novelty-shadow pattern. Emissions snapshot the
    log by COUNT only: the first ``count`` entries of an append-only log
    never change, so a lazy emission is O(1) at yield time.
    """

    __slots__ = ("seen", "ids", "count")

    def __init__(self, vcap: int = 0):
        self.seen = np.zeros(vcap, bool)
        self.ids = np.zeros(256, np.int32)
        self.count = 0

    def grow(self, vcap: int) -> None:
        if vcap > len(self.seen):
            self.seen = np.concatenate(
                [self.seen, np.zeros(vcap - len(self.seen), bool)]
            )

    def add(self, tids: np.ndarray) -> None:
        fresh = tids[~self.seen[tids]]
        if len(fresh) == 0:
            return
        self.seen[fresh] = True
        self._append(fresh)

    def _append(self, fresh: np.ndarray) -> None:
        need = self.count + len(fresh)
        if need > len(self.ids):
            cap = len(self.ids)
            while cap < need:
                cap *= 2
            grown = np.zeros(cap, np.int32)
            grown[: self.count] = self.ids[: self.count]
            self.ids = grown
        self.ids[self.count : need] = fresh
        self.count = need

    def add_grouped(self, ids: np.ndarray, counts: np.ndarray) -> list:
        """Batch K windows' touched sets in ONE vectorized pass.

        ``ids`` is a GROUP-unique concatenation in window first-seen
        order with per-window lengths ``counts`` (the shape
        ``CompactUnionFind.fold_group`` emits); per-window ``add`` calls
        cost ~0.1 ms each in numpy call overhead, which dominates
        1k-edge windows. Returns the per-window log counts (the
        emission snapshots ``add`` would have produced)."""
        fresh_mask = ~self.seen[ids]
        fresh = ids[fresh_mask]
        self.seen[fresh] = True
        before = self.count
        self._append(fresh)
        ends = np.cumsum(np.asarray(counts, np.int64))
        fresh_cum = np.concatenate(
            [[0], np.cumsum(fresh_mask.astype(np.int64))]
        )
        return (before + fresh_cum[ends]).tolist()

    def touched_bool(self, vcap: int) -> np.ndarray:
        out = np.zeros(vcap, bool)
        out[: len(self.seen)] = self.seen[:vcap]
        return out

    @staticmethod
    def from_touched_bool(tb: np.ndarray) -> "TouchLog":
        log = TouchLog(len(tb))
        log.add(np.nonzero(tb)[0].astype(np.int32))
        return log
