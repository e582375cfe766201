"""Streaming k-spanner: host-exact fold and device-batched variant.

:class:`Spanner` — behavioral parity with ``library/Spanner.java:40-118``:
per edge, if the spanner already connects the endpoints within k hops the
edge is dropped, else added (``UpdateLocal``); partial spanners merge
smaller-into-larger under the same bounded-BFS test (``CombineSpanners``).
The per-edge decision is sequential in arrival order and irregular (bounded
BFS), so this flavor stays host-side (SURVEY.md §7 build step 5), plugged
into the engine as a host-state summary (``device=False``).

:class:`DeviceSpanner` — the §7 "revisit as hop-limited relaxation on
device" variant: per window, ALL new edges test k-bounded reachability in
the spanner-as-of-window-start simultaneously. Semantics delta
(documented): edges of one window cannot reject each other, so the device
spanner may keep MORE edges than the sequential fold — but the k-spanner
guarantee (every dropped edge has a ≤k-hop spanner path) holds for any
windowing, and it converges to the host result as window size shrinks.

Round-4 redesign — ZERO mid-stream device→host reads: the round-3 flavor
downloaded every window's accept decisions to update host edge lists (a
pipeline drain per window, which was the whole system rate). Now accept
AND merge run on device (masked packed-adjacency merge
for k=2, masked append for general k); the host keeps only the
[[novelty-tracked]] shadow it can compute beside the stream — first-seen
candidate keys (growth bound + query dedup: an edge can only ever be
accepted at its FIRST appearance, since the spanner only grows and a
once-reachable pair stays reachable) and candidate degrees (a sound upper
bound on true spanner degrees for enumeration-class planning). Emission is
a lazy set-like :class:`SpannerEdges` snapshot per window; nothing syncs
until a consumer actually reads one.
"""

from __future__ import annotations

import functools
from typing import Iterator, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..aggregate.summary import SummaryBulkAggregation
from ..core.edgeblock import bucket_capacity
from ..ops.triangles import (
    degree_class_plan,
    grow_packed_columns,
    merge_packed_adjacency,
    sticky_search_steps,
)
from ..summaries.adjacency import AdjacencyListGraph
from ..utils.keyruns import SortedRunSet

_BIG = jnp.iinfo(jnp.int32).max


@functools.partial(jax.jit, static_argnums=(1,))
def _span_row_ptr(pv, num_vertices: int):
    return jnp.searchsorted(
        pv, jnp.arange(num_vertices + 1, dtype=jnp.int32)
    ).astype(jnp.int32)


@functools.partial(jax.jit, static_argnums=(6, 7, 8))
def _k2_exists_step(pn, row_ptr, qu, qv, sel, acc, enum_width: int,
                    search_steps: int, chunk: int):
    """One min-degree class of common-neighbor existence queries; results
    scatter into the shared per-window accumulator. ``chunked_class_scan``
    bounds the [chunk, enum_width] enumeration block — a whole 1M-query
    class at width 4096 would otherwise materialize 16 GB."""
    from ..ops.triangles import (
        chunked_class_scan,
        packed_common_neighbor_exists,
    )

    def body(acc, s_i):
        selc = jnp.clip(s_i, 0, qu.shape[0] - 1)
        mask = s_i >= 0
        ex = packed_common_neighbor_exists(
            pn, row_ptr, qu[selc], qv[selc], mask, enum_width,
            search_steps=search_steps,
        )
        return acc.at[jnp.where(mask, selc, acc.shape[0])].set(ex, mode="drop")

    return chunked_class_scan(body, acc, sel, chunk)


@jax.jit
def _k2_accept_merge(pv, pn, pr, qu, qv, qmask, reached):
    """Merge the window's ACCEPTED queries (qmask & ~reached) into the
    packed sorted adjacency, entirely on device. NOT donated: emission
    snapshots hold references to each window's columns (lazy download),
    so earlier windows' arrays must stay valid."""
    keep = qmask & ~reached
    pv_new = jnp.concatenate([jnp.where(keep, qu, _BIG), jnp.where(keep, qv, _BIG)])
    pn_new = jnp.concatenate([jnp.where(keep, qv, 0), jnp.where(keep, qu, 0)])
    pr_new = jnp.zeros(pv_new.shape[0], jnp.int32)
    spv, spn, spr = jax.lax.sort((pv_new, pn_new, pr_new), num_keys=2)
    n_new = 2 * keep.sum().astype(jnp.int32)
    return merge_packed_adjacency(pv, pn, pr, spv, spn, spr, n_new)


@functools.partial(jax.jit, static_argnums=(6, 7))
def _k_reach_cnt(sp, sq, cnt, u, v, m, num_vertices: int, k: int):
    """For each query edge i: is v[i] within k hops of u[i] over the first
    ``cnt`` spanner edges (sp, sq)? Batched BFS with the query batch PACKED
    into uint32 bitplanes: frontier[B//32, V] words instead of a [B, V]
    bool — 32x the queries per byte of frontier (round-2 verdict #10; at
    V=2^23 the bool frontier admitted ~32 queries per dispatch).

    There is no scatter-OR primitive, so the hop expansion sorts the
    spanner edges by target once and ORs each target's incoming words
    with a segmented ``associative_scan`` (OR is associative), then ORs
    the per-vertex result into the frontier densely. ``B`` must be a
    multiple of 32.
    """
    smask = jnp.arange(sp.shape[0], dtype=jnp.int32) < cnt
    B = u.shape[0]
    W = B // 32
    word = jnp.arange(B) // 32
    bit = (jnp.uint32(1) << (jnp.arange(B, dtype=jnp.uint32) % 32))
    frontier = jnp.zeros((W, num_vertices), jnp.uint32)
    # distinct queries carry distinct bits, so add == bitwise-or here
    frontier = frontier.at[word, u].add(jnp.where(m, bit, 0))

    # both directions of the spanner edges, sorted by target; padding
    # targets -> sentinel V
    sp2 = jnp.concatenate([sp, sq])
    sq2 = jnp.concatenate([sq, sp])
    smask2 = jnp.concatenate([smask, smask])
    q_s, p_s = jax.lax.sort(
        (jnp.where(smask2, sq2, num_vertices), jnp.where(smask2, sp2, 0)),
        num_keys=1,
    )
    S = q_s.shape[0]
    flags = jnp.concatenate([jnp.ones(1, bool), q_s[1:] != q_s[:-1]])
    seg = jnp.arange(num_vertices, dtype=q_s.dtype)
    right = jnp.searchsorted(q_s, seg, side="right")
    left = jnp.searchsorted(q_s, seg, side="left")
    nonempty = right > left
    last = jnp.clip(right - 1, 0, S - 1)

    def seg_or(vals_t):
        def op(a, b):
            fa, va = a
            fb, vb = b
            return fa | fb, jnp.where(fb[:, None], vb, va | vb)

        _, scanned = jax.lax.associative_scan(op, (flags, vals_t))
        return scanned

    for _ in range(k):
        vals_t = frontier[:, p_s].T  # [S, W] incoming words per edge
        scanned = seg_or(vals_t)
        per_vertex = jnp.where(
            nonempty[:, None], scanned[last], jnp.uint32(0)
        )  # [V, W]
        frontier = frontier | per_vertex.T
    return (((frontier[word, v] >> (jnp.arange(B) % 32)) & 1) != 0) & m


@jax.jit
def _gen_append(sp, sq, cnt, qu, qv, keep):
    """Append the ACCEPTED queries to the spanner edge columns at device-
    computed positions (prefix sum over the keep mask). NOT donated —
    emission snapshots hold per-window references."""
    off = jnp.cumsum(keep.astype(jnp.int32)) - 1
    pos = jnp.where(keep, cnt + off, sp.shape[0])  # rejected -> dropped
    sp2 = sp.at[pos].set(qu, mode="drop")
    sq2 = sq.at[pos].set(qv, mode="drop")
    return sp2, sq2, cnt + keep.sum().astype(jnp.int32)


def _grow_cols(sp, sq, need: int):
    """Grow (or create) the general-k padded edge columns to a pow2
    bucket covering ``need`` entries."""
    cap = bucket_capacity(max(need, 16))
    if sp is None:
        return jnp.zeros(cap, jnp.int32), jnp.zeros(cap, jnp.int32)
    if cap <= sp.shape[0]:
        return sp, sq
    pad = cap - sp.shape[0]
    return (
        jnp.concatenate([sp, jnp.zeros(pad, jnp.int32)]),
        jnp.concatenate([sq, jnp.zeros(pad, jnp.int32)]),
    )


class Spanner(SummaryBulkAggregation):
    """k-spanner over the edge stream (``library/Spanner.java``)."""

    device = False
    config_fields = ("k",)

    def __init__(self, k: int, transient_state: bool = False):
        super().__init__(transient_state=transient_state)
        self.k = k

    def initial_state(self, vcap: int) -> AdjacencyListGraph:
        return AdjacencyListGraph()

    def grow_state(self, state, old_vcap, new_vcap):
        return state

    def update(self, g: AdjacencyListGraph, src, dst, val, mask) -> AdjacencyListGraph:
        """Arrival-order fold (``Spanner.UpdateLocal.foldEdges``)."""
        for u, v in zip(src.tolist(), dst.tolist()):
            if not g.bounded_bfs(u, v, self.k):
                g.add_edge(u, v)
        return g

    def combine(self, g1: AdjacencyListGraph, g2: AdjacencyListGraph) -> AdjacencyListGraph:
        """Merge smaller into larger (``Spanner.CombineSpanners.reduce``)."""
        if len(g1.adj) < len(g2.adj):
            g1, g2 = g2, g1
        for u, v in g2.edges():
            if not g1.bounded_bfs(u, v, self.k):
                g1.add_edge(u, v)
        return g1

    def transform(self, g: AdjacencyListGraph, vdict) -> AdjacencyListGraph:
        # Emit a snapshot copy: the running summary keeps mutating across
        # windows, and emissions must stay stable once yielded.
        return g.copy()


class SpannerEdges:
    """One window's spanner edge set, LAZY: device references are held and
    the download happens on first read (iteration / membership / len /
    equality). Unconsumed snapshots cost zero device→host traffic, so the
    device pipeline never drains for a reader that is not there.

    Materializing also feeds the revealed TRUE accepted count back into
    the workload's capacity bound (round-4 advisor finding): under the
    normal run-loop + lazy-read consumption pattern (no checkpoint, so
    ``_host_columns``'s reconcile never fires) the carried device columns
    would otherwise grow with the stream's DISTINCT edges rather than the
    spanner size. The feedback bound is true-count-at-snapshot plus the
    entries offered SINCE, measured on the workload's monotone offer
    counter — sound under any read order (measuring "since" on the
    tightenable ``_cnt_ub`` itself is not: it understates the delta once
    a newer read reconciled and the bound regrew)."""

    __slots__ = (
        "_kind", "_arrays", "_vdict", "_set", "_workload", "_add", "_lin"
    )

    def __init__(self, kind, arrays, vdict, workload=None):
        self._kind = kind
        self._arrays = arrays
        self._vdict = vdict
        self._set = None
        self._workload = workload
        self._add = 0 if workload is None else workload._add_total
        self._lin = 0 if workload is None else workload._lineage

    def _materialize(self) -> Set[Tuple[int, int]]:
        if self._set is not None:
            return self._set
        if self._arrays is None or self._vdict is None:
            self._set = set()
            self._workload = None  # nothing to feed back; don't pin it
            return self._set
        if self._kind == "k2":
            pv, pn = jax.device_get(self._arrays)
            sel = (pv != np.iinfo(np.int32).max) & (pv < pn)
            cu, cv = pv[sel], pn[sel]
        else:
            sp, sq, cnt = jax.device_get(self._arrays)
            cu, cv = sp[: int(cnt)], sq[: int(cnt)]
        w = self._workload
        if w is not None and self._lin == w._lineage:
            true_entries = 2 * len(cu) if self._kind == "k2" else len(cu)
            w._cnt_ub = min(
                w._cnt_ub, true_entries + (w._add_total - self._add)
            )
        self._workload = None  # feedback fired; don't pin the workload
        ru = self._vdict.decode(cu)
        rv = self._vdict.decode(cv)
        self._set = {
            (min(int(a), int(b)), max(int(a), int(b)))
            for a, b in zip(ru, rv)
        }
        self._arrays = None  # release the device references once read
        return self._set

    def __iter__(self):
        return iter(self._materialize())

    def __len__(self) -> int:
        return len(self._materialize())

    def __contains__(self, e) -> bool:
        return e in self._materialize()

    def __eq__(self, other) -> bool:
        if isinstance(other, SpannerEdges):
            return self._materialize() == other._materialize()
        return self._materialize() == other

    def __repr__(self) -> str:
        return repr(self._materialize())


class DeviceSpanner:
    """Batched device k-spanner. ``run(stream)`` yields a lazy
    :class:`SpannerEdges` snapshot per window; ``edges()`` returns the
    current set (raw ids; explicit sync point).

    ``k == 2`` takes a structurally different fast path: 2-hop
    reachability between FIRST-SEEN candidate endpoints is exactly "the
    endpoint rows share a neighbor" (a direct (u,v) spanner edge would
    mean the candidate was accepted before — impossible for a first-seen
    key), so the spanner carries a packed sorted adjacency (the triangle
    pipeline's structure) and each window is a handful of class-bounded
    common-neighbor dispatches — O(Q x min-degree-class) work, no
    frontier at all. General ``k`` uses the bitplane-packed frontier BFS
    (O(k x spanner-edges x Q/32) per window). Both paths accept AND merge
    on device; no mid-stream D2H anywhere."""

    def __init__(
        self,
        k: int,
        query_chunk: int = 1024,
        mem_budget_entries: int = 1 << 28,
        expected_edges: int = 0,
    ):
        """``expected_edges``: pre-size the carried device columns for
        this many spanner edges. Purely a compile-stability hint: every
        capacity bucket is a distinct jit signature, and the remote
        compiler charges ~20-40 s per signature — growth still works
        without it."""
        self.k = k
        self.query_chunk = query_chunk
        self.expected_edges = int(expected_edges)
        #: bound on the packed-frontier footprint (uint32 words): the
        #: per-window query batch shrinks as the vertex capacity grows, so
        #: corpus-scale vertex counts cost more dispatches instead of
        #: exploding HBM.
        self.mem_budget_entries = mem_budget_entries
        self._vdict = None
        # host shadow ([[novelty-tracked]] growth): first-seen candidate
        # keys (LSM sorted runs — amortized O(N log N), no per-window
        # O(total) np.insert) + candidate degrees (sound upper bounds on
        # the accepted structures the device carries)
        self._seen = SortedRunSet()
        self._deg = np.zeros(0, np.int64)
        self._cnt_ub = 0  # upper bound on carried device entries
        # monotone sum of candidate entries ever offered to the device
        # (NEVER tightened): snapshots record it so a stale lazy read can
        # reconstruct "entries offered since this snapshot" exactly —
        # (cnt_ub_now - snapshot_ub) understates that once a newer read
        # reconciled and the bound regrew (round-5 review)
        self._add_total = 0
        self._lineage = 0  # bumped on restore; stale-lineage reads skip
        # k=2 packed-adjacency carry (device)
        self._pv = None
        self._pn = None
        self._pr = None
        # general-k edge-column carry (device)
        self._sp = None
        self._sq = None
        self._cnt = jnp.int32(0)
        # deferred checkpoint restore (device state rebuilt lazily)
        self._restore = None

    def _batch_cap(self, vcap: int) -> int:
        # budget is BYTES of frontier: [B/32, V] uint32 words hold 32
        # queries per 4 bytes, so bitplane packing buys 8x the queries of
        # the old [B, V] bool frontier at the same footprint; the kernel
        # needs B to be a multiple of 32
        words = max(1, self.mem_budget_entries // (4 * max(vcap, 1)))
        b = max(32, min(self.query_chunk, words * 32))
        b = (b // 32) * 32
        return bucket_capacity(b) // 2 if bucket_capacity(b) > b else b

    def run(self, stream) -> Iterator[SpannerEdges]:
        self._vdict = stream.vertex_dict
        for block in stream.blocks():
            s, d, _ = block.to_host()
            vcap = block.n_vertices
            self._ensure_restored(vcap)
            # host prep beside the stream: canonicalize, drop self-loops,
            # in-window dedup, FIRST-SEEN novelty filter (exact shadow of
            # what the device would accept at most once)
            u = np.minimum(s, d).astype(np.int64)
            v = np.maximum(s, d).astype(np.int64)
            ok = u != v
            u, v = u[ok], v[ok]
            if u.size:
                key = self._seen.filter_new(np.unique((u << 32) | v))
                self._seen.add(key)
                u = (key >> 32).astype(np.int32)
                v = (key & 0xFFFFFFFF).astype(np.int32)
            if u.size == 0:
                yield self._snapshot()
                continue
            if vcap > len(self._deg):
                self._deg = np.concatenate(
                    [self._deg, np.zeros(vcap - len(self._deg), np.int64)]
                )
            np.add.at(self._deg, u, 1)
            np.add.at(self._deg, v, 1)
            if self.k == 2:
                self._window_k2(u, v, vcap)
            else:
                self._window_gen(u, v, vcap)
            yield self._snapshot()

    # ------------------------------------------------------------------ #
    def _window_k2(self, u: np.ndarray, v: np.ndarray, vcap: int) -> None:
        """2-hop reachability for all first-seen window queries via
        class-bounded common-neighbor tests on the packed spanner
        adjacency, then a masked on-device accept-merge."""
        self._cnt_ub += 2 * len(u)
        self._add_total += 2 * len(u)
        self._grow_packed(max(self._cnt_ub, 2 * self.expected_edges, 1))
        row_ptr = _span_row_ptr(self._pv, vcap)
        n_q = len(u)
        qcap = bucket_capacity(n_q, minimum=32)
        qu = np.zeros(qcap, np.int32)
        qv = np.zeros(qcap, np.int32)
        qm = np.zeros(qcap, bool)
        qu[:n_q], qv[:n_q], qm[:n_q] = u, v, True
        quj, qvj, qmj = jnp.asarray(qu), jnp.asarray(qv), jnp.asarray(qm)
        acc = jnp.zeros(qcap, bool)
        # class plan from the candidate-degree shadow: >= true spanner
        # degrees, so every class's enum width covers its true rows
        mindeg = np.minimum(self._deg[u], self._deg[v])
        self._steps = sticky_search_steps(
            getattr(self, "_steps", 8), int(max(self._deg.max(), 1))
        )
        for width, sel, tcap, chunk in degree_class_plan(mindeg):
            selp = np.full(tcap, -1, np.int32)
            selp[: len(sel)] = sel
            acc = _k2_exists_step(
                self._pn, row_ptr, quj, qvj, jnp.asarray(selp), acc,
                width, self._steps, chunk,
            )
        self._pv, self._pn, self._pr = _k2_accept_merge(
            self._pv, self._pn, self._pr, quj, qvj, qmj, acc
        )

    def _window_gen(self, u: np.ndarray, v: np.ndarray, vcap: int) -> None:
        """General-k: bitplane frontier BFS per query batch against the
        window-start spanner (batches cannot reject each other — the same
        windowing relaxation as k=2), then on-device appends."""
        self._cnt_ub += len(u)
        self._add_total += len(u)
        self._sp, self._sq = _grow_cols(
            self._sp, self._sq, max(self._cnt_ub, self.expected_edges)
        )
        batch = self._batch_cap(vcap)
        cnt0 = self._cnt
        sp0, sq0 = self._sp, self._sq
        decisions = []
        for a in range(0, len(u), batch):
            b = min(a + batch, len(u))
            qcap = bucket_capacity(b - a, minimum=32)
            uq = np.zeros(qcap, np.int32)
            vq = np.zeros(qcap, np.int32)
            mq = np.zeros(qcap, bool)
            uq[: b - a], vq[: b - a] = u[a:b], v[a:b]
            mq[: b - a] = True
            uj, vj, mj = jnp.asarray(uq), jnp.asarray(vq), jnp.asarray(mq)
            reached = _k_reach_cnt(sp0, sq0, cnt0, uj, vj, mj, vcap, self.k)
            decisions.append((uj, vj, mj, reached))
        for uj, vj, mj, reached in decisions:
            self._sp, self._sq, self._cnt = _gen_append(
                self._sp, self._sq, self._cnt, uj, vj, mj & ~reached
            )

    # ------------------------------------------------------------------ #
    def _snapshot(self) -> SpannerEdges:
        if self.k == 2:
            arrays = None if self._pv is None else (self._pv, self._pn)
            return SpannerEdges("k2", arrays, self._vdict, self)
        arrays = None if self._sp is None else (self._sp, self._sq, self._cnt)
        return SpannerEdges("gen", arrays, self._vdict, self)

    def _grow_packed(self, need: int) -> None:
        self._pv, self._pn, self._pr = grow_packed_columns(
            self._pv, self._pn, self._pr, need, minimum=16
        )

    def _host_columns(self) -> Tuple[np.ndarray, np.ndarray]:
        """Current spanner edges as COMPACT canonical id columns (one
        download; the checkpoint/emission sync point). The download also
        reveals the TRUE accepted count, so reconcile the candidate-based
        capacity bound here — on a dense stream most candidates are
        rejected, and without reconcile the carried columns (and every
        per-window kernel over them) would scale with the STREAM, not the
        spanner."""
        if self._restore is not None:
            return self._restore
        if self.k == 2:
            if self._pv is None:
                return np.zeros(0, np.int32), np.zeros(0, np.int32)
            pv, pn = jax.device_get((self._pv, self._pn))
            sel = (pv != np.iinfo(np.int32).max) & (pv < pn)
            su, sv = pv[sel], pn[sel]
            self._reconcile(su, sv)
            return su, sv
        if self._sp is None:
            return np.zeros(0, np.int32), np.zeros(0, np.int32)
        sp, sq, cnt = jax.device_get((self._sp, self._sq, self._cnt))
        su, sv = sp[: int(cnt)], sq[: int(cnt)]
        self._reconcile(su, sv)
        return su, sv

    def _reconcile(self, su: np.ndarray, sv: np.ndarray) -> None:
        """Snap the capacity upper bound to the true accepted count and
        re-compact the device columns when they are >=4x oversized (the
        hysteresis avoids recompile churn: shrinking one pow2 bucket is
        not worth a fresh jit signature)."""
        true_entries = 2 * len(su) if self.k == 2 else len(su)
        self._cnt_ub = true_entries
        floor = max(true_entries, 2 * self.expected_edges
                    if self.k == 2 else self.expected_edges, 1)
        if self.k == 2:
            if self._pv is not None and (
                self._pv.shape[0] >= 4 * bucket_capacity(max(floor, 16))
            ):
                from ..ops.triangles import build_sorted_directed

                pvp, pnp, prp, _ = build_sorted_directed(su, sv)
                self._pv = jnp.asarray(pvp)
                self._pn = jnp.asarray(pnp)
                self._pr = jnp.asarray(prp)
        elif self._sp is not None and (
            self._sp.shape[0] >= 4 * bucket_capacity(max(floor, 16))
        ):
            cap = bucket_capacity(max(floor, 16))
            spn = np.zeros(cap, np.int32)
            sqn = np.zeros(cap, np.int32)
            spn[: len(su)], sqn[: len(sv)] = su, sv
            self._sp = jnp.asarray(spn)
            self._sq = jnp.asarray(sqn)
            self._cnt = jnp.int32(len(su))

    def _ensure_restored(self, vcap: int) -> None:
        """Rebuild device state from a checkpoint's host columns, once the
        first window reveals the capacity bucket."""
        if self._restore is None:
            return
        su, sv = self._restore
        self._restore = None
        self._seen = SortedRunSet(
            (su.astype(np.int64) << 32) | sv.astype(np.int64)
            if len(su) else None
        )
        self._deg = np.zeros(vcap, np.int64)
        if len(su):
            np.add.at(self._deg, su, 1)
            np.add.at(self._deg, sv, 1)
        if self.k == 2:
            self._cnt_ub = 2 * len(su)
            self._add_total = 2 * len(su)
            if len(su):
                from ..ops.triangles import build_sorted_directed

                pvp, pnp, prp, _ = build_sorted_directed(su, sv)
                self._pv = jnp.asarray(pvp)
                self._pn = jnp.asarray(pnp)
                self._pr = jnp.asarray(prp)
        else:
            self._cnt_ub = len(su)
            self._add_total = len(su)
            if len(su):
                self._sp, self._sq = _grow_cols(None, None, len(su))
                sp = np.zeros(self._sp.shape[0], np.int32)
                sq = np.zeros(self._sq.shape[0], np.int32)
                sp[: len(su)], sq[: len(sv)] = su, sv
                self._sp = jnp.asarray(sp)
                self._sq = jnp.asarray(sq)
                self._cnt = jnp.int32(len(su))

    def sync(self) -> None:
        """Block until the carried device spanner state is complete (the
        end-of-stream barrier for throughput timing), whichever carry —
        k=2 packed adjacency or general-k edge columns — is live."""
        jax.block_until_ready(
            (self._pv, self._pn, self._pr) if self.k == 2
            else (self._sp, self._sq, self._cnt)
        )

    def state_dict(self) -> dict:
        """Checkpoint surface (``aggregate/checkpoint.py:save_workload``).
        One device download at checkpoint time (a natural sync point)."""
        su, sv = self._host_columns()
        return {"su": np.ascontiguousarray(su), "sv": np.ascontiguousarray(sv)}

    def load_state_dict(self, d: dict) -> None:
        self._restore = (
            np.asarray(d["su"], np.int32), np.asarray(d["sv"], np.int32)
        )
        self._seen = SortedRunSet()
        self._deg = np.zeros(0, np.int64)
        self._cnt_ub = 0
        self._add_total = 0
        self._lineage += 1  # snapshots minted pre-restore must not feed back
        self._pv = self._pn = self._pr = None
        self._sp = self._sq = None
        self._cnt = jnp.int32(0)

    def edges(self) -> Set[Tuple[int, int]]:
        """Current spanner edges as raw-id pairs (explicit sync point)."""
        if self._vdict is None:
            return set()
        su, sv = self._host_columns()
        if len(su) == 0:
            return set()
        ru = self._vdict.decode(su)
        rv = self._vdict.decode(sv)
        return {
            (min(int(a), int(b)), max(int(a), int(b))) for a, b in zip(ru, rv)
        }
