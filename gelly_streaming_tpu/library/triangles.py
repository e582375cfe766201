"""Triangle counting workloads: per-window exact and streaming exact.

TPU-native re-designs of two reference examples:

- :class:`WindowTriangles` — exact triangle count per time slice
  (``example/WindowTriangles.java:60-139``). The reference generates
  O(Σdeg²) wedge *candidates* per vertex and joins them against real edges
  across two more shuffles; here each slice is one compiled
  sorted-adjacency-intersection step (``ops/triangles.py``), emitting
  ``(count, window_max_timestamp)`` pairs exactly like the reference's
  final ``timeWindowAll().sum(0)`` stream.

- :class:`ExactTriangleCount` — single-pass exact local + global triangle
  count over the whole stream (``example/ExactTriangleCount.java:41-207``).
  The reference pairs per-edge neighborhood snapshots in keyed state so a
  triangle is counted exactly once — when its last edge arrives. Here each
  accumulated edge carries an *arrival rank*; per window, one device step
  counts for every new edge the common neighbors whose closing edges both
  have smaller rank (same once-per-triangle semantics, batched). Duplicate
  edges are dropped (the reference's TreeSet adjacency is likewise
  duplicate-insensitive). Emission is per-window change-only: ``(vertex,
  running_count)`` for every vertex whose count changed, and ``(-1,
  running_total)`` — the reference's ``SumAndEmitCounters`` stream
  (``ExactTriangleCount.java:121-134``) at window granularity
  (SURVEY.md §7 "semantic deltas").
"""

from __future__ import annotations

import functools
from typing import Iterable, Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.edgeblock import bucket_capacity
from ..core.emission import LazyListBatch
from ..core.window import WindowPolicy, Windower
from ..utils.keyruns import SortedRunSet
from ..ops.triangles import (
    build_sorted_directed,
    degree_class_plan,
    grow_packed_columns,
    packed_triangle_update,
    prepare_packed_window,
    sticky_search_steps,
    window_triangle_count,
)

GLOBAL_KEY = -1  # the reference's "total" counter vertex id


def _pad(a: np.ndarray, cap: int) -> np.ndarray:
    out = np.zeros(cap, a.dtype)
    out[: len(a)] = a
    return out


def _pad_fill(a: np.ndarray, cap: int, fill) -> np.ndarray:
    out = np.full(cap, fill, a.dtype)
    out[: len(a)] = a
    return out


@functools.partial(jax.jit, static_argnums=(3, 4))
def _window_step(src, dst, mask, num_vertices: int, max_degree: int):
    return window_triangle_count(src, dst, mask, num_vertices, max_degree)


_BIG = jnp.iinfo(jnp.int32).max


@functools.partial(jax.jit, static_argnums=(7, 8), donate_argnums=(0, 1, 2))
def _prep_step(pv, pn, pr, src, dst, mask, rank0, num_vertices: int,
               search_steps: int):
    return prepare_packed_window(
        pv, pn, pr, src, dst, mask, rank0, num_vertices,
        search_steps=search_steps,
    )


@functools.partial(jax.jit, static_argnums=(9, 10, 11))
def _packed_count_step(
    pn, pr, row_ptr, qu, qv, qrank, qmask, sel, counts_and_delta,
    enum_width: int, search_steps: int, chunk: int,
):
    # no donation: emission is lazy (consumers may download a window's
    # counts after later windows have dispatched), so every window's
    # counts array must stay valid. `sel` (padded with -1) selects this
    # degree class's queries — the gather runs on device, so the host
    # never materializes per-class columns.
    from ..ops.triangles import chunked_class_scan

    def body(carry, s_i):
        counts, delta = carry
        selc = jnp.clip(s_i, 0, qu.shape[0] - 1)
        mask_s = (s_i >= 0) & qmask[selc]
        counts, d = packed_triangle_update(
            pn, pr, row_ptr, qu[selc], qv[selc], qrank[selc], mask_s,
            counts, enum_width, search_steps=search_steps,
        )
        return counts, delta + d

    return chunked_class_scan(body, counts_and_delta, sel, chunk)


@jax.jit
def _accum_total(total, delta):
    return total + delta


class WindowTriangles:
    """Exact triangles per tumbling window.

    ``run(edges)`` yields ``(count, max_timestamp)`` per window —
    ``max_timestamp`` is the inclusive window end for event-time windows
    (Flink's ``TimeWindow.maxTimestamp()``), the window index for count
    windows.
    """

    def __init__(self, window: WindowPolicy):
        self.window = window

    def run(self, edges: Iterable[Tuple]) -> Iterator[Tuple[int, Optional[float]]]:
        windower = Windower(self.window)
        for info, block in windower.blocks_with_info(edges):
            s, d, _ = block.to_host()
            max_deg = _oriented_degree_bucket(s, d, block.n_vertices)
            total, _ = _window_step(
                block.src, block.dst, block.mask, block.n_vertices, max_deg
            )
            ts = info.max_timestamp if info.max_timestamp is not None else info.index
            yield int(total), ts

    def run_stream(self, stream) -> Iterator[Tuple[jax.Array, int]]:
        """System path: consume a ``SimpleEdgeStream`` through
        ``stream.slice(self.window)`` (re-windowing + vertex mapping) and
        count per slice. Yields ``(count, window_index)`` with ``count``
        still a DEVICE scalar — ``int(count)`` syncs; draining without
        reading keeps the pipeline free of per-window round trips."""
        snaps = stream.slice(self.window)
        for i, block in enumerate(snaps._block_iter_fn()):
            s, d, _ = block.to_host()
            max_deg = _oriented_degree_bucket(s, d, block.n_vertices)
            total, _ = _window_step(
                block.src, block.dst, block.mask, block.n_vertices, max_deg
            )
            yield total, i


def _oriented_degree_bucket(
    s: np.ndarray, d: np.ndarray, num_vertices: int,
    dense_budget_bytes: int = 2 << 30,
) -> int:
    """Bucket (power of two) covering the max ORIENTED out-degree of the
    window — the dense-row width of the degree-oriented kernel.

    Fast path (one bincount, no sort): with degree-ordered orientation
    every out-neighbor of ``a`` has degree >= deg(a) >= outdeg(a), so
    outdeg(a)^2 <= sum of out-neighbor degrees <= 2E' — i.e. the width is
    bounded by ``min(max degree, sqrt(2E))``, both computable WITHOUT the
    dedup sort (duplicate edges only inflate the bound, never shrink it).
    The previous exact computation np.unique-sorted every window's keys
    (~100 ms per 1M-edge window — the whole system rate). If the sound
    bound would blow the kernel's dense [V, width] rows past
    ``dense_budget_bytes``, fall back to the exact sort-based width.
    """
    E = len(s)
    if E == 0:
        return bucket_capacity(0)
    deg = np.bincount(s, minlength=num_vertices)
    deg = deg + np.bincount(d, minlength=num_vertices)
    w = int(min(int(deg.max()), int(np.ceil(np.sqrt(2.0 * E))) + 1))
    cap = bucket_capacity(max(w, 8))
    if num_vertices * cap * 4 <= dense_budget_bytes:
        return cap
    # exact width: dedup + orient on host (sort-heavy, rare path)
    u = np.minimum(s, d).astype(np.int64)
    v = np.maximum(s, d).astype(np.int64)
    ok = u != v
    u, v = u[ok], v[ok]
    if u.size == 0:
        return bucket_capacity(0)
    key = np.unique(u * num_vertices + v)
    u = key // num_vertices
    v = key % num_vertices
    deg = np.bincount(u, minlength=num_vertices) + np.bincount(
        v, minlength=num_vertices
    )
    du, dv = deg[u], deg[v]
    swap = (dv < du) | ((dv == du) & (v < u))
    a = np.where(swap, v, u)
    return bucket_capacity(int(np.bincount(a, minlength=num_vertices).max()))


class TriangleBatch(LazyListBatch):
    """One window's change-only emission, LAZY: device arrays are held and
    the download happens on first read (iteration / indexing). Unconsumed
    windows cost zero device->host traffic, so the device pipeline never
    drains for them (the eager version paid two full [vcap] count
    downloads per window).

    Changes are reported against the counts at the PREVIOUS materialized
    batch — materializing batches in stream order (the normal consumption
    pattern) reproduces per-window change-only emission exactly; skipping
    windows folds their changes into the next one read, and reading an
    old batch after a newer one diffs against the newer state without
    regressing the workload's diff base.
    """

    __slots__ = ("_workload", "_counts", "_total", "_vdict", "_seq", "_items")

    def __init__(self, workload, counts, total, vdict, seq):
        self._workload = workload
        self._counts = counts
        self._total = total
        self._vdict = vdict
        self._seq = seq
        self._items = None

    def _compute(self) -> list:
        w = self._workload
        counts, total = jax.device_get((self._counts, self._total))
        total = int(total)
        prev = w._emit_prev
        if prev is None or len(prev) < len(counts):
            grown = np.zeros(len(counts), counts.dtype)
            if prev is not None:
                grown[: len(prev)] = prev
            prev = grown
        changed = np.nonzero(counts != prev[: len(counts)])[0]
        raw = self._vdict.decode(changed) if len(changed) else []
        out = [(int(r), int(counts[c])) for r, c in zip(raw, changed)]
        if total != w._emit_prev_total:
            out.append((GLOBAL_KEY, total))
        if self._seq >= w._emit_seq_base:
            # newest materialization wins; older batches read later must
            # not clobber the diff base
            w._emit_prev = counts
            w._emit_prev_total = total
            w._emit_seq_base = self._seq
        return out


class ExactTriangleCount:
    """Single-pass exact local + global triangle counting.

    ``run(stream)`` consumes a ``SimpleEdgeStream`` and yields, per window, a
    list-like :class:`TriangleBatch` of ``(raw_vertex_id, running_count)``
    for changed vertices plus ``(GLOBAL_KEY, running_total)`` when it
    changed (downloaded lazily on first read).
    """

    def __init__(self):
        # host carry: the RAW edge columns in arrival order (checkpoint
        # source of truth — canonicalization/dedup happen on device) and a
        # duplicate-inflated degree bound (bincount only, no sorts) that
        # soundly over-covers every true adjacency-row length for class
        # assignment
        # raw columns as per-window chunks (concatenated only at the
        # checkpoint sync point: a per-window concatenate of the whole
        # history is O(stream) memcpy per window — quadratic)
        self._u_chunks: List[np.ndarray] = []
        self._v_chunks: List[np.ndarray] = []
        self._deg = np.zeros(0, np.int64)
        self._have = SortedRunSet()  # distinct canonical keys (LSM runs)
        self._n_raw = 0  # cumulative rank offset (padded block widths)
        self._emit_prev = None  # host counts at the last materialized batch
        self._emit_prev_total = 0
        self._emit_seq = 0  # batches yielded (order watermark source)
        self._emit_seq_base = 0  # seq of the last materialized batch
        # device carry: counts [Vcap] + PACKED sorted adjacency — columns
        # (vertex, nbr, rank) sorted by (vertex, nbr), both directions of
        # every canonical edge, +INT32_MAX vertex sentinel padding. O(E)
        # memory: the round-2 interim [V, max_degree] dense rows let one
        # hub size every vertex's row (O(V*D) — 17 GB at a 16k-degree hub
        # over 262k vertices).
        self._counts = None
        self._pv = None
        self._pn = None
        self._pr = None
        self._n_packed = 0
        self._total = jnp.int32(0)  # device scalar (no per-window sync)

    def run(self, stream) -> Iterator[List[Tuple[int, int]]]:
        vdict = stream.vertex_dict
        for block in stream.blocks():
            yield self._process(block, vdict)

    def _raw_columns(self) -> Tuple[np.ndarray, np.ndarray]:
        """Flatten (and collapse) the per-window raw-column chunks — the
        checkpoint-time sync point; per-window code never concatenates."""
        if len(self._u_chunks) > 1:
            self._u_chunks = [np.concatenate(self._u_chunks)]
            self._v_chunks = [np.concatenate(self._v_chunks)]
        if not self._u_chunks:
            return np.zeros(0, np.int32), np.zeros(0, np.int32)
        return self._u_chunks[0], self._v_chunks[0]

    def state_dict(self) -> dict:
        """Checkpoint surface (``aggregate/checkpoint.py:save_workload``).
        The packed adjacency is NOT serialized — ``load_state_dict``
        rebuilds it from the raw edge columns (rank ORDER, the only thing
        the counting rule reads, survives the renumbering)."""
        u, v = self._raw_columns()
        return {
            "u": u, "v": v,
            "deg": self._deg,
            "n_raw": self._n_raw,
            "counts": None if self._counts is None else np.asarray(self._counts),
            "total": int(self._total),
        }

    def load_state_dict(self, d: dict) -> None:
        u, v = np.asarray(d["u"]), np.asarray(d["v"])
        self._u_chunks = [u] if len(u) else []
        self._v_chunks = [v] if len(v) else []
        self._deg = d["deg"]
        self._n_raw = int(d.get("n_raw", len(u)))
        self._counts = None if d["counts"] is None else jnp.asarray(d["counts"])
        self._total = jnp.int32(int(d["total"]))
        self._emit_prev = None if d["counts"] is None else np.asarray(d["counts"]).copy()
        self._emit_prev_total = int(d["total"])
        self._emit_seq = 0
        self._emit_seq_base = 0
        self._pv = self._pn = self._pr = None
        self._n_packed = 0
        self._have = SortedRunSet()
        if len(u):
            # rebuild the packed adjacency from the raw columns: canonical
            # first occurrences, ranked by raw arrival position
            cu = np.minimum(u, v).astype(np.int64)
            cv = np.maximum(u, v).astype(np.int64)
            ok = cu != cv
            pos_all = np.nonzero(ok)[0]
            cu, cv = cu[ok], cv[ok]
            key = (cu << 32) | cv
            _, first = np.unique(key, return_index=True)
            self._have = SortedRunSet(key)  # host shadow of the packed count
            ranks = pos_all[first].astype(np.int32)
            cu = cu[first].astype(np.int32)
            cv = cv[first].astype(np.int32)
            pvp, pnp, prp, n_new = build_sorted_directed(cu, cv, ranks)
            self._n_packed = n_new
            self._pv = jnp.asarray(pvp)
            self._pn = jnp.asarray(pnp)
            self._pr = jnp.asarray(prp)
            # future ranks must exceed every rebuilt rank
            self._n_raw = max(self._n_raw, len(u))

    # ------------------------------------------------------------------ #
    def _grow_packed(self, need: int) -> None:
        self._pv, self._pn, self._pr = grow_packed_columns(
            self._pv, self._pn, self._pr, need
        )

    def _process(self, block, vdict) -> List[Tuple[int, int]]:
        vcap = block.n_vertices
        # host columns drive CLASS assignment only (free via the block's
        # host cache on the ingest path); dedup/merge/count run on device
        cache = getattr(block, "_host_cache", None)
        if cache is not None:
            s, d = cache[0], cache[1]
            # None = prefix alignment (host row i == device slot i);
            # non-prefix producers (distinct()) record real slot positions
            pos = getattr(block, "_host_cache_pos", None)
        else:
            mask_h = np.asarray(block.mask)
            s = np.asarray(block.src)[mask_h]
            d = np.asarray(block.dst)[mask_h]
            pos = np.nonzero(mask_h)[0].astype(np.int32)
        n_raw = len(s)
        if self._counts is None:
            self._counts = jnp.zeros(vcap, jnp.int32)
        elif vcap > self._counts.shape[0]:
            self._counts = jnp.concatenate(
                [self._counts, jnp.zeros(vcap - self._counts.shape[0], jnp.int32)]
            )
        if n_raw == 0:
            return []
        self._u_chunks.append(np.asarray(s, np.int32))
        self._v_chunks.append(np.asarray(d, np.int32))
        if vcap > len(self._deg):
            self._deg = np.concatenate(
                [self._deg, np.zeros(vcap - len(self._deg), np.int64)]
            )
        np.add.at(self._deg, s, 1)
        np.add.at(self._deg, d, 1)

        # 1. one device dispatch: canonicalize/dedup/reject-known, merge
        # into the packed adjacency, rebuild row_ptr
        cap = block.capacity
        rank0 = self._n_raw
        self._n_raw += cap  # ranks are slot-indexed; only ORDER matters
        # EXACT host shadow of the packed count ([[novelty-tracked]] device
        # growth): distinct first-seen canonical keys, computed beside the
        # stream — the same dedup rule the device applies, so the packed
        # capacity grows by exactly the entries the merge will add. The
        # earlier version read the true count back from the device at
        # growth boundaries ((pv != BIG).sum()): a pipeline drain each
        # time, which was the whole system rate.
        cu = np.minimum(s, d).astype(np.int64)
        cvv = np.maximum(s, d).astype(np.int64)
        okc = cu != cvv
        new_key = self._have.filter_new(np.unique((cu[okc] << 32) | cvv[okc]))
        n_new_distinct = len(new_key)
        self._have.add(new_key)
        self._grow_packed(self._n_packed + 2 * n_new_distinct)
        search_steps = max(4, int(self._pv.shape[0]).bit_length())
        (self._pv, self._pn, self._pr, row_ptr, qu, qv, qrank,
         qmask) = _prep_step(
            self._pv, self._pn, self._pr, block.src, block.dst, block.mask,
            jnp.int32(rank0), vcap, search_steps,
        )
        self._n_packed += 2 * n_new_distinct  # exact (host novelty shadow)

        # 2. count closures per min-degree class (shared coarse-class /
        # enum-budget / sticky-steps policy: ops/triangles.py). The
        # duplicate-inflated degree bound only ever WIDENS a class — sound.
        mindeg = np.minimum(self._deg[s], self._deg[d])
        acc = (self._counts, jnp.int32(0))
        self._search_steps = sticky_search_steps(
            getattr(self, "_search_steps", 8), int(self._deg.max())
        )
        for width, sel, tcap, chunk in degree_class_plan(mindeg):
            if pos is not None:
                sel = pos[sel]
            acc = _packed_count_step(
                self._pn, self._pr, row_ptr, qu, qv, qrank, qmask,
                jnp.asarray(_pad_fill(sel, tcap, np.int32(-1))),
                acc,
                width,
                self._search_steps,
                chunk,
            )
        self._counts, delta = acc
        self._total = _accum_total(self._total, delta)
        self._emit_seq += 1
        return TriangleBatch(self, self._counts, self._total, vdict,
                             self._emit_seq)
