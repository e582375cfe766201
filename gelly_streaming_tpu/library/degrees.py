"""Fully-dynamic degree distribution (±edge events).

TPU-native re-design of ``example/DegreeDistribution.java:42-131``, the
reference's only fully-dynamic (addition + deletion) workload. Its pipeline —
flatMap to (vertex, ±1), keyed degree counts, keyed histogram counts — runs
one boxed record at a time with two HashMap states. Here each window of
events is ONE compiled step (``degree_step``, program ``jit_degree_step``)
whose cost follows the WINDOW, not the id space: nothing in it but the
degree table and its copy has a row per vertex.

- Per-vertex ordered degree folds are batched with a segmented associative
  scan over the window's ``2 * W`` (vertex, ±1) lanes, stable-sorted by
  vertex: the reference's clamped sequential update
  ``deg' = max(0, deg + d)`` (degree ≤ 0 removes the vertex,
  ``DegreeDistribution.java:93-100``) composes as ``g(x) = max(m, x + s)``;
  two such updates fuse to ``(s1+s2, max(m2, m1+s2))`` — associative, so
  in-window event order per vertex is preserved exactly while all vertices
  fold in parallel. The last lane of a vertex's run holds its whole
  update; the old degrees of those lanes are ONE gather out of the table
  and the new ones ONE sorted scatter into it (``TableOps``, the pointer
  forest's pair of table primitives).
- The histogram is derived state: subtract old-degree counts of touched
  vertices, add new-degree counts (degree 0 never tracked, matching the
  reference's remove-on-zero): two window-sized scatter-adds.

Two ways in, one step: :meth:`DegreeDistribution.run` takes records
``(src, dst, change)``; :meth:`DegreeDistribution.run_stream` takes a
column stream (``SimpleEdgeStream`` over ``(src, dst, ±1)`` columns) and
does no per-record Python work.

Emission semantics (documented delta, SURVEY.md §7): the reference emits
(degree, count) per record update; here per window, change-only. Final
histograms are identical for any windowing.
"""

from __future__ import annotations

from typing import Iterable, Iterator, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.edgeblock import bucket_capacity
from ..core.emission import LazyListBatch
from ..core.types import EventType
from ..core.window import CountWindow, WindowPolicy, Windower
from ..obs import trace as _trace
from ..ops.segment import segmented_reduce_lanes
from ..summaries.forest import TableOps


def _combine(a, b):
    """Compose clamped degree updates g(x) = max(m, x+s): b AFTER a."""
    s1, m1 = a
    s2, m2 = b
    return s1 + s2, jnp.maximum(m2, m1 + s2)


@jax.jit
def degree_step(deg, hist, src, dst, val, mask):
    """Fold one window of ±events into the carried tables; returns fresh
    ``(deg, hist)`` buffers (nothing is donated: published snapshots are
    immutable). ``src``/``dst``/``val``/``mask`` are an ``EdgeBlock``'s
    padded columns, ``val`` the event's ±1. Every operand but ``deg``
    and its copy is window-sized (``2 * W`` lanes) or the histogram."""
    vcap, hcap = deg.shape[0], hist.shape[0]
    tab = TableOps(vcap)
    # interleave [s0, d0, s1, d1, ...] — the reference emits (src, ±1)
    # then (dst, ±1) PER EVENT (``DegreeDistribution.java:73-77``), and
    # per-vertex clamp order matters when a degree crosses zero; a plain
    # [all srcs, all dsts] concat would reorder a vertex's src-role vs
    # dst-role updates
    verts = jnp.stack([src, dst], axis=1).ravel()
    s0 = jnp.stack([val, val], axis=1).ravel().astype(jnp.int32)
    lanes = jnp.stack([mask, mask], axis=1).ravel()
    ids, (s, m), last = segmented_reduce_lanes(
        (s0, jnp.zeros_like(s0)), verts, lanes, _combine, scope="degrees"
    )
    with jax.named_scope("degrees.gather"):
        old = tab.gather(deg, jnp.where(last, ids, 0))
    new = jnp.maximum(m, old + s)
    with jax.named_scope("degrees.scatter"):
        # one lane a touched vertex (rows unique); the others drop at
        # the sentinel
        deg = tab.scatter(deg, jnp.where(last, ids, vcap), new)
    with jax.named_scope("degrees.hist"):
        # degrees at or past the capacity count in the last bin
        dec = jnp.where(last & (old > 0), jnp.minimum(old, hcap - 1), hcap)
        inc = jnp.where(last & (new > 0), jnp.minimum(new, hcap - 1), hcap)
        hist = hist.at[dec].add(-1, mode="drop").at[inc].add(1, mode="drop")
    return deg, hist


class DegreeDistribution:
    """Streaming (degree -> vertex count) histogram over ±edge events.

    ``run(events)`` consumes ``(src, dst, change)`` records — ``change`` an
    :class:`EventType`, ``"+"``/``"-"``, or ±1 — and yields, per window, the
    change-only list of ``(degree, count)`` histogram entries.
    ``run_stream(stream)`` folds a column stream's windows (the ``val``
    column is the ±1) through the same step.

    ``hist_capacity=n`` fixes the histogram at ``n`` bins when the
    aggregation is built: degrees at or past ``n - 1`` count in the last
    bin, and no shape of the step changes after its first window (a
    served deployment compiles nothing while it runs). Without it the
    histogram grows with the stream, bounded by a host count over every
    window's ids.
    """

    def __init__(self, window: Optional[WindowPolicy] = None,
                 vertex_dict=None, hist_capacity: Optional[int] = None):
        self.window = window or CountWindow(1 << 16)
        if hist_capacity is not None and hist_capacity < 2:
            raise ValueError("hist_capacity must hold degree 1: at least 2")
        self.hist_capacity = hist_capacity
        # the windower (and its VertexDict) persists across run() calls so
        # a resumed stream keeps the same compact-id space as the carried
        # degree vector
        self._windower = Windower(self.window, vertex_dict, val_dtype=np.int32)
        self._vdict = self._windower.vertex_dict  # a column stream brings its own
        self._deg = None  # device int32[vcap]
        self._hist = None  # device int32[hcap]; index = degree, [0] unused
        # host shadow for histogram-capacity growth (zero device reads in
        # the producer loop): per window, no degree can rise by more than
        # that window's max per-vertex event count (host bincount on the
        # cached columns), so the running sum upper-bounds the max degree;
        # materializing any emission tightens it to the downloaded truth.
        self._max_deg_ub = 0
        # monotone sum of all shadow increments ever applied (NEVER
        # tightened): lazy batches record it at creation, so a stale
        # read can reconstruct "increments since this batch" exactly —
        # (shadow_now - batch_ub) is NOT that quantity once a newer read
        # tightened and the shadow regrew (round-5 review repro)
        self._inc_total = 0
        self._lineage = 0  # bumped on restore; stale-lineage batches skip
        self._events_total = 0
        self._emit_base = 0  # event watermark of the last materialized batch
        self._emit_prev = None  # host hist at the last materialized batch

    @classmethod
    def sliding(cls, size: int, slide: Optional[int] = None, **kwargs):
        """The EVENT-TIME shape of this workload: exact decremental
        degrees + heavy hitters over a sliding window that retracts
        expired panes (ISSUE 18) — a configured
        :class:`~gelly_streaming_tpu.eventtime.SlidingGraphAggregator`
        restricted to the degree summary. ``size``/``slide`` are event
        time units; extra kwargs pass through (``allowed_lateness``,
        ``nshards``, ``commit_dir``, ...)."""
        from ..eventtime import SlidingGraphAggregator

        return SlidingGraphAggregator(
            size, slide, summaries=("degree",), **kwargs
        )

    def run(self, events: Iterable[Tuple]) -> Iterator["HistogramBatch"]:
        """Yields one lazy :class:`HistogramBatch` per window — list-like
        ``(degree, count)`` change-only entries, downloaded on first read
        (the round-3 version downloaded two full histograms per window).
        Materializing batches in stream order reproduces per-window
        change-only emission exactly; skipping windows folds their
        changes into the next batch read."""
        rows = ((s, d, _delta(c), *rest) for s, d, c, *rest in events)
        return self._fold(self._windower.blocks(rows))

    def run_stream(self, stream) -> Iterator["HistogramBatch"]:
        """The column path: ``stream`` is a graph stream whose blocks
        carry the event's ±1 in their ``val`` column (a
        ``SimpleEdgeStream`` over ``(src, dst, ±1)`` columns or column
        chunks); compact ids are its vertex dictionary's. Yields what
        :meth:`run` yields; no record is touched in Python."""
        self._vdict = stream.vertex_dict
        return self._fold(stream.blocks())

    def _fold(self, blocks) -> Iterator["HistogramBatch"]:
        for block in blocks:
            cache = getattr(block, "_host_cache", None)
            with _trace.span("degrees.window") as sp:
                with _trace.span("degrees.prep"):
                    n_events = self._size_tables(block, cache)
                if sp.recording and cache is not None:
                    sp.set(events=n_events, lanes=2 * block.src.shape[0],
                           deletions=int(np.count_nonzero(cache[2] < 0)))
                with _trace.span("degrees.dispatch"):
                    self._deg, self._hist = degree_step(
                        self._deg, self._hist,
                        block.src, block.dst, block.val, block.mask,
                    )
            # closed before the yield: a span open across it would
            # mis-nest the ingest thread's stack (core/window.py)
            with _trace.span("window.emit"):
                self._events_total += n_events
                batch = HistogramBatch(
                    self, self._hist, self._events_total, self._inc_total
                )
            yield batch
            del batch  # the loop's frame must not hold a table

    def _size_tables(self, block, cache) -> int:
        """Allocate or grow the two carried tables for ``block``; returns
        its event count. With ``hist_capacity`` this is two allocations
        at the first window and nothing after; without, the histogram's
        capacity follows a host bound on the largest degree."""
        vcap = block.n_vertices
        if cache is not None:
            s_h, d_h = cache[0], cache[1]
        else:  # non-windower block (rare): one download
            mask_h = np.asarray(block.mask)
            s_h = np.asarray(block.src)[mask_h]
            d_h = np.asarray(block.dst)[mask_h]
        n_events = len(s_h)
        if self.hist_capacity is not None:
            hcap = self.hist_capacity
        else:
            if n_events:
                # max per-vertex event count this window bounds how far
                # any degree (hence the histogram support) can rise
                both = np.concatenate([s_h, d_h])
                inc = int(np.unique(both, return_counts=True)[1].max())
                self._max_deg_ub += inc
                self._inc_total += inc
            hcap = bucket_capacity(self._max_deg_ub + 1)
        self._deg = _grown(self._deg, vcap)
        self._hist = _grown(self._hist, hcap)
        return n_events

    def state_dict(self) -> dict:
        """Checkpoint surface (``aggregate/checkpoint.py:save_workload``);
        self-contained: includes the vertex dictionary so the compact-id
        space survives the resume."""
        hist = None if self._hist is None else np.asarray(self._hist)
        max_deg = (
            0 if hist is None or not hist.any()
            else int(np.nonzero(hist)[0][-1])
        )
        # checkpoint = a natural sync point: snap the shadow exactly
        self._max_deg_ub = min(self._max_deg_ub, max_deg)
        return {
            "deg": None if self._deg is None else np.asarray(self._deg),
            "hist": hist,
            "max_deg": max_deg,
            "vdict_raw": self._vdict.raw_ids(),
        }

    def load_state_dict(self, d: dict) -> None:
        self._deg = None if d["deg"] is None else jnp.asarray(d["deg"])
        self._hist = None if d["hist"] is None else jnp.asarray(d["hist"])
        self._max_deg_ub = int(d["max_deg"])
        # fresh lineage: batches minted before the restore hold a counter
        # from the old lineage and must not pass the _compute guard
        self._inc_total = 0
        self._lineage += 1
        self._events_total = 0
        self._emit_base = 0
        self._emit_prev = None if d["hist"] is None else np.asarray(d["hist"]).copy()
        vd = self._vdict
        if len(vd) == 0:
            vd.encode(d["vdict_raw"])
        elif vd.raw_ids().tolist() != d["vdict_raw"].tolist():
            raise ValueError(
                "restoring into a DegreeDistribution whose vertex dictionary "
                "already diverged from the checkpoint"
            )

    # ---- serving surface (serving/server.py Servable contract) ------- #
    def servable(self, vdict=None) -> "DegreeServable":
        """Adapter publishing the carried degree table and histogram per
        window for ``DegreeQuery`` and ``DegreeCountQuery`` lookups
        (``vdict`` is only consulted for the checkpoint boot payload;
        live windows use the stream's dict)."""
        return DegreeServable(self, vdict)

    def histogram(self) -> dict:
        """Current (degree -> count) map, degree >= 1 entries only.
        A natural sync point: snaps the capacity shadow to the truth."""
        if self._hist is None:
            return {}
        h = np.asarray(self._hist)
        nz = np.nonzero(h)[0]
        self._max_deg_ub = min(
            self._max_deg_ub, int(nz[-1]) if len(nz) else 0
        )
        return {int(d): int(h[d]) for d in nz if d > 0}

    def degrees(self) -> np.ndarray:
        return np.zeros(0, np.int32) if self._deg is None else np.asarray(self._deg)


class HistogramBatch(LazyListBatch):
    """One window's change-only histogram emission, LAZY (the degree
    analog of :class:`~gelly_streaming_tpu.library.triangles.TriangleBatch`):
    the device histogram downloads on first read, changes are reported
    against the histogram at the previous materialized batch, and the
    workload's capacity shadow tightens from what the download reveals.
    Materializing in stream order reproduces per-window change-only
    emission exactly; an out-of-order read diffs against whatever was
    materialized last WITHOUT regressing the workload's watermarks."""

    __slots__ = ("_workload", "_hist", "_ev", "_inc", "_lin", "_items")

    def __init__(self, workload, hist, ev, inc):
        self._workload = workload
        self._hist = hist
        self._ev = ev
        self._inc = inc  # workload._inc_total at batch creation
        self._lin = workload._lineage
        self._items = None

    def _compute(self) -> list:
        w = self._workload
        h = np.asarray(self._hist)
        prev = w._emit_prev
        if prev is None or len(prev) < len(h):
            grown = np.zeros(len(h), h.dtype)
            if prev is not None:
                grown[: len(prev)] = prev
            prev = grown
        changed = np.nonzero(h != prev[: len(h)])[0]
        items = [(int(d), int(h[d])) for d in changed]
        if self._ev >= w._emit_base:
            # newest materialization wins; an older batch read later must
            # not clobber the diff base or the watermark
            w._emit_prev = h
            w._emit_base = self._ev
        # capacity shadow: true max NOW <= true max AT THIS BATCH plus
        # the increments applied since. "Increments since" is measured on
        # the MONOTONE counter (w._inc_total - self._inc), never on the
        # shadow itself — (shadow - batch_ub) understates the increments
        # once a newer read tightened the shadow and it regrew, which
        # dragged the shadow below the true max (round-5 review repro:
        # degree-18 vertex clipped into bin 15). The monotone form is a
        # sound bound under ANY read order; the guard only skips batches
        # from a pre-restore lineage, whose counter is incomparable.
        if self._lin == w._lineage and self._inc <= w._inc_total:
            nz = np.nonzero(h)[0]
            true_max = int(nz[-1]) if len(nz) else 0
            w._max_deg_ub = min(
                w._max_deg_ub, true_max + (w._inc_total - self._inc)
            )
        return items


class DegreeServable:
    """:class:`~gelly_streaming_tpu.serving.server.Servable` adapter for
    :class:`DegreeDistribution`. Every window publishes ``deg`` (the
    degree table, for ``DegreeQuery``), ``hist`` (the degree -> vertex
    count histogram, for ``DegreeCountQuery``) and ``vdict``; both
    tables are the step's fresh output buffers, so published snapshots
    are immutable. Watermark = cumulative events folded."""

    def __init__(self, workload: DegreeDistribution, vdict=None):
        from ..serving import DegreeCountQuery, DegreeQuery

        self.query_classes = (DegreeQuery, DegreeCountQuery)
        self._workload = workload
        self._vdict = vdict

    def payloads(self, stream):
        """``stream`` is a graph stream (the column path, as the other
        servables take one) or an iterable of ``(src, dst, change)``
        records (the record path)."""
        w = self._workload
        columns = callable(getattr(stream, "blocks", None))
        batches = w.run_stream(stream) if columns else w.run(stream)
        vdict = self._vdict = w._vdict
        for _ in batches:
            yield ({"deg": w._deg, "hist": w._hist, "vdict": vdict},
                   w._events_total)

    def boot_payload(self):
        w = self._workload
        if w._deg is None:
            return None
        vdict = self._vdict or w._vdict
        return ({"deg": w._deg, "hist": w._hist, "vdict": vdict},
                w._events_total)


def _grown(table, rows: int):
    """``table`` with zero rows appended up to ``rows`` (a fresh table
    where there is none); the table itself where it is large enough."""
    if table is None:
        return jnp.zeros(rows, jnp.int32)
    if rows <= table.shape[0]:
        return table
    return jnp.concatenate(
        [table, jnp.zeros(rows - table.shape[0], jnp.int32)]
    )


def _delta(change) -> int:
    if isinstance(change, EventType):
        return 1 if change is EventType.EDGE_ADDITION else -1
    if change in ("+", 1, True):
        return 1
    if change in ("-", -1, False):
        return -1
    raise ValueError(f"bad event change {change!r}")
