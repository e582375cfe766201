"""Host-side window discretization: unbounded edge stream -> EdgeBlocks.

The reference discretizes streams with Flink tumbling windows — per-key
``timeWindow`` inside the engine (``SummaryBulkAggregation.java:79-81``) and
``slice(Time)`` at the API level (``SimpleEdgeStream.java:135-167``). Window
firing is driven by ingestion time by default and event time when a timestamp
extractor is supplied (``SimpleEdgeStream.java:69-90``).

The TPU-native equivalent lives entirely on the host: a ``Windower`` consumes
an iterator of host edge records, runs them through the
:class:`~gelly_streaming_tpu.core.vertexdict.VertexDict` (the keyBy analog),
and emits padded, capacity-bucketed
:class:`~gelly_streaming_tpu.core.edgeblock.EdgeBlock` batches — one per
tumbling window. Two policies:

- ``CountWindow(n)``: every ``n`` edges is a window. This is the reproducible
  analog of the reference's processing-time windows (whose content depends on
  wall clock; tests there pin parallelism=1 for determinism —
  ``ConnectedComponentsTest.java:62-64``). Count windows make the same tests
  deterministic by construction.
- ``EventTimeWindow(size)``: tumbling windows over a user-extracted timestamp,
  the analog of event-time ``timeWindow`` with an ascending-timestamp
  extractor (``SimpleEdgeStream.java:86-90``).

Blocks carry *compact* int32 ids; raw ids stay host-side in the dict.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Iterable, Iterator, Optional, Sequence, Tuple

import numpy as np

from ..obs import trace as _trace
from .edgeblock import (
    EdgeBlock,
    StackedEdgeBlock,
    bucket_capacity,
    stack_blocks,
    stack_host_cols,
)
from .vertexdict import VertexDict


def is_column_input(edges) -> bool:
    """True when ``edges`` is vectorized column input: an ``[N, k]``
    ndarray or a ``(src, dst[, val][, ts])`` tuple/list of 1-D arrays.

    THE shared fast-path predicate — the windower's array windows, the
    superbatch packer, and ``SimpleEdgeStream``'s ingest dispatch must
    always agree on which inputs take the array route (the per-window /
    superbatch emission-equivalence contract depends on it), so the
    rule lives in exactly one place."""
    if isinstance(edges, np.ndarray):
        return True
    return (
        isinstance(edges, (tuple, list))
        and len(edges) >= 2
        and all(isinstance(c, np.ndarray) and c.ndim == 1 for c in edges)
    )


@dataclasses.dataclass
class WindowPolicy:
    """Base class for window assignment policies."""


@dataclasses.dataclass(frozen=True)
class WindowInfo:
    """Host-side metadata for one emitted window (the ``TimeWindow`` analog).

    ``start``/``end`` are event-time bounds (end exclusive, Flink-style) for
    event-time windows, None for count windows; ``index`` counts emitted
    windows from 0 either way.
    """

    index: int
    start: Optional[float]
    end: Optional[float]

    @property
    def max_timestamp(self) -> Optional[float]:
        """Inclusive end, matching Flink's ``TimeWindow.maxTimestamp()``."""
        return None if self.end is None else self.end - 1


@dataclasses.dataclass
class CountWindow(WindowPolicy):
    """Tumbling window of a fixed number of edges."""

    size: int


class ScheduledCountWindow(CountWindow):
    """Count windows whose SIZE follows a window-indexed schedule — the
    mid-stream window-size-shift harness for the adaptive packer
    (``bench.py --autotune``'s shift cell and the controller tests).

    ``schedule`` is ``((start_index, size), ...)`` with ascending start
    indices, the first at 0: window ``i`` has the size of the last
    segment whose start is ``<= i``. Only the DYNAMIC packer
    (:meth:`Windower.superbatches_dynamic`) honors the schedule — it
    re-reads the size per group and caps each group at the next
    boundary so a group never spans two sizes; the static paths read
    ``.size`` (the first segment) like any ``CountWindow``."""

    def __init__(self, schedule):
        sched = tuple((int(a), int(b)) for a, b in schedule)
        if not sched or sched[0][0] != 0:
            raise ValueError(
                "schedule must be non-empty with its first segment at "
                f"window 0, got {schedule!r}"
            )
        for (a, sa), (b, sb) in zip(sched, sched[1:]):
            if b <= a:
                raise ValueError(
                    f"schedule starts must ascend, got {a} then {b}"
                )
        if any(s < 1 for _a, s in sched):
            raise ValueError("every scheduled size must be >= 1")
        super().__init__(size=sched[0][1])
        self.schedule = sched

    def size_at(self, index: int) -> int:
        """The window size at window ``index``."""
        size = self.schedule[0][1]
        for start, s in self.schedule:
            if start > index:
                break
            size = s
        return size

    def run_length(self, index: int) -> Optional[int]:
        """Windows from ``index`` (inclusive) until the next size
        boundary; None inside the final segment (no boundary ahead)."""
        for start, _s in self.schedule:
            if start > index:
                return start - index
        return None


@dataclasses.dataclass
class ProcessingTimeWindow(WindowPolicy):
    """Tumbling wall-clock window: close when ``seconds`` have elapsed
    since the window's first record — the micro-batch/low-latency policy
    for unbounded live sources (Flink's processing-time ``timeWindow``).

    ``max_count`` additionally caps the window's record count (close on
    whichever trips first), bounding device block capacity under bursts.
    Live sources that can go idle should yield ``None`` ticks (see
    :class:`~gelly_streaming_tpu.core.sources.SocketEdgeSource`): the
    windower treats them as pure time signals, so an open window still
    closes on schedule when no records arrive."""

    seconds: float
    max_count: int = 1 << 20


@dataclasses.dataclass
class EventTimeWindow(WindowPolicy):
    """Tumbling event-time window of ``size`` time units.

    ``timestamp_fn(edge) -> number`` extracts the (ascending) event time, the
    analog of the reference's ``AscendingTimestampExtractor`` ctor path.

    Column contract on the array fast path: array input is ``[N, 2|3]``
    (src, dst[, third]) or a (src, dst[, val][, ts]) column tuple, and
    ``timestamp_fn`` is applied to the column tuple itself — an index-based
    extractor like ``lambda e: e[2]`` therefore selects the same column it
    would select per-record, vectorized for free. A non-indexing fn must be
    numpy-broadcastable or the windower raises.
    """

    size: float
    timestamp_fn: Callable[[Tuple], float] = None  # type: ignore[assignment]


class Windower:
    """Discretize host edge records into EdgeBlocks under a window policy.

    Edge records are ``(src, dst)`` or ``(src, dst, val)`` tuples (raw ids).
    The windower owns the stream's VertexDict so compact ids are stable across
    windows — carried device state (labels, degrees, ranks) indexed by compact
    id stays valid as new vertices appear (vertex capacity only grows, in
    power-of-two buckets).
    """

    def __init__(
        self,
        policy: WindowPolicy,
        vertex_dict: Optional[VertexDict] = None,
        *,
        val_dtype=np.float32,
        capacity: Optional[int] = None,
    ):
        self.policy = policy
        self.vertex_dict = vertex_dict if vertex_dict is not None else VertexDict()
        self.val_dtype = val_dtype
        self.capacity = capacity  # fixed capacity override (else bucketed)

    # ------------------------------------------------------------------ #
    def _rows_to_cols(self, rows: Sequence[Tuple]) -> Tuple:
        """One window's record tuples -> raw ``(src, dst, val|None)``
        columns — THE record-parsing rule (val presence decided by the
        window's first record), shared by the per-window block path and
        the record superbatch packer so the two cannot drift."""
        n = len(rows)
        raw_src = np.fromiter((r[0] for r in rows), dtype=np.int64, count=n)
        raw_dst = np.fromiter((r[1] for r in rows), dtype=np.int64, count=n)
        if n and len(rows[0]) > 2 and rows[0][2] is not None:
            val = np.asarray([r[2] for r in rows], dtype=self.val_dtype)
        else:
            val = None
        return raw_src, raw_dst, val

    def _make_block(self, rows: Sequence[Tuple]) -> EdgeBlock:
        return self._block_from_arrays(*self._rows_to_cols(rows))

    def _block_from_arrays(
        self, raw_src: np.ndarray, raw_dst: np.ndarray, val: Optional[np.ndarray]
    ) -> EdgeBlock:
        n = raw_src.shape[0]
        # the span covers the whole host pack: encode + pad + device put
        # (the per-window fixed cost the superbatch path amortizes)
        with _trace.span(
            "window.pack",
            {"edges": int(n)} if _trace.on() else None,
        ):
            # Paired encode keeps first-seen order by edge arrival (src
            # before dst per edge), matching the reference's per-record
            # processing.
            src, dst = self.vertex_dict.encode_pair(raw_src, raw_dst)
            cap = (
                self.capacity if self.capacity is not None
                else bucket_capacity(n)
            )
            block = EdgeBlock.from_arrays(
                src, dst, val, n_vertices=self.vertex_dict.capacity,
                capacity=cap, val_dtype=self.val_dtype,
            )
            host_val = (
                np.zeros(n, dtype=self.val_dtype)
                if val is None
                else np.asarray(val, self.val_dtype)
            )
            return block.with_host_cache(src, dst, host_val)

    def _block_from_encoded(
        self, src: np.ndarray, dst: np.ndarray, val: Optional[np.ndarray]
    ) -> EdgeBlock:
        """Build a block from already-compact int32 columns (the fused
        native parse+encode path — the vertex dict was updated upstream)."""
        n = src.shape[0]
        with _trace.span(
            "window.pack",
            {"edges": int(n), "encoded": True} if _trace.on() else None,
        ):
            src = np.ascontiguousarray(src, np.int32)
            dst = np.ascontiguousarray(dst, np.int32)
            cap = (
                self.capacity if self.capacity is not None
                else bucket_capacity(n)
            )
            block = EdgeBlock.from_arrays(
                src, dst, val, n_vertices=self.vertex_dict.capacity,
                capacity=cap, val_dtype=self.val_dtype,
            )
            host_val = (
                np.zeros(n, dtype=self.val_dtype)
                if val is None
                else np.asarray(val, self.val_dtype)
            )
            return block.with_host_cache(src, dst, host_val)

    def blocks(self, edges: Iterable[Tuple]) -> Iterator[EdgeBlock]:
        """Yield one EdgeBlock per tumbling window."""
        for _, block in self.blocks_with_info(edges):
            yield block

    def blocks_with_info(
        self, edges: Iterable[Tuple]
    ) -> Iterator[Tuple["WindowInfo", EdgeBlock]]:
        """Like :meth:`blocks` but paired with host-side window metadata.

        The metadata stays OUT of the EdgeBlock pytree on purpose: a
        per-window id inside the block would be a static leaf changing every
        window and defeat jit caching. Flink's analog is the ``TimeWindow``
        handed to window functions (``SnapshotStream.java:146``).
        """
        policy = self.policy
        index = 0
        if is_column_input(edges):
            yield from self._array_windows(edges)
            return
        if callable(getattr(edges, "iter_chunks", None)) and isinstance(
            policy, CountWindow
        ):
            # chunk-capable source (GeneratorSource): consume column
            # chunks directly instead of per-record tuples — the
            # synthetic load generator must not itself be the
            # bottleneck. Count windows only: time policies read
            # per-record semantics (ticks, timestamps) chunks don't
            # carry, so they keep the record path.
            yield from self.blocks_from_chunks(edges.iter_chunks())
            return
        if isinstance(policy, CountWindow):
            buf: list[Tuple] = []
            for e in edges:
                if e is None:  # live-source time tick; count windows ignore
                    continue
                buf.append(e)
                if len(buf) >= policy.size:
                    yield WindowInfo(index, None, None), self._make_block(buf)
                    index += 1
                    buf = []
            if buf:
                yield WindowInfo(index, None, None), self._make_block(buf)
        elif isinstance(policy, ProcessingTimeWindow):
            import time as _time

            buf = []
            t0: Optional[float] = None
            for e in edges:
                now = _time.perf_counter()
                if e is not None:
                    if t0 is None:
                        t0 = now
                    buf.append(e)
                if buf and (
                    now - t0 >= policy.seconds or len(buf) >= policy.max_count
                ):
                    yield WindowInfo(index, None, None), self._make_block(buf)
                    index += 1
                    buf = []
                    t0 = None
            if buf:
                yield WindowInfo(index, None, None), self._make_block(buf)
        elif isinstance(policy, EventTimeWindow):
            if policy.timestamp_fn is None:
                raise ValueError(
                    "EventTimeWindow requires timestamp_fn — without it the "
                    "edge value would silently be read as the event time"
                )
            ts_fn = policy.timestamp_fn
            buf = []
            current: Optional[int] = None
            for e in edges:
                if e is None:
                    # live-source idle tick: event-time windows close on
                    # event time, never wall clock, so ticks are no-ops
                    continue
                w = int(ts_fn(e) // policy.size)
                if current is None:
                    current = w
                if w != current:
                    if buf:
                        yield self._info(index, current), self._make_block(buf)
                        index += 1
                    buf = []
                    current = w
                buf.append(e)
            if buf:
                yield self._info(index, current), self._make_block(buf)
        else:
            raise TypeError(f"unknown window policy {policy!r}")

    def _info(self, index: int, time_slot: int) -> "WindowInfo":
        size = self.policy.size
        return WindowInfo(index, time_slot * size, (time_slot + 1) * size)

    # ------------------------------------------------------------------ #
    # Superbatch packing: K windows -> one ingest group
    # ------------------------------------------------------------------ #
    def superbatches(
        self, edges: Iterable[Tuple], k: int
    ) -> Iterator["SuperbatchGroup"]:
        """Pack K consecutive windows into one :class:`SuperbatchGroup`
        (the final group may be shorter).

        This is the ingest half of the superbatch execution path: the
        per-window fixed cost below ~64k-edge windows is dominated by
        assembling one device EdgeBlock PER WINDOW (compact-id encode +
        padding + several host->device puts each), so the packer's array
        fast path (count windows over column input) never builds
        per-window blocks at all — it encodes the whole group once and
        hands out per-window host column views; the ``[K, cap]`` device
        stack materializes lazily only for consumers that dispatch on it
        (``SummaryAggregation._superbatch_step``). Window BOUNDARIES are
        unchanged — each member window keeps its own WindowInfo and mask
        row, so emission semantics stay per-window.
        """
        if k < 1:
            raise ValueError(f"superbatch k must be >= 1, got {k}")
        policy = self.policy
        if isinstance(policy, CountWindow) and is_column_input(edges):
            yield from self._array_superbatches(edges, k)
            return
        if isinstance(policy, CountWindow) and not callable(
            getattr(edges, "iter_chunks", None)
        ):
            yield from self._record_superbatches(iter(edges), k)
            return
        yield from superbatches_from_blocks(
            self.blocks_with_info(edges), k, with_info=True,
            val_dtype=self.val_dtype,
        )

    def _array_superbatches(self, edges, k: int) -> Iterator["SuperbatchGroup"]:
        """Count-window column fast path: slice the raw columns into
        per-window triples and delegate to :meth:`pack_window_cols` —
        THE one group-packing implementation (slicing here, encode +
        group assembly there), so the fast path, the sharded-ingest
        path, and the latency-curve bench all measure the same code."""
        if isinstance(edges, np.ndarray):
            if edges.ndim != 2 or not 2 <= edges.shape[1] <= 3:
                raise ValueError("edge array must be [N, 2] or [N, 3]")
            cols = [edges[:, i] for i in range(edges.shape[1])]
        else:
            cols = [np.asarray(c) for c in edges]
        src = cols[0].astype(np.int64)
        dst = cols[1].astype(np.int64)
        val = cols[2].astype(self.val_dtype) if len(cols) > 2 else None
        n = src.shape[0]
        size = self.policy.size
        index = 0
        for g0 in range(0, n, size * k):
            g1 = min(g0 + size * k, n)
            win_cols = [
                (src[w0:min(w0 + size, g1)], dst[w0:min(w0 + size, g1)],
                 None if val is None else val[w0:min(w0 + size, g1)])
                for w0 in range(g0, g1, size)
            ]
            yield self.pack_window_cols(win_cols, first_index=index)
            index += len(win_cols)

    def _record_superbatches(
        self, edges: Iterator[Tuple], k: int
    ) -> Iterator["SuperbatchGroup"]:
        """Count-window RECORD path: buffer K windows' raw records,
        convert each window to raw columns once, and pack the group
        through :meth:`pack_window_cols` — the same one-group-encode
        ingest fusion the column fast path gets. Record streams
        previously fell back to per-window block assembly + generic
        packing, which both paid the per-window device cost the
        superbatch exists to amortize AND left the group without the
        packer's seen-count watermark (``SuperbatchGroup.n_seen_before``).
        Live-source ``None`` ticks are ignored, as in :meth:`blocks`."""
        size = self.policy.size
        index = 0
        win_rows: list = []
        rows: list = []

        def flush():
            nonlocal win_rows, index
            cols = [self._rows_to_cols(rws) for rws in win_rows]
            group = self.pack_window_cols(cols, first_index=index)
            index += len(cols)
            win_rows = []
            return group

        for e in edges:
            if e is None:  # live-source time tick; count windows ignore
                continue
            rows.append(e)
            if len(rows) >= size:
                win_rows.append(rows)
                rows = []
                if len(win_rows) >= k:
                    yield flush()
        if rows:
            win_rows.append(rows)
        if win_rows:
            yield flush()

    #: windows per group while the dynamic packer replays a resume skip
    #: (packed for the vertex-dictionary replay, never surfaced — the
    #: tiling of unsurfaced groups is free to be whatever amortizes the
    #: encode best)
    SKIP_GROUP_WINDOWS = 256

    def superbatches_dynamic(
        self, edges: Iterable[Tuple], k_fn, skip: int = 0
    ) -> Iterator["SuperbatchGroup"]:
        """Adaptive-K superbatch packing: like :meth:`superbatches`, but
        the group size is re-read from ``k_fn()`` at EVERY group
        boundary — the ingest half of ``superbatch="auto"`` (the
        controller moves K between groups; window boundaries, packing,
        and emission semantics are exactly the fixed-K path's, group
        TILING is the only degree of freedom). Count windows re-read
        ``policy.size`` per window too, so a
        :class:`ScheduledCountWindow` shifts window size mid-stream
        with groups capped at each size boundary (a group never spans
        two sizes). ``skip`` consumes (packs, for the vertex-dictionary
        replay) the first ``skip`` windows without surfacing them — the
        checkpoint-resume fast-forward
        (``autockpt._SkipStream.superbatches_dynamic``)."""
        if skip < 0:
            raise ValueError(f"skip must be >= 0, got {skip}")
        policy = self.policy
        if isinstance(policy, CountWindow) and is_column_input(edges):
            yield from self._dynamic_array_superbatches(edges, k_fn, skip)
            return
        if isinstance(policy, CountWindow) and not callable(
            getattr(edges, "iter_chunks", None)
        ):
            yield from self._dynamic_record_superbatches(
                iter(edges), k_fn, skip
            )
            return
        blocks = self.blocks_with_info(edges)
        for _ in range(skip):
            if next(blocks, None) is None:
                break
        yield from superbatches_from_blocks_dynamic(
            blocks, k_fn, with_info=True, val_dtype=self.val_dtype,
        )

    def _group_k(self, index: int, k_fn, skip: int) -> Tuple[int, int]:
        """(window size, group window count) for the group starting at
        window ``index`` — the one tiling rule of the dynamic packer:
        the scheduled size at the index, the controller's K (or the
        skip-replay tile), capped so a group never crosses a size
        boundary or the skip watermark."""
        policy = self.policy
        size_at = getattr(policy, "size_at", None)
        size = int(size_at(index)) if callable(size_at) \
            else int(policy.size)
        if index < skip:
            k = min(self.SKIP_GROUP_WINDOWS, skip - index)
        else:
            k = max(1, int(k_fn()))
        run_length = getattr(policy, "run_length", None)
        if callable(run_length):
            rl = run_length(index)
            if rl is not None:
                k = min(k, max(1, rl))
        return size, k

    def _dynamic_array_superbatches(
        self, edges, k_fn, skip: int
    ) -> Iterator["SuperbatchGroup"]:
        """Count-window column fast path with per-group tiling — same
        slicing + :meth:`pack_window_cols` shape as
        :meth:`_array_superbatches`, group size decided per group."""
        if isinstance(edges, np.ndarray):
            if edges.ndim != 2 or not 2 <= edges.shape[1] <= 3:
                raise ValueError("edge array must be [N, 2] or [N, 3]")
            cols = [edges[:, i] for i in range(edges.shape[1])]
        else:
            cols = [np.asarray(c) for c in edges]
        src = cols[0].astype(np.int64)
        dst = cols[1].astype(np.int64)
        val = cols[2].astype(self.val_dtype) if len(cols) > 2 else None
        n = src.shape[0]
        index = 0
        g0 = 0
        while g0 < n:
            size, k = self._group_k(index, k_fn, skip)
            g1 = min(g0 + size * k, n)
            win_cols = [
                (src[w0:min(w0 + size, g1)], dst[w0:min(w0 + size, g1)],
                 None if val is None else val[w0:min(w0 + size, g1)])
                for w0 in range(g0, g1, size)
            ]
            group = self.pack_window_cols(win_cols, first_index=index)
            index += len(win_cols)
            g0 = g1
            if index > skip:  # groups never straddle skip (capped above)
                yield group

    def _dynamic_record_superbatches(
        self, edges: Iterator[Tuple], k_fn, skip: int
    ) -> Iterator["SuperbatchGroup"]:
        """Count-window RECORD path with per-group tiling (the dynamic
        analog of :meth:`_record_superbatches`); live-source ``None``
        ticks are ignored, as everywhere count windows consume them."""
        index = 0
        win_rows: list = []
        rows: list = []
        size, k_target = self._group_k(index, k_fn, skip)

        def flush():
            nonlocal win_rows, index, size, k_target
            cols = [self._rows_to_cols(rws) for rws in win_rows]
            group = self.pack_window_cols(cols, first_index=index)
            start = index
            index += len(cols)
            win_rows = []
            size, k_target = self._group_k(index, k_fn, skip)
            return group if start >= skip else None

        for e in edges:
            if e is None:
                continue
            rows.append(e)
            if len(rows) >= size:
                win_rows.append(rows)
                rows = []
                if len(win_rows) >= k_target:
                    group = flush()
                    if group is not None:
                        yield group
        if rows:
            win_rows.append(rows)
        if win_rows:
            group = flush()
            if group is not None:
                yield group

    def pack_window_cols(
        self, win_cols: Sequence[Tuple], first_index: int = 0
    ) -> "SuperbatchGroup":
        """Pack ALREADY-CLOSED windows (raw-id column triples
        ``(src, dst, val|None)``) into one :class:`SuperbatchGroup`
        with a single group encode and ZERO per-window device work —
        the superbatch ingest fusion for window boundaries decided
        upstream (the sharded ingest's per-shard windowers,
        ``core/ingest.py``). The count-window column fast path
        (:meth:`_array_superbatches`) is the same shape with the
        boundary slicing done here too."""
        k = len(win_cols)
        lens = [len(c[0]) for c in win_cols]
        with _trace.span(
            "window.superbatch_pack",
            {"k": k, "edges": int(sum(lens)), "window_index": first_index}
            if _trace.on() else None,
        ):
            # seen-vertex watermark BEFORE the group encode: together
            # with the encoded columns this reconstructs every member
            # window's post-encode len(vertex_dict)
            # (SuperbatchGroup.n_seen_per_window) — the per-window value
            # group-folded workloads that read the seen count
            # (IncrementalPageRank's teleport mass) need for value
            # identity with the per-window path
            n_seen_before = len(self.vertex_dict)
            if k == 1:
                src = np.ascontiguousarray(win_cols[0][0], np.int64)
                dst = np.ascontiguousarray(win_cols[0][1], np.int64)
            else:
                src = np.concatenate(
                    [np.asarray(c[0], np.int64) for c in win_cols]
                )
                dst = np.concatenate(
                    [np.asarray(c[1], np.int64) for c in win_cols]
                )
            s_g, d_g = self.vertex_dict.encode_pair(src, dst)
            s_g = np.asarray(s_g, np.int32)
            d_g = np.asarray(d_g, np.int32)
            nv = self.vertex_dict.capacity
            cols = []
            infos = []
            a = 0
            for j, c in enumerate(win_cols):
                b = a + lens[j]
                v = c[2]
                cols.append((
                    s_g[a:b], d_g[a:b],
                    None if v is None else np.asarray(v, self.val_dtype),
                ))
                infos.append(WindowInfo(first_index + j, None, None))
                a = b
            return SuperbatchGroup(
                infos, cols, nv, val_dtype=self.val_dtype,
                n_seen_before=n_seen_before,
            )

    # ------------------------------------------------------------------ #
    # Vectorized ingest: numpy columns instead of per-record tuples
    # ------------------------------------------------------------------ #
    def _array_windows(self, edges) -> Iterator[Tuple["WindowInfo", EdgeBlock]]:
        """Array fast path: ``edges`` is an [N,2|3] ndarray or a
        (src, dst[, val][, ts]) tuple/list of 1-D arrays. Window boundaries
        are computed with numpy (no per-record Python), the host ingest
        throughput fix for large streams.
        """
        if isinstance(edges, np.ndarray):
            if edges.ndim != 2 or not 2 <= edges.shape[1] <= 3:
                raise ValueError("edge array must be [N, 2] or [N, 3]")
            cols = [edges[:, i] for i in range(edges.shape[1])]
        else:
            cols = [np.asarray(c) for c in edges]
        src = cols[0].astype(np.int64)
        dst = cols[1].astype(np.int64)
        val = cols[2].astype(self.val_dtype) if len(cols) > 2 else None
        n = src.shape[0]
        policy = self.policy
        ts = None
        if isinstance(policy, EventTimeWindow):
            # Same contract as the record path: the caller must say which
            # column is the event time — never silently read the value
            # column as a timestamp.
            if policy.timestamp_fn is None:
                raise ValueError(
                    "EventTimeWindow requires timestamp_fn — without it the "
                    "edge value would silently be read as the event time"
                )
            # Apply the extractor to the column tuple: an index-based fn
            # (lambda e: e[k]) picks the same column it picks per-record,
            # vectorized. Anything non-broadcastable errors here rather
            # than silently windowing on the wrong column.
            try:
                ts = np.asarray(policy.timestamp_fn(tuple(cols)), np.float64)
            except Exception as e:
                raise ValueError(
                    "EventTimeWindow.timestamp_fn could not be applied to "
                    "the column tuple on the array ingest path; use an "
                    "index-based extractor (lambda e: e[k]) or a numpy-"
                    f"broadcastable fn ({e})"
                ) from e
            if ts.shape != (n,):
                raise ValueError(
                    "EventTimeWindow.timestamp_fn returned shape "
                    f"{ts.shape} on the array path; expected ({n},)"
                )
        if isinstance(policy, CountWindow):
            index = 0
            for start in range(0, n, policy.size):
                end = min(start + policy.size, n)
                yield WindowInfo(index, None, None), self._block_from_arrays(
                    src[start:end], dst[start:end],
                    None if val is None else val[start:end],
                )
                index += 1
        elif isinstance(policy, EventTimeWindow):
            slots = (np.asarray(ts, np.float64) // policy.size).astype(np.int64)
            # ascending timestamps: window boundaries are runs of equal slot
            bounds = np.nonzero(np.diff(slots))[0] + 1
            starts = np.concatenate([[0], bounds])
            ends = np.concatenate([bounds, [n]])
            for index, (a, b) in enumerate(zip(starts, ends)):
                yield self._info(index, int(slots[a])), self._block_from_arrays(
                    src[a:b], dst[a:b], None if val is None else val[a:b]
                )
        else:
            raise TypeError(f"unknown window policy {policy!r}")


    # ------------------------------------------------------------------ #
    # Chunked-column ingest: file-scale streams (datasets.stream_file)
    # ------------------------------------------------------------------ #
    def blocks_from_chunks(
        self, chunks: Iterable[Tuple], encoded: bool = False
    ) -> Iterator[Tuple["WindowInfo", EdgeBlock]]:
        """Discretize an iterator of column chunks ``(src, dst[, val])``
        into windows, re-slicing across chunk boundaries.

        This is the bounded-memory ingest path for file-backed streams
        (``native.iter_edge_chunks`` yields ~fixed-size column chunks; the
        window policy decides the actual block boundaries). Count windows
        buffer columns until ``size`` edges are pending; event-time windows
        assume ascending timestamps (the reference's
        ``AscendingTimestampExtractor`` contract) and flush a window when
        its slot is passed.

        ``encoded=True`` marks chunks whose endpoint columns are already
        compact int32 ids from this windower's VertexDict (the fused native
        ingest, ``VertexDict.iter_encode_file``); on that path an
        event-time ``timestamp_fn`` sees compact ids in columns 0/1.
        """
        policy = self.policy
        chunks = _timed_pulls(chunks)
        if isinstance(policy, CountWindow):
            yield from self._chunk_count_windows(chunks, policy.size, encoded)
        elif isinstance(policy, EventTimeWindow):
            yield from self._chunk_time_windows(chunks, policy, encoded)
        else:
            raise TypeError(f"unknown window policy {policy!r}")

    def _chunk_count_windows(self, chunks, size: int, encoded: bool = False):
        pending: list[Tuple] = []  # (src, dst, val|None) column triples
        have = 0
        index = 0
        build = self._block_from_encoded if encoded else self._block_from_arrays
        for cols in chunks:
            src, dst = np.asarray(cols[0]), np.asarray(cols[1])
            val = cols[2] if len(cols) > 2 else None
            if len(src) == 0:
                continue
            pending.append((src, dst, val))
            have += len(src)
            while have >= size:
                have -= size
                yield WindowInfo(index, None, None), build(
                    *take_cols(pending, size, self.val_dtype)
                )
                index += 1
        if have:
            yield WindowInfo(index, None, None), build(
                *take_cols(pending, have, self.val_dtype)
            )

    def _chunk_time_windows(
        self, chunks, policy: EventTimeWindow, encoded: bool = False
    ):
        build = self._block_from_encoded if encoded else self._block_from_arrays
        runs = iter_time_slot_runs(chunks, policy, val_dtype=self.val_dtype)
        for index, (slot, src, dst, val) in enumerate(runs):
            yield self._info(index, slot), build(src, dst, val)


_NO_CHUNK = object()


def _timed_pulls(chunks: Iterable[Tuple]) -> Iterator[Tuple]:
    """``chunks``, with every pull on it under the span
    ``ingest.wait_source``: the time the ingest thread waits for its
    next input (a paced source, a socket, a file parse), which is where
    an idle device's gaps belong. The span wraps the pull ALONE and is
    closed before the chunk is yielded: a span left open across a
    generator's yield would mis-nest the thread's span stack."""
    it = iter(chunks)
    while True:
        with _trace.span("ingest.wait_source"):
            cols = next(it, _NO_CHUNK)
        if cols is _NO_CHUNK:
            return
        yield cols


def take_cols(pend: list, take: int, val_dtype=np.float64):
    """Slice ``take`` edges off a pending list of ``(src, dst,
    val|None)`` column chunks, mutating ``pend`` in place — THE
    take-N-across-chunk-boundaries rule, shared by the windower's
    chunked count windows and the sharded ingest's per-shard window
    assembly (``core/ingest.py``). Single-chunk takes hand out slice
    VIEWS (no concatenation copy — the encoder reads views);
    multi-chunk takes concatenate once, zero-filling ``None`` value
    chunks when any chunk carries values.

    Chunks may carry a 4th element — the i64 event-time ``ts`` column of
    a GSEW v2 frame (ISSUE 18); the take then returns a matching
    4-tuple, slicing ``ts`` in lockstep. Mixed pending lists (some
    chunks timestamped, some not) are a caller bug and raise: a window
    half of whose records lost their timestamps cannot be assigned to
    event-time panes honestly."""
    with_ts = len(pend[0]) == 4
    s_parts, d_parts, v_parts, t_parts = [], [], [], []
    got = 0
    while got < take:
        chunk = pend[0]
        if (len(chunk) == 4) != with_ts:
            raise ValueError(
                "pending column chunks disagree on carrying a ts column"
            )
        s, d, v = chunk[0], chunk[1], chunk[2]
        t = chunk[3] if with_ts else None
        need = take - got
        if len(s) <= need:
            s_parts.append(s)
            d_parts.append(d)
            v_parts.append(v)
            t_parts.append(t)
            pend.pop(0)
            got += len(s)
        else:
            s_parts.append(s[:need])
            d_parts.append(d[:need])
            v_parts.append(None if v is None else v[:need])
            t_parts.append(None if t is None else t[:need])
            rest = (s[need:], d[need:],
                    None if v is None else v[need:])
            pend[0] = rest + (t[need:],) if with_ts else rest
            got = take
    if len(s_parts) == 1:
        out = (s_parts[0], d_parts[0], v_parts[0])
        return out + (t_parts[0],) if with_ts else out
    src = np.concatenate(s_parts)
    dst = np.concatenate(d_parts)
    if any(v is not None for v in v_parts):
        val = np.concatenate(
            [
                np.zeros(len(s), val_dtype) if v is None
                else np.asarray(v, val_dtype)
                for s, v in zip(s_parts, v_parts)
            ]
        )
    else:
        val = None
    if with_ts:
        ts = np.concatenate(
            [np.asarray(t, np.int64) for t in t_parts]
        )
        return src, dst, val, ts
    return src, dst, val


def iter_time_slot_runs(chunks, policy: "EventTimeWindow",
                        val_dtype=np.float64):
    """The ONE chunked event-time splitter: consume (src, dst[, val])
    column chunks and yield ``(slot, src, dst, val|None)`` per completed
    tumbling window (ascending timestamps; boundaries are runs of equal
    ``ts // size``; the final partial window is included). Carried runs
    accumulate as a LIST and concatenate once per flush — a window
    spanning many chunks costs O(window), not a per-chunk re-copy of the
    whole carry. Shared by the Windower's chunked path and the
    device-encode ingest (``datasets._device_encoded_blocks``) so slot
    semantics cannot diverge between them."""
    if policy.timestamp_fn is None:
        raise ValueError(
            "EventTimeWindow requires timestamp_fn — without it the "
            "edge value would silently be read as the event time"
        )
    slot: Optional[int] = None
    pend: list = []

    def flush():
        if not pend:
            return None
        src = np.concatenate([p[0] for p in pend])
        dst = np.concatenate([p[1] for p in pend])
        if any(p[2] is not None for p in pend):
            val = np.concatenate(
                [
                    np.zeros(len(p[0]), val_dtype) if p[2] is None
                    else np.asarray(p[2], val_dtype)
                    for p in pend
                ]
            )
        else:
            val = None
        out = (slot, src, dst, val)
        pend.clear()
        return out

    for cols in chunks:
        src, dst = np.asarray(cols[0]), np.asarray(cols[1])
        val = cols[2] if len(cols) > 2 else None
        n = len(src)
        if n == 0:
            continue
        ts = np.asarray(
            policy.timestamp_fn(tuple(
                np.asarray(c) if c is not None else None for c in cols
            )),
            np.float64,
        )
        if ts.shape != (n,):
            raise ValueError(
                "EventTimeWindow.timestamp_fn returned shape "
                f"{ts.shape} on the chunked path; expected ({n},)"
            )
        slots = (ts // policy.size).astype(np.int64)
        bounds = np.nonzero(np.diff(slots))[0] + 1
        starts = np.concatenate([[0], bounds])
        ends = np.concatenate([bounds, [n]])
        for a, b in zip(starts, ends):
            run_slot = int(slots[a])
            if slot is not None and run_slot != slot:
                w = flush()
                if w is not None:
                    yield w
            slot = run_slot
            pend.append(
                (src[a:b], dst[a:b], None if val is None else val[a:b])
            )
    w = flush()
    if w is not None:
        yield w


class SuperbatchGroup:
    """K consecutive windows as ONE ingest unit (the superbatch).

    ``cols`` holds per-window host column triples ``(src, dst, val|None)``
    of compact int32 ids — the zero-device-work view the windowed CC
    carries consume; ``None`` when the member windows were
    device-transformed (no usable host caches). :meth:`stacked`
    materializes (and caches) the ``[K, cap]``
    :class:`~gelly_streaming_tpu.core.edgeblock.StackedEdgeBlock` for
    consumers that dispatch on the device stack — built from ``cols``
    with ONE host->device transfer per column, or from the member
    blocks' device arrays as the fallback.

    ``n_seen_before`` records ``len(vertex_dict)`` at the moment the
    packer started the group encode (None when the group was packed
    from pre-built blocks and the watermark is unknown); see
    :meth:`n_seen_per_window`.
    """

    __slots__ = ("infos", "cols", "n_vertices", "val_dtype", "_blocks",
                 "_stacked", "n_seen_before")

    def __init__(self, infos, cols, n_vertices: int, *,
                 val_dtype=np.float32, blocks=None,
                 n_seen_before: Optional[int] = None):
        self.infos = infos
        self.cols = cols
        self.n_vertices = n_vertices
        self.val_dtype = val_dtype
        self._blocks = blocks
        self._stacked = None
        self.n_seen_before = n_seen_before

    def __len__(self) -> int:
        return len(self.infos)

    def n_seen_per_window(self) -> Optional[list]:
        """Per-member-window seen-vertex counts — the ``len(vertex_dict)``
        a per-window consumer would have read after each window's encode
        — reconstructed from the group's encoded columns.

        Both dictionary kinds assign/observe monotonically in first-seen
        order (``VertexDict`` hands out sequential compact ids;
        ``IdentityDict.observe`` tracks ``max raw id + 1``), so the count
        after window ``i`` is exactly ``max(n_seen_before, 1 + max
        compact id over windows <= i)``. Returns None when the packer
        did not record the pre-encode watermark (generic block packing)
        — consumers needing per-window counts then take their
        per-window fallback."""
        if self.cols is None or self.n_seen_before is None:
            return None
        out = []
        n = int(self.n_seen_before)
        for s, d, _ in self.cols:
            if len(s):
                hi = 1 + int(max(s.max(), d.max()))
                if hi > n:
                    n = hi
            out.append(n)
        return out

    def blocks(self) -> Iterator[EdgeBlock]:
        """The member windows as per-window :class:`EdgeBlock`\\ s — the
        group's PER-WINDOW fallback view (``GroupFoldable``
        implementations route unsupported groups through it). Pre-built
        blocks are handed out as-is; column-backed groups assemble one
        block per window (paying exactly the per-window device cost the
        fused path avoids — that is the point of a fallback)."""
        if self._blocks is not None:
            yield from self._blocks
            return
        for s, d, v in self.cols:
            block = EdgeBlock.from_arrays(
                np.ascontiguousarray(s, np.int32),
                np.ascontiguousarray(d, np.int32),
                v, n_vertices=self.n_vertices, val_dtype=self.val_dtype,
            )
            host_val = (
                np.zeros(len(s), dtype=self.val_dtype) if v is None
                else np.asarray(v, self.val_dtype)
            )
            yield block.with_host_cache(
                np.asarray(s, np.int32), np.asarray(d, np.int32), host_val
            )

    def stacked(self) -> StackedEdgeBlock:
        if self._stacked is not None:
            return self._stacked
        # span covers the [K, cap] device-stack materialization (one
        # host->device transfer per column on the cols path, a device
        # stack of the member blocks on the fallback)
        with _trace.span(
            "window.stack",
            {"k": len(self), "from_cols": self.cols is not None}
            if _trace.on() else None,
        ):
            if self.cols is not None:
                self._stacked = stack_host_cols(
                    self.cols, self.n_vertices, val_dtype=self.val_dtype
                )
            else:
                self._stacked = stack_blocks(self._blocks)
        return self._stacked


def _group_from_blocks(group: list, infos: list,
                       val_dtype) -> SuperbatchGroup:
    """One pre-built-block group as a :class:`SuperbatchGroup` — the
    shared emit of the fixed and dynamic block packers."""
    cols = None
    # same honesty guard as stack_blocks: prefix-aligned caches with
    # plain ndarray vals only — pytree vals (tuple-valued map_edges)
    # cannot fill a single [K, cap] val plane and take the device
    # stacking fallback instead
    if all(
        getattr(b, "_host_cache", None) is not None
        and getattr(b, "_host_cache_pos", None) is None
        and (b._host_cache[2] is None
             or isinstance(b._host_cache[2], np.ndarray))
        for b in group
    ):
        cols = [b._host_cache for b in group]
    return SuperbatchGroup(
        infos, cols, max(b.n_vertices for b in group),
        val_dtype=val_dtype, blocks=group,
    )


def superbatches_from_blocks(
    blocks: Iterable, k: int, with_info: bool = False,
    val_dtype=np.float32,
) -> Iterator[SuperbatchGroup]:
    """Pack an EdgeBlock iterator into :class:`SuperbatchGroup`\\ s of K
    (generic fallback — per-window blocks were already assembled, so
    this recovers only the dispatch fusion, not the ingest fusion).
    Host column views come from the blocks' prefix-aligned host caches
    when every member has one; otherwise ``cols`` is None and consumers
    use the device stack."""
    group: list = []
    infos: list = []
    for item in blocks:
        info, block = item if with_info else (None, item)
        group.append(block)
        infos.append(info)
        if len(group) >= k:
            yield _group_from_blocks(group, infos, val_dtype)
            group, infos = [], []
    if group:
        yield _group_from_blocks(group, infos, val_dtype)


def superbatches_from_blocks_dynamic(
    blocks: Iterable, k_fn, with_info: bool = False,
    val_dtype=np.float32,
) -> Iterator[SuperbatchGroup]:
    """The adaptive-K analog of :func:`superbatches_from_blocks`: the
    group size is re-read from ``k_fn()`` at every group boundary, so a
    controller moves the tiling between groups on streams that only
    offer pre-built blocks (derived/prefetched streams — dispatch
    fusion only, like the fixed generic path)."""
    group: list = []
    infos: list = []
    want = max(1, int(k_fn()))
    for item in blocks:
        info, block = item if with_info else (None, item)
        group.append(block)
        infos.append(info)
        if len(group) >= want:
            yield _group_from_blocks(group, infos, val_dtype)
            group, infos = [], []
            want = max(1, int(k_fn()))
    if group:
        yield _group_from_blocks(group, infos, val_dtype)


def iter_superbatches(stream, k: int) -> Iterator[SuperbatchGroup]:
    """Superbatch groups for any stream: the stream's own packer when it
    offers one (``SimpleEdgeStream.superbatches`` routes to the
    Windower's zero-per-window-device-work fast path;
    ``autockpt._SkipStream`` wraps the inner packer with a
    group-granular replay skip), else generic packing of its block
    iterator. Streams can OPT OUT of the fast path by setting
    ``superbatches = None``.

    On the generic path the block iterator is prefetched
    :func:`~gelly_streaming_tpu.core.pipeline.superbatch_prefetch_depth`
    windows deep — per-window block assembly still happens on that path
    (the blocks pre-exist), so a depth sized for the per-window cadence
    would stall each group behind its own K assemblies."""
    fast = getattr(stream, "superbatches", None)
    if callable(fast):
        yield from fast(k)
        return
    from .pipeline import prefetch, superbatch_prefetch_depth

    yield from superbatches_from_blocks(
        prefetch(stream.blocks(), superbatch_prefetch_depth(k)), k
    )


def iter_superbatches_dynamic(stream, k_fn) -> Iterator[SuperbatchGroup]:
    """Adaptive-K superbatch groups for any stream — the
    ``superbatch="auto"`` analog of :func:`iter_superbatches`: the
    stream's own dynamic packer when it offers one
    (``SimpleEdgeStream.superbatches_dynamic`` routes to the Windower's
    zero-per-window-device-work fast path;
    ``autockpt._SkipStream.superbatches_dynamic`` adds the resume
    skip), else generic dynamic packing of its block iterator."""
    fast = getattr(stream, "superbatches_dynamic", None)
    if callable(fast):
        yield from fast(k_fn)
        return
    from .pipeline import prefetch, superbatch_prefetch_depth

    yield from superbatches_from_blocks_dynamic(
        prefetch(
            stream.blocks(),
            superbatch_prefetch_depth(max(1, int(k_fn()))),
        ),
        k_fn,
    )


def blocks_from_edges(
    edges: Iterable[Tuple],
    window_size: int,
    vertex_dict: Optional[VertexDict] = None,
    **kw: Any,
) -> Iterator[EdgeBlock]:
    """Convenience: count-window discretization of an edge iterable."""
    w = Windower(CountWindow(window_size), vertex_dict, **kw)
    return w.blocks(edges)
