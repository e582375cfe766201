"""Incremental PageRank over the streaming graph (BASELINE config #4).

Not present in the reference — BASELINE.json adds it as a new algorithm on
the TPU path, cast as ``applyOnNeighbors``-style message passing. Design:

- The accumulated graph is carried as device edge arrays (compact ids,
  capacity-bucketed, like the triangle path).
- Per window, power iteration runs **warm-started from the previous
  window's ranks** — that is the "incremental" part: after a small batch of
  new edges the previous ranks are near the new fixpoint and few iterations
  are needed, vs. cold-start O(log(1/tol)/log(1/d)) every window.
- One iteration = scatter-add of ``d * rank[src]/outdeg[src]`` messages
  over the edge list (``jax.ops``-style ``segment_sum``: P2 vertex-keyed
  parallelism) + teleport and dangling mass terms; convergence by L1 delta.

Semantics: ranks over the *undirected-as-given* directed edge set; dangling
vertices (out-degree 0) redistribute their mass uniformly, the standard
convention, so ranks sum to 1.

Performance shape (the round-1 lesson): the whole window — edge append,
warm-start renormalization, and the fixpoint — is ONE jitted dispatch with
the carry buffers donated. The first build of this workload issued ~8 eager
device ops per window (``to_host`` → accumulator append → rank pad/where →
fixpoint), so per-dispatch overhead bounded the stream no matter how fast
the kernel was.
Early exit from the power iteration is a ``lax.while_loop`` over fixed
``chunk``-length ``lax.scan`` bodies: trip count stays data-dependent (no
wasted full-edge passes after convergence) but the executable is still one
program per (edge-capacity, vertex-capacity) bucket pair.
"""

from __future__ import annotations

import functools
from typing import Iterator, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.edgeblock import bucket_capacity
from ..summaries.groupfold import GroupFoldable


class PageRankEmission(NamedTuple):
    """Per-window emission. ``iterations``/``l1_delta`` are device scalars
    (sync on first read) so successive windows pipeline on device instead
    of blocking per emission; ``int()``/``float()`` them to materialize."""

    window: int
    num_vertices: int
    iterations: "jax.Array"
    l1_delta: "jax.Array"


@functools.lru_cache(maxsize=None)
def _make_pr_window_body(mesh, chunk: int, max_chunks: int):
    """Build the UN-jitted one-window fold ``step(carry, bsrc, bdst,
    n_edges0, n_new, n_seen, damping, tol) -> (carry, delta, iters)``.

    Shared verbatim by the per-window jit (:func:`_build_pr_step`) and
    the superbatch scan body (:func:`_build_pr_group_step`) so the two
    paths cannot drift — the group fold's value-identity contract
    (``summaries/groupfold.py``) rests on this being ONE function.

    One window = append + warm-start + chunked fixpoint, one dispatch.
    ``carry`` is ``(src, dst, ranks)`` device arrays at bucketed capacity,
    donated so the buffers are reused in place. ``bsrc``/``bdst`` are the
    window's padded block columns; only the first ``n_new`` entries are
    real — the padding is written into the carry too, but always beyond
    ``n_edges0 + n_new`` (the host guarantees edge capacity >= n_edges0 +
    block capacity) and masked out of every reduction, then overwritten by
    the next window's append.

    With ``mesh``, the fixpoint runs inside ``shard_map``: the edge
    columns split over the ``"edges"`` axis, each shard scatters its
    slice's rank messages into a replicated vertex table, and the
    partials ``psum`` over ICI per iteration (P1 + P3, the same shape as
    the CC engine's sharded fold). The while_loop trip count stays
    consistent across shards because every per-iteration decision reads
    post-psum (replicated) values.
    """
    if mesh is not None:
        from jax.sharding import PartitionSpec as P

        from ..parallel import comm
        from ..parallel.mesh import EDGE_AXIS

    def fixpoint(src, dst, mask, ranks, active, n, damping, tol,
                 axis_name=None):
        num_vertices = ranks.shape[0]
        m = mask.astype(ranks.dtype)
        ones = jnp.zeros(num_vertices, ranks.dtype).at[src].add(m)
        if axis_name is not None:
            ones = jax.lax.psum(ones, axis_name)
        out_deg = jnp.maximum(ones, 1.0)
        dangling = active & (ones == 0.0)

        def one_iter(r):
            contrib = jnp.where(mask, r[src] / out_deg[src], 0.0)
            new = jnp.zeros(num_vertices, r.dtype).at[dst].add(contrib)
            if axis_name is not None:
                new = jax.lax.psum(new, axis_name)
            dangling_mass = jnp.sum(jnp.where(dangling, r, 0.0))
            new = (1.0 - damping) / n + damping * (new + dangling_mass / n)
            new = jnp.where(active, new, 0.0)
            return new, jnp.abs(new - r).sum()

        # Early exit at chunk granularity: a while_loop whose body is a
        # fixed `chunk`-length scan with a converged-freeze flag. Data-
        # dependent trip count without per-iteration host sync; at most
        # chunk-1 frozen (wasted) passes after convergence.
        def scan_body(c, _):
            r, delta, iters, done = c
            new, dl = one_iter(r)
            r = jnp.where(done, r, new)
            delta = jnp.where(done, delta, dl)
            iters = iters + (~done).astype(jnp.int32)
            done = done | (dl <= tol)
            return (r, delta, iters, done), None

        def chunk_body(state):
            k, inner = state
            inner, _ = jax.lax.scan(scan_body, inner, None, length=chunk)
            return k + 1, inner

        def chunk_cond(state):
            k, (_, _, _, done) = state
            return (~done) & (k < max_chunks)

        init = (ranks, jnp.asarray(jnp.inf, ranks.dtype), jnp.int32(0),
                jnp.bool_(False))
        _, (ranks, delta, iters, _) = jax.lax.while_loop(
            chunk_cond, chunk_body, (jnp.int32(0), init)
        )
        return ranks, delta, iters

    def step(carry, bsrc, bdst, n_edges0, n_new, n_seen, damping, tol):
        src, dst, ranks = carry
        ecap = src.shape[0]
        num_vertices = ranks.shape[0]
        src = jax.lax.dynamic_update_slice(src, bsrc, (n_edges0,))
        dst = jax.lax.dynamic_update_slice(dst, bdst, (n_edges0,))
        n_edges = n_edges0 + n_new

        # Warm start: never-ranked active vertices enter at uniform mass,
        # then renormalize so the seen ranks sum to 1. (Padding slots stay
        # 0: the `active` mask keeps them out of teleport/dangling terms.)
        active = jnp.arange(num_vertices) < n_seen
        n = jnp.maximum(n_seen, 1).astype(ranks.dtype)
        ranks = jnp.where(active & (ranks == 0.0), 1.0 / n, ranks)
        ranks = ranks / jnp.maximum(ranks.sum(), 1e-30)
        mask = jnp.arange(ecap) < n_edges

        if mesh is None:
            ranks, delta, iters = fixpoint(
                src, dst, mask, ranks, active, n, damping, tol
            )
        else:
            def shard_fn(src_s, dst_s, mask_s, ranks, active, n, damping,
                         tol):
                return fixpoint(
                    src_s, dst_s, mask_s, ranks, active, n, damping, tol,
                    axis_name=EDGE_AXIS,
                )

            ranks, delta, iters = comm.shard_map(
                shard_fn, mesh,
                in_specs=(P(EDGE_AXIS), P(EDGE_AXIS), P(EDGE_AXIS),
                          P(), P(), P(), P(), P()),
                out_specs=(P(), P(), P()),
            )(src, dst, mask, ranks, active, n, damping, tol)
        return (src, dst, ranks), delta, iters

    return step


@functools.lru_cache(maxsize=None)
def _build_pr_step(mesh, chunk: int, max_chunks: int):
    """The jitted per-window step over the shared window body, carry
    donated (in-place HBM reuse; see :func:`_make_pr_window_body`)."""
    return jax.jit(
        _make_pr_window_body(mesh, chunk, max_chunks), donate_argnums=(0,)
    )


@functools.lru_cache(maxsize=None)
def _build_pr_group_step(mesh, chunk: int, max_chunks: int):
    """K window steps fused into ONE jitted ``lax.scan`` dispatch — the
    :class:`~gelly_streaming_tpu.summaries.groupfold.GroupFoldable`
    fold for PageRank, mirroring the engine's ``_superbatch_step``.

    ``superstep(carry, bsrc, bdst, n_edges0, n_new, n_seen, damping,
    tol)`` scans the shared window body over the stacked ``[K, cap]``
    block columns with per-window ``n_new``/``n_seen`` scalars riding
    the scan's xs and the edge watermark carried as a traced scalar
    (window k appends where windows < k left off — sequential window
    semantics preserved inside one dispatch). The carry is DONATED like
    the per-window step's; the stacked per-window ``(delta, iters)``
    outputs are fresh buffers backing the group's lazy emissions."""
    window_body = _make_pr_window_body(mesh, chunk, max_chunks)

    def superstep(carry, bsrc, bdst, n_edges0, n_new, n_seen, damping,
                  tol):
        def body(c, xs):
            cr, n_e = c
            bs, bd, nn, ns = xs
            cr, delta, iters = window_body(
                cr, bs, bd, n_e, nn, ns, damping, tol
            )
            return (cr, n_e + nn), (delta, iters)

        (carry, _n_end), (deltas, iters) = jax.lax.scan(
            body, (carry, n_edges0), (bsrc, bdst, n_new, n_seen)
        )
        return carry, deltas, iters

    return jax.jit(superstep, donate_argnums=(0,))


class IncrementalPageRank(GroupFoldable):
    """``run(stream)`` folds each window's edges into the carried graph and
    re-converges ranks from the previous fixpoint.

    ``max_iter`` bounds total power iterations per window (rounded up to a
    multiple of ``chunk``, the early-exit granularity).

    ``superbatch=K`` fuses K consecutive windows into ONE scanned
    dispatch (the :class:`GroupFoldable` declaration — the same
    small-window latency-cliff fix the engine and CC carries got in
    PR 2): the shared window body scans over the group's stacked
    columns with the rank/edge carry donated, per-window
    ``(iterations, l1_delta)`` surfacing as lazy device slices of the
    scan's stacked outputs. Emission VALUES are per-window identical
    (the per-window seen-vertex counts reconstruct exactly from the
    group encode — ``SuperbatchGroup.n_seen_per_window``); a group's K
    emissions surface together after its dispatch, and checkpoint
    barriers land on group boundaries (:meth:`checkpoint_granularity`).
    """

    def __init__(
        self,
        damping: float = 0.85,
        tol: float = 1e-6,
        max_iter: int = 100,
        chunk: int = 10,
        mesh=None,
        superbatch: int = 1,
    ):
        self.damping = damping
        self.tol = tol
        self.chunk = chunk
        self.max_chunks = max(1, -(-max_iter // chunk))
        #: optional device mesh: the per-window fixpoint shards the edge
        #: columns over the ``"edges"`` axis with per-iteration psum
        self.mesh = mesh
        #: ``superbatch="auto"``: the controller drives the fused path
        #: exactly like CC/bipartiteness — and because this carry's
        #: per-window cost is the fixpoint (which fusion cannot
        #: remove), the controller's JOB here is to hold K=1. That
        #: negative control is committed bench evidence
        #: (``BENCH_AUTOTUNE_CPU.json`` ``pagerank_hold`` cell): a
        #: controller that starts paying for fusion that buys nothing
        #: regresses a benchguard-watched cell.
        self.superbatch_auto = superbatch == "auto"
        if self.superbatch_auto:
            superbatch = 1
        elif isinstance(superbatch, str):
            raise ValueError(
                f'superbatch must be an int >= 1 or "auto", '
                f"got {superbatch!r}"
            )
        elif superbatch < 1:
            raise ValueError(f"superbatch must be >= 1, got {superbatch}")
        self.superbatch = int(superbatch)
        #: the live ControlPlane of an auto run (None otherwise) — same
        #: seam as ``SummaryAggregation.control``
        self.control = None
        self._step = _build_pr_step(mesh, self.chunk, self.max_chunks)
        self._group_step = None  # built on first group fold
        self._carry = None  # (src, dst, ranks) device arrays
        self._n_edges = 0  # host mirror of the append position
        self._vdict = None
        self._w = 0  # next emission's window index (run-scoped)
        #: carried seen-vertex watermark: ``max(restored, 1 + max compact
        #: id streamed so far)``. Derived from the STREAM's ids, not from
        #: ``len(vertex_dict)`` — the live dict runs ahead of consumption
        #: under prefetch/group packing (and a group-boundary checkpoint
        #: therefore restores an over-full dict), so dict length is not a
        #: per-window value; the id watermark is, for both dictionary
        #: kinds (sequential first-seen assignment / identity observe).
        self._n_seen = 0

    # ------------------------------------------------------------------ #
    def _ensure_capacity(self, block_cap: int, vcap: int) -> None:
        """Grow carry buffers (host-side, log-many times over the stream).

        Edge capacity must hold n_edges + the whole padded block so the
        in-step ``dynamic_update_slice`` never clamps into real edges.
        """
        # the sharded step splits the edge columns over the mesh's edge
        # axis: capacity must be divisible by (>= and pow2 covers) it
        min_cap = 8
        if self.mesh is not None:
            min_cap = max(min_cap, dict(self.mesh.shape).get("edges", 1))
        if self._carry is None:
            ecap = bucket_capacity(self._n_edges + block_cap, minimum=min_cap)
            self._carry = (
                jnp.zeros(ecap, jnp.int32),
                jnp.zeros(ecap, jnp.int32),
                jnp.zeros(vcap, jnp.float32),
            )
            return
        src, dst, ranks = self._carry
        ecap = bucket_capacity(self._n_edges + block_cap, minimum=min_cap)
        if ecap > src.shape[0]:
            grow = ecap - src.shape[0]
            src = jnp.pad(src, (0, grow))
            dst = jnp.pad(dst, (0, grow))
        if vcap > ranks.shape[0]:
            ranks = jnp.pad(ranks, (0, vcap - ranks.shape[0]))
        self._carry = (src, dst, ranks)

    def run(self, stream) -> Iterator[PageRankEmission]:
        self._vdict = stream.vertex_dict
        self._w = 0
        if self.superbatch > 1 or self.superbatch_auto:
            from ..summaries.groupfold import drive_group_folded

            yield from drive_group_folded(
                self, stream, self.superbatch,
                controller=self._attach_control(self.superbatch),
            )
            return
        for block in stream.blocks():
            yield self._one_window(block)

    def _attach_control(self, k: int):
        """The shared controller-attach rule (mirrors
        ``SummaryAggregation._attach_control`` — this class declares
        :class:`GroupFoldable` directly rather than through the
        aggregation base): None unless auto; a pre-set plane is
        honored; otherwise the stock default plane is built and kept
        on ``self.control``."""
        if not self.superbatch_auto:
            return None
        if self.control is None:
            from ..control import default_plane

            self.control = default_plane(k)
        return self.control

    def _one_window(self, block) -> PageRankEmission:
        """The per-window fold (shared by the plain run loop and the
        group-fold fallback for groups packed without column views)."""
        n_new = int(np.asarray(block.to_host()[0]).shape[0])
        cache = getattr(block, "_host_cache", None)
        if cache is not None and len(cache[0]):
            self._n_seen = max(
                self._n_seen,
                1 + int(max(cache[0].max(), cache[1].max())),
            )
        elif cache is None:
            # device-transformed block: no host ids to advance the
            # watermark from; the live dict is the only source
            self._n_seen = max(self._n_seen, len(self._vdict))
        n_seen = self._n_seen
        self._ensure_capacity(block.capacity, block.n_vertices)
        self._carry, delta, iters = self._step(
            self._carry, block.src, block.dst,
            jnp.int32(self._n_edges), jnp.int32(n_new),
            jnp.int32(n_seen), self.damping, self.tol,
        )
        self._n_edges += n_new
        w = self._w
        self._w += 1
        return PageRankEmission(w, n_seen, iters, delta)

    # ---- GroupFoldable declaration (summaries/groupfold.py) ---------- #
    def group_supported(self, group) -> bool:
        """The fused path needs the packer's host column views (the
        per-window seen-vertex watermark reconstructs from their compact
        ids); groups packed from pre-built blocks fall back."""
        return group.cols is not None

    def fold_group(self, group) -> Iterator[PageRankEmission]:
        """K windows as ONE scanned dispatch (see class docstring): pad
        the group's columns to one ``[K, wcap]`` stack, advance the
        carried seen-vertex watermark per member window, scan the shared
        window body with the carry donated, and emit the K per-window
        ``(iterations, l1_delta)`` as lazy device slices of the scan's
        stacked outputs."""
        from ..core.emission import iter_unstacked
        from ..obs import trace as _trace

        k = len(group)
        cols = group.cols
        lens = [len(c[0]) for c in cols]
        # per-window seen counts from the carried watermark + each
        # window's compact ids — exactly the per-window path's sequence
        # (SuperbatchGroup.n_seen_per_window applies the same rule from
        # the packer's side; the carried form survives checkpoint
        # restore, where the dict itself may have run ahead)
        n_seen_w = []
        n = self._n_seen
        for s, d, _v in cols:
            if len(s):
                n = max(n, 1 + int(max(s.max(), d.max())))
            n_seen_w.append(n)
        self._n_seen = n
        wmin = 8
        if self.mesh is not None:
            wmin = max(wmin, dict(self.mesh.shape).get("edges", 1))
        wcap = bucket_capacity(max(lens), minimum=wmin)
        total_new = int(sum(lens))
        # edge capacity must hold every member window's padded append:
        # the LAST window writes [wcap] at n_edges + (total_new - its
        # own length), the deepest offset of the group
        self._ensure_capacity(
            total_new - lens[-1] + wcap, group.n_vertices
        )
        bsrc = np.zeros((k, wcap), np.int32)
        bdst = np.zeros((k, wcap), np.int32)
        for i, (s, d, _v) in enumerate(cols):
            bsrc[i, : lens[i]] = s
            bdst[i, : lens[i]] = d
        if self._group_step is None:
            self._group_step = _build_pr_group_step(
                self.mesh, self.chunk, self.max_chunks
            )
        with _trace.span(
            "pagerank.group",
            {"k": k, "edges": total_new,
             "n_vertices": int(group.n_vertices)}
            if _trace.on() else None,
        ):
            self._carry, deltas, iters = self._group_step(
                self._carry, jnp.asarray(bsrc), jnp.asarray(bdst),
                jnp.int32(self._n_edges),
                jnp.asarray(np.asarray(lens, np.int32)),
                jnp.asarray(np.asarray(n_seen_w, np.int32)),
                self.damping, self.tol,
            )
        self._n_edges += total_new
        w0 = self._w
        self._w += k
        for i, (delta_i, iters_i) in enumerate(
            iter_unstacked((deltas, iters), k)
        ):
            yield PageRankEmission(
                w0 + i, int(n_seen_w[i]), iters_i, delta_i
            )

    def fold_group_fallback(self, group) -> Iterator[PageRankEmission]:
        """Per-window fold of a group without usable column views —
        correctness never depends on how a group was packed. Cache-less
        (device-transformed) blocks carry no host ids, so their seen
        count falls back to the live dict, which may run AHEAD of
        consumption under the drive loop's group prefetch — the same
        documented looseness every prefetched per-window stream has
        (``SimpleEdgeStream.prefetched``); streams that need exact
        per-window teleport mass keep host column views."""
        for block in group.blocks():
            yield self._one_window(block)

    def sync(self) -> None:
        """Block until the carried (edges, ranks) device state is complete
        — the end-of-stream barrier for throughput timing."""
        jax.block_until_ready(self._carry)

    # ------------------------------------------------------------------ #
    @property
    def _ranks(self):
        """Rank vector (or None before the first window) — kept as a
        property for checkpoint/test compatibility with the round-1 class."""
        return None if self._carry is None else self._carry[2]

    def state_dict(self) -> dict:
        """Checkpoint surface (``aggregate/checkpoint.py:save_workload``).
        The vertex dictionary is saved alongside by ``save_workload``."""
        if self._carry is None:
            return {"edges": {"src": np.zeros(0, np.int32),
                              "dst": np.zeros(0, np.int32)},
                    "ranks": None}
        src, dst, ranks = self._carry
        n = self._n_edges
        return {
            "edges": {"src": np.asarray(src)[:n], "dst": np.asarray(dst)[:n]},
            "ranks": np.asarray(ranks),
            "n_seen": int(self._n_seen),
        }

    def load_state_dict(self, d: dict) -> None:
        if d["ranks"] is None:
            self._carry = None
            self._n_edges = 0
            self._n_seen = 0
            return
        s = np.asarray(d["edges"]["src"], np.int32)
        t = np.asarray(d["edges"]["dst"], np.int32)
        self._n_edges = len(s)
        ecap = bucket_capacity(self._n_edges)
        ranks = np.asarray(d["ranks"], np.float32)
        # legacy checkpoints predate the carried watermark: every seen
        # vertex holds strictly positive mass after a fixpoint (teleport
        # term), padding slots hold exactly 0 — the count reconstructs
        self._n_seen = int(d.get("n_seen", np.count_nonzero(ranks)))
        self._carry = (
            jnp.asarray(np.pad(s, (0, ecap - len(s)))),
            jnp.asarray(np.pad(t, (0, ecap - len(t)))),
            jnp.asarray(ranks),
        )

    # ---- serving surface (serving/server.py Servable contract) ------- #
    def servable(self, vdict=None) -> "RankServable":
        """Adapter publishing the rank vector per window for
        ``RankQuery`` point lookups. Unlike the CC/degree carries, the
        PageRank step DONATES its carry buffers (the published array
        would be invalidated by the next window's dispatch), so the
        adapter snapshots ranks with one device-side copy per window."""
        return RankServable(self, vdict)

    def ranks(self) -> dict:
        """Current (raw vertex id -> rank), seen vertices only."""
        if self._carry is None:
            return {}
        n = len(self._vdict)
        r = np.asarray(self._carry[2])[:n]
        raw = self._vdict.decode(np.arange(n))
        return {int(v): float(x) for v, x in zip(raw, r)}


class RankServable:
    """:class:`~gelly_streaming_tpu.serving.server.Servable` adapter for
    :class:`IncrementalPageRank`. The window step donates its carry, so
    each published snapshot is ``jnp.copy`` of the rank vector — one
    device-side copy per window; readers must never hold a donated
    buffer (accessing it after the next dispatch raises). With
    ``superbatch=K`` a group's K emissions surface together, so all K
    publishes copy the END-of-group ranks and snapshots advance at
    group granularity (the CCServable caveat; run ``superbatch=1`` for
    per-window snapshot pinning)."""

    def __init__(self, workload: IncrementalPageRank, vdict=None):
        from ..serving import RankQuery

        self.query_classes = (RankQuery,)
        self._workload = workload
        self._vdict = vdict

    def payloads(self, stream):
        pr = self._workload
        vdict = stream.vertex_dict
        self._vdict = vdict
        for _ in pr.run(stream):
            yield (
                {"ranks": jnp.copy(pr._carry[2]), "vdict": vdict},
                pr._n_edges,
            )

    def boot_payload(self):
        pr = self._workload
        if pr._carry is None or self._vdict is None:
            return None
        return (
            {"ranks": jnp.copy(pr._carry[2]), "vdict": self._vdict},
            pr._n_edges,
        )
