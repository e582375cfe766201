"""The summary-aggregation engine: per-window fold + cross-shard combine +
carried global summary.

TPU-native re-design of the reference's L3 engine (``SummaryAggregation.java``,
``SummaryBulkAggregation.java``, ``SummaryTreeReduce.java``). The reference's
dataflow per window:

    stamp partition -> keyBy -> per-partition window fold(updateFun)
    -> timeWindowAll -> reduce(combineFun) -> Merger (parallelism 1,
    running summary, ListCheckpointed) -> optional transform

Here the same roles map to:

    shard the window's EdgeBlock over the mesh edge axis
    -> per-shard ``update`` from ``initial_state`` (the window fold)
    -> cross-shard ``combine`` via collectives (flat stack-and-fold for the
       bulk engine; log2(p) ppermute butterfly for the tree engine)
    -> host-carried running summary combined per window (the Merger)
    -> ``transform`` for emission.

Differences, by design (SURVEY.md §7 "semantic deltas"): the Merger emits
per *window*, not per incoming partial; every shard holds the global result
after the collective (the reference funnels to one subtask).

Subclasses supply the five state hooks (initial/update/combine/grow/
transform); ``device=False`` marks host-state aggregations (spanner,
matching) whose update/combine run on host records instead of device arrays.

Checkpoint surface (the reference's only fault-tolerance hook — ``Merger
implements ListCheckpointed``, ``SummaryAggregation.java:127-135``):
``snapshot_state()`` / ``restore_state()`` capture and restore the running
summary; see ``aggregate/checkpoint.py`` for (de)serialization.
"""

from __future__ import annotations

import abc
from typing import Any, Callable, Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..core.edgeblock import EdgeBlock, StackedEdgeBlock
from ..obs import trace as _trace
from ..obs.registry import get_registry
from ..parallel import comm
from ..parallel.mesh import EDGE_AXIS, vertex_shards
from ..summaries.groupfold import GroupFoldable, drive_group_folded
from jax.sharding import PartitionSpec as P


#: Compiled window-step executables shared across aggregation instances,
#: keyed by (step_cache_key(), vcap, mesh, tree-ness). Compiling the fused
#: window program costs seconds; a fresh aggregation object
#: per stream must not pay it again. Bounded FIFO: each cached closure
#: pins the aggregation instance it was built from (and thereby one
#: summary pytree), so unbounded growth would leak device arrays across
#: vcap buckets.
_STEP_CACHE: dict = {}
_STEP_CACHE_MAX = 16


def _step_cache_put(key, fn) -> None:
    if len(_STEP_CACHE) >= _STEP_CACHE_MAX:
        _STEP_CACHE.pop(next(iter(_STEP_CACHE)))
    _STEP_CACHE[key] = fn


class SummaryAggregation(GroupFoldable, abc.ABC):
    """Abstract engine config (``SummaryAggregation.java:22-137``).

    Parameters
    ----------
    transient_state:
        When True the running summary resets after each emission
        (``SummaryAggregation.java:113-115``).
    mesh:
        Optional ``jax.sharding.Mesh`` with an ``"edges"`` axis; falls back
        to the stream context's mesh, else single-device execution.
    superbatch:
        Fuse this many consecutive windows into ONE jitted dispatch — a
        ``lax.scan`` over a ``[K, cap]``
        :class:`~gelly_streaming_tpu.core.edgeblock.StackedEdgeBlock` —
        instead of K separate window steps. Amortizes the per-window
        fixed cost (host block assembly + dispatch) that dominates below
        ~64k-edge windows (the BENCH_CPU latency cliff: 714k eps at
        1024-edge windows vs 15.5M at 1M). Emission SEQUENCE is
        unchanged (one record per window, same values); emission TIMING
        batches — the K records of a superbatch surface together after
        its single dispatch, and the stacked per-window summaries cost
        K x summary bytes of device memory while their lazy emissions
        are live. ``1`` (default) keeps the per-window path.

    Contract for the state hooks (initial/update/combine): they must be
    pure functions of their arguments for a given constructor
    configuration. Subclasses whose constructor parameters change hook
    behavior declare them in ``config_fields`` — the default
    :meth:`step_cache_key` hashes those attribute values, so two
    differently-configured instances of one class can never silently
    share a compiled step (round-2 verdict #9 / advisor finding).
    """

    #: False for host-state aggregations (update/combine get host edge arrays)
    device: bool = True

    #: names of instance attributes whose values change the behavior of
    #: initial_state/update/combine/transform; hashed into the step-cache
    #: key. Values must be hashable.
    config_fields: tuple = ()

    def __init__(self, transient_state: bool = False, mesh=None,
                 superbatch=1):
        self.transient_state = transient_state
        self.mesh = mesh
        #: ``superbatch="auto"``: the run loop drives the fused-group
        #: path under an :class:`~gelly_streaming_tpu.control.AutoK`
        #: controller — K starts at 1 and is re-tuned at group
        #: boundaries from measured group throughput (+ span ratios
        #: when obs is on), with hysteresis and bounded steps;
        #: ``self.superbatch`` then tracks the LIVE operating point.
        self.superbatch_auto = superbatch == "auto"
        if self.superbatch_auto:
            superbatch = 1
        elif isinstance(superbatch, str):
            # a mistyped mode must fail with the accepted values, not
            # with an unrelated str-vs-int comparison TypeError below
            raise ValueError(
                f'superbatch must be an int >= 1 or "auto", '
                f"got {superbatch!r}"
            )
        elif superbatch < 1:
            raise ValueError(f"superbatch must be >= 1, got {superbatch}")
        self.superbatch = int(superbatch)
        #: the live ControlPlane of an auto run (None otherwise); tests
        #: and the bench read its AutoK history as retune evidence
        self.control = None
        self._summary = None
        self._vcap = 0
        self._sync_ref = None  # last dispatched window state (sync target)
        # run-loop context for the declared group fold (set by the
        # superbatched drive loops before drive_group_folded delegates
        # back into fold_group)
        self._gf_mesh = None
        self._gf_vdict = None
        #: whether the last superbatch dispatch DONATED the carried
        #: summary (in-place HBM update). Consumers that publish live
        #: carry buffers (``CCServable._payload``) read this to know
        #: they must copy — a published alias would be invalidated by
        #: the next group's dispatch.
        self._donated_carry = False

    def step_cache_key(self):
        """Hashable identity of the compiled window step (see class doc)."""
        return (type(self),) + tuple(
            getattr(self, f) for f in self.config_fields
        )

    # ------------------------------------------------------------------ #
    # State protocol (the updateFun / combineFun / transform slots)
    # ------------------------------------------------------------------ #
    @abc.abstractmethod
    def initial_state(self, vcap: int) -> Any:
        """Fresh per-window fold state (the ``initialValue`` analog)."""

    def grow_state(self, state: Any, old_vcap: int, new_vcap: int) -> Any:
        """Re-size carried state when the vertex capacity bucket grows."""
        raise NotImplementedError(
            f"{type(self).__name__} must implement grow_state to stream "
            "beyond its initial vertex capacity"
        )

    @abc.abstractmethod
    def update(self, state: Any, src, dst, val, mask) -> Any:
        """Fold one (shard of a) window into the state (``EdgesFold`` role).

        Device aggregations receive device arrays; host aggregations receive
        numpy arrays with padding already stripped.
        """

    @abc.abstractmethod
    def combine(self, a: Any, b: Any) -> Any:
        """Associative merge of two states (``combineFun`` role)."""

    def transform(self, state: Any, vdict) -> Any:
        """Map the running summary to the emitted record (optional)."""
        return state

    # ------------------------------------------------------------------ #
    # Engine
    # ------------------------------------------------------------------ #
    def _resolve_mesh(self, stream):
        mesh = self.mesh if self.mesh is not None else stream.get_context().mesh
        if mesh is None:
            return None
        if vertex_shards(mesh) > 1:
            return self._vertex_sharded_mesh(mesh)
        if EDGE_AXIS not in mesh.shape or mesh.shape[EDGE_AXIS] == 1:
            return None
        return mesh

    def _vertex_sharded_mesh(self, mesh):
        """The mesh a run takes when its ``vertices`` axis is above 1.
        Only an aggregation whose carry is laid out over that axis
        overrides this (the CC forest); every other vertex table is
        replicated, and running it on one chip of four in silence would
        be the wrong answer to a mesh that asks for sharded state."""
        raise NotImplementedError(
            f"{type(self).__name__} has no vertex-sharded carry: the "
            "`vertices` mesh axis shards the ConnectedComponents pointer "
            "forest only (summaries/forest.py TableOps); this "
            "aggregation's vertex table is replicated"
        )

    def _make_partial_fn(self, vcap: int, mesh) -> Callable:
        """Build the traced one-window fold: per-shard ``update`` from
        ``initial_state`` + cross-shard combine. Shared by the per-window
        step and the superbatch scan body so the two paths cannot drift."""
        p = mesh.shape[EDGE_AXIS] if mesh is not None else 1
        tree = self._is_tree()
        # a fan-in the mesh cannot honor degrades to 2 with a warning
        # (reference posture; see SummaryTreeReduce docstring). Only
        # the tree engine runs the butterfly — resolving for bulk
        # aggregations would warn about a collective they never run.
        degree = (
            comm.resolve_tree_degree(p, getattr(self, "degree", 2))
            if tree and mesh is not None else 2
        )

        def partial_fn(src, dst, val, mask):
            init = self.initial_state(vcap)
            if mesh is None:
                return self.update(init, src, dst, val, mask)

            def shard_fn(src, dst, val, mask):
                part = self.update(init, src, dst, val, mask)
                if tree:
                    return comm.tree_all_reduce(
                        part, EDGE_AXIS, self.combine, p, degree=degree,
                    )
                return jax.tree.map(lambda x: x[None], part)

            in_specs = (
                P(EDGE_AXIS), P(EDGE_AXIS), P(EDGE_AXIS), P(EDGE_AXIS)
            )
            out_specs = jax.tree.map(
                lambda _: P() if tree else P(EDGE_AXIS), init
            )
            out = comm.shard_map(shard_fn, mesh, in_specs, out_specs)(
                src, dst, val, mask
            )
            # bulk: stacked shard partials -> log-depth reduction
            # (the timeWindowAll gather analog)
            return out if tree else comm.stacked_reduce(out, p, self.combine)

        return partial_fn

    def _window_step(self, summary: Any, block: EdgeBlock, vcap: int, mesh) -> Any:
        """One window's full pipeline — per-shard fold, cross-shard combine,
        Merger merge — as ONE jitted dispatch (the keyBy->fold->reduce->
        Merger chain). Single-dispatch matters twice: host round trips
        never interleave the device pipeline, and successive windows
        overlap via async dispatch."""
        cache_key = (self.step_cache_key(), vcap, mesh, self._is_tree())
        step_fn = _STEP_CACHE.get(cache_key)
        if step_fn is None:
            partial_fn = self._make_partial_fn(vcap, mesh)

            def step(summary, src, dst, val, mask):
                return self.combine(summary, partial_fn(src, dst, val, mask))

            step_fn = jax.jit(step)
            _step_cache_put(cache_key, step_fn)
        # span measures DISPATCH (enqueue) time, not device compute —
        # the async-dispatch contract sync() documents; compile time
        # shows up as a fat first span, which is itself worth seeing
        with _trace.span(
            "engine.dispatch",
            {"vcap": vcap, "edges_capacity": int(block.capacity)}
            if _trace.on() else None,
        ):
            return step_fn(
                summary, block.src, block.dst, block.val, block.mask
            )

    def _superbatch_step(
        self, summary: Any, sblock: StackedEdgeBlock, vcap: int, mesh
    ) -> tuple:
        """K window steps as ONE jitted ``lax.scan`` over the stacked
        axis. Returns ``(carry, ys)``: the carried summary after all K
        windows, and the stacked per-window summaries ``[K, ...]`` that
        back the group's lazy emissions. ``transient_state`` resets the
        carry to a fresh ``initial_state`` INSIDE the scan (the per-yield
        reset of the per-window path, fused).

        The carried summary is DONATED to the dispatch when the backend
        supports donation and no mesh is involved: successive superbatches
        then update HBM state in place instead of allocating a fresh
        buffer per dispatch. Safe because the group's emissions reference
        ``ys`` (fresh buffers), never the donated carry, and the engine
        re-aims ``_summary``/``_sync_ref`` at the new carry immediately.
        """
        # ONE donation decision feeds the compiled donate_argnums, the
        # instance flag consumers read (see __init__), and the obs
        # evidence — computed once so they can never disagree
        donated = mesh is None and jax.default_backend() != "cpu"
        cache_key = ("superbatch", self.step_cache_key(), vcap,
                     sblock.capacity, sblock.k, mesh, self._is_tree(),
                     self.transient_state)
        step_fn = _STEP_CACHE.get(cache_key)
        if step_fn is None:
            partial_fn = self._make_partial_fn(vcap, mesh)
            transient = self.transient_state

            def superstep(summary, src, dst, val, mask):
                def body(carry, xs):
                    s, d, v, m = xs
                    new = self.combine(carry, partial_fn(s, d, v, m))
                    nxt = self.initial_state(vcap) if transient else new
                    return nxt, new

                return lax.scan(body, summary, (src, dst, val, mask))

            step_fn = jax.jit(
                superstep, donate_argnums=(0,) if donated else ()
            )
            _step_cache_put(cache_key, step_fn)
        self._donated_carry = donated
        if _trace.on():
            if donated:
                get_registry().counter("engine.donated_dispatches").inc()
            sp = _trace.span(
                "engine.superbatch_dispatch",
                {"k": int(sblock.k), "capacity": int(sblock.capacity),
                 "vcap": vcap, "donated": donated},
            )
        else:
            sp = _trace.NOOP_SPAN
        with sp:
            return step_fn(
                summary, sblock.src, sblock.dst, sblock.val, sblock.mask
            )

    def _is_tree(self) -> bool:
        return False

    def checkpoint_granularity(self) -> int:
        """Window stride at which the carried summary is observable — 1
        on the per-window path, ``superbatch`` when :meth:`run` will
        actually take the fused-group path. Checkpoint drivers
        (``aggregate/autockpt.py``) align barriers to this so a
        mid-group snapshot can never pair an end-of-group summary with
        a mid-group window count; subclasses whose run loop opts out of
        superbatching under extra conditions override it (the CC mixin
        does for ``transient_state``). Under ``superbatch="auto"`` this
        reports the LIVE operating K — barrier drivers align exactly
        through :meth:`~gelly_streaming_tpu.summaries.groupfold.GroupFoldable.checkpoint_aligned`,
        which tracks the variable group boundaries themselves."""
        if self.device and (self.superbatch > 1 or self.superbatch_auto):
            return max(1, self.superbatch)
        return 1

    def _device_block(self, block: EdgeBlock, mesh) -> None:
        """Grow + fold one block into the carried summary (the device
        branch of :meth:`run`, extracted so subclasses with a custom run
        loop — e.g. the forest-carry CC — can fall back to it)."""
        vcap = block.n_vertices
        if self._summary is None:
            self._vcap = vcap
            self._summary = self.initial_state(vcap)
        elif vcap > self._vcap:
            self._summary = self.grow_state(self._summary, self._vcap, vcap)
            self._vcap = vcap
        self._summary = self._window_step(self._summary, block, vcap, mesh)

    def run(self, stream) -> Iterator[Any]:
        """Drive the aggregation over the stream's windows
        (``SummaryAggregation.run`` / ``SummaryBulkAggregation.java:68-90``).

        With ``superbatch=K > 1`` (device aggregations only), K
        consecutive windows run as one fused ``lax.scan`` dispatch and
        still yield one record per window with identical values — only
        the records of a group surface together, after its dispatch.
        CHECKPOINT GRANULARITY under superbatching: the carried summary
        is only observable on superbatch boundaries (mid-group states
        exist solely as stacked emission rows), so checkpoint barriers
        must land on multiples of K —
        :class:`~gelly_streaming_tpu.aggregate.autockpt.AutoCheckpoint`
        aligns its ``every`` to the work's
        :meth:`checkpoint_granularity` automatically; manual
        ``snapshot_state()`` calls between a group's yields capture the
        END-of-group summary, not the mid-group window's. Vertex
        capacity growth likewise quantizes to group boundaries (see
        :meth:`_fold_group_states`). Feed the loop
        with a prefetched stream whose depth covers a full group
        (:func:`~gelly_streaming_tpu.core.pipeline.superbatch_prefetch_depth`)
        so the host assembles superbatch N+1 while the device scans N.
        """
        mesh = self._resolve_mesh(stream) if self.device else None
        vdict = stream.vertex_dict
        if self.device and (self.superbatch > 1 or self.superbatch_auto):
            yield from self._run_superbatched(stream, mesh, vdict)
            return
        for block in stream.blocks():
            if self.device:
                self._device_block(block, mesh)
            else:
                src, dst, val = block.to_host()
                raw_s = vdict.decode(src)
                raw_d = vdict.decode(dst)
                if self._summary is None:
                    self._summary = self.initial_state(0)
                partial = self.update(
                    self.initial_state(0), raw_s, raw_d, val, None
                )
                self._summary = self.combine(self._summary, partial)
            self._sync_ref = self._summary
            yield self.transform(self._summary, vdict)
            if self.transient_state:
                self._summary = (
                    self.initial_state(self._vcap) if self.device else self.initial_state(0)
                )

    def _run_superbatched(self, stream, mesh, vdict) -> Iterator[Any]:
        """The fused-group drive loop — the engine's
        :class:`~gelly_streaming_tpu.summaries.groupfold.GroupFoldable`
        declaration driven by the shared
        :func:`~gelly_streaming_tpu.summaries.groupfold.drive_group_folded`
        loop (groups from the stream's packer, prefetched one ahead so
        the host assembles superbatch N+1 while the device scans N).
        ``superbatch="auto"`` attaches a fresh
        :class:`~gelly_streaming_tpu.control.ControlPlane` (AutoK +
        adaptive group prefetch over one SignalReader) and lets the
        drive loop re-tile at group boundaries."""
        self._gf_mesh = mesh
        self._gf_vdict = vdict
        yield from drive_group_folded(
            self, stream, self.superbatch,
            controller=self._attach_control(self.superbatch),
        )

    def _attach_control(self, k: int):
        """The ONE ``superbatch="auto"`` controller-attach rule for
        every group-folded run loop (engine, CC, bipartiteness): None
        unless auto; a pre-set plane is honored (the injection seam —
        pin the knob via ``AutoK(k0=K, k_max=K)``, or share one
        SignalReader across loops); otherwise the stock
        :func:`~gelly_streaming_tpu.control.default_plane` is built
        and kept on ``self.control``."""
        if not self.superbatch_auto:
            return None
        if self.control is None:
            from ..control import default_plane

            self.control = default_plane(k)
        return self.control

    def fold_group(self, group) -> Iterator[Any]:
        """The engine's declared group fold (see
        :class:`~gelly_streaming_tpu.summaries.groupfold.GroupFoldable`):
        one fused scan over the group's stacked block, per-window
        summaries unstacked lazily. Supports EVERY group — device-
        transformed members dispatch on the device stack."""
        for state in self._fold_group_states(group, self._gf_mesh):
            yield self.transform(state, self._gf_vdict)

    def _fold_group_states(self, group, mesh) -> Iterator[Any]:
        """Grow + fold one :class:`SuperbatchGroup` through the fused
        scan, yielding the K per-window summary states (shared by the
        engine loop and the CC mixin's dense group path).

        Capacity growth quantizes to GROUP boundaries here: a group
        whose windows grow the vertex table folds (and emits) every
        window at the group's FINAL capacity — scatter-style summaries
        are value-identical on the shared prefix with initial-state
        tails, but an aggregation whose update/transform depends on the
        table SIZE itself observes the quantized capacity one group
        early. Per-window growth semantics need the per-window path."""
        from ..core.emission import iter_unstacked

        vmax = max(1, group.n_vertices)
        if self._summary is None:
            self._vcap = vmax
            self._summary = self.initial_state(self._vcap)
        elif vmax > self._vcap:
            self._summary = self.grow_state(self._summary, self._vcap, vmax)
            self._vcap = vmax
        carry, ys = self._superbatch_step(
            self._summary, group.stacked(), self._vcap, mesh
        )
        # the carry IS the post-reset summary under transient_state
        # (the scan body resets it), so one assignment serves both
        self._summary = carry
        self._sync_ref = carry
        yield from iter_unstacked(ys, len(group))

    def sync(self) -> None:
        """Block until the carried summary's device work completes — the
        end-of-stream barrier. The aggregate loop only DISPATCHES async
        device steps; anyone timing throughput (bench.py does) must call
        this inside the timed region, or they measure an enqueue rate.
        Per-window emissions stay async/lazy either way. Also blocks the
        last DISPATCHED window state: with ``transient_state`` the run
        loop resets ``_summary`` to a fresh initial state after each
        yield, which would otherwise make this a silent no-op barrier."""
        jax.block_until_ready((self._summary, self._sync_ref))

    # ------------------------------------------------------------------ #
    # Checkpoint surface (ListCheckpointed analog)
    # ------------------------------------------------------------------ #
    def snapshot_state(self) -> Any:
        """The running summary, as a host pytree
        (``SummaryAggregation.java:127-130`` snapshotState)."""
        return jax.tree.map(np.asarray, self._summary)

    def infer_vcap(self, state: Any) -> int:
        """Vertex capacity implied by a state pytree (override when the
        leading dim is not the base vertex capacity, e.g. double covers)."""
        leaves = jax.tree.leaves(state)
        return int(leaves[0].shape[0]) if leaves else 0

    def restore_state(self, state: Any, vcap: Optional[int] = None) -> None:
        """Restore a summary captured by :meth:`snapshot_state`
        (``SummaryAggregation.java:132-135`` restoreState)."""
        self._summary = jax.tree.map(jnp.asarray, state) if self.device else state
        if vcap is not None:
            self._vcap = vcap
        elif self.device:
            self._vcap = self.infer_vcap(self._summary)


class SummaryBulkAggregation(SummaryAggregation):
    """Flat-combine engine (``SummaryBulkAggregation.java:51-131``):
    per-shard fold, then a stack-and-fold global combine — the analog of the
    ``timeWindowAll`` gather + reduce + Merger tail."""

    def _is_tree(self) -> bool:
        return False


class SummaryTreeReduce(SummaryAggregation):
    """Tree-combine engine (``SummaryTreeReduce.java:47-160``): the shard
    partials merge through a ``log_degree(p)``-round ppermute butterfly
    (:func:`gelly_streaming_tpu.parallel.comm.tree_all_reduce`), the ICI
    equivalent of ``enhance()``'s recursive parallelism reduction
    (``SummaryTreeReduce.java:95-123``).

    ``degree`` here GENERALIZES the reference rather than mirroring it:
    the reference's ``degree`` sets the partial-aggregation parallelism
    (``setParallelism(degree)``) while ``enhance()``'s fan-in is fixed
    at 2 (``key = f0/2``, ``nextParal = p/2``); the butterfly promotes
    it to a true tree fan-in — higher degrees run fewer collective
    rounds with more combines per round. A degree the mesh edge axis
    cannot honor (the axis size must be a power of the fan-in) degrades
    to the degree-2 butterfly with a warning, matching the reference's
    warn-and-run posture for non-conforming degrees
    (:func:`~gelly_streaming_tpu.parallel.comm.resolve_tree_degree`).
    The combine must be commutative as well as associative — all engine
    workloads' join-semilattice merges are."""

    #: degree changes the compiled collective program
    config_fields: tuple = ("degree",)

    def __init__(self, transient_state: bool = False, mesh=None,
                 degree: int = 2, superbatch: int = 1):
        super().__init__(transient_state=transient_state, mesh=mesh,
                         superbatch=superbatch)
        if degree < 2:
            raise ValueError(f"degree must be >= 2, got {degree}")
        self.degree = degree

    def _is_tree(self) -> bool:
        return True
