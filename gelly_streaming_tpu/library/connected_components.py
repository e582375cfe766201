"""Streaming Connected Components — the flagship workload.

TPU-native re-design of ``library/ConnectedComponents.java:41-126``: the
reference folds each edge into a per-partition ``DisjointSet`` (``UpdateCC``)
and merges partials smaller-into-larger (``CombineCC``).

Three carries implement that contract here (``carry=`` constructor
option, default ``"auto"``):

- **Forest carry** (auto default with an accelerator attached): a pointer
  forest ``canon[vcap]`` updated by window-local kernels — host-computed
  touched set, root chase, T-sized local fixpoint, one masked scatter
  (``summaries/forest.py``). Per-window cost scales with the WINDOW, the
  reference's cost shape (``SummaryBulkAggregation.java:76-80``), not
  with the vertex capacity; chains canonicalize lazily at emission or
  checkpoint. This is the round-5 answer to the measured V-bound of the
  dense path (BENCH_CPU r4: 0.45x the compiled baseline at 1M windows).
  Under a sharded mesh the T-sized local fixpoint runs as the engine's
  fold+combine shape — per-shard folds over the edge columns, label
  tables merged by the bulk stack or the degree-d butterfly — so the
  vcap-sized carry never crosses the mesh. Under a ``vertices`` mesh
  axis above 1 the forest itself is split by rows, one contiguous block
  a chip (``summaries/forest.py`` ``TableOps``): the same step, the
  published ``labels`` is the sharded array, and nothing gathers it.
  That layout runs the forest carry only (``"auto"`` resolves to it)
  and refuses the host and dense carries, superbatching and an
  ``edges`` axis above 1.
- **Host carry** (auto default on a CPU backend): the native incremental
  union-find (``native/ingest.cpp: cuf_*``) folds each window beside the
  parser and the device keeps a pointer-forest MIRROR updated by one
  O(touched) scatter. Union-find is control flow, not math — the P6
  "centralized sequential" placement (SURVEY.md §2.5), same rationale as
  the matching/spanner host paths. Emission/checkpoint are identical to
  the forest carry (the mirror IS a forest).
- **Dense labels** (``summaries/labels.py``): full-table min-label
  fixpoint + pointer-graph combine. Used for device-transformed streams
  whose compact columns never exist on host (the windowed carries'
  touched set is host-computed) and on explicit ``carry="dense"``. A
  stream can downgrade to dense mid-run (either carry canonicalizes to
  flat labels); it never needs to upgrade back.

Emission converts either carry to a
:class:`~gelly_streaming_tpu.summaries.labels.Components` view (the
``DisjointSet`` stand-in); checkpoints always store canonical flat labels
+ touched, so the two carries share one checkpoint format.

``component_sizes=True`` carries a second int32 table, ``sizes``, beside
the forest and folds it in the window's own step (``summaries/forest.py``
``fold_sizes``: one gather, one sum in fast memory and one sorted
scatter, all window-sized), so that ``ComponentSizeQuery`` is one root
chase and one gather out of the snapshot ``labels`` came in. Semantics,
cost and what it refuses: :class:`ConnectedComponents`.

``superbatch=K`` fuses K consecutive windows into one dispatch on every
carry (the small-window latency-cliff fix, ISSUE 2): the forest carry
runs a group-local fused fold (one vcap-sized chase/commit per GROUP,
scan over window-sized label tables), the host carry folds the group in
ONE native call (``cuf_fold_group``) with one batched mirror commit,
and dense mode scans the group's stacked block through the generic
engine. Emission VALUES are per-window identical (equivalence-tested);
a group's K records surface together after its dispatch, mid-group
snapshots reconstruct lazily on first read, and checkpoint barriers
land on group boundaries (see ``aggregate/autockpt.py``).
``transient_state`` keeps the per-window loop (its carry reset is
window-granular by definition).

Usage parity with the reference::

    for comps in stream.aggregate(ConnectedComponents()):
        print(comps)   # {1=[1, 2, 3, 5], 6=[6, 7], 8=[8, 9]}
"""

from __future__ import annotations

from typing import Any, Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..aggregate.summary import SummaryBulkAggregation, SummaryTreeReduce
from ..obs import trace as _trace
from ..parallel.mesh import vertex_sharding, vertex_shards
from ..summaries.forest import (
    MirrorReplay,
    TouchLog,
    WindowPrep,
    forest_superbatch,
    forest_window,
    grow_forest,
    grow_sizes,
    init_forest,
    init_sizes,
    mirror_update,
    resolve_flat,
    resolve_flat_host,
    sized_layout,
    vertex_layout,
)
from ..summaries.labels import (
    Components,
    cc_fold,
    grow_labels,
    init_labels,
    label_combine,
)


def _validate_min_rooted(lab: np.ndarray) -> None:
    """Reject labels violating the min-rooted invariant (mirroring
    ``cuf_load``): a corrupt table with ``label[v] > v`` would spin
    ``resolve_flat_host``/``resolve_flat`` (and the serving root chase)
    forever instead of failing fast."""
    iota = np.arange(len(lab), dtype=lab.dtype)
    if np.any(lab > iota) or np.any(lab < 0):
        raise ValueError(
            "restored labels are not a min-rooted forest "
            "(label[v] must be in [0, v])"
        )


def _auto_carry() -> str:
    """Pick the windowed-ingest carry for this process.

    ``host`` — the native incremental union-find beside the parser with a
    device pointer-forest mirror (one O(touched) scatter per window).
    Union-find is the one graph kernel that is control flow, not math: on
    a CPU backend the XLA path would re-do scalar pointer chasing as
    vector passes, so the P6 "centralized sequential" placement
    (SURVEY.md §2.5, same rationale as matching/spanner host paths) wins
    outright — measured 2.1x the compiled hash-map baseline where the
    dense device path was 0.45x.

    ``forest`` — the window-local device kernels; the default whenever an
    accelerator is attached (its HBM absorbs the table passes, and host
    cycles belong to the parser).
    """
    if jax.default_backend() != "cpu":
        return "forest"
    try:
        from .. import native

        native.CompactUnionFind()
        return "host"
    except RuntimeError:
        # no toolchain (native.build_error() says why): the forest carry
        # needs no native code
        return "forest"


class _CCMixin:
    def __init__(self, *args, carry: str = "auto",
                 component_sizes: bool = False, **kwargs):
        super().__init__(*args, **kwargs)
        if carry not in ("auto", "forest", "host", "dense"):
            raise ValueError(f"carry must be auto/forest/host/dense, got {carry!r}")
        self.carry = carry
        self.component_sizes = bool(component_sizes)
        if self.component_sizes:
            self._refuse_unsized_carries()
        self._sizes = None    # device size table (component_sizes)
        self._cc_mode = None  # None | "forest" | "host" | "dense"
        self._canon = None    # device pointer forest (forest/host carries)
        self._log = None      # host TouchLog
        self._uf = None       # native CompactUnionFind (host carry)
        self._prep = None     # WindowPrep scratch (forest carry)
        self._vmesh = None    # the mesh, when its `vertices` axis shards the forest
        self._gf_degree = 2   # resolved tree degree for the group fold

    # ---- dense-engine hooks (mesh / device-transformed fallback) ---- #
    def initial_state(self, vcap: int):
        return init_labels(max(1, vcap))

    def grow_state(self, state, old_vcap: int, new_vcap: int):
        return grow_labels(state, new_vcap)

    def update(self, state, src, dst, val, mask):
        return cc_fold(state, src, dst, mask)

    def combine(self, a, b):
        return label_combine(a, b)

    def transform(self, state, vdict) -> Components:
        return Components.from_labels(state, vdict)

    # ---- windowed-carry run loop ---- #
    def _refuse_unsized_carries(self) -> None:
        """``component_sizes`` is folded by the forest's per-window step
        alone; what else a constructor can ask for is refused here."""
        if self.carry in ("host", "dense"):
            raise NotImplementedError(
                f"component_sizes with carry={self.carry!r}: the size table "
                "is folded by the forest carry's device step; the host "
                "union-find's mirror and the dense label table keep none"
            )
        if self.superbatch > 1 or self.superbatch_auto:
            raise NotImplementedError(
                "component_sizes with superbatch above 1: the group fold "
                "(forest.group_body) carries no size table; run superbatch=1"
            )

    def _vertex_sharded_mesh(self, mesh):
        """The forest carry is laid out over the ``vertices`` axis; what
        that layout lacks is refused here, before the first window."""
        k = int(getattr(self, "superbatch", 1) or 1)
        vertex_layout(mesh, superbatch=k > 1 or self.superbatch_auto)
        if self.carry not in ("auto", "forest"):
            raise NotImplementedError(
                f"carry={self.carry!r} keeps its table whole (the host "
                "union-find's device mirror, the dense label table); under "
                "a `vertices` mesh axis above 1 only the forest carry is "
                "sharded"
            )
        return mesh

    def _pick_carry(self) -> str:
        if self.carry != "auto":
            return self.carry
        if self._vmesh is not None or self.component_sizes:
            return "forest"
        return _auto_carry()

    def run(self, stream) -> Iterator[Components]:
        mesh = self._resolve_mesh(stream)
        if self.component_sizes:
            sized_layout(mesh)
        self._vmesh = mesh if vertex_shards(mesh) > 1 else None
        eff_degree = getattr(self, "degree", 2)
        if mesh is not None and self._vmesh is None and self._is_tree():
            # resolve the tree degree against the mesh EAGERLY: the host
            # carry never runs the butterfly, so without this a
            # misconfigured degree would pass silently (or warn midway
            # through the stream after a downgrade to dense). A degree
            # the mesh cannot honor degrades to 2 with ONE warning here.
            from ..parallel import comm
            from ..parallel.mesh import EDGE_AXIS

            eff_degree = comm.resolve_tree_degree(
                mesh.shape[EDGE_AXIS], eff_degree
            )
        vdict = stream.vertex_dict
        k = int(getattr(self, "superbatch", 1) or 1)
        if (k > 1 or self.superbatch_auto) and not self.transient_state:
            # the superbatched drive loop (fused K-window groups); the
            # transient_state edge case keeps the per-window loop — its
            # per-yield carry reset is inherently window-granular here
            yield from self._run_superbatched_cc(
                stream, mesh, eff_degree, vdict, k
            )
            return
        for block in stream.blocks():
            cache = getattr(block, "_host_cache", None)
            yield from self._one_window(block, cache, mesh, eff_degree, vdict)

    def _run_superbatched_cc(self, stream, mesh, eff_degree, vdict, k):
        """Drive the stream in fused K-window groups through the shared
        :func:`~gelly_streaming_tpu.summaries.groupfold.drive_group_folded`
        loop — the CC carries' ``GroupFoldable`` declaration. Each group
        folds as ONE batched dispatch (``_host_group`` /
        ``_forest_group``) with mid-group canons reconstructed lazily by
        the group's emissions; dense mode superbatches through the
        generic engine scan (``_dense_group``)."""
        from ..summaries.groupfold import drive_group_folded

        self._gf_mesh = mesh
        self._gf_vdict = vdict
        self._gf_degree = eff_degree
        yield from drive_group_folded(
            self, stream, k, controller=self._attach_control(k)
        )

    def fold_group(self, group) -> Iterator[Components]:
        """The CC carries' declared group fold: host union-find group
        call / forest group-local fused scan / dense engine scan, picked
        by the live carry mode. Supports every group — members without
        host column views downgrade to the dense carry, exactly like the
        per-window path."""
        mesh, vdict = self._gf_mesh, self._gf_vdict
        windowed = (
            group.cols is not None
            and self.carry != "dense"
            and self._cc_mode != "dense"
        )
        if windowed and self._cc_mode is None:
            self._cc_mode = self._pick_carry()
        if windowed and self._cc_mode in ("forest", "host"):
            if self._cc_mode == "host":
                yield from self._host_group(group, vdict)
            else:
                yield from self._forest_group(
                    group, mesh, self._gf_degree, vdict
                )
        else:
            if self._cc_mode in ("forest", "host"):
                self._to_dense()
            self._cc_mode = "dense"
            yield from self._dense_group(group, mesh, vdict)

    def _one_window(self, block, cache, mesh, eff_degree, vdict):
        """The per-window path (every carry; superbatch groups bypass it)."""
        if (
            cache is None
            or self.carry == "dense"
            or self._cc_mode == "dense"
        ):
            if self._vmesh is not None:
                raise NotImplementedError(
                    "a window without host column views (a "
                    "device-transformed stream) folds through the dense "
                    "label table, which is not sharded over `vertices`"
                )
            if self.component_sizes:
                raise NotImplementedError(
                    "component_sizes: a window without host column views "
                    "(a device-transformed stream) folds through the dense "
                    "label table, which keeps no size table"
                )
            if self._cc_mode in ("forest", "host"):
                self._to_dense()
            self._cc_mode = "dense"
            self._device_block(block, mesh)
            with _trace.span("window.emit"):
                self._sync_ref = self._summary
                out = self.transform(self._summary, vdict)
            yield out
        else:
            if self._cc_mode is None:
                self._cc_mode = self._pick_carry()
            self._ensure_windowed(block.n_vertices)
            src_h, dst_h = cache[0], cache[1]
            if self._cc_mode == "host":
                # the host union-find computes the merge exactly; a
                # mesh adds nothing (the mirror is one scatter)
                tids, roots, changed, chroots = self._uf.fold(
                    src_h, dst_h, self._vcap
                )
                self._canon = mirror_update(
                    self._canon,
                    np.concatenate([tids, changed]),
                    np.concatenate([roots, chroots]),
                    self._vcap,
                )
            else:
                folded = forest_window(
                    self._canon, src_h, dst_h, self._vcap, self._prep,
                    mesh=mesh, tree=self._is_tree(),
                    degree=eff_degree, sizes=self._sizes,
                )
                if self.component_sizes:
                    self._canon, tids, self._sizes = folded
                else:
                    self._canon, tids = folded
            # what follows the fold on the host: the first-seen log
            # (a gather and a scatter of the touched ids in a table of a
            # row per vertex) and the lazy emission. Closed before the
            # yield (core/window.py _timed_pulls says why)
            with _trace.span("window.emit") as sp:
                fresh = self._log.add(tids)
                # sync()/bench barriers block on _summary; keep it aimed
                # at the live carry
                self._summary = {"labels": self._canon}
                self._sync_ref = self._canon
                out = Components.from_forest(self._canon, self._log, vdict)
                if sp.recording:
                    sp.set(fresh=fresh)
            yield out
        if self.transient_state:
            self._reset_transient()

    def _forest_group(self, group, mesh, eff_degree, vdict):
        """Fold a K-window group as ONE fused group-local dispatch
        (:func:`~gelly_streaming_tpu.summaries.forest.forest_superbatch`)
        and yield the K per-window emissions, resolution-identical to K
        :func:`forest_window` steps. Mid-group canons exist only as the
        group's delta stack; emissions reconstruct them lazily on first
        read (``Components.from_forest_replay``), so unread windows cost
        nothing and the group pays ONE vcap-sized buffer copy where the
        per-window path paid K."""
        # span covers the fold dispatch + log advance, NOT the lazy
        # per-window emissions reconstructed later on first read
        with _trace.span(
            "cc.forest_group",
            {"k": len(group), "n_vertices": int(group.n_vertices)}
            if _trace.on() else None,
        ):
            self._ensure_windowed(group.n_vertices)
            windows = [(c[0], c[1]) for c in group.cols]
            self._canon, tids_list, replay = forest_superbatch(
                self._canon, windows, self._vcap, self._prep,
                mesh=mesh, tree=self._is_tree(), degree=eff_degree,
            )
            # first-seen log advances in window order BEFORE the
            # emissions surface; each snapshot is a count into the
            # append-only log
            counts = []
            for tids in tids_list:
                self._log.add(tids)
                counts.append(self._log.count)
            self._summary = {"labels": self._canon}
            self._sync_ref = self._canon
        for i, count in enumerate(counts):
            yield Components.from_forest_replay(
                replay, i, self._log, count, vdict
            )

    def _host_group(self, group, vdict):
        """Host-carry superbatch: K union-find window folds in ONE
        native call (``CompactUnionFind.fold_group`` — the per-window
        python/ctypes fold overhead dominates sub-8k windows), ONE
        batched device mirror scatter per group from the C-deduped
        group delta. The per-window deltas the UF computes anyway become
        the group's lazy replay
        (:class:`~gelly_streaming_tpu.summaries.forest.MirrorReplay`),
        so mid-group emissions reconstruct on first read and the group
        pays one vcap buffer copy where the per-window mirror paid K."""
        # span covers the native group fold + mirror commit, NOT the
        # lazy per-window emissions reconstructed later on first read
        with _trace.span(
            "cc.host_group",
            {"k": len(group), "n_vertices": int(group.n_vertices)}
            if _trace.on() else None,
        ):
            self._ensure_windowed(group.n_vertices)
            wins, gids, groots, gtcnt = self._uf.fold_group(
                group.cols, self._vcap
            )
            ngt = int(np.sum(gtcnt))
            counts = self._log.add_grouped(gids[:ngt], gtcnt)
            # group commit on HOST: the union-find's truth is host-side
            # anyway, and one numpy fancy-assign (+ two vcap memcpys)
            # beats the XLA scatter by ~10x on the CPU backend where
            # this carry runs; the published device canon is a fresh
            # immutable buffer per group, same contract as
            # mirror_update's functional scatter
            base = np.asarray(self._canon)  # zero-copy view on CPU
            new_np = base.copy()
            new_np[gids] = groots
            self._canon = jnp.asarray(new_np)
            replay = MirrorReplay(base, wins)
            self._summary = {"labels": self._canon}
            self._sync_ref = self._canon
        for i, count in enumerate(counts):
            yield Components.from_forest_replay(
                replay, i, self._log, count, vdict
            )

    def _dense_group(self, group, mesh, vdict):
        """Dense-mode superbatch: the generic engine scan over the
        group's stacked block (``SummaryAggregation._fold_group_states``),
        one lazy ``Components`` per stacked summary row."""
        for state in self._fold_group_states(group, mesh):
            yield self.transform(state, vdict)

    def checkpoint_granularity(self) -> int:
        """Superbatching (and thus group-aligned barriers) is skipped
        under ``transient_state`` — the per-yield carry reset is
        window-granular, so every window is a valid barrier point."""
        return 1 if self.transient_state else super().checkpoint_granularity()

    def _ensure_windowed(self, vcap: int) -> None:
        if self._canon is None:
            if self._summary is not None and "touched" in self._summary:
                # restored (or converted) dense state: flat labels ARE a
                # valid forest; rebuild the host touched log from the mask
                _validate_min_rooted(np.asarray(self._summary["labels"]))
                self._canon = self._summary["labels"]
                if self._vmesh is not None:
                    self._canon = jax.device_put(
                        self._canon, vertex_sharding(self._vmesh)
                    )
                self._log = TouchLog.from_touched_bool(
                    np.asarray(self._summary["touched"])
                )
                self._vcap = self._canon.shape[0]
            else:
                self._vcap = vcap
                self._canon = init_forest(vcap, self._vmesh)
                self._log = TouchLog(vcap)
            if self._cc_mode == "host":
                from .. import native

                self._uf = native.CompactUnionFind()
                self._uf.load(np.asarray(self._canon))
            else:
                self._prep = WindowPrep()
            if self.component_sizes:
                self._sizes = self._derive_sizes()
        if vcap > self._vcap:
            self._canon = grow_forest(self._canon, vcap, self._vmesh)
            if self.component_sizes:
                self._sizes = grow_sizes(self._sizes, vcap)
            self._vcap = vcap
        self._log.grow(self._vcap)

    def _derive_sizes(self):
        """The size table of the carry as it stands: ones beside a fresh
        forest, and for restored labels the members counted per root
        (the whole-table derivation the serving tier keeps for
        snapshots without ``sizes``)."""
        if self._summary is None or "touched" not in self._summary:
            return init_sizes(self._vcap)
        from ..serving.query import _component_size_table

        return _component_size_table(self._canon)[1]

    def _to_dense(self) -> None:
        """Downgrade to the dense engine; the dense path owns growth from
        here. The host carry flattens exactly on host; the forest carry
        canonicalizes in one device fixpoint."""
        if self._cc_mode == "host":
            flat = jnp.asarray(self._uf.flatten(self._vcap))
        else:
            flat = resolve_flat(self._canon)
        touched = jnp.asarray(self._log.touched_bool(self._vcap))
        self._summary = {"labels": flat, "touched": touched}
        self._canon = None
        self._log = None
        self._uf = None
        self._prep = None

    def _reset_transient(self) -> None:
        if self._cc_mode in ("forest", "host"):
            self._canon = init_forest(self._vcap, self._vmesh)
            if self.component_sizes:
                self._sizes = init_sizes(self._vcap)
            self._log = TouchLog(self._vcap)
            self._summary = {"labels": self._canon}
            if self._cc_mode == "host":
                self._uf.load(np.arange(self._vcap, dtype=np.int32))
        else:
            self._summary = self.initial_state(self._vcap)

    # ---- checkpoint surface: one canonical format for all carries ---- #
    def snapshot_state(self) -> Any:
        if self._cc_mode == "host":
            return {
                "labels": self._uf.flatten(self._vcap),
                "touched": self._log.touched_bool(self._vcap),
            }
        if self._cc_mode == "forest":
            lab = resolve_flat_host(np.asarray(self._canon))
            return {
                "labels": lab,
                "touched": self._log.touched_bool(self._vcap),
            }
        return super().snapshot_state()

    def restore_state(self, state: Any, vcap: Optional[int] = None) -> None:
        super().restore_state(state, vcap)
        # undecided until the first block reveals the stream's shape; the
        # restored flat labels work as any carry
        self._cc_mode = None
        self._canon = None
        self._sizes = None
        self._log = None
        self._uf = None
        self._prep = None

    # ---- serving surface (serving/server.py Servable contract) ------- #
    def servable(self, vdict=None) -> "CCServable":
        """Adapter mapping this aggregation's carry to per-window
        serving snapshots: ``labels`` is the live pointer forest (forest/
        host carries — each window's functional scatter leaves the
        published buffer immutable) or the dense flat-label table; the
        :class:`~gelly_streaming_tpu.serving.query.QueryEngine` chases
        either. Serves ``ConnectedQuery`` and ``ComponentSizeQuery``;
        with ``component_sizes=True`` every snapshot also holds
        ``sizes``, folded by the same step as ``labels``.
        ``vdict`` seeds the boot payload when restoring from a
        checkpoint before any stream is attached."""
        return CCServable(self, vdict)


def _counted_blocks(blocks, total):
    """Pass blocks through, accumulating the edge watermark into
    ``total[0]``: exact from host caches, the padded capacity (an upper
    bound) for device-transformed blocks — never a mid-stream D2H."""
    for b in blocks:
        cache = getattr(b, "_host_cache", None)
        total[0] += len(cache[0]) if cache is not None else int(b.capacity)
        yield b


class CCServable:
    """:class:`~gelly_streaming_tpu.serving.server.Servable` adapter for
    the CC aggregation. Every carry publishes one ``labels`` array per
    window — the live pointer forest for the forest/host carries (each
    window's functional update allocates a fresh buffer, so the
    published one is immutable; under a ``vertices`` mesh axis it is the
    sharded array itself, a block of rows a chip, and is never gathered)
    or the dense flat table — plus the stream's vertex dict for raw-id
    resolution. An aggregation built with ``component_sizes=True``
    publishes its size table ``sizes`` in the same payload.

    SUPERBATCH GRANULARITY: with ``superbatch=K`` the aggregation
    yields a group's K emissions after its fused fold, so the live
    carry read here is the END-of-group state for all K publishes (the
    per-window replay views exist only for emission consumers). That
    is safe — the CC carry is monotone, so a query sees a FRESHER
    snapshot, never a wrong one — but snapshots and their seq
    watermark advance at group granularity: serving deployments that
    need per-window snapshot pinning should run ``superbatch=1``."""

    def __init__(self, agg, vdict=None):
        from ..serving import (
            ComponentSizeQuery,
            ConnectedQuery,
            SummaryPullQuery,
        )

        # SummaryPullQuery makes the servable ROUTABLE: a shard router
        # pulls the forest as a raw-id mergeable summary (the
        # cross-shard union input) through the same query wire
        self.query_classes = (
            ConnectedQuery, ComponentSizeQuery, SummaryPullQuery,
        )
        self._agg = agg
        self._vdict = vdict

    def _payload(self, vdict) -> dict:
        agg = self._agg
        if agg._cc_mode in ("forest", "host") and agg._canon is not None:
            labels = agg._canon
        elif agg._summary is not None and "labels" in agg._summary:
            labels = agg._summary["labels"]
            if agg._donated_carry:
                # the dense superbatch carry is DONATED to the next
                # group's dispatch (in-place HBM update) — publishing
                # the live buffer would hand queries an alias that the
                # dispatch invalidates. Snapshots must own their
                # buffer; one vcap copy per publish is the price of
                # donation on serving streams.
                labels = jnp.array(labels)
        else:
            return None
        payload = {"labels": labels, "vdict": vdict}
        if agg._sizes is not None:
            # folded by the step that made ``labels``: one snapshot, one
            # prefix, and ready on one is ready on both
            payload["sizes"] = agg._sizes
        log = getattr(agg, "_log", None)
        if log is not None:
            # the TouchLog novelty shadow rides every snapshot (count-
            # snapshotted: the first tcount entries of an append-only
            # log never change) — the delta-pull diff's candidate
            # bound, same publish shape as the bipartiteness cover
            payload["tids"] = log.ids
            payload["tcount"] = log.count
        return payload

    def payloads(self, stream):
        vdict = stream.vertex_dict
        self._vdict = vdict
        total = [0]
        derive = getattr(stream, "_derive", None)
        counted = (
            stream if derive is None
            else derive(lambda blocks: _counted_blocks(blocks, total))
        )
        window = 0
        for _ in self._agg.run(counted):
            window += 1
            payload = self._payload(vdict)
            if payload is None:  # carry not inspectable this window
                continue
            yield payload, (total[0] or window)

    def boot_payload(self):
        """The restored summary as a servable payload (None when nothing
        was restored yet, or no vdict is known). Validates the
        min-rooted invariant like ``_ensure_windowed``: a corrupt
        checkpoint served as a boot snapshot would otherwise spin the
        query worker's root chase forever on the first query, long
        before the first live window could raise."""
        if self._vdict is None:
            return None
        payload = self._payload(self._vdict)
        if payload is None:
            return None
        _validate_min_rooted(np.asarray(payload["labels"]))
        return payload, 0


class ConnectedComponents(_CCMixin, SummaryBulkAggregation):
    """Flat-combine streaming CC (``library/ConnectedComponents.java``).

    ``carry`` picks the carried summary (module docstring; ``"auto"``).
    ``component_sizes=True`` carries a size table beside the pointer
    forest and publishes it with every snapshot, so that
    ``ComponentSizeQuery(v)`` answers ``sizes[root(v)]`` from the
    snapshot ``root(v)`` was chased in: every id of the id space is a
    vertex and starts as a component of size 1 (over ``IdentityDict``
    an untouched id answers 1), sizes are exact at the roots of the
    stamped prefix, duplicate edges and self-loops change nothing. It
    costs one more ``vcap``-row int32 table a snapshot and window-sized
    work a step; without it the query derives a whole size table per
    snapshot version. Forest carry on one chip only: ``carry="host"``
    or ``"dense"``, ``superbatch`` above 1 and a ``vertices`` or
    ``edges`` mesh axis above 1 each raise ``NotImplementedError``."""

    @classmethod
    def sliding(cls, size: int, slide=None, **kwargs):
        """The EVENT-TIME shape of this workload: CC over a sliding
        window that retracts expired panes via bounded forest repair
        (ISSUE 18) — a configured
        :class:`~gelly_streaming_tpu.eventtime.SlidingGraphAggregator`
        restricted to the CC summary. ``size``/``slide`` are event time
        units; extra kwargs pass through (``allowed_lateness``,
        ``nshards``, ``commit_dir``, ...)."""
        from ..eventtime import SlidingGraphAggregator

        return SlidingGraphAggregator(
            size, slide, summaries=("cc",), **kwargs
        )


class ConnectedComponentsTree(_CCMixin, SummaryTreeReduce):
    """Tree-combine variant (``library/ConnectedComponentsTree.java:26-36``):
    same UDFs on the butterfly engine. The tree/bulk split only matters
    under a sharded mesh, which is exactly where the dense engine runs;
    the single-device forest carry is shared."""
