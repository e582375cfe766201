"""Bipartiteness state: connected components on the signed double cover.

The reference tracks 2-colored candidate components in a nested
TreeMap structure with sign-flipping merges and a global failure latch
(``summaries/Candidates.java:27-197``). SURVEY.md §7 replaces the whole
structure with a classic reduction: run connected components on the *signed
double cover* — every vertex v becomes two cover nodes (v,+) and (v,-), and
every edge (u,v) becomes cover edges (u,+)-(v,-) and (u,-)-(v,+). The graph
is bipartite iff no vertex's two cover nodes land in the same component.
That turns all of ``Candidates``' pointer logic into the folds CC runs:

- the dense carry (:func:`cover_fold`) is ``labels._propagate`` over the
  cover edges, on a label table of 2*vcap rows;
- the forest carry (:func:`cover_forest_window`, and
  :func:`cover_forest_superbatch` for K windows fused) is the forest fold
  of ``summaries/forest.py``, the same ``window_body`` and ``group_body``
  CC's programs are, over the 2*vcap cover id space, plus three things
  written here: the lanes doubled (:func:`_cover_lanes`), an edge mask
  over the pad rows, and the conflict latch (:func:`_conflict`). What the
  step costs on the chip is in ``PERF.md`` section 5.

Layout: cover node (v,+) = index v, (v,-) = index v + vcap, in a table of
size 2*vcap.

:class:`Candidates` is the host-side emission object, reproducing the
reference's output format byte-for-byte: ``(true,{1={1=(1,true), ...}})`` /
``(false,{})`` (golden strings in ``BipartitenessCheckTest.java:19-21`` and
``NonBipartitnessCheckTest.java:19-20``).
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..obs import trace as _trace
from .forest import (
    ForestReplay,
    TableOps,
    _make_local_fixpoint,
    cached_step,
    group_body,
    note_buckets,
    pad_group,
    pad_window,
    window_body,
    window_span,
)
from .labels import _propagate, init_labels


def init_cover(vcap: int) -> Dict[str, jax.Array]:
    """Fresh signed-double-cover label state (2*vcap cover nodes)."""
    return init_labels(2 * vcap)


def cover_fold(
    state: Dict[str, jax.Array],
    src: jax.Array,
    dst: jax.Array,
    mask: jax.Array,
    vcap: int,
) -> Dict[str, jax.Array]:
    """Fold a window's edges into the cover labels.

    Edge (u,v) adds cover constraints (u,+)~(v,-) and (u,-)~(v,+)
    — the dense replacement for ``Candidates.add`` / ``merge``
    (``Candidates.java:52-139``).
    """
    u = jnp.concatenate([src, src + vcap])
    w = jnp.concatenate([dst + vcap, dst])
    m = jnp.concatenate([mask, mask])
    labels = _propagate(state["labels"], u, w, m)
    touched = state["touched"].at[src].max(mask).at[dst].max(mask)
    return {"labels": labels, "touched": touched}


def _shift_cover_labels(lab: np.ndarray, old_vcap: int, new_vcap: int) -> np.ndarray:
    """The cover re-indexing rule, shared by BOTH carries (divergence here
    would break their cross-restorable checkpoints): cover node (v,-)
    moves from v+old to v+new, and label/pointer VALUES into the negative
    half shift by the same amount."""
    new_lab = np.arange(2 * new_vcap, dtype=np.int32)
    shifted = np.where(lab >= old_vcap, lab - old_vcap + new_vcap, lab)
    new_lab[:old_vcap] = shifted[:old_vcap]
    new_lab[new_vcap : new_vcap + old_vcap] = shifted[old_vcap:]
    return new_lab


def cover_grow(state: Dict[str, jax.Array], old_vcap: int, new_vcap: int) -> Dict[str, jax.Array]:
    """Re-index the cover when the vertex capacity bucket grows
    (see :func:`_shift_cover_labels`)."""
    if new_vcap <= old_vcap:
        return state
    tch = np.asarray(state["touched"])
    new_lab = _shift_cover_labels(np.asarray(state["labels"]), old_vcap, new_vcap)
    new_tch = np.zeros(2 * new_vcap, dtype=bool)
    new_tch[:old_vcap] = tch[:old_vcap]
    new_tch[new_vcap : new_vcap + old_vcap] = tch[old_vcap:]
    return {"labels": jnp.asarray(new_lab), "touched": jnp.asarray(new_tch)}


def _cover_lanes(tid, tmask, lu, lv, emask, tcap: int, vcap: int):
    """The cover's lanes, derived in-graph from the base prep (no extra
    host pass): ``(tid2, tmask2, lu2, lv2, emask2)``. The touched bucket
    holds the base touched set twice — lane i is cover node (t_i, +) =
    t_i and lane i + tcap is (t_i, -) = t_i + vcap — so a lane's sibling
    is at a fixed offset; row (u, v) becomes the cover edges (u,+)~(v,-)
    and (u,-)~(v,+). ``lu, lv, emask`` are ``[wcap]`` (one window) or
    ``[k, wcap]`` (a group). UNLIKE the plain CC fold, pad rows need a
    real mask: a pad (0,0) is a harmless self-loop in base space but
    maps to (0,+)~(0,-) in the cover — a fabricated odd cycle."""
    return (
        jnp.concatenate([tid, tid + vcap]),
        jnp.concatenate([tmask, tmask]),
        jnp.concatenate([lu, lu + tcap], axis=-1),
        jnp.concatenate([lv + tcap, lv], axis=-1),
        jnp.concatenate([emask, emask], axis=-1),
    )


def _conflict(nr, tmask, tcap: int):
    """Sibling conflict over the touched lanes of ``nr`` (``[2*tcap]``,
    or ``[k, 2*tcap]``: one verdict a window). CONFLICT COMPLETENESS: a
    new odd cycle means some vertex's two cover nodes connect THIS
    window; the merged cover component is then sign-symmetric, so every
    touched member's sibling lies in the same component — checking
    ``final_root[i] == final_root[i + tcap]`` over the touched lanes
    alone misses nothing."""
    return jnp.any(tmask & (nr[..., :tcap] == nr[..., tcap:]), axis=-1)


def _cover_step_fn(tcap: int, wcap: int, vcap: int):
    """Window-local signed-cover step: the forest fold of one window
    (``forest.window_body``) over the 2*vcap cover id space — the lanes
    doubled and the pad rows masked (:func:`_cover_lanes`) — plus the
    bipartiteness conflict latch (:func:`_conflict`). The latch carries
    on device (monotone OR), so the producer loop stays zero-D2H."""

    def build():
        body = window_body(
            2 * tcap, 2 * vcap, TableOps(2 * vcap),
            _make_local_fixpoint(2 * tcap),
        )

        def step(canon, failed, tid, tmask, lu, lv, emask):
            canon, nr, _sizes = body(
                canon, *_cover_lanes(tid, tmask, lu, lv, emask, tcap, vcap)
            )
            with jax.named_scope("forest.latch"):
                failed = failed | _conflict(nr, tmask, tcap)
            return canon, failed

        return jax.jit(step)

    return cached_step(("cover", tcap, wcap, vcap), build)


def cover_forest_window(canon, failed, src_h, dst_h, vcap: int, prep):
    """Fold one window (host base columns) into the cover forest.
    Returns ``(canon, failed, base_touched_ids)``."""
    n = len(src_h)
    if n == 0:
        return canon, failed, np.zeros(0, np.int32)
    with window_span(n) as sp:
        tids, tcap, wcap, tid, tmask, lu, lv = pad_window(
            prep, src_h, dst_h, vcap
        )
        note_buckets(sp, tids, tcap, wcap)
        with _trace.span("forest.dispatch"):
            emask = np.zeros(wcap, bool)
            emask[:n] = True
            step = _cover_step_fn(tcap, wcap, vcap)
            canon, failed = step(
                canon, failed,
                jnp.asarray(tid), jnp.asarray(tmask),
                jnp.asarray(lu), jnp.asarray(lv), jnp.asarray(emask),
            )
    return canon, failed, tids


def _cover_superbatch_fn(tcap: int, wcap: int, vcap: int, k: int):
    """K cover window-steps fused into one jitted dispatch, GROUP-LOCAL
    (the bipartiteness carry's ``GroupFoldable`` kernel): the forest
    fold of a group (``forest.group_body``) over the cover's lanes
    (:func:`_cover_lanes`), plus the latch AFTER EACH window, read off
    the scan's per-window assignments ``nr_s``: window k's is ``failed``
    OR a conflict in any of ``nr_s[:k+1]`` over the GROUP's touched
    lanes — sound, because ``nr_k`` equality means "same cover component
    as of window k" for every group-touched lane, and complete, because
    a conflict arising at window k lives in a sign-symmetric component
    whose touched members witness it (:func:`_conflict`).

    Returns ``(canon, failed after the group, r, nr_s, fail_s[k])``.
    Mid-group canons reconstruct lazily from ``(r, nr_k)`` via
    :class:`~gelly_streaming_tpu.summaries.forest.ForestReplay` (the
    cover id space is just a forest of 2*vcap nodes, so the CC replay
    applies verbatim); the input canon is NOT donated — the pre-group
    buffer backs the group's lazy emissions."""

    def build():
        body = group_body(
            2 * tcap, 2 * vcap, _make_local_fixpoint(2 * tcap)
        )

        def step(canon, failed, tid, tmask, lu, lv, emask):
            canon, r, nr_s = body(
                canon, *_cover_lanes(tid, tmask, lu, lv, emask, tcap, vcap)
            )
            with jax.named_scope("forest.latch"):
                fail_s = failed | lax.associative_scan(
                    jnp.logical_or, _conflict(nr_s, tmask, tcap)
                )
            return canon, fail_s[-1], r, nr_s, fail_s

        return jax.jit(step)

    return cached_step(("cover-group", tcap, wcap, vcap, k), build)


def cover_forest_superbatch(canon, failed, windows, vcap: int, prep):
    """Fold K windows (list of host base ``(src_h, dst_h)`` column
    pairs) into the cover forest as ONE fused group-local dispatch —
    the cover analog of :func:`~gelly_streaming_tpu.summaries.forest.forest_superbatch`,
    over the same host prep (``forest.pad_group``).

    Returns ``(new_canon, new_failed, [touched_ids per window], replay,
    fail_stack)`` — ``replay`` is a cover-space
    :class:`~gelly_streaming_tpu.summaries.forest.ForestReplay` for lazy
    mid-group canon reconstruction, ``fail_stack`` the device ``[k]``
    per-window failure latches."""
    win_tids, tcap, wcap, tid, tmask, lu, lv, lens = pad_group(
        prep, windows, vcap
    )
    emask = np.arange(wcap) < lens[:, None]
    step = _cover_superbatch_fn(tcap, wcap, vcap, len(windows))
    new_canon, new_failed, r_dev, nr_s, fail_s = step(
        canon, failed, *(jnp.asarray(c) for c in (tid, tmask, lu, lv, emask))
    )
    # the replay works in the 2*vcap cover id space: both cover halves
    # of the touched bucket, the chased old roots, the per-window
    # assignments — exactly the CC replay's contract
    replay = ForestReplay(
        canon, np.concatenate([tid, tid + vcap]),
        np.concatenate([tmask, tmask]), r_dev, nr_s,
    )
    return new_canon, new_failed, win_tids, replay, fail_s


def cover_grow_forest(canon, old_vcap: int, new_vcap: int):
    """Re-index the cover forest when the vertex capacity bucket grows
    (one host rebuild per pow2 growth event, same cost shape and SAME
    rule as the dense ``cover_grow`` — see :func:`_shift_cover_labels`;
    a pointer forest re-indexes exactly like flat labels)."""
    if new_vcap <= old_vcap:
        return canon
    return jnp.asarray(
        _shift_cover_labels(np.asarray(canon), old_vcap, new_vcap)
    )


class Candidates:
    """Host emission object with reference-format string output.

    ``success`` False means an odd cycle was found; the map is then empty
    (``Candidates.fail``, ``Candidates.java:194-196``). On success the map is
    component -> {vertex: (vertex, sign)} with the component keyed by its
    smallest raw vertex id, that root colored ``true``, and every other
    vertex's sign = (same cover side as the root).
    """

    def __init__(self, success=None, components=None, *, _lazy=None):
        self._success = success
        self._components = components
        # (canon_dev | (replay, window_k, fail_stack), failed_dev,
        # touch_log, count, vcap, vdict): forest-carry emission — one
        # device read + host canonicalization on first access, so
        # unread windows cost nothing. The replay form is the
        # superbatched carry's mid-group view (from_forest_replay).
        self._lazy = _lazy

    def _mat(self) -> None:
        if self._lazy is None:
            return
        from .forest import resolve_flat_host

        canon, failed, log, count, vcap, vdict = self._lazy
        if isinstance(canon, tuple):
            # superbatch replay: reconstruct this window's cover canon
            # from the group's delta stack, verdict from the stacked
            # per-window latch (one device read each, on first access)
            replay, kk, fail_s = canon
            self._lazy = None
            if bool(np.asarray(fail_s[kk])):
                self._success, self._components = False, {}
                return
            lab = resolve_flat_host(replay.canon_np(kk))
        else:
            lab_np, failed_np = jax.device_get((canon, failed))
            self._lazy = None
            if bool(failed_np):
                self._success, self._components = False, {}
                return
            lab = resolve_flat_host(np.asarray(lab_np))
        # the log holds BASE ids only (< vcap at snapshot time); the
        # negative cover half derives as base + vcap, and from_cover only
        # reads the base half of the mask — so a dict that grew past the
        # snapshot's vcap cannot push ids into the negative half (a held
        # emission stays a valid snapshot)
        touched = np.zeros(2 * vcap, bool)
        touched[np.asarray(log.ids[:count])] = True
        c = Candidates.from_cover(
            {"labels": lab, "touched": touched}, vcap, vdict
        )
        self._success, self._components = c.success, c.components

    @property
    def success(self) -> bool:
        self._mat()
        return self._success

    @property
    def components(self) -> Dict[int, Dict[int, bool]]:
        self._mat()
        return self._components

    @staticmethod
    def from_forest(canon, failed, log, count, vcap, vdict) -> "Candidates":
        return Candidates(_lazy=(canon, failed, log, count, vcap, vdict))

    @staticmethod
    def from_forest_replay(replay, k, fail_stack, log, count, vcap,
                           vdict) -> "Candidates":
        """Lazy mid-group emission for the superbatched cover carry
        (:func:`cover_forest_superbatch`): window ``k``'s cover canon
        reconstructs from the group ``replay`` on first read, its
        verdict from the stacked per-window latch ``fail_stack[k]``."""
        return Candidates(
            _lazy=((replay, k, fail_stack), None, log, count, vcap, vdict)
        )

    def __bool__(self) -> bool:
        """Truthiness == the bipartiteness verdict (``success``): a
        failed check printing ``(false,{})`` must not read as truthy
        through Python's default object truthiness."""
        return self.success

    @staticmethod
    def from_cover(state: Dict[str, jax.Array], vcap: int, vdict) -> "Candidates":
        labels = np.asarray(state["labels"])
        touched = np.asarray(state["touched"])
        n = len(vdict)
        seen = np.nonzero(touched[:n])[0]
        pos = labels[seen]
        neg = labels[seen + vcap]
        if np.any(pos == neg):
            return Candidates(False, {})
        # Base component id: the min cover label of the pair identifies the
        # base component (each base component owns exactly 2 cover comps).
        base = np.minimum(pos, neg)
        comps: Dict[int, Dict[int, bool]] = {}
        for b in np.unique(base):
            members = seen[base == b]
            raws = np.asarray([vdict.decode_one(int(c)) for c in members])
            order = np.argsort(raws)
            members, raws = members[order], raws[order]
            root = members[0]  # min raw id
            root_side = labels[root]
            signs = labels[members] == root_side
            comps[int(raws[0])] = {
                int(r): bool(s) for r, s in zip(raws.tolist(), signs.tolist())
            }
        return Candidates(True, comps)

    def __eq__(self, other) -> bool:
        return (
            isinstance(other, Candidates)
            and self.success == other.success
            and self.components == other.components
        )

    def __str__(self) -> str:
        if not self.success:
            return "(false,{})"
        outer = ", ".join(
            "%d={%s}"
            % (
                comp,
                ", ".join(
                    "%d=(%d,%s)" % (v, v, "true" if s else "false")
                    for v, s in sorted(vs.items())
                ),
            )
            for comp, vs in sorted(self.components.items())
        )
        return "(true,{%s})" % outer

    __repr__ = __str__
