"""Bipartiteness state: signed double cover over dense labels.

The reference tracks 2-colored candidate components in a nested
TreeMap structure with sign-flipping merges and a global failure latch
(``summaries/Candidates.java:27-197``). SURVEY.md §7 replaces the whole
structure with a classic reduction: run connected components on the *signed
double cover* — every vertex v becomes two cover nodes (v,+) and (v,-), and
every edge (u,v) becomes cover edges (u,+)-(v,-) and (u,-)-(v,+). The graph
is bipartite iff no vertex's two cover nodes land in the same component.
That turns all of ``Candidates``' pointer logic into the same dense label
kernels CC uses (``summaries/labels.py``), sharing its collectives.

Layout: cover node (v,+) = index v, (v,-) = index v + vcap, in a label table
of size 2*vcap.

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

from ..core.edgeblock import bucket_capacity
from ..obs import trace as _trace
from .forest import (
    chase_and_group,
    commit_roots,
    new_roots,
    note_buckets,
    pad_window,
    reroot,
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


#: jitted cover window steps, keyed (tcap, wcap, vcap); bounded FIFO
_COVER_STEP_CACHE: dict = {}
_COVER_STEP_CACHE_MAX = 32


def _cover_step_fn(tcap: int, wcap: int, vcap: int):
    """Window-local signed-cover step (round 5): the forest CC step
    (``summaries/forest.py``) over the 2*vcap cover id space, plus the
    bipartiteness conflict latch.

    Layout: the touched bucket holds the window's base touched set twice
    — lane i is cover node (t_i, +) = t_i and lane i + tcap is
    (t_i, -) = t_i + vcap — so a lane's sibling is at a fixed offset.
    CONFLICT COMPLETENESS: a new odd cycle means some vertex's two cover
    nodes connect THIS window; the merged cover component is then
    sign-symmetric, so every touched member's sibling lies in the same
    component — checking ``final_root[i] == final_root[i + tcap]`` over
    the touched lanes alone misses nothing. The latch carries on device
    (monotone OR), so the producer loop stays zero-D2H.
    """
    key = (tcap, wcap, vcap)
    fn = _COVER_STEP_CACHE.get(key)
    if fn is not None:
        return fn

    tcap2, vcap2 = 2 * tcap, 2 * vcap

    def step(canon, failed, tid, tmask, lu, lv, emask):
        # cover touched bucket + cover edges, derived in-graph from the
        # base prep (no extra host pass): (u,+)~(v,-) and (u,-)~(v,+).
        # UNLIKE the plain CC forest step, pad rows need a real mask: a
        # pad (0,0) is a harmless self-loop in base space but maps to
        # (0,+)~(0,-) in the cover — a fabricated odd cycle.
        tid2 = jnp.concatenate([tid, tid + vcap])
        tmask2 = jnp.concatenate([tmask, tmask])
        lu2 = jnp.concatenate([lu, lu + tcap])
        lv2 = jnp.concatenate([lv + tcap, lv])
        emask2 = jnp.concatenate([emask, emask])
        r, v2, key_, iota = chase_and_group(canon, tid2, tmask2, tcap2, vcap2)
        with jax.named_scope("forest.fixpoint"):
            u = jnp.concatenate([lu2, iota])
            w = jnp.concatenate([lv2, v2])
            m = jnp.concatenate([emask2, jnp.ones(tcap2, bool)])
            local = _propagate(iota, u, w, m)
        canon, nr = commit_roots(
            canon, local, key_, r, tid2, tmask2, tcap2, vcap2
        )
        # sibling conflict over the touched lanes (see docstring)
        with jax.named_scope("forest.latch"):
            failed = failed | jnp.any(
                tmask & (nr[:tcap] == nr[tcap:])
            )
        return canon, failed

    fn = jax.jit(step)
    if len(_COVER_STEP_CACHE) >= _COVER_STEP_CACHE_MAX:
        _COVER_STEP_CACHE.pop(next(iter(_COVER_STEP_CACHE)))
    _COVER_STEP_CACHE[key] = fn
    return fn


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
    """K cover window-steps fused into one jitted dispatch, GROUP-LOCAL —
    the signed-cover analog of ``forest._forest_superbatch_fn`` (the
    bipartiteness carry's ``GroupFoldable`` kernel):

    1. ONE root chase + same-root grouping over the group's union
       touched set, expanded to BOTH cover halves (lane i = (t_i, +),
       lane i + tcap = (t_i, -)) — one 2*vcap scratch memset per GROUP;
    2. a ``lax.scan`` over the K windows whose carry is the 2*tcap-sized
       local label table plus the failure latch: window k folds its
       cover edges ((u,+)~(v,-), (u,-)~(v,+); pad rows carry a real edge
       mask, the ``_cover_step_fn`` caveat) into the carried table and
       emits its new-root assignment ``nr_k`` PLUS the latch after the
       window (the per-window sibling-conflict check runs over the
       GROUP's touched lanes — sound, because ``nr_k`` equality means
       "same cover component as of window k" for every group-touched
       lane, and complete, because a conflict arising at window k lives
       in a sign-symmetric component whose touched members witness it);
    3. ONE masked scatter pair commits the final assignment.

    Mid-group canons reconstruct lazily from ``(r, nr_k)`` via
    :class:`~gelly_streaming_tpu.summaries.forest.ForestReplay` (the
    cover id space is just a forest of 2*vcap nodes, so the CC replay
    applies verbatim); the input canon is NOT donated — the pre-group
    buffer backs the group's lazy emissions."""
    key = ("superbatch", tcap, wcap, vcap, k)
    fn = _COVER_STEP_CACHE.get(key)
    if fn is not None:
        return fn

    tcap2, vcap2 = 2 * tcap, 2 * vcap

    def step(canon, failed, tid, tmask, lu, lv, emask):
        # cover touched bucket + per-window cover edges, derived
        # in-graph from the base prep (lu/lv/emask are [k, wcap])
        tid2 = jnp.concatenate([tid, tid + vcap])
        tmask2 = jnp.concatenate([tmask, tmask])
        lu2 = jnp.concatenate([lu, lu + tcap], axis=1)
        lv2 = jnp.concatenate([lv + tcap, lv], axis=1)
        emask2 = jnp.concatenate([emask, emask], axis=1)
        r, v2, key_, iota = chase_and_group(canon, tid2, tmask2, tcap2, vcap2)
        # v2 is a depth-1 min-rooted forest encoding the pre-group
        # same-root constraints — already a valid label table seed
        lab0 = v2

        def body(c, xs):
            lab, fail = c
            lu_k, lv_k, em_k = xs
            with jax.named_scope("forest.fixpoint"):
                u = jnp.concatenate([lu_k, iota])
                w = jnp.concatenate([lv_k, lab])
                m = jnp.concatenate([em_k, jnp.ones(tcap2, bool)])
                lab = _propagate(lab, u, w, m)
            with jax.named_scope("forest.commit"):
                nr = new_roots(lab, key_, tcap2)
            with jax.named_scope("forest.latch"):
                fail = fail | jnp.any(tmask & (nr[:tcap] == nr[tcap:]))
            return (lab, fail), (nr, fail)

        (_lab_end, fail_end), (nr_s, fail_s) = lax.scan(
            body, (lab0, failed), (lu2, lv2, emask2)
        )
        with jax.named_scope("forest.commit"):
            canon = reroot(canon, nr_s[-1], r, tid2, tmask2, vcap2)
        return canon, fail_end, r, nr_s, fail_s

    fn = jax.jit(step)
    if len(_COVER_STEP_CACHE) >= _COVER_STEP_CACHE_MAX:
        _COVER_STEP_CACHE.pop(next(iter(_COVER_STEP_CACHE)))
    _COVER_STEP_CACHE[key] = fn
    return fn


def cover_forest_superbatch(canon, failed, windows, vcap: int, prep):
    """Fold K windows (list of host base ``(src_h, dst_h)`` column
    pairs) into the cover forest as ONE fused group-local dispatch —
    the cover analog of :func:`~gelly_streaming_tpu.summaries.forest.forest_superbatch`,
    sharing its host prep shape: one prep per window for the per-window
    touched ids (the first-seen log advances in window order), one prep
    over the concatenated columns for the group touched set + the
    group-local renumbering.

    Returns ``(new_canon, new_failed, [touched_ids per window], replay,
    fail_stack)`` — ``replay`` is a cover-space
    :class:`~gelly_streaming_tpu.summaries.forest.ForestReplay` for lazy
    mid-group canon reconstruction, ``fail_stack`` the device ``[k]``
    per-window failure latches."""
    from .forest import ForestReplay

    if prep is None:
        raise ValueError(
            "cover_forest_superbatch requires a per-stream WindowPrep "
            "(see forest_window)"
        )
    k = len(windows)
    _e = np.zeros(0, np.int32)
    win_tids = [
        prep.prep(s, d, vcap)[0] if len(s) else _e for s, d in windows
    ]
    src_g = np.concatenate([s for s, _ in windows]) if k else _e
    dst_g = np.concatenate([d for _, d in windows]) if k else _e
    if len(src_g):
        tids_g, lu_all, lv_all = prep.prep(src_g, dst_g, vcap)
    else:
        tids_g, lu_all, lv_all = _e, _e, _e
    n_max = max((len(s) for s, _ in windows), default=0)
    tcap = bucket_capacity(len(tids_g), minimum=8)
    wcap = bucket_capacity(n_max, minimum=8)
    t = len(tids_g)
    tid = np.zeros(tcap, np.int32)
    tid[:t] = tids_g
    tmask = np.zeros(tcap, bool)
    tmask[:t] = True
    lu = np.zeros((k, wcap), np.int32)
    lv = np.zeros((k, wcap), np.int32)
    emask = np.zeros((k, wcap), bool)
    off = 0
    for i, (s, _) in enumerate(windows):
        n = len(s)
        lu[i, :n] = lu_all[off:off + n]
        lv[i, :n] = lv_all[off:off + n]
        emask[i, :n] = True
        off += n
    step = _cover_superbatch_fn(tcap, wcap, vcap, k)
    new_canon, new_failed, r_dev, nr_s, fail_s = step(
        canon, failed,
        jnp.asarray(tid), jnp.asarray(tmask),
        jnp.asarray(lu), jnp.asarray(lv), jnp.asarray(emask),
    )
    # the replay works in the 2*vcap cover id space: both cover halves
    # of the touched bucket, the chased old roots, the per-window
    # assignments — exactly the CC replay's contract
    tid2 = np.concatenate([tid, tid + vcap])
    tmask2 = np.concatenate([tmask, tmask])
    replay = ForestReplay(canon, tid2, tmask2, r_dev, nr_s)
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
