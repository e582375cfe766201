"""The fixpoint runs on the window's quotient graph (ISSUE 33): the
same-root groups are folded into their representative lanes once,
before the rounds. Held, value for value, to the fixpoint that carried
every lane's pointer edge through every round (``_fixpoint_ref``): the
steps on every row of the table, the fixpoint alone on the shapes that
bite, and the precondition on what the two callers hand it."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gelly_streaming_tpu.summaries import candidates, forest

from _fixpoint_ref import (  # noqa: F401  (carried_steps is a fixture)
    FOLDS,
    RAGGED,
    carried_fixpoint,
    carried_steps,
)
from _scatter_ref import kronecker_windows

SEEDS = [1, 2**31 + 5]


# --------------------------------------------------------------------- #
# the steps: the same table on EVERY row, pointer shape included
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("which,seed", [
    (which, seed) for which in FOLDS
    for seed in ([None] if which.endswith("odd-cycle") else SEEDS)])
def test_the_quotient_fixpoint_gives_the_carried_pointer_edges_table(
        which, seed, carried_steps):
    got = FOLDS[which](seed)
    carried_steps()
    want = FOLDS[which](seed)
    assert len(got) == len(want) >= len(RAGGED)
    for w, (a, b) in enumerate(zip(got, want)):
        np.testing.assert_array_equal(a, b, err_msg=f"window {w}")
    if which.endswith("odd-cycle"):
        # the latch rides as the last row: clear over the star, set by
        # the window that closes the cycle, and kept
        latch = [int(t[-1]) for t in got]
        assert latch[0] == 0 and latch[-1] == 1 and latch == sorted(latch)
    else:
        rows = got[-1][:-1] if which.startswith("cover") else got[-1]
        assert (rows != np.arange(len(rows))).sum() > 100


# --------------------------------------------------------------------- #
# the fixpoint alone, on the shapes that bite
# --------------------------------------------------------------------- #
TCAP, WCAP = 256, 128


def _groups(rng, giant: float = 0.5):
    """``targets``: every lane's group's min lane; ``giant`` of the
    lanes in one group, the rest in groups of a few."""
    group = rng.integers(0, TCAP // 3, TCAP)
    group[rng.random(TCAP) < giant] = 0
    rep = np.full(TCAP, TCAP, np.int64)
    np.minimum.at(rep, group, np.arange(TCAP))
    return rep[group].astype(np.int32)


def _edges(rng):
    return (rng.integers(0, TCAP, WCAP).astype(np.int32),
            rng.integers(0, TCAP, WCAP).astype(np.int32))


def _one_group(rng):
    return (np.arange(TCAP), *_edges(rng), np.zeros(TCAP, np.int32), None)


def _every_lane_its_own(rng):
    return (np.arange(TCAP), *_edges(rng), np.arange(TCAP), None)


def _no_live_edge(rng):
    return (np.arange(TCAP), *_edges(rng), _groups(rng),
            np.zeros(WCAP, bool))


def _pads_only(rng):
    zeros = np.zeros(WCAP, np.int32)
    return np.arange(TCAP), zeros, zeros, _groups(rng, 0.1), None


def _masked_row_between_live_lanes(rng):
    """Rows 0 and 1 are live and make lanes 10 and 20 endpoints; the
    masked row 2 joins them, and must not."""
    targets = np.arange(TCAP, dtype=np.int32)
    targets[[11, 12]] = 10
    targets[[21, 22]] = 20
    lu = np.zeros(WCAP, np.int32)
    lv = np.zeros(WCAP, np.int32)
    lu[:3], lv[:3] = [11, 21, 12], [30, 40, 22]
    emask = np.zeros(WCAP, bool)
    emask[:2] = True
    return np.arange(TCAP), lu, lv, targets, emask


def _a_flat_label_table(rng):
    """The group body's call: seed and targets the carried table."""
    lab = _groups(rng)
    return lab, *_edges(rng), lab, None


def _random_groups(rng):
    lu, lv = _edges(rng)
    return np.arange(TCAP), lu, lv, _groups(rng), rng.random(WCAP) < 0.7


SHAPES = [_one_group, _every_lane_its_own, _no_live_edge, _pads_only,
          _masked_row_between_live_lanes, _a_flat_label_table,
          _random_groups]


def _local(make, seed, lu, lv, targets, emask):
    args = [jnp.asarray(a, jnp.int32) for a in (seed, lu, lv, targets)]
    if emask is not None:
        args.append(jnp.asarray(emask))
    return np.asarray(jax.jit(make(TCAP))(*args))


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("shape", SHAPES,
                         ids=[f.__name__.strip("_") for f in SHAPES])
def test_the_fixpoint_alone_gives_the_carried_one_lane_for_lane(shape, seed):
    case = shape(np.random.default_rng(seed))
    targets = case[3]
    assert np.array_equal(targets[targets], targets)      # the precondition
    got = _local(forest._make_local_fixpoint, *case)
    np.testing.assert_array_equal(got, _local(carried_fixpoint, *case))
    # a label is the min lane of its component, and flat
    assert (got <= np.arange(TCAP)).all() and np.array_equal(got[got], got)
    if shape is _masked_row_between_live_lanes:
        assert got[11] == got[30] == 10 and got[21] == got[40] == 20
    if shape in (_no_live_edge, _pads_only):
        np.testing.assert_array_equal(got, targets)
    if shape is _one_group:
        assert not got.any()


def test_a_chain_in_targets_is_what_the_precondition_excludes():
    """Lane 2 points at lane 1, which points at lane 0: no depth-1
    forest. Carried as edges the chain is followed; contracted, lane 2
    reads the label of lane 1, which no row relabelled. That is why the
    fixpoint asks for ``targets[targets] == targets``."""
    targets = np.arange(TCAP, dtype=np.int32)
    targets[[1, 2]] = [0, 1]
    assert not np.array_equal(targets[targets], targets)
    zeros = np.zeros(WCAP, np.int32)
    case = (np.arange(TCAP), zeros, zeros, targets, None)
    assert _local(carried_fixpoint, *case)[:3].tolist() == [0, 0, 0]
    assert _local(forest._make_local_fixpoint, *case)[:3].tolist() == [0, 0, 1]


def _recording(seen, real=forest._make_local_fixpoint):
    def make(tcap, *mesh):
        fixpoint = real(tcap, *mesh)

        def record(seed, lu, lv, targets, emask=None):
            seen.append((np.asarray(seed), np.asarray(targets)))
            return fixpoint(seed, lu, lv, targets, emask)
        return record
    return make


@pytest.mark.parametrize("cover", [False, True], ids=["cc", "cover"])
def test_both_callers_meet_the_fixpoints_precondition(cover, monkeypatch):
    """What ``window_body`` and ``group_body`` hand the fixpoint over a
    Kronecker stream, read with the jit off: ``targets`` is a depth-1
    forest, and ``seed[targets[i]]`` the label lane ``i`` enters with
    (its group's min lane per window; the carried, flat table in a
    group, pads self-looping in both)."""
    seen, vcap = [], 1 << 9
    for module in (forest, candidates):
        monkeypatch.setattr(module, "_make_local_fixpoint", _recording(seen))
    monkeypatch.setattr(forest, "_STEP_CACHE", {})
    windows = [(s[:n], d[:n]) for (s, d), n in zip(
        kronecker_windows(3, 9, 6, 64, bipartite=cover), (64, 64, 64, 9, 0, 64))]
    prep, failed = forest.WindowPrep(), jnp.bool_(False)
    canon = forest.init_forest(2 * vcap if cover else vcap)
    with jax.disable_jit():
        for s, d in windows[:2]:
            if cover:
                canon, failed, _t = candidates.cover_forest_window(
                    canon, failed, s, d, vcap, prep)
            else:
                canon, _t = forest.forest_window(canon, s, d, vcap, prep)
        per_window = len(seen)
        if cover:
            candidates.cover_forest_superbatch(
                canon, failed, windows[2:], vcap, prep)
        else:
            forest.forest_superbatch(canon, windows[2:], vcap, prep)
    assert per_window == 2 and len(seen) == 2 + 4
    grouped = 0
    for k, (seed, targets) in enumerate(seen):
        lanes = np.arange(len(targets))
        assert np.array_equal(targets[targets], targets), k
        assert (targets <= lanes).all()
        if k < per_window:            # the window body: seeded from iota
            assert np.array_equal(seed, lanes)
        else:                         # the group body: the carried table
            assert np.array_equal(seed, targets)
        assert np.array_equal(seed[targets], targets)
        grouped += int((targets != lanes).sum())
    assert grouped > 50, "the stream was to put lanes under one root"

