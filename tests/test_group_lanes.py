"""The group is a window-sized fact (ISSUE 37): every lane's same-root
representative comes from two sorts and a scan of the window's lanes,
and nothing in the group has a row per vertex. Held, lane for lane, to
the grouping over a table-sized scratch (``_group_ref``): the group
alone on the lanes that bite, the steps on every row of every table
they return, and the lowered steps on what their group touches."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gelly_streaming_tpu.summaries import candidates, forest

from _fixpoint_ref import FOLDS, _four as _devices
from _group_ref import (  # noqa: F401  (scratch_steps is a fixture)
    numpy_group,
    scratch_group,
    scratch_steps,
)
from _scatter_ref import kronecker_windows, ops_under, scoped_ops

_I32_MAX = np.iinfo(np.int32).max
SHARDS = 4


def _four():
    return _devices(n_edge_shards=1, n_vertex_shards=SHARDS)


# --------------------------------------------------------------------- #
# the group alone: a forest, the lanes that touch it, and what the
# front half of a step makes of them
# --------------------------------------------------------------------- #
def _random_forest(rng, vcap: int, giant: float = 0.5) -> np.ndarray:
    """Pointers ``canon[v] <= v``, chains of a few hops: ``giant`` of
    the ids under row 0, a tenth of the rest roots of their own."""
    canon = np.arange(vcap)
    u = rng.random(vcap)
    under = rng.integers(0, np.maximum(canon, 1))
    canon = np.where(u < giant, under // 4, np.where(u < 0.9, under, canon))
    canon[0] = 0
    return canon.astype(np.int32)


def _roots(canon: np.ndarray) -> np.ndarray:
    r = canon.copy()
    while not np.array_equal(r, r[r]):
        r = r[r]
    return r


def _lanes(rng, tcap: int, live: int, pool) -> tuple:
    """``live`` distinct ids of ``pool`` on the first lanes, pads behind."""
    tid = np.zeros(tcap, np.int32)
    tid[:live] = rng.choice(pool, live, replace=False)
    return tid, np.arange(tcap) < live


def _cc(rng, tcap=1 << 10, vcap=1 << 13):
    canon = _random_forest(rng, vcap)
    return (canon, *_lanes(rng, tcap, tcap - 100, vcap))


def _cover(rng):
    # the cover's id space: a vertex's two signed copies, ``v`` and
    # ``v + vcap``, ride one window's lanes (twice CC's)
    vcap = 1 << 12
    canon = _random_forest(rng, 2 * vcap)
    tid, tmask = _lanes(rng, 1 << 11, 900, vcap)
    tid[900:1800], tmask[900:1800] = tid[:900] + vcap, True
    return canon, tid, tmask


def _one_root(rng):
    canon = np.zeros(1 << 12, np.int32)
    return (canon, *_lanes(rng, 512, 400, len(canon)))


def _all_distinct(rng):
    canon = np.arange(1 << 12, dtype=np.int32)
    return (canon, *_lanes(rng, 512, 512, len(canon)))


def _pads_only(rng):
    return (_random_forest(rng, 1 << 12), np.zeros(256, np.int32),
            np.zeros(256, bool))


def _pads_between(rng):
    canon, tid, _m = _cc(rng, 512, 1 << 12)
    tmask = rng.random(512) < 0.6
    return canon, np.where(tmask, tid, 0).astype(np.int32), tmask


def _root_at_row_0(rng):
    # row 0 is where the pads chase from: a live group of root 0 whose
    # smallest lane comes AFTER pad lanes must not be led by a pad
    canon, tid, tmask = _pads_between(rng)
    tmask[:7] = False
    tid[:7] = 0
    assert (_roots(canon)[tid[tmask]] == 0).sum() > 10
    return canon, tid, tmask


def _root_at_last_row(rng):
    vcap = 1 << 12
    canon = _random_forest(rng, vcap)
    canon[vcap - 1] = vcap - 1
    tid, tmask = _lanes(rng, 512, 300, vcap - 1)
    tid[17] = vcap - 1
    return canon, tid, tmask


CASES = {
    "cc_lanes": _cc,
    "covers_doubled_lanes": _cover,
    "all_lanes_one_root": _one_root,
    "all_roots_distinct": _all_distinct,
    "pads_only": _pads_only,
    "pads_between_live_lanes": _pads_between,
    "a_root_at_row_0": _root_at_row_0,
    "a_root_at_the_last_row": _root_at_last_row,
    "lanes_under_the_slabs_floor": lambda rng: _cc(
        rng, forest._SLAB_MIN_LANES // 2, 1 << 14),
    "lanes_over_the_slabs_floor": lambda rng: _cc(
        rng, forest._SLAB_MIN_LANES * 2, 1 << 15),
    "four_shards": _cc,
    "four_shards_pads_between": _pads_between,
}


def _front_half(canon, tid, tmask, shards: int):
    vcap = len(canon)

    def fn(c, t, m):
        r, v2, key_, _lanes = forest.chase_and_group(
            c, t, m, vcap, forest.TableOps(vcap, shards))
        return jnp.stack([r, v2, key_])

    if shards > 1:
        fn = forest.sharded_table_fn(fn, _four(), 2, table_out=False)
    return np.asarray(jax.jit(fn)(
        jnp.asarray(canon), jnp.asarray(tid), jnp.asarray(tmask)))


def _held(v2, want, tmask):
    lanes = np.arange(len(v2))
    np.testing.assert_array_equal(v2, want)
    np.testing.assert_array_equal(v2[v2], v2)
    assert (v2 <= lanes).all() and (v2[~tmask] == lanes[~tmask]).all()
    assert tmask[v2[tmask]].all()


@pytest.mark.parametrize("seed", [3, 2**31 + 11])
@pytest.mark.parametrize("case", sorted(CASES))
def test_every_lane_learns_the_scratch_groups_representative(case, seed):
    canon, tid, tmask = CASES[case](np.random.default_rng(seed))
    vcap = len(canon)
    r, v2, key_ = _front_half(
        canon, tid, tmask, SHARDS if case.startswith("four_shards") else 1)
    want_r = np.where(tmask, _roots(canon)[tid], 0)
    np.testing.assert_array_equal(r, want_r)
    np.testing.assert_array_equal(key_, np.where(tmask, want_r, _I32_MAX))
    _held(v2, numpy_group(want_r, tmask), tmask)
    np.testing.assert_array_equal(v2, np.asarray(scratch_group(
        jnp.asarray(r), jnp.asarray(tmask), vcap)))


@pytest.mark.parametrize("seed", [3, 2**31 + 11])
def test_roots_next_to_the_sentinel_of_the_largest_table_keep_their_groups(
        seed):
    """``vcap`` = 2^30, the four-chip cell's: the pads' sentinel is
    ``vcap`` itself and the last rows sit one under it; int32 holds
    both, and no scratch of 2^30 rows is ever built (the reference
    here is the numpy twin: 4 GiB is no test's to fill)."""
    rng = np.random.default_rng(seed)
    vcap, tcap = 1 << 30, 1 << 11
    r = (vcap - 1 - rng.integers(0, 40, tcap)).astype(np.int32)
    r[rng.random(tcap) < 0.2] = 0
    tmask = rng.random(tcap) < 0.8
    r[5], tmask[5] = vcap - 1, True
    v2 = np.asarray(jax.jit(lambda a, m: forest.group_reps(a, m, vcap))(
        jnp.asarray(r), jnp.asarray(tmask)))
    _held(v2, numpy_group(r, tmask), tmask)
    assert len(np.unique(v2[tmask])) == len(np.unique(r[tmask]))


# --------------------------------------------------------------------- #
# the steps: the same tables on EVERY row over ten Kronecker windows
# --------------------------------------------------------------------- #
def _sized_tables(seed: int, scale: int = 12) -> list:
    """``labels`` and ``sizes`` after each window, one above the other."""
    vcap = 1 << scale
    canon, sizes = forest.init_forest(vcap), forest.init_sizes(vcap)
    prep, out = forest.WindowPrep(), []
    for s, d in kronecker_windows(seed, scale, 10, 512):
        canon, _tids, sizes = forest.forest_window(
            canon, s, d, vcap, prep, sizes=sizes)
        out.append(np.concatenate([np.asarray(canon), np.asarray(sizes)]))
    return out


STEPS = {**{k: FOLDS[k] for k in (
    "cc-step", "cover-step", "cc-step-four-shards", "cc-group",
    "cover-group")}, "sized-step": _sized_tables}


@pytest.mark.parametrize("seed", [1, 2**31 + 5])
@pytest.mark.parametrize("which", sorted(STEPS))
def test_the_lanes_group_gives_the_scratch_groups_tables_on_every_row(
        which, seed, scratch_steps):
    got = STEPS[which](seed)
    scratch_steps()
    want = STEPS[which](seed)
    assert len(got) == len(want) >= 10
    for w, (a, b) in enumerate(zip(got, want)):
        np.testing.assert_array_equal(a, b, err_msg=f"window {w}")
    rows = got[-1][:len(got[-1]) // 2] if which == "sized-step" else got[-1]
    rows = rows[:-1] if which.startswith("cover") else rows
    assert (rows != np.arange(len(rows))).sum() > 100
    if which == "sized-step":
        sizes = got[-1][len(rows):]
        roots = np.flatnonzero(rows == np.arange(len(rows)))
        assert sizes[roots].sum() == len(rows) and sizes.max() > 100


# --------------------------------------------------------------------- #
# the lowered steps: nothing under ``forest.group`` has a row per vertex
# --------------------------------------------------------------------- #
def _lowered(which: str, tcap: int, wcap: int, vcap: int) -> tuple:
    """``(text, rows)``: the step lowered with its locations, and the
    rows of the table as the step's body sees it."""
    S = jax.ShapeDtypeStruct
    lanes = (S((tcap,), jnp.int32), S((tcap,), jnp.bool_),
             S((wcap,), jnp.int32), S((wcap,), jnp.int32))
    table = S((vcap,), jnp.int32)
    if which == "cover":
        low = candidates._cover_step_fn(tcap, wcap, vcap).lower(
            S((2 * vcap,), jnp.int32), S((), jnp.bool_), *lanes,
            S((wcap,), jnp.bool_))
        return low.as_text(debug_info=True), 2 * vcap
    if which == "sized":
        low = forest._forest_step_fn(tcap, wcap, vcap, sizes=True).lower(
            table, *lanes, table)
        return low.as_text(debug_info=True), vcap
    if which == "four-shards":
        low = forest._forest_step_fn(tcap, wcap, vcap, _four()).lower(
            table, *lanes)
        return low.as_text(debug_info=True), vcap // SHARDS
    return forest._forest_step_fn(tcap, wcap, vcap).lower(
        table, *lanes).as_text(debug_info=True), vcap


@pytest.mark.parametrize("which", ["cc", "cover", "sized", "four-shards"])
def test_nothing_under_the_lowered_steps_group_has_a_row_per_vertex(which):
    tcap, wcap, vcap = 1 << 10, 1 << 9, 1 << 16
    text, rows = _lowered(which, tcap, wcap, vcap)
    group = ops_under(text, "forest.group")
    assert sum("forest.group/forest.sort" in path
               for path, _ln in scoped_ops(text, "sort")) == 2
    for ln in group:
        assert not re.search(r"tensor<(%d|%d)x" % (rows, vcap), ln), ln
        assert "all_reduce" not in ln and "stablehlo.scatter" not in ln
    # the reader does see a table-sized operand where there is one
    assert any(f"tensor<{rows}x" in ln for ln in ops_under(
        text, "forest.commit"))
