"""The one pointer chase (ISSUE 27): ``forest.chase_roots`` carries each
lane's next pointer, so a round gathers once out of the table and the
loop's condition not at all. Values against a numpy pointer walk, the
loop's form from every caller's jaxpr, and the CC and cover steps
against the former loop, kept here as the plain reference."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from gelly_streaming_tpu.summaries import candidates, forest

_I = jax.ShapeDtypeStruct


# --------------------------------------------------------------------- #
# (a) values: a numpy walk, one pointer at a time
# --------------------------------------------------------------------- #
def _walk(canon: np.ndarray, starts: np.ndarray):
    """-> (roots, depth): every lane stepped until none moves."""
    r, depth = starts.copy(), 0
    while True:
        nxt = canon[r]
        if np.array_equal(nxt, r):
            return r, depth
        r, depth = nxt, depth + 1


def _forest_of_depth(n: int, depth: int, rng) -> np.ndarray:
    """A min-rooted forest (``canon[v] <= v``) whose deepest vertex lies
    exactly ``depth`` pointers from its root: ``level[v]`` is drawn,
    and ``v`` points at a smaller vertex one level up (vertex ``l`` is
    the first of level ``l``, so there always is one)."""
    level = np.minimum(np.arange(n), rng.integers(0, depth + 1, n))
    level[: depth + 1] = np.arange(depth + 1)
    canon = np.arange(n, dtype=np.int32)
    for v in range(1, n):
        if level[v]:
            above = np.flatnonzero(level[:v] == level[v] - 1)
            canon[v] = rng.choice(above)
    return canon


@pytest.mark.parametrize("seed", [3, 2**31 + 11])
@pytest.mark.parametrize("depth", [0, 1, 8, 13])
def test_chase_roots_is_the_pointer_walk(depth, seed):
    rng = np.random.default_rng(seed)
    n, lanes = 512, 128
    canon = _forest_of_depth(n, depth, rng)
    starts = rng.integers(0, n, lanes).astype(np.int32)
    starts[0] = depth                        # a vertex of the last level
    starts[lanes // 2:] = 0                  # padding lanes chase from 0
    want, walked = _walk(canon, starts)
    assert walked == depth
    got = forest.chase_roots(jnp.asarray(canon), jnp.asarray(starts))
    assert got.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got), want)
    assert (canon[want] == want).all()
    assert (want[lanes // 2:] == 0).all()


@pytest.mark.parametrize("seed", [5, 2**31 + 7])
def test_chase_roots_leaves_roots_where_they_are(seed):
    rng = np.random.default_rng(seed)
    canon = _forest_of_depth(256, 9, rng)
    roots = np.flatnonzero(canon == np.arange(256)).astype(np.int32)
    starts = rng.choice(roots, 64).astype(np.int32)
    got = forest.chase_roots(jnp.asarray(canon), jnp.asarray(starts))
    np.testing.assert_array_equal(np.asarray(got), starts)


def test_batch_roots_chases_ids_through_the_same_loop():
    from gelly_streaming_tpu.serving.query import _batch_roots

    rng = np.random.default_rng(17)
    canon = _forest_of_depth(512, 10, rng)
    ids = rng.integers(0, 512, 64).astype(np.int32)
    got = _batch_roots(jnp.asarray(canon), jnp.asarray(ids))
    np.testing.assert_array_equal(np.asarray(got), _walk(canon, ids)[0])


# --------------------------------------------------------------------- #
# (b) structure: one chase loop a caller, no gather in its condition,
# one in its body
# --------------------------------------------------------------------- #
TCAP, WCAP, VCAP, K = 16, 8, 64, 3
_CANON, _COVER = _I((VCAP,), jnp.int32), _I((2 * VCAP,), jnp.int32)
_TID, _TMASK = _I((TCAP,), jnp.int32), _I((TCAP,), jnp.bool_)
_COL, _COLS = _I((WCAP,), jnp.int32), _I((K, WCAP), jnp.int32)
_FAILED = _I((), jnp.bool_)


def _batch_roots_jaxpr():
    from gelly_streaming_tpu.serving.query import _batch_roots

    return jax.make_jaxpr(_batch_roots)(_CANON, _I((8,), jnp.int32))


CALLERS = {
    "cc-step": ("forest.chase", lambda: jax.make_jaxpr(
        forest._forest_step_fn(TCAP, WCAP, VCAP))(
            _CANON, _TID, _TMASK, _COL, _COL)),
    "cc-superbatch": ("forest.chase", lambda: jax.make_jaxpr(
        forest._forest_superbatch_fn(TCAP, WCAP, VCAP, K))(
            _CANON, _TID, _TMASK, _COLS, _COLS)),
    "cover-step": ("forest.chase", lambda: jax.make_jaxpr(
        candidates._cover_step_fn(TCAP, WCAP, VCAP))(
            _COVER, _FAILED, _TID, _TMASK, _COL, _COL,
            _I((WCAP,), jnp.bool_))),
    "cover-superbatch": ("forest.chase", lambda: jax.make_jaxpr(
        candidates._cover_superbatch_fn(TCAP, WCAP, VCAP, K))(
            _COVER, _FAILED, _TID, _TMASK, _COLS, _COLS,
            _I((K, WCAP), jnp.bool_))),
    "batch-roots": ("query.chase", _batch_roots_jaxpr),
}


def _subjaxprs(eqn):
    for v in eqn.params.values():
        for sub in v if isinstance(v, (list, tuple)) else [v]:
            sub = getattr(sub, "jaxpr", sub)
            if hasattr(sub, "eqns"):
                yield sub


def _eqns(jaxpr):
    """Every equation, those of nested programs too."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _subjaxprs(eqn):
            yield from _eqns(sub)


def _gathers(jaxpr) -> int:
    return sum(e.primitive.name == "gather" for e in _eqns(jaxpr))


@pytest.mark.parametrize("caller", list(CALLERS))
def test_the_chase_gathers_once_a_round_and_never_in_its_condition(caller):
    scope, make = CALLERS[caller]
    loops = [e for e in _eqns(make().jaxpr)
             if e.primitive.name == "while"
             and scope in str(e.source_info.name_stack)]
    assert len(loops) == 1, "one chase loop a program"
    (loop,) = loops
    assert _gathers(loop.params["cond_jaxpr"].jaxpr) == 0
    assert _gathers(loop.params["body_jaxpr"].jaxpr) == 1
    # the loop carries (r, nxt): two lane-sized columns, the table rides
    # along as a constant of the body alone
    assert loop.params["cond_nconsts"] == 0
    assert loop.params["body_nconsts"] == 1
    assert len(loop.outvars) == 2


# --------------------------------------------------------------------- #
# (c) the steps against the former loop: the same table, bit for bit
# --------------------------------------------------------------------- #
def _former_chase(canon, r0, tab=None):
    """The loop as it was: the condition gathers ``canon[r]``, the body
    gathers it again (whole tables only: ``tab`` is not looked at)."""
    return lax.while_loop(
        lambda r: jnp.any(canon[r] != r), lambda r: canon[r], r0)


@pytest.fixture
def former_steps(monkeypatch):
    """-> a context that builds the steps over the former loop."""
    def swap():
        monkeypatch.setattr(forest, "chase_roots", _former_chase)
        forest._STEP_CACHE.clear()

    yield swap
    monkeypatch.undo()
    forest._STEP_CACHE.clear()


def _windows(seed: int, vcap: int, n: int, size: int, bipartite: bool):
    """Skewed windows (low ids drawn often: roots move and chains grow
    through former roots from window to window)."""
    rng = np.random.default_rng(seed)
    for _ in range(n):
        m = int(rng.integers(size // 2, size + 1))
        s = (rng.integers(0, vcap, m) * rng.random(m) ** 2).astype(np.int32)
        d = rng.integers(0, vcap, m).astype(np.int32)
        if bipartite:
            s, d = s & ~1, d | 1
        yield s, d


def _fold_cc(seed, vcap=256):
    canon, prep, out = forest.init_forest(vcap), forest.WindowPrep(), []
    for s, d in _windows(seed, vcap, 12, 24, False):
        canon, _tids = forest.forest_window(canon, s, d, vcap, prep)
        out.append(np.asarray(canon))
    return out


def _fold_cover(seed, bipartite, vcap=256):
    canon, failed = forest.init_forest(2 * vcap), jnp.bool_(False)
    prep, out = forest.WindowPrep(), []
    for s, d in _windows(seed, vcap, 12, 24, bipartite):
        canon, failed, _tids = candidates.cover_forest_window(
            canon, failed, s, d, vcap, prep)
        out.append((np.asarray(canon), bool(failed)))
    return out


@pytest.mark.parametrize("seed", [1, 2, 2**31 + 3])
def test_cc_step_gives_the_former_loops_table(seed, former_steps):
    got = _fold_cc(seed)
    former_steps()
    want = _fold_cc(seed)
    for w, (a, b) in enumerate(zip(got, want)):
        np.testing.assert_array_equal(a, b, err_msg=f"window {w}")
    # the stream did build chains for the chase to follow
    assert _walk(got[-1], np.arange(256))[1] >= 2


@pytest.mark.parametrize("bipartite", [True, False],
                         ids=["bipartite", "odd-cycles"])
@pytest.mark.parametrize("seed", [1, 2, 2**31 + 3])
def test_cover_step_gives_the_former_loops_table_and_latch(
        seed, bipartite, former_steps):
    got = _fold_cover(seed, bipartite)
    former_steps()
    want = _fold_cover(seed, bipartite)
    for w, ((a, fa), (b, fb)) in enumerate(zip(got, want)):
        np.testing.assert_array_equal(a, b, err_msg=f"window {w}")
        assert fa == fb, f"window {w}"
    assert got[-1][1] == (not bipartite)
    assert _walk(got[-1][0], np.arange(512))[1] >= 2


# --------------------------------------------------------------------- #
# tools/trace_phases.py: what one trip of each loop costs
# --------------------------------------------------------------------- #
def _phases(metrics: dict) -> dict:
    import os
    import sys

    tools = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools")
    if tools not in sys.path:
        sys.path.insert(0, tools)
    import trace_phases

    return trace_phases.phases_block(
        {k: {"value": v, "unit": "-"} for k, v in metrics.items()})


_STEP = {"forest_step_ms.sat": 96.0, "forest_chase_ms.sat": 17.0,
         "forest_group_ms.sat": 15.0, "forest_fixpoint_ms.sat": 35.0,
         "forest_commit_ms.sat": 26.0}


@pytest.mark.parametrize("rounds,want", [
    ({"forest_chase_rounds.sat": 5.0, "forest_fixpoint_rounds.sat": 7.0},
     {"chase_ms_per_round": 3.4, "fixpoint_ms_per_round": 5.0}),
    ({"forest_chase_rounds.sat": 0.0, "forest_fixpoint_rounds.sat": 7.0},
     {"fixpoint_ms_per_round": 5.0}),       # every lane met its root
    ({}, {}),                                # a program without the scopes
    # the fixpoint's contraction runs once a step: out before dividing
    ({"forest_chase_rounds.sat": 5.0, "forest_fixpoint_rounds.sat": 7.0,
      "forest_contract_ms.sat": 2.1},
     {"chase_ms_per_round": 3.4, "fixpoint_ms_per_round": 4.7}),
], ids=["both-loops", "no-chase-trip", "no-rounds-read", "contraction"])
def test_phases_block_gives_ms_per_round_of_each_loop(rounds, want):
    got = _phases({**_STEP, **rounds})
    assert got["sum_ms"] == pytest.approx(93.0)
    assert got["share"] == pytest.approx(93.0 / 96.0)
    per_round = {k: v for k, v in got.items() if k.endswith("_per_round")}
    assert per_round == pytest.approx(want)


@pytest.mark.parametrize("nested", ["sort", "exchange", "contract"])
def test_phases_block_keeps_a_nested_scope_beside_the_sum(nested):
    """``forest.sort`` runs inside group and commit, ``forest.exchange``
    inside chase and group, ``forest.contract`` inside the fixpoint:
    read, and not added a second time."""
    got = _phases({**_STEP, f"forest_{nested}_ms.sat": 0.24})
    assert got["sum_ms"] == pytest.approx(93.0)
    assert got[f"{nested}_ms"] == pytest.approx(0.24)
    assert got[f"{nested}_share"] == pytest.approx(0.24 / 96.0)


def test_phases_block_is_empty_without_a_step():
    assert _phases({"answer_ms": 25.0}) == {}
