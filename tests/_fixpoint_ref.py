"""The forest's local fixpoint as it was before the same-root groups were
folded into their representative lanes (ISSUE 33): the pointer edges
``(i, targets[i])`` ride through every round as EDGES beside the
window's rows. Kept as the plain reference the fixpoint on the window's
quotient graph is held to, with the folds the steps are compared on:
per window and per group, CC and the cover, one chip, four shards and
an ``edges`` mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from gelly_streaming_tpu.parallel import comm
from gelly_streaming_tpu.parallel.mesh import EDGE_AXIS, make_mesh
from gelly_streaming_tpu.summaries import candidates, forest
from gelly_streaming_tpu.summaries.labels import _propagate

from _scatter_ref import cc_tables, kronecker_windows


def carried_fixpoint(tcap: int, mesh=None, tree: bool = False,
                     degree: int = 2):
    """``forest._make_local_fixpoint`` with every lane's pointer edge
    carried as an edge: ``wcap + tcap`` lanes a round."""
    iota = jnp.arange(tcap, dtype=jnp.int32)
    if mesh is not None:
        p = mesh.shape[EDGE_AXIS]
        combine = forest._table_combine(tcap)

    def fixpoint(seed, lu, lv, targets, emask=None):
        def fold(lu_s, lv_s, em_s=None):
            u = jnp.concatenate([lu_s, iota])
            w = jnp.concatenate([lv_s, targets])
            m = (jnp.ones(u.shape[0], bool) if em_s is None
                 else jnp.concatenate([em_s, jnp.ones(tcap, bool)]))
            return _propagate(seed, u, w, m)

        cols = (lu, lv) if emask is None else (lu, lv, emask)
        if mesh is None:
            return fold(*cols)

        def shard_fn(*cols_s):
            lab = fold(*cols_s)
            if tree:
                return comm.tree_all_reduce(
                    lab, EDGE_AXIS, combine, p, degree=degree)
            return lab[None]

        out = comm.shard_map(
            shard_fn, mesh, (P(EDGE_AXIS),) * len(cols),
            P() if tree else P(EDGE_AXIS),
        )(*cols)
        return out if tree else comm.stacked_reduce(out, p, combine)

    return fixpoint


@pytest.fixture
def carried_steps(monkeypatch):
    """-> a call that rebuilds every step over :func:`carried_fixpoint`."""
    def swap():
        for module in (forest, candidates):
            monkeypatch.setattr(module, "_make_local_fixpoint",
                                carried_fixpoint)
        forest._STEP_CACHE.clear()

    yield swap
    monkeypatch.undo()
    forest._STEP_CACHE.clear()


# --------------------------------------------------------------------- #
# the folds: the table after each window, every row; the cover's with
# its latch as one more row
# --------------------------------------------------------------------- #
#: ten windows cut ragged, so that a group of four holds an empty one
RAGGED = (512, 37, 0, 512, 300, 512, 1, 512, 0, 129)

#: a star, then an odd cycle that arrives over several windows of two
#: edges (``test_cover_forest_bipartite_star_and_odd_cycle``'s stream)
ODD_CYCLE = [(0, i) for i in range(1, 40)] + [(1, 2), (2, 3), (3, 1),
                                              (50, 51)]


def _with_latch(canon, failed) -> np.ndarray:
    return np.append(np.asarray(canon), np.int32(bool(failed)))


def _cover_step_tables(windows, vcap: int) -> list:
    canon, failed = forest.init_forest(2 * vcap), jnp.bool_(False)
    prep, out = forest.WindowPrep(), []
    for s, d in windows:
        canon, failed, _tids = candidates.cover_forest_window(
            canon, failed, s, d, vcap, prep)
        out.append(_with_latch(canon, failed))
    return out


def _groups(windows, k: int = 4):
    windows = list(windows)
    return [windows[a:a + k] for a in range(0, len(windows), k)]


def _cc_group_tables(windows, vcap: int) -> list:
    canon, prep, out = forest.init_forest(vcap), forest.WindowPrep(), []
    for group in _groups(windows):
        canon, _tids, replay = forest.forest_superbatch(
            canon, group, vcap, prep)
        out += [replay.canon_np(k) for k in range(len(group))]
        out.append(np.asarray(canon))
    return out


def _cover_group_tables(windows, vcap: int) -> list:
    canon, failed = forest.init_forest(2 * vcap), jnp.bool_(False)
    prep, out = forest.WindowPrep(), []
    for group in _groups(windows):
        canon, failed, _tids, replay, fail_s = (
            candidates.cover_forest_superbatch(
                canon, failed, group, vcap, prep))
        out += [_with_latch(replay.canon_np(k), fail_s[k])
                for k in range(len(group))]
        out.append(_with_latch(canon, failed))
    return out


def _ragged(seed: int, scale: int, bipartite: bool = False):
    for (s, d), n in zip(
            kronecker_windows(seed, scale, 10, 512, bipartite), RAGGED):
        yield s[:n], d[:n]


def _odd_cycle_windows():
    edges = np.asarray(ODD_CYCLE, np.int32)
    return [(edges[a:a + 2, 0], edges[a:a + 2, 1])
            for a in range(0, len(edges), 2)]


def _four(**axes):
    return make_mesh(devices=jax.devices()[:4], **axes)


FOLDS = {
    "cc-step": lambda seed: cc_tables(seed),
    "cover-step": lambda seed: _cover_step_tables(
        kronecker_windows(seed, 11, 10, 512, bipartite=True), 1 << 11),
    "cc-group": lambda seed: _cc_group_tables(_ragged(seed, 12), 1 << 12),
    "cover-group": lambda seed: _cover_group_tables(
        _ragged(seed, 11, bipartite=True), 1 << 11),
    "cc-step-four-shards": lambda seed: cc_tables(
        seed, mesh=_four(n_edge_shards=1, n_vertex_shards=4)),
    "cc-step-edges-stacked": lambda seed: cc_tables(
        seed, mesh=_four(n_edge_shards=4)),
    "cc-step-edges-tree": lambda seed: cc_tables(
        seed, mesh=_four(n_edge_shards=4), tree=True),
    "cover-step-odd-cycle": lambda _seed: _cover_step_tables(
        _odd_cycle_windows(), 64),
    "cover-group-odd-cycle": lambda _seed: _cover_group_tables(
        _odd_cycle_windows(), 64),
}
