"""Connected components served WITH their sizes (``ComponentSizeQuery``
beside ``ConnectedQuery``).

The program side is ``ConnectedComponents(component_sizes=True)`` with
its carry at ``"auto"`` (the pointer forest on an accelerator, a size
table carried beside it and folded by the same per-window step) and its
servable, which publishes ``labels`` and ``sizes`` in one snapshot. The
reference side is the benchmark's union-find over the same edges plus a
size vector of its own, kept window by window from its OWN roots.
"""

from __future__ import annotations

import numpy as np

# A program without the carried size table (the parent of the PR that
# added this file) fails HERE, when the harness loads the module: before
# the stream is generated and long before a table is allocated. By then
# the harness has started the backend, so this loads nothing new.
from gelly_streaming_tpu.summaries.forest import fold_sizes  # noqa: F401

from ..lib.unionfind import ForestReference
from . import cc

#: the payload key of the published snapshot that holds the carried
#: table the harness waits for and reads back (``sizes`` comes out of
#: the same program: ready on one is ready on both)
PAYLOAD_KEY = "labels"

#: ``records`` rows are ``(kind, u, v)``; a size query's ``v`` is ``u``
SIZE_OF, CONNECTED = 0, 1


def build(config: dict):
    """The aggregation the server serves, built as a user builds it."""
    from gelly_streaming_tpu.library import ConnectedComponents

    return ConnectedComponents(**config["aggregation_args"])


def chip_paths_problem(agg, server):
    """What, if anything, shows that the chip's paths did not run."""
    problem = cc.chip_paths_problem(agg, server)
    if problem is None and "sizes" not in server.snapshot().payload:
        problem = "the published snapshot holds no size table"
    return problem


def table_rows(config: dict) -> int:
    return int(config["id_space"])


def fold_shape(config: dict, src, dst) -> dict:
    """The shapes one window gives a byte model of the fold: TWO carried
    int32 tables of ``id_space`` rows, each copied once a window."""
    return dict(cc.fold_shape(config, src, dst),
                rows=2 * table_rows(config))


def draw_queries(rng, n: int, recent_src, recent_dst, config: dict):
    """``n`` queries, three quarters ``ComponentSizeQuery(v)`` and a
    quarter ``ConnectedQuery(u, v)`` drawn as ``cc.draw_queries`` draws
    them. Half the sized vertices are endpoints of edges of the windows
    most recently handed to the system (a client asks how big the
    cluster is that an account has just joined, and an answer from a
    staler prefix than its stamp then shows), half are uniform ids
    (mostly vertices the stream never touches: components of one)."""
    from gelly_streaming_tpu.serving import ComponentSizeQuery

    n_size = 3 * (n // 4)
    k = n_size // 2
    edges = rng.integers(0, len(recent_src), k)
    vs = np.concatenate([
        np.where(rng.integers(0, 2, k) == 0,
                 recent_src[edges], recent_dst[edges]),
        rng.integers(0, int(config["id_space"]), n_size - k),
    ]).astype(np.int64)
    pairs, uv = cc.draw_queries(
        rng, n - n_size, recent_src, recent_dst, config)
    queries = [ComponentSizeQuery(v) for v in vs.tolist()] + pairs
    records = np.concatenate([
        np.stack([np.full(n_size, SIZE_OF), vs, vs], axis=1),
        np.concatenate([np.full((len(uv), 1), CONNECTED), uv], axis=1)])
    return queries, records


def answer_value(answer) -> int:
    return int(answer.value)


# ---- the reference side: nothing below touches the program ---------- #
class Reference(ForestReference):
    """The benchmark's union-find over the same windows, in order, and
    a size for every root: every id starts as a component of one; a
    window's fold takes the distinct roots of its endpoints BEFORE the
    unions and their roots AFTER them, and writes, at each root after,
    the sum of the sizes of the roots before that ended under it. A
    root that was absorbed keeps a stale size, which nothing reads."""

    def __init__(self, config: dict):
        super().__init__(table_rows(config))
        self.size = np.ones(table_rows(config), np.int64)

    def fold(self, src, dst) -> None:
        before = np.unique(self.uf.find(np.concatenate([src, dst])))
        self.union(src, dst)
        after, under = np.unique(self.uf.find(before), return_inverse=True)
        total = np.zeros(len(after), np.int64)
        np.add.at(total, under, self.size[before])
        self.size[after] = total

    def expected(self, records):
        """What each recorded query has to answer at the current prefix."""
        kind, u, v = records[:, 0], records[:, 1], records[:, 2]
        ru = self.uf.find(u)
        return np.where(kind == SIZE_OF, self.size[ru],
                        ru == self.uf.find(v)).astype(np.int64)
