"""Streaming bipartiteness check, served (``BipartiteQuery``).

The program side is ``BipartitenessCheck()`` with its carry at
``"auto"`` (the signed double cover as a pointer forest on an
accelerator). The reference side unions the same double cover with the
benchmark's union-find: base edge (u, v) joins (u,+)~(v,-) and
(u,-)~(v,+), with (v,+) = v and (v,-) = v + n; the graph is bipartite
while no vertex's two cover nodes share a component.
"""

from __future__ import annotations

import numpy as np

from ..lib.unionfind import ForestReference

PAYLOAD_KEY = "cover"


def build(config: dict):
    from gelly_streaming_tpu.library import BipartitenessCheck

    return BipartitenessCheck(**config.get("aggregation_args", {}))


def chip_paths_problem(agg, server):
    if str(agg._bp_mode) != "forest":
        return f"carry is {agg._bp_mode!r}, not the cover forest"
    if server.engine.prefer_host:
        return "the query engine answers on the host"
    return None


def table_rows(config: dict) -> int:
    return 2 * int(config["id_space"])


def fold_shape(config: dict, src, dst) -> dict:
    """Two cover rows per id and two cover edges per stream edge."""
    return {"rows": table_rows(config), "window_edges": 2 * len(src),
            "touched": 2 * len(np.unique(np.concatenate([src, dst])))}


def draw_queries(rng, n: int, recent_src, recent_dst, config: dict):
    """The verdict is graph-global: every query of a batch is the same
    question. The record keeps nothing but a placeholder column."""
    from gelly_streaming_tpu.serving import BipartiteQuery

    rng.integers(0, 2, 1)  # one draw a batch keeps the generator's stride
    return [BipartiteQuery() for _ in range(n)], np.zeros((n, 2), np.int64)


def answer_value(answer) -> int:
    """1 bipartite, 0 not; a witness beside a 'bipartite' verdict (or
    none beside the other) is a malformed answer and reads -1."""
    doc = answer.value
    ok = bool(doc["bipartite"])
    if ok != (doc["witness"] is None):
        return -1
    return int(ok)


class Reference(ForestReference):
    """The double cover unioned with the benchmark's union-find; the
    verdict latches when a window joins some endpoint's two cover nodes."""

    def __init__(self, config: dict):
        super().__init__(table_rows(config))
        self._n = np.int32(config["id_space"])
        self.odd_cycle = False

    def fold(self, src, dst) -> None:
        n = self._n
        self.union(np.concatenate([src, src + n]),
                   np.concatenate([dst + n, dst]))
        if not self.odd_cycle:
            ends = np.concatenate([src, dst])
            self.odd_cycle = bool(
                np.any(self.uf.find(ends) == self.uf.find(ends + n)))

    def expected(self, records):
        return np.full(len(records), 0 if self.odd_cycle else 1, np.int64)
