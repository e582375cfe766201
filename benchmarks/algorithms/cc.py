"""Streaming connected components, served (``ConnectedQuery``).

The program side is ``ConnectedComponents()`` with its carry at
``"auto"`` (the pointer forest on an accelerator) and its servable; the
reference side is the benchmark's union-find over the same edges.
"""

from __future__ import annotations

import numpy as np

from ..lib.unionfind import ForestReference

#: the payload key of the published snapshot that holds the carried table
PAYLOAD_KEY = "labels"


def build(config: dict):
    """The aggregation the server serves, built as a user builds it."""
    from gelly_streaming_tpu.library import ConnectedComponents

    return ConnectedComponents(**config.get("aggregation_args", {}))


def chip_paths_problem(agg, server):
    """What, if anything, shows that the chip's paths did not run."""
    if str(agg._cc_mode) != "forest":
        return f"carry is {agg._cc_mode!r}, not the forest"
    if server.engine.prefer_host:
        return "the query engine answers on the host"
    return None


def table_rows(config: dict) -> int:
    return int(config["id_space"])


def fold_shape(config: dict, src, dst) -> dict:
    """The shapes one window gives a byte model of the fold."""
    return {"rows": table_rows(config), "window_edges": len(src),
            "touched": len(np.unique(np.concatenate([src, dst])))}


def draw_queries(rng, n: int, recent_src, recent_dst, config: dict):
    """``n`` (u, v) pairs with both answers likely, in thirds as
    ``chip_smoke.make_queries`` draws them: edges of the stream
    (connected once their window has folded, not before), two unrelated
    endpoints, and uniform ids (mostly vertices the stream never
    touches). The stream's edges are drawn from the windows most
    recently handed to the system: a client asks about what it has just
    written, and an answer computed from a staler prefix than its stamp
    then shows."""
    from gelly_streaming_tpu.serving import ConnectedQuery

    k = n // 3
    m = len(recent_src)
    id_bound = int(config["id_space"])
    i, j, l = (rng.integers(0, m, k) for _ in range(3))
    us = np.concatenate(
        [recent_src[i], recent_src[j], rng.integers(0, id_bound, n - 2 * k)]
    ).astype(np.int64)
    vs = np.concatenate(
        [recent_dst[i], recent_dst[l], rng.integers(0, id_bound, n - 2 * k)]
    ).astype(np.int64)
    queries = [ConnectedQuery(u, v) for u, v in zip(us.tolist(), vs.tolist())]
    return queries, np.stack([us, vs], axis=1)


def answer_value(answer) -> int:
    return int(bool(answer.value))


# ---- the reference side: nothing below touches the program ---------- #
class Reference(ForestReference):
    """The benchmark's union-find over the same windows, in order."""

    def __init__(self, config: dict):
        super().__init__(table_rows(config))

    def fold(self, src, dst) -> None:
        self.union(src, dst)

    def expected(self, records):
        """What each recorded query has to answer at the current prefix."""
        return self.uf.connected(records[:, 0], records[:, 1]).astype(np.int64)
