"""The fully dynamic degree distribution, served (``DegreeQuery``,
``DegreeCountQuery``): upstream's ``example/DegreeDistribution.java``
over a stream of edge additions and deletions.

The program side is ``DegreeDistribution(hist_capacity=...)`` on its
column path (``SimpleEdgeStream`` over ``(src, dst, +1|-1)`` column
chunks) and its servable, which publishes the degree table and the
degree histogram in one snapshot. The reference side is a numpy degree
vector folded event by event in stream order with upstream's clamp at
zero. The stream's two columns carry the event's sign packed in the
first (``generators/graph500_dynamic.unpack``); the stream handed to
the program, the reference and the queries all decode it there, and the
program never sees the packed column.
"""

from __future__ import annotations

import numpy as np

# A program without the histogram's query class (the parent of the PR
# that added this file) fails HERE, when the harness loads the module:
# before the stream is generated and long before a table is allocated.
# By then the harness has started the backend, so this loads nothing new.
from gelly_streaming_tpu.serving import DegreeCountQuery, DegreeQuery

from ..generators.graph500_dynamic import unpack

#: the payload key of the published snapshot that holds the carried table
PAYLOAD_KEY = "deg"

#: ``records`` rows are ``(kind, key)``
DEGREE_OF, COUNT_AT = 0, 1
#: a DegreeCountQuery asks for degree floor(2^x), x uniform in [0, this)
COUNT_LOG2_BOUND = 12


def build(config: dict):
    """The aggregation the server serves, built as a user builds it."""
    from gelly_streaming_tpu.library.degrees import DegreeDistribution

    return DegreeDistribution(**config["aggregation_args"])


class _Unpacked:
    """The benchmark's window source with every chunk decoded: what the
    program ingests is ``(src, dst, +1|-1)`` columns."""

    def __init__(self, source):
        self._source = source

    def iter_chunks(self):
        for src, dst in self._source.iter_chunks():
            yield unpack(src, dst)


def make_stream(config: dict, source):
    from ..lib import cellrun

    return cellrun.default_stream(config, _Unpacked(source))


def chip_paths_problem(agg, server):
    """What, if anything, shows that the chip's paths did not run."""
    if server.engine.prefer_host:
        return "the query engine answers on the host"
    if agg.hist_capacity is None:
        return "the histogram grows with the stream: its step recompiles"
    return None


def table_rows(config: dict) -> int:
    return int(config["id_space"])


def fold_shape(config: dict, src, dst) -> dict:
    """The shapes one window gives a byte model of the fold."""
    ids, dst, _sign = unpack(src, dst)
    return {"rows": table_rows(config), "window_edges": len(ids),
            "touched": len(np.unique(np.concatenate([ids, dst])))}


def draw_queries(rng, n: int, recent_src, recent_dst, config: dict):
    """``n`` queries, three quarters ``DegreeQuery(v)`` and a quarter
    ``DegreeCountQuery(d)``. The vertices in thirds: endpoints of
    additions of the windows most recently handed to the system,
    endpoints of their deletions (a client asks about what it has just
    written, and an answer from a staler prefix than its stamp then
    shows), and uniform ids (mostly vertices the stream never touches).
    The degrees are ``floor(2^x)``, ``x`` uniform: the histogram of a
    power-law graph falls by orders of magnitude over them."""
    ids, dst, sign = unpack(recent_src, recent_dst)
    n_v = 3 * (n // 4)
    k = n_v // 3
    added, deleted = np.flatnonzero(sign > 0), np.flatnonzero(sign < 0)
    if not len(deleted):
        deleted = added
    events = np.concatenate([added[rng.integers(0, len(added), k)],
                             deleted[rng.integers(0, len(deleted), k)]])
    vs = np.concatenate([
        np.where(rng.integers(0, 2, 2 * k) == 0, ids[events], dst[events]),
        rng.integers(0, int(config["id_space"]), n_v - 2 * k),
    ]).astype(np.int64)
    ds = np.floor(2.0 ** rng.uniform(0, COUNT_LOG2_BOUND, n - n_v)).astype(
        np.int64)
    queries = ([DegreeQuery(v) for v in vs.tolist()]
               + [DegreeCountQuery(d) for d in ds.tolist()])
    records = np.concatenate([
        np.stack([np.full(n_v, DEGREE_OF), vs], axis=1),
        np.stack([np.full(n - n_v, COUNT_AT), ds], axis=1)])
    return queries, records


def answer_value(answer) -> int:
    return int(answer.value)


# ---- the reference side: nothing below touches the program ---------- #
class Reference:
    """A degree vector folded one event at a time, in stream order:
    each event moves both endpoints by its sign, source first, and a
    degree that would fall under zero stays at zero (upstream removes
    the vertex at zero and ignores a deletion at a vertex it does not
    hold). Most of a window's vertices cannot reach zero inside it and
    are summed at once (``np.add.at``); the others, a few thousand a
    window, are walked event by event. The histogram is the count of
    vertices at each degree above zero, kept current from the degrees
    of the window's vertices before and after it (a ``np.bincount`` of
    the whole vector, 2 GiB of it, for every stamped window would take
    minutes); ``compare_final`` holds it to the whole vector once."""

    def __init__(self, config: dict):
        self.deg = np.zeros(table_rows(config), np.int64)
        self.hist = np.zeros(2, np.int64)     # index: degree; [0] unused
        self._walked = np.zeros(len(self.deg), bool)   # scratch of fold()
        self.hist_capacity = int(config["aggregation_args"]["hist_capacity"])

    def fold(self, src, dst) -> None:
        ids, dst, sign = unpack(src, dst)
        verts = np.stack([ids, dst], axis=1).reshape(-1).astype(np.int64)
        signs = np.repeat(sign.astype(np.int64), 2)
        touched = np.unique(verts)
        before = self.deg[touched]
        # a vertex can only meet the clamp if the window deletes at it
        # more often than its degree stands at
        at, times = np.unique(verts[signs < 0], return_counts=True)
        walked = at[self.deg[at] < times]
        self._walked[walked] = True
        walk = self._walked[verts]
        self._walked[walked] = False
        np.add.at(self.deg, verts[~walk], signs[~walk])
        running = dict(zip(walked.tolist(), self.deg[walked].tolist()))
        for v, s in zip(verts[walk].tolist(), signs[walk].tolist()):
            running[v] = max(0, running[v] + s)
        if running:
            self.deg[np.fromiter(running, np.int64, len(running))] = (
                np.fromiter(running.values(), np.int64, len(running)))
        after = self.deg[touched]
        top = int(max(before.max(initial=0), after.max(initial=0)))
        if top >= len(self.hist):
            self.hist = np.concatenate(
                [self.hist, np.zeros(2 * top - len(self.hist), np.int64)])
        self.hist -= np.bincount(before, minlength=len(self.hist))
        self.hist += np.bincount(after, minlength=len(self.hist))
        self.hist[0] = 0

    def expected(self, records):
        """What each recorded query has to answer at the current prefix."""
        kind, key = records[:, 0], records[:, 1]
        of = kind == DEGREE_OF
        bins = np.where(~of & (key < len(self.hist)), key, 0)  # hist[0]: 0
        return np.where(of, self.deg[np.where(of, key, 0)], self.hist[bins])

    def table(self):
        return self.deg.astype(np.int32)

    def compare_final(self, table) -> dict:
        """The final device degree table against the vector, row by row;
        rows of it that reached the histogram's capacity (its last bin
        would then hold more than one degree); and, of the reference's
        own, the kept histogram against a count of the whole vector."""
        n = min(len(table), len(self.deg))
        counted = np.bincount(self.deg, minlength=len(self.hist))
        counted[0] = 0
        kept = np.pad(self.hist, (0, len(counted) - len(self.hist)))
        return {
            "table_mismatches": int(np.sum(table[:n] != self.deg[:n])
                                    + np.sum(self.deg[n:] != 0)
                                    + np.sum(table[n:] != 0)),
            "hist_overflow": int(np.sum(table >= self.hist_capacity)),
            "reference_hist_drift": int(np.sum(counted != kept)),
        }
