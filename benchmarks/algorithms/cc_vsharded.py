"""Streaming connected components over a pointer forest that no one
chip holds, served (``ConnectedQuery``).

The program side is ``ConnectedComponents()`` with its carry at
``"auto"`` under ``StreamContext(mesh=make_mesh(n_vertex_shards=...))``:
the forest is split by vertex id in contiguous blocks over the mesh's
``vertices`` axis, one block a chip, and the published table is that
sharded array. The configuration's ``layout`` says how many chips. The
reference side is the benchmark's union-find over the same edges, whole
on the host.
"""

from __future__ import annotations

import numpy as np

from ..lib.unionfind import ForestReference
from .cc import PAYLOAD_KEY, answer_value, build, draw_queries  # noqa: F401


#: the run's configuration: ``make_stream`` notes it for
#: ``chip_paths_problem``, which the harness calls with the aggregation
#: and the server alone
_RUN: dict = {}


def _chips(config: dict) -> int:
    return int(config["layout"]["chips"])


def make_stream(config: dict, source):
    """Count windows over ``IdentityDict``, under a mesh whose
    ``vertices`` axis has the layout's chips. A program without that
    axis fails here, at the import, before anything is allocated."""
    from gelly_streaming_tpu.core.stream import StreamContext
    from gelly_streaming_tpu.parallel.mesh import VERTEX_AXIS, make_mesh

    from ..lib import cellrun

    mesh = make_mesh(n_edge_shards=1, n_vertex_shards=_chips(config))
    assert mesh.shape[VERTEX_AXIS] == _chips(config)
    _RUN["config"] = config
    return cellrun.default_stream(config, source, StreamContext(mesh=mesh))


def layout_problem(table, config: dict):
    """Why ``table`` is not laid out as the configuration says (one
    block of ``id_space / chips`` rows on each of ``chips`` devices, no
    row twice), or None."""
    chips, rows = _chips(config), table_rows(config) // _chips(config)
    shards = getattr(table, "addressable_shards", None)
    if shards is None:
        return f"the published table is a {type(table).__name__} on the host"
    devices = {s.device for s in shards}
    if len(devices) != chips:
        return (f"the published table lies on {len(devices)} devices, "
                f"not {chips}")
    spans = sorted((s.index[0].start or 0, s.data.shape[0]) for s in shards)
    if spans != [(k * rows, rows) for k in range(chips)]:
        return (f"the published table's blocks are {spans[:8]}, not {chips} "
                f"blocks of {rows} rows: it is replicated or gathered")
    return None


def chip_paths_problem(agg, server):
    """What, if anything, shows that the chips' paths did not run."""
    if str(agg._cc_mode) != "forest":
        return f"carry is {agg._cc_mode!r}, not the forest"
    if server.engine.prefer_host:
        return "the query engine answers on the host"
    snap = server.snapshot()
    if snap is None:
        return "nothing was published"
    return layout_problem(snap.payload[PAYLOAD_KEY], _RUN["config"])


def table_rows(config: dict) -> int:
    return int(config["id_space"])


def fold_shape(config: dict, src, dst) -> dict:
    """The shapes one window gives a byte model of the fold, as ONE chip
    sees them: its block of the table, and the window's edges and
    touched ids whole (every chip sees every lane)."""
    return {"rows": table_rows(config) // _chips(config),
            "window_edges": len(src),
            "touched": len(np.unique(np.concatenate([src, dst])))}


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
