"""The least bytes a kernel has to move, from shapes alone.

A byte model is named by a per-layer metric's file (``"bytes_model"``)
and is the same whatever implements the kernel: it counts what the fold
needs, not what today's program does (which also fills a table-sized
scratch and chases pointers through several gathers)."""

from __future__ import annotations


def forest_step(*, rows: int, window_edges: int, touched: int) -> int:
    """One window folded into a carried int32 pointer table of ``rows``
    entries, publishing an immutable snapshot: one copy of the table
    (read and write, ``8 * rows``), the window's two int32 edge columns
    (``8 * window_edges``), and one read and one write per touched entry
    (``8 * touched``)."""
    if min(rows, window_edges, touched) < 0:
        raise ValueError("negative shape")
    return 8 * rows + 8 * window_edges + 8 * touched


MODELS = {"forest_step": forest_step}
