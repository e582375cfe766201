"""Upstream's ``DegreeDistribution`` replayed one event at a time: the
plain reference of the degree tests (``test_degree_step.py``,
``test_degree_serving.py``)."""

from __future__ import annotations

import numpy as np


def replay(src, dst, sign, n_ids: int, deg=None):
    """Both endpoints move by the event's sign, source first; a degree
    that would fall under zero stays at zero. ``deg``, where given, is
    the vector the events continue from (it is not written)."""
    deg = np.zeros(n_ids, np.int64) if deg is None else deg.copy()
    for s, d, c in zip(src.tolist(), dst.tolist(), sign.tolist()):
        for v in (s, d):
            deg[v] = max(0, deg[v] + c)
    return deg


def hist_of(deg, capacity=None):
    """degree -> number of vertices, degree 0 never tracked; with a
    capacity, degrees at or past its last bin count there."""
    d = deg[deg > 0]
    if capacity is not None:
        d = np.minimum(d, capacity - 1)
    return {int(k): int(c) for k, c in zip(*np.unique(d, return_counts=True))}
