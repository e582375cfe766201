"""The plain reference of connected components served with their sizes:
a sequential union-find, one edge at a time in stream order, that keeps
a member count at every root. Plain Python and numpy; it imports
nothing of the program (nor of the benchmark), and is what both the
program's size table (``tests/test_component_sizes.py``) and the
benchmark's own reference (``tests/bench_harness/test_perf_ccsize.py``)
are held to."""

from __future__ import annotations

import numpy as np


class SizeRef:
    """Every id of ``[0, n)`` is a vertex and starts as its own
    component of size 1."""

    def __init__(self, n: int):
        self.parent = list(range(n))
        self.count = [1] * n          # exact at a root, stale elsewhere

    def find(self, x: int) -> int:
        parent = self.parent
        while parent[x] != x:
            parent[x] = parent[parent[x]]     # path halving
            x = parent[x]
        return x

    def add(self, u: int, v: int) -> None:
        """One edge: a duplicate or a self-loop changes nothing."""
        ru, rv = self.find(u), self.find(v)
        if ru == rv:
            return
        if self.count[ru] < self.count[rv]:
            ru, rv = rv, ru
        self.parent[rv] = ru
        self.count[ru] += self.count[rv]

    def fold(self, src, dst) -> None:
        """A window, edge by edge in the order given."""
        for u, v in zip(np.asarray(src).tolist(), np.asarray(dst).tolist()):
            self.add(u, v)

    def size(self, v: int) -> int:
        return self.count[self.find(v)]

    def sizes(self) -> np.ndarray:
        """The component size of every vertex."""
        return np.asarray([self.size(v) for v in range(len(self.parent))],
                          np.int64)

    def connected(self, u: int, v: int) -> bool:
        return self.find(u) == self.find(v)
