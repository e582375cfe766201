"""The plain reference: a min-rooted union-find over numpy arrays.

Independent of the program under test (it imports nothing of it): one
``parent`` table, vectorised find, and unions by hooking the larger
root under the smaller. Pointers only ever decrease, so the table is
acyclic and the root of a component is its smallest id: the same
canonical form the program's forest keeps, reached another way
(sequential hooking with retries here, a local label fixpoint and one
masked scatter there).
"""

from __future__ import annotations

import numpy as np


class UnionFind:
    def __init__(self, n: int):
        self.parent = np.arange(n, dtype=np.int32)

    def find(self, x: np.ndarray) -> np.ndarray:
        """Roots of ``x`` (any shape of ids); read-only."""
        parent = self.parent
        r = parent[x]
        while True:
            p = parent[r]
            if np.array_equal(p, r):
                return r
            r = p

    def union_edges(self, u: np.ndarray, v: np.ndarray) -> None:
        """Fold a batch of edges. Each round hooks, for every edge whose
        endpoints still differ, the larger root under the smaller; where
        several edges name one larger root the last write wins and the
        others retry, so the loop ends when every edge is satisfied."""
        parent = self.parent
        u = np.asarray(u)
        v = np.asarray(v)
        while len(u):
            ru = self.find(u)
            rv = self.find(v)
            # path compression for the endpoints just resolved
            parent[u] = ru
            parent[v] = rv
            open_ = ru != rv
            if not open_.any():
                return
            ru, rv = ru[open_], rv[open_]
            parent[np.maximum(ru, rv)] = np.minimum(ru, rv)
            u, v = ru, rv

    def connected(self, u: np.ndarray, v: np.ndarray) -> np.ndarray:
        return self.find(np.asarray(u)) == self.find(np.asarray(v))


def resolve_some(table: np.ndarray, ids: np.ndarray) -> np.ndarray:
    """Roots of ``ids`` in any pointer table (``table[r] == r`` at a
    root): how the comparison reads the program's final forest without
    the program's own resolver."""
    r = table[ids]
    while True:
        p = table[r]
        if np.array_equal(p, r):
            return r
        r = p


class ForestReference:
    """The union-find as a cell's reference for a program that carries
    a min-rooted pointer table: folds edges given in table rows, and
    holds the program's final table to its own (an algorithm module's
    ``Reference`` builds on it)."""

    def __init__(self, rows: int):
        self.uf = UnionFind(rows)
        self.touched = np.zeros(rows, bool)

    def union(self, u: np.ndarray, v: np.ndarray) -> None:
        self.uf.union_edges(u, v)
        self.touched[u] = True
        self.touched[v] = True

    def table(self) -> np.ndarray:
        """The reference's state in the program's form: a copy of its
        own pointer table (what the control publishes)."""
        return self.uf.parent.copy()

    def compare_final(self, table: np.ndarray) -> dict:
        """``table`` against the reference's current prefix: the same
        root on every touched row, and no untouched row moved."""
        touched = np.flatnonzero(self.touched)
        wrong = int(np.sum(resolve_some(table, touched)
                           != self.uf.find(touched)))
        moved = np.flatnonzero(
            table != np.arange(len(table), dtype=table.dtype))
        wrong += int(np.sum(~self.touched[moved]))
        return {"table_mismatches": wrong}
