"""The table's scatter as it was before its lanes were sorted (ISSUE 31),
kept as the plain reference the sorted one is held to, with what the
tests of the one-chip and of the vertex-sharded steps share: Kronecker
windows, and a reader of a lowered step's scatters and sorts."""

import re

import jax.numpy as jnp
import numpy as np
import pytest

from gelly_streaming_tpu.datasets import rmat_edges
from gelly_streaming_tpu.summaries import candidates, forest


def plain_scatter(self, table, idx, val):
    """``TableOps.scatter`` with the lanes in the order they come."""
    if self.shards > 1:
        mine, off = self._local(idx)
        idx = jnp.where(mine, off, self.rows)
    return table.at[idx].set(val, mode="drop")


def _clear_steps():
    forest._STEP_CACHE.clear()


@pytest.fixture
def unsorted_steps(monkeypatch):
    """-> a call that rebuilds every step over :func:`plain_scatter`."""
    def swap():
        monkeypatch.setattr(forest.TableOps, "scatter", plain_scatter)
        _clear_steps()

    yield swap
    monkeypatch.undo()
    _clear_steps()


def kronecker_windows(seed: int, scale: int, n: int, size: int,
                      bipartite: bool = False):
    """``n`` windows of ``size`` edges: a prefix of a Graph500 Kronecker
    stream (hubs at the low ids, so roots move and rows repeat among a
    window's old roots); sources even and targets odd if ``bipartite``."""
    src, dst = rmat_edges(n * size, scale, seed=seed)
    if bipartite:
        src, dst = src & ~1, dst | 1
    for k in range(n):
        cut = slice(k * size, (k + 1) * size)
        yield src[cut].astype(np.int32), dst[cut].astype(np.int32)


def cc_tables(seed: int, scale: int = 12, mesh=None, **fold) -> list:
    """The CC forest after each of ten Kronecker windows, every row
    (``fold``: ``forest_window``'s ``tree`` and ``degree``)."""
    vcap = 1 << scale
    canon, prep, out = forest.init_forest(vcap, mesh), forest.WindowPrep(), []
    for s, d in kronecker_windows(seed, scale, 10, 512):
        canon, _tids = forest.forest_window(canon, s, d, vcap, prep,
                                            mesh=mesh, **fold)
        out.append(np.asarray(canon))
    return out


def cover_tables(seed: int, scale: int = 11) -> list:
    """The cover forest after each of ten bipartite Kronecker windows."""
    vcap = 1 << scale
    canon, failed = forest.init_forest(2 * vcap), jnp.bool_(False)
    prep, out = forest.WindowPrep(), []
    for s, d in kronecker_windows(seed, scale, 10, 512, bipartite=True):
        canon, failed, _tids = candidates.cover_forest_window(
            canon, failed, s, d, vcap, prep)
        out.append(np.asarray(canon))
    assert not bool(failed)
    return out


_LOC_DEF = re.compile(r'^(#loc\d+) = loc\("([^"]*)"')
_LOC_USE = re.compile(r"loc\((#loc\d+)\)\s*$")


def _scoped(text: str, name: str):
    """``(op_name path, the op's own line, the line with its types)`` of
    every ``stablehlo.<name>`` in ``lowered.as_text(debug_info=True)``.
    An op with a region carries its types and its location on the line
    that closes the region."""
    lines = text.splitlines()
    paths = dict(m.groups() for m in map(_LOC_DEF.match, lines) if m)
    for i, ln in enumerate(lines):
        if f'"stablehlo.{name}"' not in ln:
            continue
        end = next(x for x in lines[i:] if _LOC_USE.search(x)
                   and (x is ln or x.lstrip().startswith("})")))
        yield paths[_LOC_USE.search(end).group(1)], ln, end


def scoped_ops(text: str, name: str) -> list:
    """``(op_name path, the op's own line)`` of every ``stablehlo.<name>``."""
    return [(path, ln) for path, ln, _end in _scoped(text, name)]


def scoped_lanes(text: str, name: str) -> list:
    """``(op_name path, lanes)`` of every ``stablehlo.<name>``: the
    leading dimension of its last operand, which is a gather's indices
    and a scatter's updates."""
    out = []
    for path, _ln, end in _scoped(text, name):
        operands = end[end.rindex(" : (") + 4:end.rindex(") -> ")]
        out.append((path, int(re.findall(r"tensor<(\d+)", operands)[-1])))
    return out


def ops_under(text: str, scope: str) -> list:
    """The line that carries the location (and, for an op with a region,
    the types) of every operation under the named scope ``scope``."""
    lines = text.splitlines()
    paths = dict(m.groups() for m in map(_LOC_DEF.match, lines) if m)
    used = ((_LOC_USE.search(ln), ln) for ln in lines)
    return [ln for m, ln in used
            if m and scope in paths.get(m.group(1), "").split("/")]


def _from_scope(path: str) -> str:
    """``jit(step)/[shard_map/]forest.group/...`` from its phase on."""
    return path[path.index("forest."):]


def assert_table_scatters_go_out_sorted(text: str) -> None:
    """The commit's two sets say ``indices_are_sorted``, each behind a
    sort under ``forest.sort``; the window-sized scatters stay as they
    were. Since ISSUE 37 the group scatters nothing: its two sorts (by
    root, and back by lane) stand under ``forest.group/forest.sort``."""
    scatters = scoped_ops(text, "scatter")
    said = [_from_scope(path) for path, ln in scatters
            if "indices_are_sorted = true" in ln]
    assert said == ["forest.commit/scatter", "forest.commit/scatter"]
    assert len(scatters) == 5
    assert all("unique_indices = false" in ln for _p, ln in scatters)
    sorts = [_from_scope(path) for path, _ln in scoped_ops(text, "sort")]
    assert sorts == ["forest.group/forest.sort/sort",
                     "forest.group/forest.sort/sort",
                     "forest.commit/forest.sort/sort",
                     "forest.commit/forest.sort/sort"]
