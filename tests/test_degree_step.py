"""The window-sized degree step (``library/degrees.py`` ``degree_step``,
ISSUE 34) against a plain per-event replay of
``DegreeDistribution.java:83-131``: seeded streams with deletions and
clamps at zero, a vertex added, removed and added again in ONE window,
self-loops, padded windows, a fixed histogram capacity used and unused;
the column path against the record path; and the lowered step's shapes
(nothing but the table and its copy has a row per vertex, so the dense
step cannot come back unnoticed)."""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _degree_ref
from _degree_ref import hist_of
from gelly_streaming_tpu.core.stream import SimpleEdgeStream
from gelly_streaming_tpu.core.window import CountWindow
from gelly_streaming_tpu.datasets import IdentityDict
from gelly_streaming_tpu.library.degrees import (
    DegreeDistribution,
    degree_step,
)
from gelly_streaming_tpu.ops.segment import (
    segmented_reduce_generic,
    segmented_reduce_lanes,
)

N_IDS = 48


def replay(src, dst, sign, n_ids=N_IDS):
    return _degree_ref.replay(src, dst, sign, n_ids)


def events(seed: int, n: int, n_ids: int = N_IDS, p_add: float = 0.6):
    """Few ids and many deletions: degrees cross zero all the time, and
    one event in eight is a self-loop."""
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n_ids, n).astype(np.int32)
    dst = np.where(rng.random(n) < 0.125, src,
                   rng.integers(0, n_ids, n)).astype(np.int32)
    sign = np.where(rng.random(n) < p_add, 1, -1).astype(np.int32)
    return src, dst, sign


def column_stream(src, dst, sign, window: int, n_ids: int = N_IDS):
    return SimpleEdgeStream((src, dst, sign), window=CountWindow(window),
                            vertex_dict=IdentityDict(n_ids))


def fold_columns(src, dst, sign, window, **kw):
    dd = DegreeDistribution(**kw)
    for _ in dd.run_stream(column_stream(src, dst, sign, window)):
        pass
    return dd


# 300 events in windows of 1 (no order inside a window to keep), 7 and 37
# (padded to 8 and 64 lanes a column, and a ragged last window), 64 (no
# pad) and 300 (everything in one window)
@pytest.mark.parametrize("window", [1, 7, 37, 64, 300])
@pytest.mark.parametrize("seed", [3, 11, 2**31 + 7])
def test_the_step_folds_what_upstream_folds_event_by_event(window, seed):
    src, dst, sign = events(seed, 300)
    dd = fold_columns(src, dst, sign, window, hist_capacity=512)
    want = replay(src, dst, sign)
    got = dd.degrees()
    assert np.array_equal(got[:N_IDS], want) and not got[N_IDS:].any()
    assert dd.histogram() == hist_of(want)
    assert int(np.asarray(dd._hist)[0]) == 0      # degree 0 is not tracked


@pytest.mark.parametrize("window", [3, 16])
@pytest.mark.parametrize("seed", [5, 9])
def test_degrees_past_a_fixed_capacity_count_in_its_last_bin(window, seed):
    """The capacity USED: 200 additions on 4 ids pass 8 at every vertex;
    the table stays exact and the last bin holds every such vertex."""
    src, dst, sign = events(seed, 200, n_ids=4, p_add=0.9)
    dd = fold_columns(src, dst, sign, window, hist_capacity=8)
    want = replay(src, dst, sign)
    assert want.max() >= 8
    assert np.array_equal(dd.degrees()[:N_IDS], want)
    assert dd.histogram() == hist_of(want, capacity=8)
    assert np.asarray(dd._hist).shape == (8,)


@pytest.mark.parametrize("case,rows,want", [
    # deg(1) = deg(2) = 1 from the first window; then, in ONE window:
    ("added_removed_added", [(1, 2, 1), (1, 2, -1), (1, 2, -1), (1, 2, 1)],
     {1: 1, 2: 1}),
    # the clamp: two deletions at a degree of one, then an addition
    ("minus_minus_plus", [(1, 2, 1), (1, 2, -1), (1, 2, -1), (1, 2, 1),
                          (1, 2, 1)], {1: 2, 2: 2}),
    # a self-loop moves its vertex twice an event, in order
    ("self_loop", [(3, 3, 1), (3, 3, -1), (3, 3, -1), (3, 3, 1)], {3: 2}),
    # a deletion at a vertex that was never seen is ignored
    ("unseen_deleted", [(7, 8, -1), (7, 9, 1)], {7: 1, 9: 1}),
    # a vertex as target of a deletion and then source of an addition
    ("role_order", [(9, 5, -1), (5, 7, 1)], {5: 1, 7: 1}),
])
@pytest.mark.parametrize("window", ["one_window", "per_event"])
def test_order_inside_one_window_is_upstreams(case, rows, want, window):
    src, dst, sign = (np.asarray(c, np.int32) for c in zip(*rows))
    w = len(rows) if window == "one_window" else 1
    dd = fold_columns(src, dst, sign, w, hist_capacity=16)
    deg = dd.degrees()
    assert {v: int(deg[v]) for v in np.flatnonzero(deg)} == want
    assert np.array_equal(deg[:N_IDS], replay(src, dst, sign))
    assert dd.histogram() == hist_of(replay(src, dst, sign))


@pytest.mark.parametrize("window", [5, 64])
@pytest.mark.parametrize("seed", [2, 13])
@pytest.mark.parametrize("capacity", [None, 256], ids=["grown", "fixed"])
def test_column_path_and_record_path_give_the_same_tables(
        window, seed, capacity):
    src, dst, sign = events(seed, 260)
    by_column = fold_columns(src, dst, sign, window, hist_capacity=capacity)
    by_record = DegreeDistribution(
        CountWindow(window), vertex_dict=IdentityDict(N_IDS),
        hist_capacity=capacity)
    marks = ["+" if c > 0 else "-" for c in sign.tolist()]
    changes = [list(b) for b in by_record.run(
        zip(src.tolist(), dst.tolist(), marks))]
    assert np.array_equal(by_column.degrees(), by_record.degrees())
    assert by_column.histogram() == by_record.histogram()
    h_c, h_r = np.asarray(by_column._hist), np.asarray(by_record._hist)
    n = min(len(h_c), len(h_r))
    assert np.array_equal(h_c[:n], h_r[:n])
    assert not h_c[n:].any() and not h_r[n:].any()
    # the record path's change-only emission still adds up to the table
    final = {}
    for batch in changes:
        final.update(dict(batch))
    assert {d: c for d, c in final.items() if c} == by_record.histogram()


def test_a_fixed_capacity_changes_no_shape_after_the_first_window():
    src, dst, sign = events(4, 256)
    dd = DegreeDistribution(hist_capacity=64)
    sizes = set()
    before = degree_step._cache_size()
    for _ in dd.run_stream(column_stream(src, dst, sign, 32)):
        sizes.add((dd._deg.shape, dd._hist.shape))
    assert sizes == {((64,), (64,))}
    assert degree_step._cache_size() - before <= 1
    with pytest.raises(ValueError):
        DegreeDistribution(hist_capacity=1)


def test_published_tables_are_fresh_buffers_every_window():
    """Nothing is donated: the tables of window k are still whole after
    window k + 1 has folded (a published snapshot is immutable)."""
    src, dst, sign = events(6, 96)
    dd = DegreeDistribution(hist_capacity=64)
    kept = []
    for k, _ in enumerate(dd.run_stream(column_stream(src, dst, sign, 32))):
        kept.append((dd._deg, dd._hist))
        want = replay(src[:32 * (k + 1)], dst[:32 * (k + 1)],
                      sign[:32 * (k + 1)])
        for j, (deg, hist) in enumerate(kept):
            w = replay(src[:32 * (j + 1)], dst[:32 * (j + 1)],
                       sign[:32 * (j + 1)])
            assert np.array_equal(np.asarray(deg)[:N_IDS], w)
        assert np.array_equal(np.asarray(dd._deg)[:N_IDS], want)


# ---- the shapes of the lowered step ---------------------------------- #
VCAP, HCAP, W = 1 << 20, 256, 64


def _lowered():
    s = jax.ShapeDtypeStruct
    return degree_step.lower(
        s((VCAP,), jnp.int32), s((HCAP,), jnp.int32), s((W,), jnp.int32),
        s((W,), jnp.int32), s((W,), jnp.float32), s((W,), jnp.bool_))


def test_nothing_but_the_table_and_its_copy_has_a_row_per_vertex():
    """The cost follows the window: in the lowered step a tensor of
    ``vcap`` rows is the table argument, the gather's operand, the
    scatter's operand and result, and the returned table. An
    ``arange(vcap)``, a ``searchsorted`` over the id space or a
    ``where`` over ``vcap`` rows (the dense step) would add one."""
    text = _lowered().as_text()
    rows = f"{VCAP}x"
    ops = []
    for line in text.splitlines():
        if rows not in line:
            continue
        m = re.search(r'(stablehlo\.\w+|func\.func|return)', line)
        ops.append(m.group(1) if m else line.strip())
    # a scatter's type signature stands on the line that closes its
    # region: `}) : (tensor<vcap>, indices, updates) -> tensor<vcap>`
    closing = [o for o in ops if o.startswith("})")]
    named = [o for o in ops if not o.startswith("})")]
    assert sorted(named) == ["func.func", "return", "stablehlo.gather"], ops
    # three scatters in all: the table's and the histogram's two
    assert len(closing) == 1 and text.count('"stablehlo.scatter"(') == 3
    assert f"tensor<{VCAP}xi1>" not in text and "iota" not in "".join(
        line for line in text.splitlines() if rows in line)
    # every other tensor is a window's (2 * W lanes) or the histogram's
    sizes = {int(n) for n in re.findall(r"tensor<(\d+)x", text)}
    assert sizes <= {VCAP, HCAP, W, 2 * W, W - 1, 2 * W - 1, 1} | {
        2 ** k for k in range(8)} | {2 ** k - 1 for k in range(8)}, sizes


def test_the_compiled_step_keeps_to_the_window_too():
    """After XLA's passes (the CPU's here; the TPU's compile is checked
    on the chip by tools/trace_phases.py): the instructions with a
    ``vcap``-row operand or result are parameter, copy, gather, scatter
    and the root tuple."""
    text = _lowered().compile().as_text()
    kinds = set()
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%\S+ = \S+ ([a-z\-]+)\(", line)
        if m and f"[{VCAP}]" in line:
            kinds.add(m.group(1))
    assert kinds <= {"parameter", "copy", "gather", "scatter", "tuple",
                     "fusion"}, kinds
    assert "copy" in kinds and "scatter" in kinds


# ---- ops/segment.py: the window-sized form --------------------------- #
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_lanes_form_agrees_with_the_per_segment_form(seed):
    rng = np.random.default_rng(seed)
    n, segs = 96, 13
    ids = jnp.asarray(rng.integers(0, segs, n).astype(np.int32))
    mask = jnp.asarray(rng.random(n) < 0.8)
    vals = jnp.asarray(rng.integers(-3, 4, n).astype(np.int32))

    def combine(a, b):
        return a[0] + b[0], jnp.maximum(b[1], a[1] + b[0])

    init = (vals, jnp.zeros_like(vals))
    (s, m), nonempty = segmented_reduce_generic(init, ids, mask, segs, combine)
    sorted_ids, (ls, lm), last = segmented_reduce_lanes(
        init, ids, mask, combine)
    rows = np.asarray(sorted_ids)[np.asarray(last)]
    assert np.array_equal(rows, np.flatnonzero(np.asarray(nonempty)))
    assert len(set(rows.tolist())) == len(rows)         # one lane a segment
    assert np.array_equal(np.asarray(ls)[np.asarray(last)],
                          np.asarray(s)[rows])
    assert np.array_equal(np.asarray(lm)[np.asarray(last)],
                          np.asarray(m)[rows])
    # arrival order inside a segment: a plain ordered fold agrees
    for seg in rows.tolist():
        x = 0
        for v, k, ok in zip(np.asarray(vals).tolist(),
                            np.asarray(ids).tolist(),
                            np.asarray(mask).tolist()):
            if ok and k == seg:
                x = max(0, x + v)
        i = int(np.flatnonzero(np.asarray(last)
                               & (np.asarray(sorted_ids) == seg))[0])
        assert max(int(lm[i]), 0 + int(ls[i])) == x


def test_values_of_another_rank_take_the_sort_by_index():
    """``sort_by_segment`` rides rank-1 columns on one variadic sort and
    gathers anything else by the sorted order: both give arrival order
    inside a segment."""
    from gelly_streaming_tpu.ops.segment import sort_by_segment

    ids = jnp.asarray([2, 0, 2, 1, 0], jnp.int32)
    mask = jnp.asarray([True, True, True, False, True])
    flat = jnp.arange(5, dtype=jnp.int32)
    wide = jnp.stack([flat, flat + 10], axis=1)
    a = sort_by_segment(ids, mask, flat)
    b = sort_by_segment(ids, mask, wide)
    assert np.asarray(a[0]).tolist() == np.asarray(b[0]).tolist()
    assert np.asarray(a[1]).tolist() == [True] * 4 + [False]
    assert np.asarray(a[1]).tolist() == np.asarray(b[1]).tolist()
    assert np.asarray(a[2]).tolist() == [1, 4, 0, 2, 3]
    assert np.asarray(b[2])[:, 0].tolist() == [1, 4, 0, 2, 3]
