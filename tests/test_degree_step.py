"""The window-sized degree step (``library/degrees.py`` ``degree_step``,
ISSUE 34) against a plain per-event replay of
``DegreeDistribution.java:83-131``: seeded streams with deletions and
clamps at zero, a vertex added, removed and added again in ONE window,
self-loops, padded windows, a fixed histogram capacity used and unused;
the histogram window by window through its head's contraction, its
tail's slab and the slab's fallback; the column path against the record
path; and the lowered step's shapes (nothing but the table and its copy
has a row per vertex, so the dense step cannot come back unnoticed)."""

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
from gelly_streaming_tpu.library import degrees
from gelly_streaming_tpu.library.degrees import (
    DegreeDistribution,
    degree_step,
)
from gelly_streaming_tpu.ops.segment import (
    segmented_reduce_generic,
    segmented_reduce_lanes,
)

N_IDS = 48


def replay(src, dst, sign, n_ids=N_IDS, deg=None):
    return _degree_ref.replay(src, dst, sign, n_ids, deg)


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


# ---- the histogram's head, slab and fallback ------------------------- #
HEAD_BINS = degrees.HEAD[0] * degrees.HEAD[1]


def hub_events(seed: int, n_hubs: int, n_mixed: int,
               ramp: int = HEAD_BINS // 2 + 400):
    """``n_hubs`` vertices raised past the head by ``ramp * n_hubs``
    additions between two of them, then ``n_mixed`` events of a hub or
    any id against any id, a third of them deletions."""
    rng = np.random.default_rng(seed)
    hubs = rng.choice(N_IDS, n_hubs, replace=False).astype(np.int32)
    pairs = hubs[rng.integers(0, n_hubs, (ramp * n_hubs, 2))]
    src = np.where(rng.random(n_mixed) < 0.5,
                   hubs[rng.integers(0, n_hubs, n_mixed)],
                   rng.integers(0, N_IDS, n_mixed)).astype(np.int32)
    dst = rng.integers(0, N_IDS, n_mixed).astype(np.int32)
    sign = np.where(rng.random(n_mixed) < 2 / 3, 1, -1).astype(np.int32)
    return (np.concatenate([pairs[:, 0], src]),
            np.concatenate([pairs[:, 1], dst]),
            np.concatenate([np.ones(len(pairs), np.int32), sign]))


def tail_lanes(before, after, capacity):
    """The lanes of a window whose bin lies past the head: a touched
    vertex's old degree and its new one, clipped as the step clips."""
    n_head = min(capacity, HEAD_BINS)
    return sum(int(np.sum((np.minimum(d, capacity - 1) >= n_head) & (d > 0)))
               for d in (before, after))


# case: (events, window, hist_capacity, what the windows' tail lanes show)
HIST_CASES = {
    # every lane in the head of a histogram that has a tail
    "all_head": (lambda: events(21, 600), 37, 2 * HEAD_BINS, "none"),
    # hubs past the head, at most 8 tail lanes a window over 2 blocks
    "tail_on_slab": (lambda: hub_events(22, 4, 4096), 1024, 2 * HEAD_BINS,
                     "slab"),
    # 6 hubs in windows of 256 events: 12 tail lanes in one block of 8
    # slots, so the block overflows and the fallback counts
    "slab_overflow": (lambda: hub_events(23, 6, 2048), 256, 2 * HEAD_BINS,
                      "overflow"),
    # the head is the whole histogram: no tail, hubs clipped in its last
    # bin
    "hcap_is_the_head": (lambda: hub_events(24, 4, 1024), 64, HEAD_BINS,
                         "none"),
    # a histogram under the head and no multiple of its rows' width
    "ragged_head": (lambda: hub_events(25, 3, 1024, ramp=700), 64, 1000,
                    "none"),
    # a tail no multiple of the head's width, hubs clipped into its
    # last bin
    "ragged_tail_clipped": (lambda: hub_events(26, 3, 1024), 128,
                            HEAD_BINS + 100, "slab"),
    # degrees that reach zero and meet the clamp all the time
    "deletions_to_zero": (lambda: events(27, 900, n_ids=8, p_add=0.45), 16,
                          1 << 13, "none"),
}


@pytest.mark.parametrize("case", list(HIST_CASES))
def test_the_histogram_is_exact_window_by_window(case):
    """Every window's histogram against upstream's event-by-event replay,
    through the head's contraction, the tail's slab and its fallback."""
    make, window, capacity, tail = HIST_CASES[case]
    src, dst, sign = make()
    dd = DegreeDistribution(hist_capacity=capacity)
    want = np.zeros(N_IDS, np.int64)
    lanes, zeroed = [], 0
    for k, _ in enumerate(dd.run_stream(
            column_stream(src, dst, sign, window))):
        part = slice(k * window, (k + 1) * window)
        before = want
        want = replay(src[part], dst[part], sign[part], deg=before)
        touched = np.unique(np.concatenate([src[part], dst[part]]))
        lanes.append(tail_lanes(before[touched], want[touched], capacity))
        zeroed += int(np.sum((before > 0) & (want == 0)))
        assert dd.histogram() == hist_of(want, capacity=capacity), k
        assert np.array_equal(dd.degrees()[:N_IDS], want), k
    assert k + 1 == -(-len(src) // window)
    # the windows took the path the case is for
    slots = degrees.SLAB[1]
    blocks = -(-4 * window // degrees.SLAB[0])
    if tail == "none":
        assert max(lanes) == 0
    elif tail == "slab":      # no block can hold more than its slots
        assert 0 < max(lanes) <= slots
    else:                     # some block must hold more
        assert max(lanes) > blocks * slots
    if case in ("hcap_is_the_head", "ragged_head", "ragged_tail_clipped"):
        assert want.max() >= capacity      # the last bin holds the hubs
    if case == "deletions_to_zero":
        assert zeroed > 0


@pytest.mark.parametrize("capacity,n,n_tail,one_block,fits", [
    (37, 64, 0, False, True),      # the head is the whole histogram
    (1000, 256, 0, False, True),   # and no multiple of its rows' width
    (HEAD_BINS + 3000, 8192, 0, False, True),    # a tail, none in use
    (HEAD_BINS + 3000, 8192, 8, True, True),     # one block's 8 slots
    (HEAD_BINS + 3000, 8192, 9, True, False),    # 9 in it: fallback
    (HEAD_BINS + 3000, 8192, 24, False, True),   # 6 a block of 4
    (HEAD_BINS + 3000, 6000, 60, False, False),  # 3 ragged blocks
])
def test_count_bins_is_the_scatter_add_it_replaces(
        capacity, n, n_tail, one_block, fits):
    """``count_bins`` against ``np.add.at``: random head lanes, lanes at
    and past the capacity, and ``n_tail`` lanes past the head, side by
    side (dealt over the slab's blocks, lane ``i`` to block
    ``i % blocks``) or all in one block, so that the slab fits or the
    fallback counts."""
    rng = np.random.default_rng(capacity + n + n_tail)
    n_head = min(capacity, HEAD_BINS)
    bins = rng.integers(0, n_head, n)
    bins[rng.random(n) < 0.1] = capacity + rng.integers(0, 3)
    blocks = -(-n // degrees.SLAB[0])
    # neighbouring lanes go to neighbouring blocks; every ``blocks``-th
    # lane to one
    at = rng.integers(0, n - blocks * n_tail) + (
        blocks if one_block else 1) * np.arange(n_tail)
    bins[at] = rng.integers(n_head, capacity, n_tail)
    bins = jnp.asarray(bins.astype(np.int32))
    sign = jnp.asarray(rng.choice(np.array([-1, 1], np.int32), n))
    hist = rng.integers(0, 100, capacity).astype(np.int32)
    want = hist.copy()
    live = np.asarray(bins) < capacity
    np.add.at(want, np.asarray(bins)[live], np.asarray(sign)[live])
    got = jax.jit(degrees.count_bins)(jnp.asarray(hist), bins, sign)
    assert np.array_equal(np.asarray(got), want)
    if capacity > HEAD_BINS:
        tail = (bins >= HEAD_BINS) & (bins < capacity)
        assert bool(degrees._fill_slab(tail, bins, sign, capacity)[2]) == fits


# ---- the shapes of the lowered step ---------------------------------- #
# a histogram with a tail past the head: the contraction, the slab and
# the fallback are all in the program
VCAP, HCAP, W = 1 << 20, 1 << 15, 64


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
    # three scatters in all: the table's, and the histogram's tail's two
    # (the slab's and the fallback's, the branches of ONE conditional);
    # the head is ONE int8 contraction into int32
    assert len(closing) == 1 and text.count('"stablehlo.scatter"(') == 3
    assert text.count("stablehlo.case") == 1
    (dot,) = [ln for ln in text.splitlines() if "stablehlo.dot_general" in ln]
    assert "xi8>" in dot and "xi32>" in dot.rsplit("->", 1)[1]
    assert f"tensor<{VCAP}xi1>" not in text and "iota" not in "".join(
        line for line in text.splitlines() if rows in line)
    # every other tensor is a window's (2 * W lanes, 4 * W weighted
    # lanes), the histogram's, its head's (128 x 128 bins) or the slab's
    n_head = degrees.HEAD[0] * degrees.HEAD[1]
    sizes = {int(n) for n in re.findall(r"tensor<(\d+)x", text)}
    assert sizes <= {VCAP, HCAP, n_head, 4 * W, 2 * W, W, W - 1, 2 * W - 1,
                     *degrees.HEAD, degrees.SLAB[1], 1} | {
        2 ** k for k in range(8)} | {2 ** k - 1 for k in range(8)}, sizes


def test_the_compiled_step_keeps_to_the_window_too():
    """After XLA's passes (the CPU's here; the TPU's compile is checked
    on the chip by tools/trace_phases.py): the instructions with a
    ``vcap``-row operand or result are parameter, copy, gather, scatter
    and the root tuple; the histogram's conditional is not among them."""
    text = _lowered().compile().as_text()
    kinds = set()
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%\S+ = \S+ ([a-z\-]+)\(", line)
        if m and f"[{VCAP}]" in line:
            kinds.add(m.group(1))
    assert kinds <= {"parameter", "copy", "gather", "scatter", "tuple",
                     "fusion"}, kinds
    assert "copy" in kinds and "scatter" in kinds
    assert re.search(rf"s32\[{HCAP}\][^\n]* conditional\(", text)


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
