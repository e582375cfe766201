"""Windowed CC carries (summaries/forest.py + native CompactUnionFind):
differential equivalence with the dense engine, lazy-canonicalization
correctness, snapshot isolation, and adversarial chain growth. Every
test runs against BOTH windowed carries — the device forest kernels and
the native host union-find with its device mirror."""

import re

import numpy as np
import pytest

from gelly_streaming_tpu.core.stream import SimpleEdgeStream
from gelly_streaming_tpu.core.window import CountWindow
from gelly_streaming_tpu.library import ConnectedComponents

from _scatter_ref import (  # noqa: F401  (unsorted_steps is a fixture)
    assert_table_scatters_go_out_sorted,
    cc_tables,
    cover_tables,
    scoped_lanes,
    unsorted_steps,
)
from _uf import union_find_components as _union_find_components


def _stream(edges, window):
    return SimpleEdgeStream(edges, window=CountWindow(window))


@pytest.fixture(params=["forest", "host"])
def carry(request):
    if request.param == "host":
        from gelly_streaming_tpu import native

        try:
            native.CompactUnionFind()
        except Exception:
            pytest.skip("native toolchain unavailable")
    return request.param


def _dense_cc():
    """A CC instance pinned to the dense engine (the mesh / device-
    transformed fallback), for differential comparison."""
    return ConnectedComponents(carry="dense")


@pytest.mark.parametrize("window", [1, 3, 16, 64])
def test_carry_matches_dense_and_truth(window, carry):
    rng = np.random.default_rng(17)
    edges = [
        (int(a), int(b), 0.0)
        for a, b in rng.integers(0, 40, size=(120, 2))
    ]
    carry_out = [
        str(c)
        for c in _stream(edges, window).aggregate(
            ConnectedComponents(carry=carry)
        )
    ]
    dense_out = [
        str(c) for c in _stream(edges, window).aggregate(_dense_cc())
    ]
    assert carry_out == dense_out
    last = None
    for last in _stream(edges, window).aggregate(
        ConnectedComponents(carry=carry)
    ):
        pass
    assert sorted(last.component_sets()) == _union_find_components(edges)


def test_auto_carry_engages_a_windowed_path():
    edges = [(i, i + 1, 0.0) for i in range(20)]
    agg = ConnectedComponents()
    for _ in _stream(edges, 4).aggregate(agg):
        pass
    assert agg._cc_mode in ("forest", "host")
    assert agg._canon is not None


def test_emission_snapshot_isolation(carry):
    """Materializing an early emission AFTER later windows must reflect
    the state at ITS window (canon buffer + touched-count watermark),
    exactly like the dense path's immutable label tables."""
    edges = [(0, 1, 0.0), (2, 3, 0.0), (1, 2, 0.0), (4, 5, 0.0)]
    agg = ConnectedComponents(carry=carry)
    emissions = list(_stream(edges, 1).aggregate(agg))
    # read LAST first, then the early ones (worst-case ordering)
    assert sorted(emissions[-1].component_sets()) == sorted(
        [frozenset({0, 1, 2, 3}), frozenset({4, 5})]
    )
    assert sorted(emissions[0].component_sets()) == [frozenset({0, 1})]
    assert sorted(emissions[1].component_sets()) == sorted(
        [frozenset({0, 1}), frozenset({2, 3})]
    )
    assert sorted(emissions[2].component_sets()) == [frozenset({0, 1, 2, 3})]


def test_adversarial_rerooting_chains(carry):
    """Each window joins a new SMALLER vertex to the running component,
    re-rooting it every time — the worst case for pointer chains. The
    lazy canonicalization must still produce the right components, both
    at the end and at a mid-stream emission."""
    n = 60
    # vertices n, n-1, ..., 1, 0 join one component in decreasing order
    edges = [(n - i, n - i - 1, 0.0) for i in range(n)]
    agg = ConnectedComponents(carry=carry)
    emissions = list(_stream(edges, 1).aggregate(agg))
    assert sorted(emissions[-1].component_sets()) == [
        frozenset(range(n + 1))
    ]
    mid = emissions[n // 2]  # after n//2 + 1 edges
    (comp,) = mid.component_sets()
    assert comp == frozenset(range(n - (n // 2) - 1, n + 1))
    # root is always the min raw id
    assert list(emissions[-1].components.keys()) == [0]


def test_growth_across_capacity_buckets(carry):
    """Vertex ids climbing across pow2 capacity buckets grow the forest
    and the touch log without losing earlier merges."""
    edges = [(i, i + 1, 0.0) for i in range(300)]  # one long path
    agg = ConnectedComponents(carry=carry)
    last = None
    for last in _stream(edges, 7).aggregate(agg):
        pass
    assert sorted(last.component_sets()) == [frozenset(range(301))]


def test_checkpoint_roundtrip_continues(carry, tmp_path):
    from gelly_streaming_tpu.aggregate import checkpoint
    from gelly_streaming_tpu.core.window import Windower

    rng = np.random.default_rng(23)
    edges = [
        (int(a), int(b), 0.0)
        for a, b in rng.integers(0, 30, size=(80, 2))
    ]
    stream = _stream(edges, 10)
    agg = ConnectedComponents(carry=carry)
    it = stream.aggregate(agg)
    for _ in range(4):
        next(it)
    assert agg._cc_mode == carry
    path = str(tmp_path / "ck")
    checkpoint.save_aggregation(path, agg, stream.vertex_dict)

    # restore into the OTHER windowed carry: the checkpoint format is
    # carry-independent (canonical flat labels + touched)
    other = "host" if carry == "forest" else "forest"
    agg2 = ConnectedComponents(carry=other)
    vdict = checkpoint.restore_aggregation(path, agg2)
    wi = Windower(CountWindow(10), vdict)
    cont = SimpleEdgeStream(
        _blocks=lambda: wi.blocks(iter(edges[40:])), _vdict=vdict
    )
    last = None
    for last in agg2.run(cont):
        pass
    assert sorted(last.component_sets()) == _union_find_components(edges)


def test_transient_state_is_per_window(carry):
    edges = [(0, 1, 0.0), (1, 2, 0.0), (3, 4, 0.0), (0, 4, 0.0)]
    agg = ConnectedComponents(transient_state=True, carry=carry)
    out = [e.component_sets() for e in _stream(edges, 1).aggregate(agg)]
    assert out[0] == [frozenset({0, 1})]
    assert out[1] == [frozenset({1, 2})]   # no memory of window 0
    assert out[2] == [frozenset({3, 4})]
    assert out[3] == [frozenset({0, 4})]


def test_downgrade_to_dense_midstream(carry):
    """A restored windowed carry hitting a cache-less (device-
    transformed) stream downgrades to the dense engine without losing
    merges."""
    edges1 = [(0, 1, 0.0), (2, 3, 0.0)]
    edges2 = [(1, 2, 0.0), (4, 5, 0.0)]
    agg = ConnectedComponents(carry=carry)
    s1 = _stream(edges1, 1)
    for _ in agg.run(s1):
        pass
    assert agg._cc_mode == carry
    # a device-transformed continuation (no host cache on its blocks),
    # sharing the vertex dictionary
    s2 = SimpleEdgeStream(
        edges2, window=CountWindow(1), vertex_dict=s1.vertex_dict
    ).map_edges(lambda s, d, v: v)
    last = None
    for last in agg.run(s2):
        pass
    assert agg._cc_mode == "dense"
    assert sorted(last.component_sets()) == sorted(
        [frozenset({0, 1, 2, 3}), frozenset({4, 5})]
    )


# --------------------------------------------------------------------- #
# Cover-forest bipartiteness (round 5)
# --------------------------------------------------------------------- #
def _bp(edges, window, carry):
    from gelly_streaming_tpu.library import BipartitenessCheck

    out = None
    agg = BipartitenessCheck(carry=carry)
    for out in _stream(edges, window).aggregate(agg):
        pass
    return out, agg


def _py_bipartite(edges):
    color = {}

    def bfs(s):
        from collections import deque

        color[s] = 0
        q = deque([s])
        while q:
            x = q.popleft()
            for y in adj.get(x, ()):
                if y not in color:
                    color[y] = color[x] ^ 1
                    q.append(y)
                elif color[y] == color[x]:
                    return False
        return True

    adj = {}
    for a, b, *_ in edges:
        adj.setdefault(a, set()).add(b)
        adj.setdefault(b, set()).add(a)
    return all(bfs(v) for v in list(adj) if v not in color)


@pytest.mark.parametrize("window", [1, 3, 16, 64])
@pytest.mark.parametrize("seed", [1, 2, 5])
def test_cover_forest_matches_dense_and_truth(window, seed):
    rng = np.random.default_rng(seed)
    edges = [
        (int(a), int(b), 0.0)
        for a, b in rng.integers(0, 24, size=(60, 2))
        if a != b
    ]
    f_out, f_agg = _bp(edges, window, "forest")
    d_out, d_agg = _bp(edges, window, "dense")
    assert f_agg._bp_mode == "forest" and d_agg._bp_mode == "dense"
    assert str(f_out) == str(d_out)
    assert f_out.success == _py_bipartite(edges)


def test_cover_forest_bipartite_star_and_odd_cycle():
    star = [(0, i, 0.0) for i in range(1, 40)]
    out, agg = _bp(star, 7, "forest")
    assert out.success and agg._bp_mode == "forest"
    # odd cycle arriving across several windows latches failure forever
    cyc = star + [(1, 2, 0.0), (2, 3, 0.0), (3, 1, 0.0), (50, 51, 0.0)]
    emissions = list(
        _stream(cyc, 2).aggregate(
            __import__(
                "gelly_streaming_tpu.library", fromlist=["BipartitenessCheck"]
            ).BipartitenessCheck(carry="forest")
        )
    )
    assert emissions[-1].success is False
    assert str(emissions[-1]) == "(false,{})"


def test_cover_forest_growth_across_buckets():
    """Vertex growth re-indexes the negative cover half (ids AND pointer
    values shift) without corrupting components or the verdict."""
    edges = [(i, i + 1, 0.0) for i in range(300)]  # even path: bipartite
    out, agg = _bp(edges, 7, "forest")
    assert out.success
    assert agg._bp_mode == "forest"
    # and a late odd cycle after several growth events still trips it
    edges2 = edges + [(0, 299, 0.0)]  # 300-cycle: even -> still bipartite
    out2, _ = _bp(edges2, 7, "forest")
    assert out2.success
    edges3 = edges + [(0, 298, 0.0)]  # odd cycle
    out3, _ = _bp(edges3, 7, "forest")
    assert not out3.success


def test_cover_forest_checkpoint_cross_carry(tmp_path):
    from gelly_streaming_tpu.aggregate import checkpoint
    from gelly_streaming_tpu.core.window import Windower
    from gelly_streaming_tpu.library import BipartitenessCheck

    rng = np.random.default_rng(9)
    edges = [
        (int(a), int(b), 0.0)
        for a, b in rng.integers(0, 20, size=(40, 2))
        if a != b
    ]
    stream = _stream(edges, 5)
    agg = BipartitenessCheck(carry="forest")
    it = stream.aggregate(agg)
    for _ in range(4):
        next(it)
    assert agg._bp_mode == "forest"
    path = str(tmp_path / "bp")
    checkpoint.save_aggregation(path, agg, stream.vertex_dict)

    agg2 = BipartitenessCheck(carry="dense")
    vdict = checkpoint.restore_aggregation(path, agg2)
    wi = Windower(CountWindow(5), vdict)
    cont = SimpleEdgeStream(
        _blocks=lambda: wi.blocks(iter(edges[20:])), _vdict=vdict
    )
    last = None
    for last in agg2.run(cont):
        pass
    assert last.success == _py_bipartite(edges)

    # and forest restored FROM a dense checkpoint: the odd-cycle latch
    # recomputes from the restored cover labels
    agg3 = BipartitenessCheck(carry="dense")
    it3 = _stream(edges, 5).aggregate(agg3)
    for _ in range(4):
        next(it3)
    path2 = str(tmp_path / "bp2")
    checkpoint.save_aggregation(path2, agg3, None)
    agg4 = BipartitenessCheck(carry="forest")
    agg4.restore_state(checkpoint.load_pytree(
        path2, agg4.initial_state(agg3._vcap))[0])
    from gelly_streaming_tpu.summaries.forest import resolve_flat_host

    lab = np.asarray(agg3._summary["labels"])
    flat = resolve_flat_host(lab)
    vcap = len(lab) // 2
    agg4._ensure_forest(vcap)
    tch = np.asarray(agg3._summary["touched"])[:vcap]
    base = np.nonzero(tch)[0]
    expect_failed = bool(np.any(flat[base] == flat[base + vcap]))
    assert bool(np.asarray(agg4._failed)) == expect_failed


def test_cover_forest_held_emission_survives_dict_growth():
    """Round-5 review crash repro: hold an early window's Candidates
    emission, stream until the vertex dict grows past the snapshot's
    vcap, then read it — the snapshot must materialize with its OWN
    vcap/touched (base-only log), not the live dict size."""
    from gelly_streaming_tpu.library import BipartitenessCheck

    edges = [(i, i + 1, 0.0) for i in range(60)]  # path; grows buckets
    agg = BipartitenessCheck(carry="forest")
    emissions = list(_stream(edges, 2).aggregate(agg))
    first = emissions[0]
    # read LAST first (newest state), then the held EARLY emission
    assert emissions[-1].success
    assert first.success
    assert str(first).startswith("(true,")
    # the early snapshot reflects ITS window: only vertices 0..2 touched
    assert set(first.components) == {0, 2} or set(first.components) == {0}


def test_carry_with_event_time_windows(carry):
    """The windowed carries engage on event-time blocks too (the
    windower caches host columns for any policy); equality with dense
    across a window-spanning event-time stream."""
    from gelly_streaming_tpu import EventTimeWindow

    edges = [
        (1, 2, 0.0), (2, 3, 1.0), (4, 5, 5.0),
        (3, 4, 9.0), (5, 6, 12.0), (1, 6, 13.0), (7, 8, 27.0),
    ]

    def run(c):
        agg = ConnectedComponents(carry=c)
        out = [str(x) for x in SimpleEdgeStream(
            edges, window=EventTimeWindow(10, timestamp_fn=lambda e: e[2])
        ).aggregate(agg)]
        return out, agg._cc_mode

    got, mode = run(carry)
    dense, _ = run("dense")
    assert mode == carry
    assert got == dense
    assert "1=[1, 2, 3, 4, 5, 6]" in got[-1] and "7=[7, 8]" in got[-1]


# --------------------------------------------------------------------- #
# Incremental merged-forest delta (apply_forest_delta_host, ISSUE 17)
# --------------------------------------------------------------------- #
def test_apply_forest_delta_matches_scratch_fold():
    """Repeated incremental application equals a from-scratch fold of
    the full edge set (after resolve), and the size table stays exact
    at every root — the router's O(changed) merge-refresh contract."""
    from gelly_streaming_tpu.summaries.forest import (
        apply_forest_delta_host,
        fold_edges_host,
        resolve_flat_host,
    )

    rng = np.random.default_rng(31)
    n = 200
    base_s = rng.integers(0, n, 300)
    base_d = rng.integers(0, n, 300)
    flat = fold_edges_host(np.arange(n, dtype=np.int32), base_s, base_d)
    lab = flat.astype(np.int64)
    sizes = np.bincount(flat, minlength=n).astype(np.int64)
    all_s, all_d = base_s.tolist(), base_d.tolist()
    for _ in range(6):
        ds = rng.integers(0, n, 15)
        dd = rng.integers(0, n, 15)
        apply_forest_delta_host(lab, sizes, ds, dd)
        all_s += ds.tolist()
        all_d += dd.tolist()
        want = fold_edges_host(
            np.arange(n, dtype=np.int32),
            np.asarray(all_s), np.asarray(all_d),
        )
        assert np.array_equal(resolve_flat_host(lab),
                              want.astype(np.int64))
        for r in np.unique(want):
            assert sizes[r] == int(np.sum(want == r))
    # the final state also matches the union-find oracle
    comps = _union_find_components(zip(all_s, all_d))
    got = resolve_flat_host(lab)
    for comp in comps:
        assert len({int(got[v]) for v in comp}) == 1


def test_apply_forest_delta_reports_touched_roots():
    from gelly_streaming_tpu.summaries.forest import (
        apply_forest_delta_host,
    )

    lab = np.arange(8, dtype=np.int64)
    sizes = np.ones(8, np.int64)
    # an effective union touches BOTH sides (winner and absorbed)
    t = apply_forest_delta_host(lab, sizes,
                                np.asarray([3]), np.asarray([5]))
    assert sorted(t.tolist()) == [3, 5]
    assert lab[5] == 3 and sizes[3] == 2
    # the same edge again is a no-op: nothing touched
    t = apply_forest_delta_host(lab, sizes,
                                np.asarray([3]), np.asarray([5]))
    assert len(t) == 0
    # a chained union reports the ROOTS involved, not the raw endpoints
    t = apply_forest_delta_host(lab, sizes,
                                np.asarray([5]), np.asarray([1]))
    assert sorted(t.tolist()) == [1, 3]
    assert sizes[1] == 3
    # torn delta columns are rejected, never half-applied
    with pytest.raises(ValueError):
        apply_forest_delta_host(lab, sizes,
                                np.asarray([1]), np.asarray([], np.int64))


# --------------------------------------------------------------------- #
# Every scatter into the table goes out sorted (ISSUE 31): the steps
# against the scatter as it was, kept in ``_scatter_ref`` as the plain
# reference; the same table on EVERY row, pointer shape included
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("seed", [1, 2**31 + 5])
@pytest.mark.parametrize("fold", [cc_tables, cover_tables],
                         ids=["cc-step", "cover-step"])
def test_sorted_scatters_give_the_unsorted_steps_table_on_every_row(
        fold, seed, unsorted_steps):
    got = fold(seed)
    unsorted_steps()
    want = fold(seed)
    for w, (a, b) in enumerate(zip(got, want)):
        np.testing.assert_array_equal(a, b, err_msg=f"window {w}")
    # the stream did re-root: rows other than the touched ids' moved, and
    # some old root was written once per touched member
    moved = np.flatnonzero(got[-1] != np.arange(len(got[-1])))
    assert len(moved) > 100 and len(np.unique(got[-1][moved])) < len(moved)


def _lowered_step(which: str, tcap: int = 1 << 10, wcap: int = 1 << 9,
                  vcap: int = 1 << 14) -> str:
    import jax
    import jax.numpy as jnp

    from gelly_streaming_tpu.summaries import candidates, forest

    S = jax.ShapeDtypeStruct
    lanes = (S((tcap,), jnp.int32), S((tcap,), jnp.bool_),
             S((wcap,), jnp.int32), S((wcap,), jnp.int32))
    if which == "cc-step":
        lowered = forest._forest_step_fn(tcap, wcap, vcap).lower(
            S((vcap,), jnp.int32), *lanes)
    else:
        lowered = candidates._cover_step_fn(tcap, wcap, vcap).lower(
            S((2 * vcap,), jnp.int32), S((), jnp.bool_), *lanes,
            S((wcap,), jnp.bool_))
    return lowered.as_text(debug_info=True)


@pytest.mark.parametrize("which", ["cc-step", "cover-step"])
def test_the_lowered_step_says_its_table_scatters_are_sorted(which):
    assert_table_scatters_go_out_sorted(_lowered_step(which))


@pytest.mark.parametrize("which,lanes", [("cc-step", 1), ("cover-step", 2)])
def test_the_lowered_step_says_a_round_handles_the_windows_rows_alone(
        which, lanes):
    """ISSUE 33: inside the fixpoint's loop the hook's two gathers and
    two scatter-mins carry ``wcap`` lanes (``2 * wcap`` on the cover)
    and the shortcut ``tcap``; none carries ``wcap + tcap``. The
    contraction's three gathers sit outside the loop, under
    ``forest.fixpoint/forest.contract``."""
    tcap, wcap = lanes << 10, lanes << 9
    text = _lowered_step(which)
    inside = {name: sorted(
        n for path, n in scoped_lanes(text, name)
        if "forest.fixpoint/while/body" in path)
        for name in ("gather", "scatter")}
    assert inside == {"gather": [wcap, wcap, tcap], "scatter": [wcap, wcap]}
    contract = [(path[path.index("forest."):], n)
                for path, n in scoped_lanes(text, "gather")
                if "forest.contract" in path]
    assert contract == [("forest.fixpoint/forest.contract/gather", wcap)] * 2 \
        + [("forest.fixpoint/forest.contract/gather", tcap)]
    assert f"tensor<{wcap + tcap}x" not in text


def test_ccs_lowered_step_holds_no_constant_the_size_of_its_lanes():
    """ROADMAP S14, closed by ISSUE 33: the pointer edges' sources were
    a host-built ``arange(tcap)``, a literal of CC's program (1.07 MB
    of lowered text at the cells' shapes, growing with ``tcap``)."""
    # both past the chase's static floor (ISSUE 35): under it the step
    # has no slab, and its text is shorter for that and no constant's sake
    small = _lowered_step("cc-step", 1 << 12, 1 << 11, 1 << 14)
    cells = _lowered_step("cc-step", 1 << 17, 1 << 16, 1 << 28)
    # the group's doubling (ISSUE 37) is unrolled: one stretch of ops,
    # some 1,600 characters, for every doubling of the lanes, so five
    # more at the cells' 2^17 lanes than at 2^12; a literal of ``tcap``
    # entries would be a quarter of a megabyte
    assert len(cells) < 100_000
    assert 0 <= len(cells) - len(small) < 5 * 2_000
    assert not re.search(r"stablehlo\.constant dense<[^>]{64,}>", cells)


# --------------------------------------------------------------------- #
# The forest fold is written once (ISSUE 32): one group prep under CC's
# and the cover's group drivers, one cache under all four programs
# --------------------------------------------------------------------- #
def _ragged_windows(seed: int, sizes, vcap: int = 1 << 9):
    """Windows of the given lengths (an empty one among them is legal);
    sources even and targets odd, so the cover stays bipartite."""
    rng = np.random.default_rng(seed)
    return [((rng.integers(0, vcap, n) & ~1).astype(np.int32),
             (rng.integers(0, vcap, n) | 1).astype(np.int32))
            for n in sizes], vcap


@pytest.mark.parametrize("n", [1, 7, 64, 200])
def test_pad_group_of_one_window_gives_pad_windows_lanes(n):
    from gelly_streaming_tpu.summaries import forest

    ((s, d),), vcap = _ragged_windows(n, [n])
    prep = forest.WindowPrep()
    tids, tcap, wcap, tid, tmask, lu, lv = forest.pad_window(prep, s, d, vcap)
    (win_tids, g_tcap, g_wcap, g_tid, g_tmask, g_lu, g_lv,
     lens) = forest.pad_group(prep, [(s, d)], vcap)
    assert (g_tcap, g_wcap, lens.tolist()) == (tcap, wcap, [n])
    np.testing.assert_array_equal(win_tids[0], tids)
    for got, want in ((g_tid, tid), (g_tmask, tmask),
                      (g_lu, lu[None]), (g_lv, lv[None])):
        assert got.dtype == want.dtype
        np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("sizes", [(64, 64, 64), (5, 0, 33, 64, 1), (0, 0)])
def test_cc_and_cover_group_drivers_hand_the_device_the_same_lanes(
        sizes, monkeypatch):
    """Both drivers go through ``pad_group``: the same ``tid, tmask, lu,
    lv`` for the same windows (the cover adds its mask of the pad rows),
    and the lanes say the windows' own edges."""
    import jax.numpy as jnp

    from gelly_streaming_tpu.summaries import candidates, forest

    windows, vcap = _ragged_windows(len(sizes), sizes)
    seen = {}

    def recording(name, n_lead, builder):
        def build(*shape, **kw):
            step = builder(*shape, **kw)

            def run(*args):
                seen[name] = (shape[:4],
                              [np.asarray(a) for a in args[n_lead:]])
                return step(*args)
            return run
        return build

    monkeypatch.setattr(forest, "_forest_superbatch_fn", recording(
        "cc", 1, forest._forest_superbatch_fn))
    monkeypatch.setattr(candidates, "_cover_superbatch_fn", recording(
        "cover", 2, candidates._cover_superbatch_fn))
    prep = forest.WindowPrep()
    _c, cc_tids, _r = forest.forest_superbatch(
        forest.init_forest(vcap), windows, vcap, prep)
    _c, failed, cover_tids, _r, fail_s = candidates.cover_forest_superbatch(
        forest.init_forest(2 * vcap), jnp.bool_(False), windows, vcap, prep)
    assert not bool(failed) and not np.asarray(fail_s).any()

    (cc_shape, cc_lanes), (cover_shape, cover_lanes) = seen["cc"], seen["cover"]
    assert cc_shape == cover_shape == (*cc_shape[:3], len(sizes))
    for a, b in zip(cc_lanes, cover_lanes[:4]):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(a, b)
    tid, tmask, lu, lv = cc_lanes
    emask = cover_lanes[4]
    assert emask.sum(axis=1).tolist() == list(sizes)
    for k, (s, d) in enumerate(windows):
        n = len(s)
        assert emask[k, :n].all() and not lu[k, n:].any() and not lv[k, n:].any()
        assert tmask[lu[k, :n]].all() and tmask[lv[k, :n]].all()
        np.testing.assert_array_equal(tid[lu[k, :n]], s)
        np.testing.assert_array_equal(tid[lv[k, :n]], d)
        for tids in (cc_tids[k], cover_tids[k]):
            assert sorted(tids.tolist()) == sorted(set(s) | set(d))
    touched = np.unique(np.concatenate([np.concatenate(w) for w in windows]))
    assert sorted(tid[tmask].tolist()) == touched.tolist()


def test_the_one_step_cache_stays_bounded_under_cc_and_cover_together(
        monkeypatch):
    """All four programs live in ``forest._STEP_CACHE``: FIFO, bounded,
    and a CC and a cover program of one shape are two entries."""
    from gelly_streaming_tpu.summaries import candidates, forest

    monkeypatch.setattr(forest, "_STEP_CACHE", {})
    monkeypatch.setattr(forest, "_STEP_CACHE_MAX", 6)
    builders = [
        lambda t: forest._forest_step_fn(t, 8, 64),
        lambda t: candidates._cover_step_fn(t, 8, 64),
        lambda t: forest._forest_superbatch_fn(t, 8, 64, 2),
        lambda t: candidates._cover_superbatch_fn(t, 8, 64, 2),
    ]
    first = [b(8) for b in builders]
    assert len({id(f) for f in first}) == 4 and len(forest._STEP_CACHE) == 4
    assert [b(8) for b in builders] == first  # found again, not rebuilt
    for tcap in (16, 32, 64):
        for b in builders:
            b(tcap)
            assert len(forest._STEP_CACHE) <= 6
    kinds = {key[0] for key in forest._STEP_CACHE}
    assert kinds == {"cc", "cover", "cc-group", "cover-group"}
    assert [key[1] for key in forest._STEP_CACHE] == [32, 32] + [64] * 4
    assert builders[0](8) is not first[0]  # evicted long ago: built anew
    assert not hasattr(candidates, "_COVER_STEP_CACHE")
