"""Group-fold protocol conformance (ISSUE 14 acceptance).

The generalized contract (``summaries/groupfold.py``) must make EVERY
declaring carry's fused K-window path emission-identical to its
per-window path: the two new implementations (IncrementalPageRank's
scanned group body, the bipartiteness cover group fold) are pinned here
alongside the refactored engine/CC paths, over random streams, with
mid-group out-of-order emission reads, dict growth, unsupported-group
fallback, and mid-superbatch kill/resume through AutoCheckpoint. The
reusable :func:`verify_group_fold` helper is exercised directly — it is
the conformance test any NEW GroupFoldable carry reuses.
"""

import numpy as np
import pytest

from gelly_streaming_tpu.core.stream import SimpleEdgeStream
from gelly_streaming_tpu.core.window import (
    CountWindow,
    Windower,
    iter_superbatches,
)
from gelly_streaming_tpu.datasets import IdentityDict
from gelly_streaming_tpu.library import (
    BipartitenessCheck,
    ConnectedComponents,
    IncrementalPageRank,
)
from gelly_streaming_tpu.summaries.groupfold import (
    GroupFoldable,
    verify_group_fold,
)

N_VERTS = 160
WINDOW = 23  # deliberately not a divisor of the edge count


def _edges(seed=0, n=700, lo=0, hi=N_VERTS):
    rng = np.random.default_rng(seed)
    return [
        (int(a), int(b), 0.0)
        for a, b in rng.integers(lo, hi, size=(n, 2))
    ]


def _bip_edges(seed=0, n=400, half=80):
    """A bipartite-preserving stream: every edge crosses the two halves."""
    rng = np.random.default_rng(seed)
    a = rng.integers(0, half, n)
    b = rng.integers(half, 2 * half, n)
    return [(int(x), int(y), 0.0) for x, y in zip(a, b)]


def _stream(edges, vdict=None):
    return SimpleEdgeStream(edges, window=CountWindow(WINDOW),
                            vertex_dict=vdict)


# --------------------------------------------------------------------- #
# The reusable conformance helper, applied to every declaring carry
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("k", [2, 7, 64])
def test_conformance_cc(k):
    edges = _edges(1)
    verify_group_fold(
        lambda kk: ConnectedComponents(carry="forest", superbatch=kk),
        lambda: _stream(edges), k,
    )


@pytest.mark.parametrize("carry", ["forest", "host"])
@pytest.mark.parametrize("k", [2, 7, 64])
def test_conformance_bipartiteness(carry, k):
    if carry == "host" and not _have_native():
        pytest.skip("native toolchain unavailable")
    for seed, edges in ((2, _bip_edges(2)), (3, _edges(3))):
        verify_group_fold(
            lambda kk: BipartitenessCheck(carry=carry, superbatch=kk),
            lambda e=edges: _stream(e), k,
        )


def _have_native():
    try:
        from gelly_streaming_tpu import native

        native.CompactUnionFind()
        return True
    except Exception:
        return False


def test_bipartiteness_host_vs_forest_identical():
    """The host cover union-find and the device cover forest are two
    implementations of ONE carry contract — emissions must match
    verbatim, grouped or not."""
    if not _have_native():
        pytest.skip("native toolchain unavailable")
    edges = _bip_edges(20, n=300) + [(0, 1, 0.0), (1, 2, 0.0),
                                     (2, 0, 0.0)]
    base = [
        str(c) for c in BipartitenessCheck(carry="forest").run(
            _stream(edges))
    ]
    for k in (1, 8):
        got = [
            str(c) for c in BipartitenessCheck(
                carry="host", superbatch=k).run(_stream(edges))
        ]
        assert got == base


@pytest.mark.parametrize("k", [3, 16, 64])
def test_conformance_pagerank(k):
    edges = _edges(4)
    # iterations + seen counts compare exactly; l1_delta is a float sum
    # whose checked-separately tolerance lives in test_pagerank_group_*
    verify_group_fold(
        lambda kk: IncrementalPageRank(superbatch=kk),
        lambda: _stream(edges), k,
        normalize=lambda e: (e.window, e.num_vertices,
                             int(e.iterations)),
    )


def test_verify_group_fold_reports_diverging_window():
    """The helper a new carry reuses must NAME the diverging window."""

    class Broken(GroupFoldable):
        def __init__(self, superbatch=1):
            self.superbatch = superbatch

        def run(self, stream):
            for i, _ in enumerate(stream.blocks()):
                # the "grouped" run diverges at window 2
                yield ("x", i if self.superbatch == 1 or i < 2 else -i)

        def fold_group(self, group):  # pragma: no cover - not driven
            raise AssertionError

    edges = _edges(5, n=120)
    with pytest.raises(AssertionError, match="window 2"):
        verify_group_fold(Broken, lambda: _stream(edges), 4)


# --------------------------------------------------------------------- #
# PageRank: scanned group body
# --------------------------------------------------------------------- #
def _pr_run(edges, k, vdict=None):
    pr = IncrementalPageRank(superbatch=k)
    ems = [
        (e.window, e.num_vertices, int(e.iterations), float(e.l1_delta))
        for e in pr.run(_stream(edges, vdict))
    ]
    return ems, pr


@pytest.mark.parametrize("k", [3, 16])
def test_pagerank_group_values_and_ranks(k):
    edges = _edges(6)
    base, pr1 = _pr_run(edges, 1)
    got, prk = _pr_run(edges, k)
    assert len(got) == len(base)
    for a, b in zip(base, got):
        assert a[:3] == b[:3]
        np.testing.assert_allclose(a[3], b[3], rtol=1e-5, atol=1e-12)
    np.testing.assert_allclose(
        np.asarray(pr1._carry[2]), np.asarray(prk._carry[2]), rtol=1e-6
    )
    assert pr1._n_edges == prk._n_edges == len(edges)


def test_pagerank_group_identity_dict():
    """IdentityDict's constant-bound len() semantics must reconstruct
    per-window (its observed watermark is a running max, the other
    branch of SuperbatchGroup.n_seen_per_window)."""
    edges = _edges(7)
    base, _ = _pr_run(edges, 1, IdentityDict(N_VERTS))
    got, _ = _pr_run(edges, 16, IdentityDict(N_VERTS))
    for a, b in zip(base, got):
        assert a[:3] == b[:3]
        np.testing.assert_allclose(a[3], b[3], rtol=1e-5, atol=1e-12)


def test_pagerank_generic_packed_groups_still_fused():
    """Groups generically packed from pre-built blocks still carry host
    column views, so the fused path applies — and the carried
    seen-vertex watermark keeps per-window values exact even though the
    pre-built dict is already complete."""

    class Bare:
        """Block-backed stream without a superbatch packer."""

        def __init__(self, blocks, vdict):
            self._b = blocks
            self.vertex_dict = vdict

        def blocks(self):
            return iter(self._b)

    edges = _edges(8)
    w = Windower(CountWindow(WINDOW))
    blocks = list(w.blocks(iter(edges)))
    groups = list(iter_superbatches(Bare(blocks, w.vertex_dict), 4))
    assert all(g.n_seen_per_window() is None for g in groups)
    assert IncrementalPageRank(superbatch=4).group_supported(groups[0])

    base, _ = _pr_run(edges, 1)

    def rerun(kk):
        w2 = Windower(CountWindow(WINDOW))
        blocks2 = list(w2.blocks(iter(edges)))
        work = IncrementalPageRank(superbatch=kk)
        return [
            (e.window, e.num_vertices, int(e.iterations),
             float(e.l1_delta))
            for e in work.run(Bare(blocks2, w2.vertex_dict))
        ]

    got = rerun(4)
    assert len(got) == len(base)
    for a, b in zip(base, got):
        assert a[:3] == b[:3]
        np.testing.assert_allclose(a[3], b[3], rtol=1e-5, atol=1e-12)


def test_pagerank_cacheless_group_falls_back():
    """Groups whose member blocks carry no host caches (device-
    transformed streams) have no column views; the fold must route them
    per-window through the declared fallback — correctness never
    depends on how a group was packed."""
    from gelly_streaming_tpu.core.edgeblock import EdgeBlock

    class Bare:
        def __init__(self, blocks, vdict):
            self._b = blocks
            self.vertex_dict = vdict

        def blocks(self):
            return iter(self._b)

    rng = np.random.default_rng(19)
    wins = [
        (rng.integers(0, N_VERTS, 40).astype(np.int32),
         rng.integers(0, N_VERTS, 40).astype(np.int32))
        for _ in range(6)
    ]

    def make_blocks():
        return [
            EdgeBlock.from_arrays(s, d, None, n_vertices=N_VERTS)
            for s, d in wins
        ]

    def full_dict():
        d = IdentityDict(N_VERTS)
        d.observe(N_VERTS - 1)  # device path reads the live dict length
        return d

    groups = list(iter_superbatches(Bare(make_blocks(), full_dict()), 4))
    assert all(g.cols is None for g in groups)
    pr = IncrementalPageRank(superbatch=4)
    assert not pr.group_supported(groups[0])

    def rerun(kk):
        work = IncrementalPageRank(superbatch=kk)
        return [
            (e.window, e.num_vertices, int(e.iterations),
             float(e.l1_delta))
            for e in work.run(Bare(make_blocks(), full_dict()))
        ]

    base, got = rerun(1), rerun(4)
    assert len(got) == len(base)
    for a, b in zip(base, got):
        assert a[:3] == b[:3]
        np.testing.assert_allclose(a[3], b[3], rtol=1e-5, atol=1e-12)


def test_n_seen_per_window_matches_live_dict():
    """The group packer's reconstructed per-window seen counts must
    equal what a per-window consumer reads from the live dict — for
    both dictionary kinds."""
    edges = _edges(9, n=300)
    for vd_factory in (lambda: None, lambda: IdentityDict(N_VERTS)):
        w1 = Windower(CountWindow(WINDOW), vd_factory())
        per_window = []
        for _ in w1.blocks(iter(edges)):
            per_window.append(len(w1.vertex_dict))
        w2 = Windower(CountWindow(WINDOW), vd_factory())
        got = []
        for g in w2.superbatches(iter(edges), 4):
            got.extend(g.n_seen_per_window())
        assert got == per_window


# --------------------------------------------------------------------- #
# Bipartiteness: cover group fold
# --------------------------------------------------------------------- #
def _bp_run(edges, k, carry="auto"):
    agg = BipartitenessCheck(superbatch=k, carry=carry)
    out = [str(c) for c in agg.run(_stream(edges))]
    return out, agg


def test_bipartiteness_out_of_order_reads():
    """Mid-group cover canons reconstruct lazily; reads must not depend
    on consumption order."""
    edges = _bip_edges(10)
    base, _ = _bp_run(edges, 1)
    ems = list(BipartitenessCheck(superbatch=8).run(_stream(edges)))
    for i in (5, 2, 7, 0, 6, 2):
        assert str(ems[i]) == base[i], f"window {i}"


@pytest.mark.parametrize("carry", ["forest", "host"])
def test_bipartiteness_verdict_flip_mid_group(carry):
    """The per-window failure latch must flip at the SAME window the
    per-window path flips, even when the odd cycle lands mid-group: on
    the forest carry (the group step reads window k's latch off the
    scan's assignments up to k) and on the host carry alike."""
    if carry == "host" and not _have_native():
        pytest.skip("native toolchain unavailable")
    edges = _bip_edges(11, n=200)
    # inject an odd triangle late, mid-way through a k=8 group
    edges = edges[:130] + [(0, 1, 0.0), (1, 2, 0.0), (2, 0, 0.0)] + edges[130:]
    base, _ = _bp_run(edges, 1, carry)
    got, agg = _bp_run(edges, 8, carry)
    assert agg._bp_mode == carry
    assert got == base
    flips = [i for i, s in enumerate(base) if s == "(false,{})"]
    assert flips and flips[0] > 0  # the stream really was bipartite first


def test_bipartiteness_growth_mid_group():
    """Vertex-capacity growth quantizes to group boundaries; emission
    VALUES (component maps, verdicts) must still match per-window."""
    rng = np.random.default_rng(12)
    # ids grow past several pow2 buckets as the stream advances
    edges = []
    for step in range(6):
        hi = 40 * (step + 1)
        a = rng.integers(0, hi, 60)
        b = rng.integers(hi, 2 * hi, 60)
        edges += [(int(x), int(y), 0.0) for x, y in zip(a, b)]
    base = [c for c in BipartitenessCheck().run(_stream(edges))]
    got = [c for c in BipartitenessCheck(superbatch=8).run(_stream(edges))]
    assert len(got) == len(base)
    for i, (x, y) in enumerate(zip(base, got)):
        assert x == y, f"window {i}"


def test_bipartiteness_host_downgrades_to_dense_mid_stream():
    """A device-transformed block mid-stream must convert the HOST
    carry to dense (keeping its accumulated components), exactly like
    the forest carry — the union-find state is flattened, never
    dropped."""
    if not _have_native():
        pytest.skip("native toolchain unavailable")
    from gelly_streaming_tpu.core.edgeblock import EdgeBlock

    class Mixed:
        def __init__(self, blocks, vdict):
            self._b = blocks
            self.vertex_dict = vdict

        def get_context(self):
            from gelly_streaming_tpu.core.stream import StreamContext

            return StreamContext()

        def blocks(self):
            return iter(self._b)

    edges = _bip_edges(21, n=200)
    w = Windower(CountWindow(WINDOW), IdentityDict(N_VERTS))
    blocks = list(w.blocks(iter(edges)))
    # strip the host cache off the tail: rebuilt device-only blocks
    stripped = [
        EdgeBlock.from_arrays(
            *[np.asarray(c) for c in b._host_cache[:2]], None,
            n_vertices=b.n_vertices,
        )
        for b in blocks[4:]
    ]
    base = [
        str(c) for c in BipartitenessCheck(carry="forest").run(
            Mixed(blocks[:4] + stripped, IdentityDict(N_VERTS)))
    ]
    got = [
        str(c) for c in BipartitenessCheck(carry="host").run(
            Mixed(list(Windower(CountWindow(WINDOW),
                                IdentityDict(N_VERTS)).blocks(iter(edges)))[:4]
                  + stripped, IdentityDict(N_VERTS)))
    ]
    assert got == base


def test_bipartiteness_checkpoint_state_identical():
    edges = _bip_edges(13)
    _, ref = _bp_run(edges, 1)
    _, sup = _bp_run(edges, 5)
    a, b = ref.snapshot_state(), sup.snapshot_state()
    np.testing.assert_array_equal(np.asarray(a["labels"]),
                                  np.asarray(b["labels"]))
    np.testing.assert_array_equal(np.asarray(a["touched"]),
                                  np.asarray(b["touched"]))


def test_checkpoint_granularity_declarations():
    assert IncrementalPageRank().checkpoint_granularity() == 1
    assert IncrementalPageRank(superbatch=4).checkpoint_granularity() == 4
    assert BipartitenessCheck(superbatch=4).checkpoint_granularity() == 4
    assert BipartitenessCheck(
        superbatch=4, transient_state=True
    ).checkpoint_granularity() == 1


# --------------------------------------------------------------------- #
# Mid-superbatch kill/resume through AutoCheckpoint
# --------------------------------------------------------------------- #
def _ckpt_run(tmp_path, make_work, edges, kill_after=None, every=2, k=3,
              normalize=str):
    from gelly_streaming_tpu.aggregate.autockpt import AutoCheckpoint

    tmp_path.mkdir(exist_ok=True)
    ac = AutoCheckpoint(str(tmp_path / "gf.ckpt"), every=every)
    work = make_work(k)

    def make_stream(vdict):
        return _stream(edges, vdict)

    out = []
    it = ac.run(make_stream, work)
    for i, c in enumerate(it):
        out.append(normalize(c))
        if kill_after is not None and i + 1 >= kill_after:
            it.close()  # the kill: mid-group, between a group's yields
            break
    return ac, work, out


def test_bipartiteness_mid_superbatch_kill_and_resume(tmp_path):
    edges = _bip_edges(14, n=300)
    n_windows = -(-len(edges) // WINDOW)
    make = lambda kk: BipartitenessCheck(superbatch=kk)
    _, ref_agg, ref_out = _ckpt_run(tmp_path / "ref", make, edges)
    assert len(ref_out) == n_windows

    ac, _, _ = _ckpt_run(tmp_path / "kr", make, edges, kill_after=7)
    done = ac.windows_done()
    assert done > 0 and done % 3 == 0  # barriers group-aligned

    ac2, agg2, resumed = _ckpt_run(tmp_path / "kr", make, edges)
    assert len(resumed) == n_windows - done
    assert resumed == ref_out[done:]
    a, b = ref_agg.snapshot_state(), agg2.snapshot_state()
    np.testing.assert_array_equal(np.asarray(a["labels"]),
                                  np.asarray(b["labels"]))


def test_pagerank_mid_superbatch_kill_and_resume(tmp_path):
    edges = _edges(15, n=300)
    n_windows = -(-len(edges) // WINDOW)
    make = lambda kk: IncrementalPageRank(superbatch=kk)
    norm = lambda e: (e.num_vertices, int(e.iterations))
    _, ref_pr, ref_out = _ckpt_run(
        tmp_path / "ref", make, edges, normalize=norm
    )
    assert len(ref_out) == n_windows

    ac, _, _ = _ckpt_run(
        tmp_path / "kr", make, edges, kill_after=7, normalize=norm
    )
    done = ac.windows_done()
    assert done > 0 and done % 3 == 0

    ac2, pr2, resumed = _ckpt_run(
        tmp_path / "kr", make, edges, normalize=norm
    )
    assert len(resumed) == n_windows - done
    assert resumed == ref_out[done:]
    np.testing.assert_allclose(
        np.asarray(ref_pr._carry[2]), np.asarray(pr2._carry[2]),
        rtol=1e-6,
    )
    assert ref_pr._n_edges == pr2._n_edges == len(edges)


# --------------------------------------------------------------------- #
# Serving: the bipartiteness adapter + BipartiteQuery
# --------------------------------------------------------------------- #
def test_bipartite_servable_yes_no_witness():
    from gelly_streaming_tpu.serving import BipartiteQuery
    from gelly_streaming_tpu.serving.server import StreamServer

    edges = _bip_edges(16, n=200)
    for extra, want in (
        ([], True),
        ([(0, 1, 0.0), (1, 2, 0.0), (2, 0, 0.0)], False),
    ):
        agg = BipartitenessCheck(superbatch=4)
        with StreamServer(agg.servable(), _stream(edges + extra)) as srv:
            srv.join(60)
            ans = srv.submit(BipartiteQuery()).result(timeout=30)
        assert ans.value["bipartite"] is want
        if want:
            assert ans.value["witness"] is None
        else:
            # the witness must actually sit on the odd cycle's merged
            # cover component: its two cover nodes share a root
            w = ans.value["witness"]
            assert isinstance(w, int)


def test_bipartite_query_wire_codec_round_trip():
    from gelly_streaming_tpu.serving import BipartiteQuery, ConnectedQuery
    from gelly_streaming_tpu.serving.rpc import (
        decode_queries,
        encode_queries,
    )

    qs = [BipartiteQuery(), ConnectedQuery(1, 2), BipartiteQuery()]
    assert decode_queries(encode_queries(qs)) == qs


def test_bipartite_query_dense_carry_payload():
    """The dense carry publishes flat cover labels + a touched table;
    the engine must answer from that shape too (and from a restored
    checkpoint, which shares it)."""
    from gelly_streaming_tpu.serving import BipartiteQuery
    from gelly_streaming_tpu.serving.server import StreamServer

    edges = _edges(17, n=200)
    agg = BipartitenessCheck(carry="dense")
    with StreamServer(agg.servable(), _stream(edges)) as srv:
        srv.join(60)
        ans = srv.submit(BipartiteQuery()).result(timeout=30)
    # random edges over one id space: odd cycles are near-certain; pin
    # against the direct per-window oracle rather than assuming
    oracle = [c for c in BipartitenessCheck().run(_stream(edges))][-1]
    assert ans.value["bipartite"] is bool(oracle.success)


# --------------------------------------------------------------------- #
# Windower: one packing implementation
# --------------------------------------------------------------------- #
def test_array_superbatches_route_through_pack_window_cols(monkeypatch):
    """The count-window column fast path must delegate to the shared
    pack_window_cols helper (the latency-curve bench measures the real
    path through it)."""
    calls = []
    orig = Windower.pack_window_cols

    def spy(self, win_cols, first_index=0):
        calls.append(len(win_cols))
        return orig(self, win_cols, first_index)

    monkeypatch.setattr(Windower, "pack_window_cols", spy)
    rng = np.random.default_rng(18)
    src = rng.integers(0, N_VERTS, 200).astype(np.int64)
    dst = rng.integers(0, N_VERTS, 200).astype(np.int64)
    w = Windower(CountWindow(37), IdentityDict(N_VERTS))
    groups = list(w.superbatches((src, dst), 3))
    assert calls and sum(calls) == sum(len(g) for g in groups)
    assert all(g.n_seen_before is not None for g in groups)
