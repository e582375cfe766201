"""Connected components served WITH their sizes (ISSUE 36):
``ConnectedComponents(component_sizes=True)`` carries a size table
beside the pointer forest, folded by the window's own step, and
``ComponentSizeQuery`` is one root chase and one gather. Every answer
is held to ``tests/_size_ref.py`` (a sequential union-find with a count
per root) after EVERY window, through the servable's snapshots and the
server's query engine on both of its paths."""

from __future__ import annotations

import contextlib
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gelly_streaming_tpu.core.stream import SimpleEdgeStream, StreamContext
from gelly_streaming_tpu.core.window import CountWindow
from gelly_streaming_tpu.datasets import IdentityDict, rmat_edges
from gelly_streaming_tpu.library import (
    ConnectedComponents,
    ConnectedComponentsTree,
)
from gelly_streaming_tpu.obs import trace as obs_trace
from gelly_streaming_tpu.parallel.mesh import make_mesh
from gelly_streaming_tpu.serving import (
    ComponentSizeQuery,
    ConnectedQuery,
    DegreeCountQuery,
    DegreeQuery,
    RankQuery,
    SnapshotStore,
    StreamServer,
)
from gelly_streaming_tpu.serving import query as squery
from gelly_streaming_tpu.serving.query import QueryEngine
from gelly_streaming_tpu.serving.snapshot_store import PublishedSnapshot
from gelly_streaming_tpu.summaries import forest

from _size_ref import SizeRef


# ---- streams: (id space, window, src, dst) ---------------------------- #
def _kronecker(seed=5, scale=9, windows=8, w=128):
    src, dst = rmat_edges(windows * w, scale, seed=seed)
    return 1 << scale, w, src.astype(np.int32), dst.astype(np.int32)


def _worst_path(n=128, w=8):
    """A path whose edges arrive far end first, so that every window
    hooks a long tail under a new smaller root: the deepest chases the
    min-rooted forest can be made to take."""
    a = np.arange(n - 1, 0, -1, dtype=np.int32)
    return n, w, a, a - 1


def _duplicates_and_loops(n=64, w=32, windows=6, seed=2):
    """Every window repeats a handful of edges many times over, in both
    directions, between self-loops."""
    rng = np.random.default_rng(seed)
    src, dst = [], []
    for _ in range(windows):
        few = rng.integers(0, n, (4, 2))
        rows = few[rng.integers(0, 4, w)]
        flip = rng.random(w) < 0.5
        rows = np.where(flip[:, None], rows[:, ::-1], rows)
        loops = rng.random(w) < 0.3
        rows[loops, 1] = rows[loops, 0]
        src.append(rows[:, 0])
        dst.append(rows[:, 1])
    return (n, w, np.concatenate(src).astype(np.int32),
            np.concatenate(dst).astype(np.int32))


def _many_into_one(n=128, w=16):
    """Three windows build sixteen components of four, the fourth
    chains all sixteen together at once, the fifth merges NOTHING (its
    edges lie inside the one component, with a self-loop on an id the
    stream never touches otherwise)."""
    groups = np.arange(64).reshape(16, 4)
    src = np.concatenate([groups[:, 0], groups[:, 1], groups[:, 2]])
    dst = np.concatenate([groups[:, 1], groups[:, 2], groups[:, 3]])
    bridge_s = np.concatenate([groups[:-1, 3], [groups[0, 0]]])
    bridge_d = np.concatenate([groups[1:, 0], [groups[0, 0]]])
    inside_s = np.concatenate([groups[:15, 2], [100]])
    inside_d = np.concatenate([groups[1:, 1], [100]])
    return (n, w,
            np.concatenate([src, bridge_s, inside_s]).astype(np.int32),
            np.concatenate([dst, bridge_d, inside_d]).astype(np.int32))


STREAMS = {
    "kronecker": _kronecker,
    "worst_order_path": _worst_path,
    "duplicates_and_self_loops": _duplicates_and_loops,
    "many_into_one_then_nothing": _many_into_one,
}


def _snapshots(n, w, src, dst, **agg_kw):
    """Every window's published snapshot, in order, through the
    servable and a server's store (the retention ring kept whole)."""
    stream = SimpleEdgeStream((src, dst), window=CountWindow(w),
                              vertex_dict=IdentityDict(n))
    agg = ConnectedComponents(**agg_kw)
    snaps = []
    server = StreamServer(agg.servable(), stream,
                          store=SnapshotStore(retention=len(src) // w + 1))
    server.store.add_listener(snaps.append)
    with server:
        server.join(120)
        live = [server.ask(ComponentSizeQuery(v), 60).value
                for v in range(0, n, max(1, n // 16))]
    return agg, snaps, live


def _ask_all(engine, snap, n):
    """``ComponentSizeQuery`` of every vertex and a ``ConnectedQuery``
    of every neighbouring pair, in ONE mixed sweep."""
    queries = ([ComponentSizeQuery(v) for v in range(n)]
               + [ConnectedQuery(v, (v * 7 + 3) % n) for v in range(n)])
    got = [a.value for a in engine.answer_batch(snap, queries)]
    return np.asarray(got[:n], np.int64), np.asarray(got[n:], bool)


@pytest.mark.parametrize("prefer_host", [True, False],
                         ids=["host_path", "device_path"])
@pytest.mark.parametrize("name", sorted(STREAMS))
def test_every_vertex_answers_the_references_size_after_every_window(
        name, prefer_host):
    n, w, src, dst = STREAMS[name]()
    agg, snaps, live = _snapshots(n, w, src, dst, component_sizes=True)
    assert agg._cc_mode == "forest" and len(snaps) == -(-len(src) // w)
    engine = QueryEngine(prefer_host=prefer_host)
    ref = SizeRef(n)
    for k, snap in enumerate(snaps):
        ref.fold(src[k * w:(k + 1) * w], dst[k * w:(k + 1) * w])
        assert sorted(snap.payload)[:2] == ["labels", "sizes"]
        sizes, conn = _ask_all(engine, snap, n)
        want = ref.sizes()
        assert np.array_equal(sizes, want), (name, k)
        assert conn.tolist() == [
            ref.connected(v, (v * 7 + 3) % n) for v in range(n)]
        # exact at the roots, and the roots' sizes partition the id space
        canon = np.asarray(snap.payload["labels"])
        table = np.asarray(snap.payload["sizes"])
        roots = np.flatnonzero(canon == np.arange(n))
        assert int(table[roots].sum()) == n, (name, k)
        assert np.array_equal(table[roots], want[roots])
    assert live == ref.sizes()[::max(1, n // 16)].tolist()


def test_a_window_that_merges_nothing_rewrites_what_stood():
    n, w, src, dst = _many_into_one()
    _agg, snaps, _live = _snapshots(n, w, src, dst, component_sizes=True)
    merged, idle = snaps[-2].payload, snaps[-1].payload
    assert np.array_equal(np.asarray(merged["sizes"]),
                          np.asarray(idle["sizes"]))
    assert int(np.asarray(idle["sizes"])[0]) == 64
    # the id the idle window touched for the first time is still alone
    assert int(np.asarray(idle["sizes"])[100]) == 1


def test_a_snapshot_one_window_stale_fails_the_comparison():
    """What the benchmark's control relies on: on a stream whose every
    window merges something, the answers of window ``k - 1``'s snapshot
    are NOT those of the prefix ``k``."""
    n, w, src, dst = _kronecker()
    _agg, snaps, _live = _snapshots(n, w, src, dst, component_sizes=True)
    engine, ref = QueryEngine(prefer_host=True), SizeRef(n)
    ref.fold(src[:w], dst[:w])
    for k in range(1, len(snaps)):
        ref.fold(src[k * w:(k + 1) * w], dst[k * w:(k + 1) * w])
        stale, _conn = _ask_all(engine, snaps[k - 1], n)
        assert not np.array_equal(stale, ref.sizes()), k


def test_a_mixed_sweep_is_one_chase_one_gather_and_one_wait(monkeypatch):
    n, w, src, dst = _kronecker(windows=3)
    _agg, snaps, _live = _snapshots(n, w, src, dst, component_sizes=True)
    calls = {"_batch_roots": 0, "_gather": 0, "_fetch": 0}

    def counted(name):
        inner = getattr(squery, name)

        def call(*a, **kw):
            calls[name] += 1
            return inner(*a, **kw)
        return call

    for name in calls:
        monkeypatch.setattr(squery, name, counted(name))
    engine = QueryEngine(prefer_host=False)
    queries = ([ComponentSizeQuery(v) for v in range(48)]
               + [ConnectedQuery(v, v + 1) for v in range(16)])
    answers = engine.answer_batch(snaps[-1], queries)
    assert calls == {"_batch_roots": 1, "_gather": 1, "_fetch": 1}
    ref = SizeRef(n)
    ref.fold(src, dst)
    assert [a.value for a in answers] == (
        [ref.size(v) for v in range(48)]
        + [ref.connected(v, v + 1) for v in range(16)])
    # a sweep of sizes alone, and one of pairs alone, chase once too
    for qs, gathers in ((queries[:48], 1), (queries[48:], 0)):
        calls.update(dict.fromkeys(calls, 0))
        engine.answer_batch(snaps[-1], qs)
        assert calls == {"_batch_roots": 1, "_gather": gathers, "_fetch": 1}


# ---- the order of a sweep (ISSUE 39) ----------------------------------- #
_KERNELS = ("_batch_roots", "_gather", "_gather_sizes",
            "_component_size_table")


def _every_table(n, w, src, dst, sized):
    """A snapshot that holds EVERY table a read is made of: the served
    forest of a Kronecker stream (with its size table where ``sized``)
    and, made by hand over the same id space, a degree table, its
    histogram and a rank table."""
    _agg, snaps, _live = _snapshots(n, w, src, dst, component_sizes=sized)
    deg = np.bincount(np.concatenate([src, dst]), minlength=n).astype(np.int32)
    payload = dict(snaps[-1].payload)
    payload.update(
        deg=jnp.asarray(deg),
        hist=jnp.asarray(np.bincount(deg[deg > 0], minlength=64)[:64]
                         .astype(np.int32)),
        ranks=jnp.asarray((deg / max(1, deg.sum())).astype(np.float32)))
    return PublishedSnapshot(payload, window=7, watermark=len(src),
                             version=3, epoch=39), deg


def _one_by_one(engine, snap, queries):
    """The same queries through the class's own public method, a class
    at a time."""
    def col(cls, field):
        return np.asarray([getattr(q, field) for q in queries
                           if type(q) is cls], np.int64)

    return {
        ConnectedQuery: lambda: engine.connected(
            snap, col(ConnectedQuery, "u"), col(ConnectedQuery, "v")),
        ComponentSizeQuery: lambda: engine.component_size(
            snap, col(ComponentSizeQuery, "v")),
        DegreeQuery: lambda: engine.degree(snap, col(DegreeQuery, "v")),
        DegreeCountQuery: lambda: engine.degree_count(
            snap, col(DegreeCountQuery, "d")),
        RankQuery: lambda: engine.rank(snap, col(RankQuery, "v")),
    }


_PAIRS = [ConnectedQuery(v, (v * 7 + 3) % 512) for v in range(24)]
_SIZES = [ComponentSizeQuery(v) for v in range(0, 480, 10)]
_DEGREES = [DegreeQuery(v) for v in range(0, 512, 8)]
_COUNTS = [DegreeCountQuery(d) for d in (1, 2, 3, 5, 8, 13, 63)]
_RANKS = [RankQuery(v) for v in range(5, 500, 45)]
#: mix -> (queries, the snapshot holds ``sizes``, device reads, the
#: kernels the sweep runs)
MIXES = {
    "degrees_and_degree_counts": (
        _DEGREES + _COUNTS, False, 2, {"_gather": 2}),
    "pairs_alone": (_PAIRS, False, 1, {"_batch_roots": 1}),
    "pairs_and_sizes_over_a_sized_snapshot": (
        _SIZES + _PAIRS, True, 1, {"_batch_roots": 1, "_gather": 1}),
    "sizes_alone_over_a_sized_snapshot": (
        _SIZES, True, 1, {"_batch_roots": 1, "_gather": 1}),
    "pairs_and_sizes_over_an_unsized_snapshot": (
        _PAIRS + _SIZES, False, 2,
        {"_batch_roots": 1, "_component_size_table": 1, "_gather_sizes": 1}),
    "degrees_and_ranks": (_DEGREES + _RANKS, False, 2, {"_gather": 2}),
    "every_class_over_a_sized_snapshot": (
        _PAIRS + _DEGREES + _SIZES + _COUNTS + _RANKS, True, 4,
        {"_batch_roots": 1, "_gather": 4}),
    "invalid_and_unseen_ids": (
        [DegreeQuery(v) for v in (3, -1, 512, 10**12, 0, 511)]
        + [DegreeCountQuery(d) for d in (0, -4, 64, 999, 1, 2)]
        + [ConnectedQuery(u, v) for u, v in (
            (-1, -1), (700, 700), (700, 3), (3, 3), (1, 2), (2**40, 1))]
        + [RankQuery(v) for v in (-7, 4, 4096)],
        False, 4, {"_batch_roots": 1, "_gather": 3}),
}


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_a_sweep_enqueues_every_read_before_its_first_fetch(
        mix, monkeypatch):
    queries, sized, n_reads, kernels = MIXES[mix]
    # the classes interleaved: answers come back in the INPUT's order
    order = np.random.default_rng(39).permutation(len(queries))
    queries = [queries[i] for i in order]
    n, w, src, dst = _kronecker(windows=4)
    snap, deg = _every_table(n, w, src, dst, sized)
    engine = QueryEngine(prefer_host=False)
    # (an engine of its own: the unsized size table is cached a version)
    want = {cls: ask().tolist()
            for cls, ask in _one_by_one(
                QueryEngine(prefer_host=False), snap, queries).items()
            if any(type(q) is cls for q in queries)}
    log = []

    def logged(name):
        inner = getattr(squery, name)

        def call(*a, **kw):
            log.append(name)
            return inner(*a, **kw)
        return call

    for name in _KERNELS + ("_fetch",):
        monkeypatch.setattr(squery, name, logged(name))
    answers = engine.answer_batch(snap, queries)
    # every kernel of the sweep is out before the first result is
    # fetched, and every read is fetched once
    assert log[-n_reads:] == ["_fetch"] * n_reads, log
    assert {k: log.count(k) for k in kernels} == kernels
    assert len(log) == n_reads + sum(kernels.values()), log
    assert engine.last_sweep[0] == n_reads
    assert {(a.window, a.version, a.watermark, a.staleness)
            for a in answers} == {(7, 3, len(src), 0)}
    # value for value what the class's own method says, in input order
    taken = dict.fromkeys(want, 0)
    for q, a in zip(queries, answers):
        assert a.value == want[type(q)][taken[type(q)]], (q, a)
        taken[type(q)] += 1
    # ... what the host path says, which enqueues and waits for nothing
    host = QueryEngine(prefer_host=True)
    del log[:]
    assert [a.value for a in host.answer_batch(snap, queries)] == [
        a.value for a in answers]
    assert log == [] and host.last_sweep == (0, 0)
    # ... and what the tables say
    for q, a in zip(queries, answers):
        if type(q) is DegreeQuery:
            assert a.value == (int(deg[q.v]) if 0 <= q.v < n else 0), q
        elif type(q) is DegreeCountQuery:
            assert a.value == (int(np.sum(deg == q.d))
                               if 1 <= q.d < 64 else 0), q


@contextlib.contextmanager
def _span_events():
    """-> the list the program's span events land in while tracing is
    on."""
    events = []

    class Sink:
        def emit(self, event):
            events.append(event)

    sink = Sink()
    obs_trace.enable(registry_spans=False)
    obs_trace.add_sink(sink)
    try:
        yield events
    finally:
        obs_trace.remove_sink(sink)
        obs_trace.disable()


def test_the_size_lookup_span_counts_the_sweeps_lanes():
    n, w, src, dst = _kronecker(windows=2)
    with _span_events() as events:
        _agg, snaps, _live = _snapshots(n, w, src, dst, component_sizes=True)
        del events[:]
        QueryEngine(prefer_host=False).answer_batch(
            snaps[-1], [ComponentSizeQuery(3), ComponentSizeQuery(4),
                        ConnectedQuery(1, 2)])
    spans = {e["name"]: e for e in events if e.get("kind") == "span"}
    assert spans["serving.size_lookup"]["attrs"] == {"n": 2, "ids": 4}
    assert (spans["serving.device_wait"]["parent"]
            == spans["serving.size_lookup"]["sid"])


def test_the_size_lookup_span_ends_with_its_own_wait_in_a_longer_sweep():
    """The sized pair beside a second read: both kernels are out before
    the pair's wait, which alone lies under ``serving.size_lookup``."""
    n, w, src, dst = _kronecker(windows=2)
    with _span_events() as events:
        snap, _deg = _every_table(n, w, src, dst, sized=True)
        del events[:]
        engine = QueryEngine(prefer_host=False)
        engine.answer_batch(snap, _DEGREES[:5] + _SIZES[:3] + _PAIRS[:2])
    assert engine.last_sweep[0] == 2
    spans = [e for e in events if e.get("kind") == "span"]
    (lookup,) = [e for e in spans if e["name"] == "serving.size_lookup"]
    assert lookup["attrs"] == {"n": 3, "ids": 7}
    waits = [e for e in spans if e["name"] == "serving.device_wait"]
    assert [(e["attrs"]["n"], e.get("parent") == lookup["sid"])
            for e in waits] == [(7, True), (5, False)]
    assert lookup["t0"] + lookup["dur_s"] <= waits[1]["t0"]


def test_the_forest_window_span_says_the_step_is_sized():
    with _span_events() as events:
        for sized in (True, False):
            n, w, src, dst = _kronecker(windows=1)
            _snapshots(n, w, src, dst, carry="forest",
                       component_sizes=sized)
    windows = [e.get("attrs", {}) for e in events
               if e.get("name") == "forest.window"]
    assert [a.get("sizes") for a in windows] == [True, None]


def test_without_the_option_no_size_table_is_published_or_carried():
    n, w, src, dst = _kronecker(windows=4)
    agg, snaps, live = _snapshots(n, w, src, dst, carry="forest")
    assert agg._sizes is None and not agg.component_sizes
    assert all("sizes" not in s.payload for s in snaps)
    # the query still answers, by the whole-table derivation: sizes of
    # the components the stream has touched are the reference's
    ref = SizeRef(n)
    ref.fold(src, dst)
    engine = QueryEngine(prefer_host=False)
    got = engine.component_size(snaps[-1], np.arange(n))
    assert np.array_equal(got, ref.sizes())
    assert engine._size_cache[0] is not None
    assert live == ref.sizes()[::max(1, n // 16)].tolist()


def test_the_sized_step_is_one_program_named_step_with_its_scope():
    tcap, wcap, vcap = 64, 32, 256
    S = jax.ShapeDtypeStruct
    cols = (S((tcap,), jnp.int32), S((tcap,), jnp.bool_),
            S((wcap,), jnp.int32), S((wcap,), jnp.int32))
    table = S((vcap,), jnp.int32)
    sized = forest._forest_step_fn(tcap, wcap, vcap, sizes=True)
    plain = forest._forest_step_fn(tcap, wcap, vcap)
    assert sized is not plain
    text = sized.lower(table, *cols, table).as_text(debug_info=True)
    assert "@jit_step" in text and "forest.sizes" in text
    assert "forest.sizes" not in plain.lower(table, *cols).as_text(
        debug_info=True)
    out = jax.eval_shape(sized, table, *cols, table)
    assert [o.shape for o in out] == [(vcap,), (vcap,)]


def test_restored_labels_bring_their_sizes_back():
    n, w, src, dst = _kronecker(windows=6)
    half = 3 * w
    first = ConnectedComponents(component_sizes=True)
    for _ in first.run(SimpleEdgeStream(
            (src[:half], dst[:half]), window=CountWindow(w),
            vertex_dict=IdentityDict(n))):
        pass
    state = first.snapshot_state()
    again = ConnectedComponents(component_sizes=True)
    again.restore_state(state, n)
    for _ in again.run(SimpleEdgeStream(
            (src[half:], dst[half:]), window=CountWindow(w),
            vertex_dict=IdentityDict(n))):
        pass
    ref = SizeRef(n)
    ref.fold(src, dst)
    canon = np.asarray(again._canon)
    roots = np.flatnonzero(canon == np.arange(n))
    assert np.array_equal(np.asarray(again._sizes)[roots],
                          ref.sizes()[roots])
    assert int(np.asarray(again._sizes)[roots].sum()) == n


def test_the_tables_grow_together():
    sizes = forest.grow_sizes(forest.init_sizes(4).at[0].set(3), 8)
    assert sizes.tolist() == [3, 1, 1, 1, 1, 1, 1, 1]
    assert forest.grow_sizes(sizes, 8) is sizes


def test_an_empty_window_leaves_both_tables_alone():
    canon, sizes = forest.init_forest(8), forest.init_sizes(8)
    none = np.zeros(0, np.int32)
    c, tids, s = forest.forest_window(
        canon, none, none, 8, forest.WindowPrep(), sizes=sizes)
    assert c is canon and s is sizes and len(tids) == 0


# ---- what the sized carry refuses, by name --------------------------- #
@pytest.mark.parametrize("kwargs,needle", [
    ({"carry": "host"}, "carry='host'"),
    ({"carry": "dense"}, "carry='dense'"),
    ({"superbatch": 4}, "superbatch above 1"),
    ({"superbatch": "auto"}, "superbatch above 1"),
])
def test_the_constructor_refuses_what_carries_no_size_table(kwargs, needle):
    with pytest.raises(NotImplementedError, match=needle):
        ConnectedComponents(component_sizes=True, **kwargs)
    ConnectedComponents(**kwargs)       # without sizes: as ever


@pytest.mark.parametrize("shape,needle", [
    ({"n_edge_shards": 1, "n_vertex_shards": 4}, "`vertices` axis above 1"),
    ({"n_edge_shards": 4, "n_vertex_shards": 1}, "`edges` axis above 1"),
])
@pytest.mark.parametrize("cls", [ConnectedComponents,
                                 ConnectedComponentsTree])
def test_a_mesh_axis_above_one_is_refused_before_the_first_window(
        cls, shape, needle):
    n, w, src, dst = _kronecker(windows=2)
    stream = SimpleEdgeStream(
        (src, dst), window=CountWindow(w), vertex_dict=IdentityDict(n),
        context=StreamContext(mesh=make_mesh(**shape)))
    with pytest.raises(NotImplementedError, match=needle):
        next(iter(cls(component_sizes=True).run(stream)))
    with pytest.raises(NotImplementedError, match=needle):
        forest._forest_step_fn(64, 32, 256, make_mesh(**shape), sizes=True)


def test_a_window_without_host_columns_is_refused():
    n, w, src, dst = _kronecker(windows=1)
    stream = SimpleEdgeStream((src, dst), window=CountWindow(w),
                              vertex_dict=IdentityDict(n))
    agg = ConnectedComponents(component_sizes=True)
    block = next(iter(stream.blocks()))
    with pytest.raises(NotImplementedError, match="no size table"):
        next(agg._one_window(block, None, None, 2, stream.vertex_dict))


def test_the_ingest_thread_and_a_sweep_do_not_share_a_half_folded_pair():
    """``labels`` and ``sizes`` of one snapshot come from one program:
    while windows fold, every sweep's sizes are those of SOME published
    prefix (its own stamp's), never a mix of two."""
    n, w, src, dst = _kronecker(windows=12)
    refs, ref = [], SizeRef(n)
    for k in range(12):
        ref.fold(src[k * w:(k + 1) * w], dst[k * w:(k + 1) * w])
        refs.append(ref.sizes())
    stream = SimpleEdgeStream((src, dst), window=CountWindow(w),
                              vertex_dict=IdentityDict(n))
    server = StreamServer(
        ConnectedComponents(component_sizes=True).servable(), stream)
    bad = []

    def ask():
        for _ in range(20):
            got = [f.result(60) for f in server.submit_many(
                [ComponentSizeQuery(v) for v in range(0, n, 8)])]
            for a, v in zip(got, range(0, n, 8)):
                if a.value != refs[a.window][v]:
                    bad.append((a.window, v, a.value))

    with server:
        threads = [threading.Thread(target=ask) for _ in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
        assert not any(t.is_alive() for t in threads)
        server.join(60)
    assert bad == []
