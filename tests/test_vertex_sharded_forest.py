"""The vertex-sharded forest (ISSUE 28): the SAME step over a table of
which every chip holds one contiguous block of rows.

On four of the suite's eight virtual CPU devices, small tables, seeded:
(a) window by window the sharded step's table equals the one-chip
step's and its roots a sequential union-find's; (b) each chip's block
alone is its rows of the one-chip table and no row lies on two chips;
(c) ``ConnectedQuery`` batches through a live ``StreamServer`` on the
sharded carry equal the oracle at the stamped prefix; (d) growth of
``vcap`` keeps shards and answers right; (e) what the layout lacks is
refused by name; (f) the mesh-less step is the program it was. Nothing
here is a device number.
"""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gelly_streaming_tpu.core.stream import SimpleEdgeStream, StreamContext
from gelly_streaming_tpu.core.window import CountWindow
from gelly_streaming_tpu.datasets import IdentityDict
from gelly_streaming_tpu.library import ConnectedComponents
from gelly_streaming_tpu.library.bipartiteness import BipartitenessCheck
from gelly_streaming_tpu.obs import trace as obs_trace
from gelly_streaming_tpu.parallel.mesh import (
    make_mesh,
    table_vertex_shards,
    vertex_shards,
)
from gelly_streaming_tpu.serving import (
    ComponentSizeQuery,
    ConnectedQuery,
    StreamServer,
    SummaryPullQuery,
)
from gelly_streaming_tpu.serving import query as squery
from gelly_streaming_tpu.summaries import forest

from _scatter_ref import (  # noqa: F401  (unsorted_steps is a fixture)
    assert_table_scatters_go_out_sorted,
    cc_tables,
    plain_scatter,
    unsorted_steps,
)

SHARDS = 4


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(n_edge_shards=1, n_vertex_shards=SHARDS,
                     devices=jax.devices()[:SHARDS])


class Oracle:
    """A sequential union-find, min id as the root."""

    def __init__(self, n: int):
        self.p = list(range(n))

    def find(self, x: int) -> int:
        while self.p[x] != x:
            self.p[x] = self.p[self.p[x]]
            x = self.p[x]
        return x

    def fold(self, src, dst) -> None:
        for a, b in zip(src.tolist(), dst.tolist()):
            ra, rb = self.find(a), self.find(b)
            if ra != rb:
                self.p[max(ra, rb)] = min(ra, rb)

    def roots(self, ids) -> np.ndarray:
        return np.asarray([self.find(int(i)) for i in ids])


def _windows(seed: int, vcap: int, n: int, size: int):
    """A stream with a giant component: low ids are drawn often, so the
    giant's root has ONE owner and chains run through former roots."""
    rng = np.random.default_rng(seed)
    for _ in range(n):
        m = int(rng.integers(size // 2, size + 1))
        s = (rng.integers(0, vcap, m) * rng.random(m) ** 2).astype(np.int32)
        d = rng.integers(0, vcap, m).astype(np.int32)
        yield s, d


def _blocks(table) -> list:
    """The blocks as the chips hold them, in the order of their rows."""
    shards = sorted(table.addressable_shards, key=lambda s: s.index[0].start)
    return [(s.index[0].start, s.index[0].stop, np.asarray(s.data))
            for s in shards]


# --------------------------------------------------------------------- #
# (a) + (b): the step, window by window
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("seed,vcap,size", [
    (1, 1 << 10, 256), (2, 1 << 12, 1024), (3, 1 << 16, 16384)])
def test_the_sharded_step_is_the_one_chip_step_window_by_window(
        mesh, seed, vcap, size):
    whole, split = forest.init_forest(vcap), forest.init_forest(vcap, mesh)
    p1, p4, oracle = forest.WindowPrep(), forest.WindowPrep(), Oracle(vcap)
    rng = np.random.default_rng(seed + 100)
    giant = 0
    for s, d in _windows(seed, vcap, 10, size):
        whole, tids = forest.forest_window(whole, s, d, vcap, p1)
        split, tids4 = forest.forest_window(split, s, d, vcap, p4, mesh=mesh)
        oracle.fold(s, d)
        assert table_vertex_shards(split) == SHARDS
        table = np.asarray(whole)
        # (a) shards laid end to end: the one-chip table, bit for bit
        blocks = _blocks(split)
        assert np.array_equal(np.concatenate([b for _, _, b in blocks]), table)
        assert np.array_equal(np.sort(tids), np.sort(tids4))
        # ... and its roots a sequential union-find's
        ids = np.concatenate([tids, rng.integers(0, vcap, 64)])
        got = np.asarray(squery._batch_roots_fn(mesh)(
            split, jnp.asarray(squery._pad_ids(ids))))[:len(ids)]
        want = oracle.roots(ids)
        assert np.array_equal(got, want)
        giant = max(giant, int(np.bincount(want).max()))
        # (b) each chip's block alone is its rows of the whole, and the
        # blocks tile [0, vcap) once: no row on two chips, none on none
        rows = vcap // SHARDS
        assert [(lo, hi) for lo, hi, _ in blocks] == [
            (k * rows, (k + 1) * rows) for k in range(SHARDS)]
        assert len({s.device for s in split.addressable_shards}) == SHARDS
        for lo, hi, block in blocks:
            assert block.shape == (rows,)
            assert np.array_equal(block, table[lo:hi])
    assert giant > len(ids) // 3, "the stream was to have a giant component"


def test_a_fresh_sharded_forest_is_built_block_by_block(mesh):
    split = forest.init_forest(1 << 10, mesh)
    assert vertex_shards(split.sharding.mesh) == SHARDS
    for lo, hi, block in _blocks(split):
        assert np.array_equal(block, np.arange(lo, hi, dtype=np.int32))


# --------------------------------------------------------------------- #
# (c) served: ConnectedQuery through a live StreamServer
# --------------------------------------------------------------------- #
def _edges(seed: int, vcap: int, n: int):
    rng = np.random.default_rng(seed)
    s = (rng.integers(0, vcap, n) * rng.random(n) ** 2).astype(np.int64)
    d = rng.integers(0, vcap, n).astype(np.int64)
    return s, d


class _Gated:
    """An edge iterable that hands out ``window`` edges at a time and
    waits, between windows, until the test lets the next one go."""

    def __init__(self, src, dst):
        import threading

        self.src, self.dst = src, dst
        self.allowed = threading.Semaphore(0)

    def __iter__(self):
        for i, (a, b) in enumerate(zip(self.src.tolist(), self.dst.tolist())):
            if i % 128 == 0:
                self.allowed.acquire()
            yield a, b


@pytest.mark.parametrize("seed", [11, 12, 13])
def test_connected_queries_through_a_live_server_equal_the_oracle(mesh, seed):
    vcap, window, n_win = 1 << 12, 128, 8
    src, dst = _edges(seed, vcap, window * n_win)
    gated = _Gated(src, dst)
    stream = SimpleEdgeStream(
        gated, window=CountWindow(window), vertex_dict=IdentityDict(vcap),
        context=StreamContext(mesh=mesh))
    agg = ConnectedComponents()          # carry "auto"
    server = StreamServer(agg.servable(), stream)
    server.start()
    rng = np.random.default_rng(seed)
    asked = 0
    try:
        for w in range(n_win):
            gated.allowed.release()
            # mid-stream: ask while later windows are still to come
            i = rng.integers(0, window * (w + 1), 24)
            us = np.concatenate([src[i], rng.integers(0, vcap, 8)])
            vs = np.concatenate([dst[i], rng.integers(0, vcap, 8)])
            futs = server.submit_many(
                [ConnectedQuery(int(u), int(v)) for u, v in zip(us, vs)])
            for u, v, f in zip(us, vs, futs):
                a = f.result(60)
                oracle = Oracle(vcap)    # the prefix the answer is stamped with
                k = (a.window + 1) * window
                oracle.fold(src[:k], dst[:k])
                assert bool(a.value) == (
                    oracle.find(int(u)) == oracle.find(int(v))), (w, a.window)
                asked += 1
        gated.allowed.release()
        server.join(60)
        final = server.snapshot()
        assert final.window == n_win - 1
        labels = final.payload["labels"]
        # the published table is the sharded array itself
        assert table_vertex_shards(labels) == SHARDS
        assert {s.data.shape for s in labels.addressable_shards} == {
            (vcap // SHARDS,)}
        oracle = Oracle(vcap)
        oracle.fold(src, dst)
        us, vs = rng.integers(0, vcap, 64), rng.integers(0, vcap, 64)
        for u, v, f in zip(us, vs, server.submit_many(
                [ConnectedQuery(int(u), int(v)) for u, v in zip(us, vs)])):
            assert bool(f.result(60).value) == (
                oracle.find(int(u)) == oracle.find(int(v)))
    finally:
        server.close(10)
    assert agg._cc_mode == "forest" and asked == n_win * 32


# --------------------------------------------------------------------- #
# (d) growth
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("factor", [2, 4, 16])
def test_growth_keeps_shards_and_answers_right(mesh, factor):
    vcap = 1 << 10
    whole, split = forest.init_forest(vcap), forest.init_forest(vcap, mesh)
    p1, p4, oracle = (forest.WindowPrep(), forest.WindowPrep(),
                      Oracle(vcap * factor))
    for s, d in _windows(5, vcap, 4, 96):
        whole, _ = forest.forest_window(whole, s, d, vcap, p1)
        split, _ = forest.forest_window(split, s, d, vcap, p4, mesh=mesh)
        oracle.fold(s, d)
    vcap *= factor
    whole = forest.grow_forest(whole, vcap)
    split = forest.grow_forest(split, vcap, mesh)
    assert table_vertex_shards(split) == SHARDS
    for lo, hi, block in _blocks(split):
        assert hi - lo == vcap // SHARDS
        assert np.array_equal(block, np.asarray(whole)[lo:hi])
    for s, d in _windows(6, vcap, 4, 96):
        whole, _ = forest.forest_window(whole, s, d, vcap, p1)
        split, tids = forest.forest_window(split, s, d, vcap, p4, mesh=mesh)
        oracle.fold(s, d)
        assert np.array_equal(np.asarray(split), np.asarray(whole))
        got = np.asarray(squery._batch_roots_fn(mesh)(
            split, jnp.asarray(squery._pad_ids(tids))))[:len(tids)]
        assert np.array_equal(got, oracle.roots(tids))


def test_a_stream_that_outgrows_its_table_stays_sharded(mesh):
    """Through the library: a ``VertexDict``-less stream whose id bound
    doubles mid-run (``IdentityDict`` capacity follows the bound)."""
    src, dst = _edges(21, 1 << 11, 512)
    small = src < (1 << 9)
    order = np.argsort(~small, kind="stable")   # low ids first, then all
    src, dst = src[order], np.where(small[order], dst[order] % (1 << 9),
                                    dst[order])

    class Growing(IdentityDict):
        @property
        def capacity(self):
            return 1 << 9 if self._observed <= (1 << 9) else 1 << 11

    stream = SimpleEdgeStream(
        list(zip(src.tolist(), dst.tolist())), window=CountWindow(64),
        vertex_dict=Growing(1 << 11), context=StreamContext(mesh=mesh))
    agg = ConnectedComponents()
    seen = set()
    for _ in agg.run(stream):
        assert table_vertex_shards(agg._canon) == SHARDS
        seen.add(int(agg._canon.shape[0]))
    assert len(seen) > 1, "the table was to grow mid-stream"
    oracle = Oracle(1 << 11)
    oracle.fold(src, dst)
    ids = np.arange(0, 1 << 11, 7)
    got = squery._host_batch_roots(np.asarray(agg._canon), ids)
    assert np.array_equal(got, oracle.roots(ids))


# --------------------------------------------------------------------- #
# (e) what the layout lacks is refused, by name
# --------------------------------------------------------------------- #
def _stream(mesh, n=256, vcap=1 << 10):
    s, d = _edges(3, vcap, n)
    return SimpleEdgeStream(
        list(zip(s.tolist(), d.tolist())), window=CountWindow(64),
        vertex_dict=IdentityDict(vcap), context=StreamContext(mesh=mesh))


def _served(mesh):
    server = StreamServer(ConnectedComponents().servable(), _stream(mesh))
    server.start()
    server.join(60)
    return server


def _refused_query(mesh, query):
    server = _served(mesh)
    try:
        (fut,) = server.submit_many([query])
        fut.result(60)
    finally:
        server.close(10)


def _both_axes():
    return make_mesh(n_edge_shards=2, n_vertex_shards=SHARDS)


@pytest.mark.parametrize("what,run,names", [
    ("ComponentSizeQuery",
     lambda mesh: _refused_query(mesh, ComponentSizeQuery(3)),
     "ComponentSizeQuery"),
    ("SummaryPullQuery",
     lambda mesh: _refused_query(mesh, SummaryPullQuery()),
     "SummaryPullQuery"),
    ("superbatch",
     lambda mesh: list(ConnectedComponents(superbatch=4).run(_stream(mesh))),
     "superbatch"),
    ("superbatch-step",
     lambda mesh: forest._forest_superbatch_fn(8, 8, 1 << 10, 2, mesh),
     "superbatch"),
    ("edges-with-vertices",
     lambda mesh: list(ConnectedComponents().run(_stream(_both_axes()))),
     "`edges` axis"),
    ("cover-forest",
     lambda mesh: list(BipartitenessCheck().run(_stream(mesh))),
     "BipartitenessCheck has no vertex-sharded carry"),
    ("host-carry",
     lambda mesh: list(ConnectedComponents(carry="host").run(_stream(mesh))),
     "carry='host'"),
    ("dense-carry",
     lambda mesh: list(ConnectedComponents(carry="dense").run(_stream(mesh))),
     "carry='dense'"),
])
def test_what_the_sharded_layout_lacks_is_refused_by_name(
        mesh, what, run, names):
    with pytest.raises(NotImplementedError, match=re.escape(names)):
        run(mesh)


@pytest.mark.parametrize("shards", [0, 3, 6])
def test_a_shard_count_that_is_no_power_of_two_is_refused(shards):
    with pytest.raises(ValueError, match="power of two"):
        make_mesh(n_edge_shards=1, n_vertex_shards=shards)


# --------------------------------------------------------------------- #
# (f) the mesh-less step is the program it was
# --------------------------------------------------------------------- #
#: ops of the step lowered at (tcap 2^10, wcap 2^9, vcap 2^14) and of
#: ``_batch_roots`` at 256 ids, counted on the PARENT commit (b46204a)
#: before this layout was added; the two texts were identical to the
#: parent's letter for letter when this was written. ISSUE 33 added the
#: three gathers of the fixpoint's contraction (window-sized, off the
#: table: no all-reduce comes with them); ISSUE 37 took the group's
#: scatter-min into a table-sized scratch and its gather back out (two
#: sorts of the lanes and a doubling stand in their place), and with the
#: gather its all-reduce
PARENT_OPS = {
    "step": {"gather": 10, "scatter": 5, "while": 2},
    "batch_roots": {"gather": 3, "scatter": 0, "while": 1},
}
COLLECTIVES = ("all_reduce", "all_gather", "all_to_all",
               "collective_permute", "reduce_scatter",
               "collective_broadcast")


def _ops(text: str, name: str) -> int:
    """Instructions ``stablehlo.<name>`` in a lowered module's text
    (the attribute ``#stablehlo.<name><...>`` is not one)."""
    return len(re.findall(r'(?<!#)"?stablehlo\.%s"?[ (]' % name, text))


def _lowered(which: str, mesh=None, debug_info: bool = False) -> str:
    S = jax.ShapeDtypeStruct
    if which == "batch_roots":
        return squery._batch_roots_fn(mesh).lower(
            S((1 << 14,), jnp.int32), S((256,), jnp.int32)).as_text(
                debug_info=debug_info)
    tcap, wcap, vcap = 1 << 10, 1 << 9, 1 << 14
    return forest._forest_step_fn(tcap, wcap, vcap, mesh).lower(
        S((vcap,), jnp.int32), S((tcap,), jnp.int32), S((tcap,), jnp.bool_),
        S((wcap,), jnp.int32), S((wcap,), jnp.int32)).as_text(
            debug_info=debug_info)


@pytest.mark.parametrize("which", ["step", "batch_roots"])
def test_the_mesh_less_program_is_the_one_the_cells_run(which):
    text = _lowered(which)
    assert f"@jit_{'step' if which == 'step' else '_batch_roots'}" in text
    for name in COLLECTIVES:
        assert _ops(text, name) == 0, name
    assert "sdy.manual_computation" not in text and "shard_map" not in text
    assert {k: _ops(text, k) for k in PARENT_OPS[which]} == PARENT_OPS[which]


@pytest.mark.parametrize("which,exchange,n", [
    ("step", "forest.exchange", 3), ("batch_roots", "query.exchange", 3)])
def test_the_sharded_program_keeps_its_name_and_scopes_its_collectives(
        mesh, which, exchange, n):
    """Same gathers, scatters and loops; one all-reduce a gather, each
    under the exchange scope; no other collective (a sharded table is
    never gathered)."""
    text = _lowered(which, mesh)
    assert f"@jit_{'step' if which == 'step' else '_batch_roots'}" in text
    assert {k: _ops(text, k) for k in PARENT_OPS[which]} == PARENT_OPS[which]
    assert _ops(text, "all_reduce") == n
    for name in COLLECTIVES[1:]:
        assert _ops(text, name) == 0, name
    with_locs = _lowered(which, mesh, debug_info=True)
    scoped = [ln for ln in with_locs.splitlines()
              if "all_reduce" in ln and "stablehlo" in ln]
    assert len(scoped) == n
    assert with_locs.count(exchange) >= n


# --------------------------------------------------------------------- #
# every scatter into the table goes out sorted (ISSUE 31)
# --------------------------------------------------------------------- #
def _scatter_lanes(seed: int, vcap: int, lanes: int, rows: str):
    """Lanes out of order, a third of them pads at the sentinel
    ``vcap``: rows that repeat with one value a row (what the commit
    and the size phase write), or rows met once with a value each (what
    the degree step writes)."""
    rng = np.random.default_rng(seed)
    if rows == "repeated":
        idx = rng.integers(0, vcap, lanes // 8).astype(np.int32)
        idx = rng.choice(idx, lanes).astype(np.int32)
        val = (idx * 7 + 3).astype(np.int32)
    else:
        idx = rng.choice(vcap, lanes, replace=False).astype(np.int32)
        val = rng.integers(0, 1 << 20, lanes).astype(np.int32)
    idx[rng.random(lanes) < 1 / 3] = vcap
    assert (np.diff(idx) < 0).any()
    assert (len(np.unique(idx)) < lanes // 4) == (rows == "repeated")
    return idx, val


@pytest.mark.parametrize("seed", [7, 2**31 + 9])
@pytest.mark.parametrize("rows", ["repeated", "unique"])
@pytest.mark.parametrize("shards", [1, SHARDS])
def test_the_tables_scatter_is_the_plain_one_lane_order_and_all(
        mesh, shards, rows, seed):
    vcap, lanes = 1 << 10, 512
    idx, val = _scatter_lanes(seed, vcap, lanes, rows)
    table = np.random.default_rng(seed + 1).integers(
        1 << 19, 1 << 21, vcap).astype(np.int32)
    want = table.copy()
    keep = idx < vcap
    want[idx[keep]] = val[keep]
    tab = forest.TableOps(vcap, shards)

    def run(scatter, order):
        def fn(t, i, v):
            return scatter(tab, t, i, v)

        if shards > 1:
            fn = forest.sharded_table_fn(fn, mesh, 2, table_out=True)
        return np.asarray(jax.jit(fn)(
            jnp.asarray(table), jnp.asarray(idx[order]),
            jnp.asarray(val[order])))

    order = np.arange(lanes)
    got = run(forest.TableOps.scatter, order)
    assert np.array_equal(got, want)
    assert np.array_equal(got, run(plain_scatter, order))
    assert np.array_equal(got, np.asarray(
        jnp.asarray(table).at[jnp.asarray(idx)].set(
            jnp.asarray(val), mode="drop")))
    assert np.array_equal(
        got, run(forest.TableOps.scatter, order[::-1].copy()))


@pytest.mark.parametrize("seed", [1, 2**31 + 5])
def test_sorted_scatters_give_the_unsorted_sharded_steps_table(
        mesh, seed, unsorted_steps):
    got = cc_tables(seed, mesh=mesh)
    unsorted_steps()
    want = cc_tables(seed, mesh=mesh)
    for w, (a, b) in enumerate(zip(got, want)):
        np.testing.assert_array_equal(a, b, err_msg=f"window {w}")
    assert (got[-1] != np.arange(len(got[-1]))).sum() > 100


def test_the_lowered_sharded_step_says_its_table_scatters_are_sorted(mesh):
    assert_table_scatters_go_out_sorted(
        _lowered("step", mesh, debug_info=True))


# --------------------------------------------------------------------- #
# tracing: forest.place and the owners' share, only under the axis
# --------------------------------------------------------------------- #
class _Sink:
    def __init__(self):
        self.events = []

    def emit(self, event):
        if event.get("kind") == "span":
            self.events.append(event)


def _traced_windows(mesh):
    vcap = 1 << 10
    canon, prep = forest.init_forest(vcap, mesh), forest.WindowPrep()
    sink = _Sink()
    obs_trace.enable(registry_spans=False)
    obs_trace.add_sink(sink)
    try:
        for s, d in _windows(9, vcap, 3, 64):
            canon, _ = forest.forest_window(canon, s, d, vcap, prep, mesh=mesh)
    finally:
        obs_trace.remove_sink(sink)
        obs_trace.disable()
    return sink.events


def test_a_sharded_window_places_its_columns_and_notes_its_owners(mesh):
    events = _traced_windows(mesh)
    names = [e["name"] for e in events]
    assert names.count("forest.place") == names.count("forest.window") == 3
    windows = {e["sid"]: e for e in events if e["name"] == "forest.window"}
    for e in events:
        if e["name"] == "forest.place":
            assert e["parent"] in windows
    for w in windows.values():
        assert w["attrs"]["shards"] == SHARDS
        # low ids are drawn often: the first block owns the most lanes
        assert 1 / SHARDS < w["attrs"]["owner_max_share"] <= 1.0


def test_with_tracing_off_a_sharded_window_builds_no_span(mesh, monkeypatch):
    built = []
    real_init = obs_trace.Span.__init__

    def counting_init(self, *a, **kw):
        built.append(a[0])
        real_init(self, *a, **kw)

    monkeypatch.setattr(obs_trace.Span, "__init__", counting_init)
    vcap = 1 << 10
    canon, prep = forest.init_forest(vcap, mesh), forest.WindowPrep()
    for s, d in _windows(9, vcap, 2, 64):
        canon, _ = forest.forest_window(canon, s, d, vcap, prep, mesh=mesh)
    assert built == []


def test_on_one_chip_a_window_emits_what_it_did():
    events = _traced_windows(None)
    assert sorted({e["name"] for e in events}) == [
        "forest.dispatch", "forest.prep", "forest.window"]
    for e in events:
        assert "shards" not in e.get("attrs", {})
        assert "owner_max_share" not in e.get("attrs", {})
