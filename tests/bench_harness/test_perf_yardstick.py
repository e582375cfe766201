"""The benchmark's yardstick, on the CPU at a tiny scale: generator,
reference union-find, byte model, peaks, traffic schedule and the
closed-loop source. No chip number is produced here."""

from __future__ import annotations

import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks.generators import graph500  # noqa: E402
from benchmarks.lib import bytes_model, peaks  # noqa: E402
from benchmarks.lib.traffic import (  # noqa: E402
    QueryLoad, WindowSource, query_schedule,
)
from benchmarks.lib.unionfind import (  # noqa: E402
    ForestReference, UnionFind, resolve_some,
)


# ---- generator ------------------------------------------------------- #
@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11, 2**32 + 5])
def test_generator_is_deterministic_in_seed(seed):
    a = graph500.kronecker_edges(seed, 10, 3000)
    b = graph500.kronecker_edges(seed, 10, 3000)
    c = graph500.kronecker_edges(seed + 1, 10, 3000)
    assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])
    assert not np.array_equal(a[0], c[0])
    assert a[0].dtype == np.int32 and a[0].min() >= 0
    assert max(a[0].max(), a[1].max()) < 1 << 10


def test_generator_longer_stream_extends_shorter():
    short = graph500.kronecker_edges(3, 9, 100, chunk=64)
    long_ = graph500.kronecker_edges(3, 9, 200, chunk=64)
    assert np.array_equal(short[0][:64], long_[0][:64])


def test_generator_is_skewed_like_kronecker():
    """A = 0.57 piles edges on few vertices: unscrambled, the low ids
    take far more than their uniform share."""
    s, _d = graph500.kronecker_edges(1, 12, 1 << 14, scrambled=False)
    assert np.mean(s < (1 << 10)) > 0.5   # a quarter of ids, uniform = 0.25


@pytest.mark.parametrize("seed", [1, 2**31 + 3])
def test_the_seed_orders_the_same_windows_another_way(seed):
    s, d = graph500.kronecker_edges(4, 10, 64 * 5 + 7)
    a = graph500.order_by_seed(s, d, 64, seed)
    b = graph500.order_by_seed(s, d, 64, seed)
    c = graph500.order_by_seed(s, d, 64, seed + 1)
    assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])
    assert not np.array_equal(a[0], c[0])
    assert len(a[0]) == len(s) and np.array_equal(a[0][-7:], s[-7:])
    for k in range(5):      # every window holds the same edges
        w = slice(64 * k, 64 * (k + 1))
        same = sorted(zip(a[0][w].tolist(), a[1][w].tolist()))
        assert same == sorted(zip(s[w].tolist(), d[w].tolist()))
        assert same == sorted(zip(c[0][w].tolist(), c[1][w].tolist()))


_G500 = {"scale": 10, "window_edges": 64,
         "graph500": {"graph_seed": 4, "a": 0.57, "b": 0.19, "c": 0.19,
                      "seeded_closing_windows": 2}}


@pytest.mark.parametrize("seed", [1, 2**31 + 3])
def test_every_seed_times_the_same_windows_and_closes_on_a_graph_of_its_own(
        seed):
    """The stream is the configuration's graph in the seed's order, so
    that every seed times the same work; the closing windows, handed
    out after the measured window, are a graph no other seed has."""
    n = 64 * 7 + 5
    a = graph500.edges(_G500, n, seed, 64 * 3)
    b = graph500.edges(_G500, n, seed, 64 * 3)
    c = graph500.edges(_G500, n, seed + 1, 64 * 3)
    assert len(a[0]) == len(a[1]) == n and a[0].dtype == np.int32
    assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])
    assert not np.array_equal(a[0], c[0])
    body = graph500.kronecker_edges(4, 10, n)
    for k in range(7):      # the same edges in every window
        w = slice(64 * k, 64 * (k + 1))
        same = sorted(zip(body[0][w].tolist(), body[1][w].tolist()))
        assert same == sorted(zip(a[0][w].tolist(), a[1][w].tolist()))
        assert same == sorted(zip(c[0][w].tolist(), c[1][w].tolist()))
    mine = graph500.closing_edges(_G500, seed)
    again = graph500.closing_edges(_G500, seed)
    other = graph500.closing_edges(_G500, seed + 1)
    assert len(mine[0]) == len(mine[1]) == 64 * 2
    assert np.array_equal(mine[0], again[0])
    assert not np.array_equal(mine[0], other[0])
    assert np.array_equal(mine[0], graph500.kronecker_edges(seed, 10, 128)[0])
    no_closing = dict(_G500, graph500=dict(_G500["graph500"],
                                           seeded_closing_windows=0))
    assert graph500.closing_edges(no_closing, seed) is None


@pytest.mark.parametrize("scale", [4, 11, 16])
def test_scramble_is_a_bijection(scale):
    consts = graph500.scramble_constants(99)
    v = np.arange(1 << scale, dtype=np.uint32)
    out = graph500.scramble(v, scale, consts)
    assert len(np.unique(out)) == 1 << scale and out.max() < 1 << scale


def test_bipartite_mapping_is_bipartite():
    s, d = graph500.kronecker_edges(5, 11, 5000, bipartite=True)
    assert np.all(s % 2 == 0) and np.all(d % 2 == 1)
    # and the reference's double cover agrees: no odd cycle
    n = 1 << 11
    uf = UnionFind(2 * n)
    uf.union_edges(np.concatenate([s, s + n]), np.concatenate([d + n, d]))
    ends = np.unique(np.concatenate([s, d]))
    assert not np.any(uf.find(ends) == uf.find(ends + n))


# ---- reference union-find ------------------------------------------- #
def _brute_components(n, edges):
    adj = {i: set() for i in range(n)}
    for u, v in edges:
        adj[u].add(v)
        adj[v].add(u)
    label = [-1] * n
    for start in range(n):
        if label[start] >= 0:
            continue
        stack = [start]
        label[start] = start
        while stack:
            x = stack.pop()
            for y in adj[x]:
                if label[y] < 0:
                    label[y] = start
                    stack.append(y)
    return np.asarray(label)


@pytest.mark.parametrize("seed,n,m", [(0, 50, 30), (1, 200, 150),
                                      (2, 300, 600), (3, 64, 0)])
def test_unionfind_agrees_with_brute_force(seed, n, m):
    rng = np.random.default_rng(seed)
    u = rng.integers(0, n, m).astype(np.int32)
    v = rng.integers(0, n, m).astype(np.int32)
    uf = UnionFind(n)
    for lo in range(0, m, 37):          # several batches, like windows
        uf.union_edges(u[lo:lo + 37], v[lo:lo + 37])
    want = _brute_components(n, zip(u.tolist(), v.tolist()))
    ids = np.arange(n)
    # brute labels are the smallest id of each component (scan order),
    # which is exactly the min-rooted form
    assert np.array_equal(uf.find(ids), want)
    assert np.array_equal(resolve_some(uf.parent, ids), want)
    assert np.all(uf.parent <= ids)


def test_unionfind_duplicate_and_conflicting_hooks():
    uf = UnionFind(8)
    uf.union_edges(np.array([7, 7, 7, 3]), np.array([1, 2, 3, 3]))
    assert len(set(uf.find(np.array([1, 2, 3, 7])).tolist())) == 1
    assert uf.find(np.array([7]))[0] == 1


@pytest.mark.parametrize("fault,wrong", [
    ("none", 0),            # the reference's own table, and any other
    ("uncompressed", 0),    # pointer table with the same roots, pass
    ("stale", 2),           # the table of one union earlier: 5 and 6
    ("moved", 1),           # an untouched row that no longer roots itself
])
def test_forest_reference_holds_a_table_to_its_roots(fault, wrong):
    ref = ForestReference(10)
    ref.union(np.array([1, 2]), np.array([2, 3]))
    stale = ref.table()
    ref.union(np.array([5, 3]), np.array([6, 5]))
    table = {"none": ref.table(), "stale": stale,
             "uncompressed": np.array([0, 1, 1, 2, 4, 3, 5, 7, 8, 9],
                                      np.int32),
             "moved": ref.table()}[fault]
    if fault == "moved":
        table[8] = 7
    assert ref.compare_final(table) == {"table_mismatches": wrong}


# ---- byte model and peaks -------------------------------------------- #
def test_forest_step_byte_model():
    assert bytes_model.forest_step(rows=1 << 28, window_edges=1 << 17,
                                   touched=200000) == (
        8 * (1 << 28) + 8 * (1 << 17) + 8 * 200000)
    assert bytes_model.MODELS["forest_step"] is bytes_model.forest_step
    with pytest.raises(ValueError):
        bytes_model.forest_step(rows=-1, window_edges=1, touched=1)


def test_peaks_table_and_unknown_kind():
    v5e = peaks.peaks_for("TPU v5 lite")
    assert v5e == {"bf16_flops": 197e12, "hbm_bytes_s": 819e9,
                   "hbm_bytes": 16e9}
    with pytest.raises(ValueError, match="no published peaks"):
        peaks.peaks_for("TPU v9 imaginary")


# ---- traffic ---------------------------------------------------------- #
def test_query_schedule_is_fixed_by_t0_and_period():
    due = query_schedule(100.0, 1.0, 0.1)
    assert len(due) == 10 and due[0] == 100.0
    assert np.allclose(np.diff(due), 0.1)
    assert len(query_schedule(0.0, 1.05, 0.1)) == 11


class _Future:
    def __init__(self):
        self._cbs, self._done = [], False

    def add_done_callback(self, cb):
        self._cbs.append(cb)
        if self._done:
            cb(self)

    def result(self):
        return "answer"

    def finish(self):
        self._done = True
        for cb in self._cbs:
            cb(self)


def test_open_loop_stamps_latency_from_due_times():
    """A stalled system delays the answers, never the sends: batch i is
    sent at its due time while batch 0 is still unanswered, and its
    latency is counted from when it was due."""
    futures = []

    def submit(queries):
        fs = [_Future() for _ in queries]
        futures.extend(fs)
        return fs

    t0 = time.perf_counter() + 0.05
    due = query_schedule(t0, 0.3, 0.1)
    load = QueryLoad(submit, lambda: (["q", "q"], np.zeros((2, 2))), due,
                     lambda: 4)
    load.start()
    load.join(5)
    assert not load.is_alive() and len(futures) == 6
    # every batch went out on schedule although nothing was answered
    assert np.all(load.sent - due < 0.05) and np.all(load.sent >= due)
    time.sleep(0.1)
    for f in futures:
        f.finish()
    lat = np.concatenate(load.done_t) - np.repeat(due, 2)
    assert lat[0] > 0.29 and lat[-1] > 0.09  # the stall is charged
    assert np.all(load.head_at_submit == 4)


def test_open_loop_records_a_rejected_batch():
    def submit(queries):
        raise RuntimeError("Overloaded")

    load = QueryLoad(submit, lambda: (["q"], np.zeros((1, 2))),
                     query_schedule(time.perf_counter(), 0.02, 0.01),
                     lambda: 0)
    load.start()
    load.join(5)
    assert len(load.rejected) == 2 and load.done_t[0] is None


@pytest.mark.parametrize("outstanding", [1, 2, 3])
def test_closed_loop_source_bounds_windows_outstanding(outstanding):
    src = np.arange(64 * 20, dtype=np.int32)
    source = WindowSource(src, src, 64,
                          {"mode": "closed", "outstanding": outstanding})
    seen = []

    def consume():
        for s, _d in source.iter_chunks():
            seen.append(int(s[0]) // 64)

    t = threading.Thread(target=consume, daemon=True)
    t.start()
    for n_ready in range(1, 13):
        time.sleep(0.01)
        # the consumer is blocked exactly `outstanding` ahead
        assert source.handed - source.ready <= outstanding
        source.mark_ready(n_ready)
    time.sleep(0.05)
    source.stop()
    t.join(5)
    assert not t.is_alive()
    assert source.max_outstanding == outstanding
    assert seen == list(range(len(seen))) and len(seen) >= 12
    assert not source.exhausted


@pytest.mark.parametrize("ingest", [
    {"mode": "closed", "outstanding": 2},
    {"mode": "open", "edges_per_s": 16 * 200}])
def test_the_closing_windows_follow_wherever_the_stream_was_cut(ingest):
    """``finish()`` ends the stream where it stands; the closing
    windows follow, closed loop, then the end. ``window(k)`` and
    ``recent()`` give every window as it was handed out."""
    src = np.arange(16 * 400, dtype=np.int32)
    closing = -np.arange(1, 16 * 3 + 1, dtype=np.int32)
    source = WindowSource(src, src, 16, ingest, closing=(closing, closing))
    got = []

    def consume():
        for s, _d in source.iter_chunks():
            got.append(s.copy())
            time.sleep(0.002)
            source.mark_ready(len(got))

    t = threading.Thread(target=consume, daemon=True)
    t.start()
    source.start_measuring(time.perf_counter())
    time.sleep(0.03)
    source.finish()
    t.join(5)
    assert not t.is_alive() and not source.exhausted
    n_main = source.n_main
    assert 2 <= n_main < 400 and source.handed == n_main + 3 == len(got)
    assert source.max_outstanding <= 2 or ingest["mode"] == "open"
    for k, s in enumerate(got):
        assert np.array_equal(source.window(k)[0], s)
        assert (s[0] == 16 * k) if k < n_main else (s[0] < 0)
    recent = source.recent(4)[0]
    assert np.array_equal(recent, np.concatenate(got[-4:]))
    # a hard stop hands no closing window out
    source = WindowSource(src, src, 16, ingest, closing=(closing, closing))
    source.stop()
    assert list(source.iter_chunks()) == [] and source.n_main == 0


def test_open_loop_source_hands_windows_out_at_their_due_times():
    src = np.arange(16 * 50, dtype=np.int32)
    source = WindowSource(src, src, 16,
                          {"mode": "open", "edges_per_s": 16 * 100})
    got = []

    def consume():
        for _ in source.iter_chunks():
            got.append(time.perf_counter())

    t = threading.Thread(target=consume, daemon=True)
    t.start()
    source.mark_ready(2)            # warm-up is closed loop: 2, then 2 more
    time.sleep(0.05)
    assert source.handed == 4
    t0 = time.perf_counter()
    source.start_measuring(t0)
    time.sleep(0.1)
    source.stop()
    t.join(5)
    due = np.asarray(source.due_t[4:])
    assert len(due) >= 8            # never waited for readiness
    assert np.allclose(np.diff(due), 0.01)
    assert np.all(np.asarray(source.handed_t[4:]) >= due)


def test_open_loop_source_holds_back_at_its_backlog_and_keeps_due_times():
    src = np.arange(16 * 60, dtype=np.int32)
    source = WindowSource(src, src, 16, {
        "mode": "open", "edges_per_s": 16 * 200, "max_backlog": 3})
    t = threading.Thread(target=lambda: list(source.iter_chunks()),
                         daemon=True)
    t.start()
    source.mark_ready(2)
    time.sleep(0.02)
    source.start_measuring(time.perf_counter())
    time.sleep(0.06)                # 12 windows fall due, nothing is ready
    assert source.handed - source.ready == 3
    held = source.handed
    source.mark_ready(held)         # the system catches up: the rest follow
    time.sleep(0.03)
    source.stop()
    t.join(5)
    assert source.max_outstanding == 3 and source.handed > held + 1
    due = np.asarray(source.due_t[4:])
    assert np.allclose(np.diff(due), 0.005)     # the schedule's, not the wait's
    assert source.handed_t[held] - source.due_t[held] > 0.02


# ---- the rate: all work over all time, ends pro-rated ---------------- #
@pytest.mark.parametrize("ready,t0,t_end,want", [
    ([0.5, 1.5, 2.5, 3.5], 1.0, 3.0, 2.0),    # half a window at each end
    ([0.5, 1.5, 2.5, 3.5], 1.3, 3.3, 2.0),    # the same wherever the ends fall
    ([0.5, 1.5, 1.5, 2.5, 3.5], 1.0, 3.0, 3.0),   # two ready at one instant
    ([0.2, 0.4], 0.0, 1.0, 2.0),              # all inside
    ([5.0, 6.0], 0.0, 1.0, 0.2),              # one fifth of the first window
    ([], 0.0, 1.0, 0.0),
])
def test_windows_done_credits_the_straddling_windows_by_their_share(
        ready, t0, t_end, want):
    from benchmarks.lib.cellrun import windows_done

    assert windows_done(ready, t0, t_end) == pytest.approx(want)


# ---- no accelerator library at import time --------------------------- #
def test_importing_the_benchmark_loads_no_jax():
    code = (
        "import sys; sys.path.insert(0, %r);"
        "import benchmarks.lib.spec, benchmarks.lib.cellrun, "
        "benchmarks.lib.trace_reduce, benchmarks.lib.lastline, "
        "benchmarks.generators.graph500, benchmarks.lib.traffic, "
        "benchmarks.algorithms.cc, benchmarks.algorithms.bipartite;"
        "assert 'jax' not in sys.modules and 'libtpu' not in sys.modules"
    ) % REPO
    subprocess.run([sys.executable, "-c", code], check=True, timeout=60)
