"""Tests for the unified observability subsystem (ISSUE 3).

Covers: span nesting/attributes, histogram percentiles vs a numpy
oracle, Prometheus/JSONL exporter round-trip (the event log replays to
an identical registry snapshot — live serving run included),
disabled-mode zero-allocation fast path, the prefetch coupling gauges,
and the overhead guard (by count: no Span and no clock read when off, a
fixed number of events per window and per sweep when on).
"""

import threading
import time
import tracemalloc

import numpy as np
import pytest

from gelly_streaming_tpu import obs
from gelly_streaming_tpu.obs.export import (
    JsonlSink,
    prometheus_text,
    read_jsonl,
    replay,
    snapshot_stream,
)
from gelly_streaming_tpu.obs.registry import (
    MetricRegistry,
    nearest_rank,
)


@pytest.fixture(autouse=True)
def _obs_hygiene():
    """Every test starts and ends with observability fully reset: no
    global-state leakage between tests (or into the rest of the suite)."""
    obs.reset()
    yield
    obs.reset()


# --------------------------------------------------------------------- #
# Registry
# --------------------------------------------------------------------- #
def test_counter_gauge_basic():
    reg = MetricRegistry()
    c = reg.counter("ingest.edges")
    c.inc()
    c.inc(41.5)
    assert c.value == 42.5
    g = reg.gauge("queue.depth")
    g.set(7)
    g.inc(-2)
    assert g.value == 5.0
    snap = reg.snapshot()
    assert snap["counters"]["ingest.edges"] == 42.5
    assert snap["gauges"]["queue.depth"] == 5.0


def test_labeled_instruments_and_find():
    reg = MetricRegistry()
    reg.counter("q", cls="A").inc(1)
    reg.counter("q", cls="B").inc(2)
    assert reg.counter("q", cls="A") is reg.counter("q", cls="A")
    found = dict(
        (labels["cls"], m.value) for labels, m in reg.find("q")
    )
    assert found == {"A": 1.0, "B": 2.0}
    assert "q{cls=A}" in reg.snapshot()["counters"]


def test_kind_conflict_raises():
    reg = MetricRegistry()
    reg.counter("x")
    with pytest.raises(TypeError):
        reg.gauge("x")


def test_histogram_percentiles_vs_numpy_oracle():
    reg = MetricRegistry()
    h = reg.histogram("lat")
    rng = np.random.default_rng(7)
    xs = rng.lognormal(size=2001)
    for v in xs:
        h.observe(v)
    s = np.sort(xs)
    for q in (0.0, 1.0, 25.0, 50.0, 90.0, 95.0, 99.0, 100.0):
        # the exact nearest-rank definition, indexed on the numpy sort
        k = min(len(s) - 1, max(0, int(round(q / 100 * (len(s) - 1)))))
        assert h.percentile(q) == s[k]
        # and sanity vs numpy's own percentile (any interpolation lands
        # within one sample of nearest-rank on a dense sample set)
        assert abs(h.percentile(q) - np.percentile(xs, q)) <= (
            np.percentile(xs, min(100.0, q + 1)) -
            np.percentile(xs, max(0.0, q - 1)) + 1e-12
        )
    assert h.count == len(xs)
    assert h.sum == pytest.approx(float(xs.sum()))
    assert h.min == s[0] and h.max == s[-1]


def test_histogram_bounded_eviction_keeps_lifetime_exact():
    reg = MetricRegistry()
    h = reg.histogram("lat", max_samples=8)
    for i in range(20):
        h.observe(float(i))
    assert h.count == 20
    assert h.sum == float(sum(range(20)))
    assert h.max == 19.0 and h.min == 0.0
    # drop-oldest-half: the sample window only holds recent values
    assert len(h.samples()) <= 8
    assert min(h.samples()) > 0.0


def test_nearest_rank_is_the_shared_percentile():
    """The dedup satellite: both historical implementations now route
    through obs.registry.nearest_rank and agree with it exactly."""
    from gelly_streaming_tpu.serving.stats import ServingStats
    from gelly_streaming_tpu.utils.profiling import (
        StreamProfiler,
        WindowStats,
    )

    xs = [0.5, 0.1, 0.9, 0.3, 0.7]
    prof = StreamProfiler()
    for i, v in enumerate(xs):
        prof.record(WindowStats(i, v, None))
    st = ServingStats()
    for v in xs:
        st.record("Q", v, 0)
    for q in (0, 10, 50, 95, 100):
        want = nearest_rank(sorted(xs), q)
        assert prof.latency_percentile(q) == want
        got_ms = st.snapshot()["queries"]["Q"] if q == 50 else None
        if got_ms is not None:
            assert got_ms["p50_ms"] == want * 1e3


# --------------------------------------------------------------------- #
# Spans
# --------------------------------------------------------------------- #
def test_span_nesting_and_attributes():
    sink = JsonlSink()
    obs.enable()
    obs.attach_sink(sink)
    with obs.span("outer", {"window_index": 3}):
        with obs.span("inner", {"k": 4, "edges": 1024}) as sp:
            time.sleep(0.002)
            sp.set(donated=True)
    spans = [e for e in sink.events if e["kind"] == "span"]
    # completion order: inner closes first
    inner, outer = spans[0], spans[1]
    assert inner["name"] == "inner" and outer["name"] == "outer"
    assert inner["depth"] == 1 and outer["depth"] == 0
    assert inner["parent"] == outer["sid"]
    assert "parent" not in outer
    assert inner["attrs"] == {"k": 4, "edges": 1024, "donated": True}
    assert outer["attrs"] == {"window_index": 3}
    assert inner["dur_s"] >= 0.002
    assert outer["dur_s"] >= inner["dur_s"]
    # span durations also land in the registry histogram, labeled
    hist = {
        labels["span"]: m
        for labels, m in obs.get_registry().find("trace.span_seconds")
    }
    assert hist["inner"].count == 1 and hist["outer"].count == 1


def test_span_stacks_are_per_thread():
    sink = JsonlSink()
    obs.enable()
    obs.attach_sink(sink)
    barrier = threading.Barrier(2)

    def work(name):
        with obs.span(name):
            barrier.wait(5)  # both spans open concurrently
            with obs.span(name + ".child"):
                pass

    ts = [threading.Thread(target=work, args=(n,)) for n in ("a", "b")]
    for t in ts:
        t.start()
    for t in ts:
        t.join(10)
    spans = {e["name"]: e for e in sink.events if e["kind"] == "span"}
    # each child nests under ITS thread's root, never the other's
    assert spans["a.child"]["parent"] == spans["a"]["sid"]
    assert spans["b.child"]["parent"] == spans["b"]["sid"]
    assert spans["a"]["depth"] == spans["b"]["depth"] == 0


def test_disabled_span_is_zero_allocation_noop():
    assert not obs.enabled()
    s1 = obs.span("pack")
    s2 = obs.span("dispatch")
    # one shared singleton: nothing allocated per disabled call
    assert s1 is s2 is obs.NOOP_SPAN
    with s1 as sp:
        assert sp is obs.NOOP_SPAN
        sp.set(anything=1)  # no-op, no state
    tracemalloc.start()
    for _ in range(1000):
        with obs.span("hot"):
            pass
    _, peak = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    # the loop itself must not allocate per iteration (tracemalloc's own
    # bookkeeping costs a few hundred bytes; 1000 spans of even one
    # small object each would be tens of KB)
    assert peak < 8192, f"disabled span loop allocated {peak} bytes"


def test_trace_context_wire_round_trip_and_tolerance():
    ctx = obs.TraceContext(parent_sid=obs.next_sid())
    wire = ctx.to_wire()
    back = obs.TraceContext.from_wire(wire)
    assert back.trace_id == ctx.trace_id
    assert back.parent_sid == ctx.parent_sid
    # a context without a parent serializes without the sid key
    assert "s" not in obs.TraceContext().to_wire()
    # from_wire is tolerant BY CONTRACT: garbage is an untraced batch,
    # never an error (tracing must not change the wire's accept set)
    for garbage in (None, 17, "x", [], {}, {"s": 3}, {"t": 9},
                    {"t": ""}):
        assert obs.TraceContext.from_wire(garbage) is None
    # two minted contexts never share a trace id
    assert obs.TraceContext().trace_id != obs.TraceContext().trace_id


def test_activate_stamps_spans_and_hands_off_across_threads():
    obs.enable()
    sink = JsonlSink()
    obs.attach_sink(sink)
    ctx = obs.TraceContext(parent_sid=obs.next_sid())
    with obs.activate(ctx):
        assert obs.current_context() is ctx
        with obs.span("stage"):
            pass
    assert obs.current_context() is None

    # the EXPLICIT handoff: another thread activates the carried
    # context object — thread-locals never leak it across by themselves
    seen = {}

    def worker():
        seen["before"] = obs.current_context()
        with obs.activate(ctx):
            with obs.span("worker.stage"):
                pass

    t = threading.Thread(target=worker)
    t.start()
    t.join(10)
    assert seen["before"] is None
    spans = {e["name"]: e for e in sink.events if e["kind"] == "span"}
    # both root spans carry the trace id and parent to the context sid
    for name in ("stage", "worker.stage"):
        assert spans[name]["trace"] == ctx.trace_id
        assert spans[name]["parent"] == ctx.parent_sid


def test_nested_span_under_context_parents_to_its_local_root():
    obs.enable()
    sink = JsonlSink()
    obs.attach_sink(sink)
    ctx = obs.TraceContext(parent_sid=obs.next_sid())
    with obs.activate(ctx):
        with obs.span("outer"):
            with obs.span("inner"):
                pass
    spans = {e["name"]: e for e in sink.events if e["kind"] == "span"}
    assert spans["outer"]["parent"] == ctx.parent_sid
    # nesting stays LOCAL: the inner span's parent is the outer span,
    # while the trace id still rides both
    assert spans["inner"]["parent"] == spans["outer"]["sid"]
    assert spans["inner"]["trace"] == ctx.trace_id


def test_record_span_emits_event_and_registry_mirror():
    obs.enable()
    sink = JsonlSink()
    obs.attach_sink(sink)
    ctx = obs.TraceContext(parent_sid=obs.next_sid())
    sid = obs.record_span(
        "async.stage", 0.25, trace_id=ctx.trace_id,
        parent=ctx.parent_sid, attrs={"n": 3},
    )
    assert isinstance(sid, int)
    (e,) = [e for e in sink.events if e["kind"] == "span"]
    assert e["name"] == "async.stage" and e["dur_s"] == 0.25
    assert e["trace"] == ctx.trace_id
    assert e["parent"] == ctx.parent_sid and e["attrs"] == {"n": 3}
    # the duration lands in the same histogram as with-block spans
    h = obs.get_registry().histogram("trace.span_seconds",
                                     span="async.stage")
    assert h.count == 1 and h.sum == 0.25
    # a pre-reserved sid (the client's batch-root idiom) is honored
    sid2 = obs.next_sid()
    assert obs.record_span("root", 0.1, sid=sid2) == sid2


def test_record_span_disabled_is_a_noop():
    assert not obs.enabled()
    sink = JsonlSink()
    obs.attach_sink(sink)
    assert obs.record_span("x", 0.1) is None
    assert len(sink.events) == 0


def test_histogram_exemplars_keep_largest_and_replay_identically():
    reg = MetricRegistry()
    sink = JsonlSink()
    reg.add_sink(sink)
    h = reg.histogram("lat")
    values = [(0.010, "t0"), (0.500, "t1"), (0.020, "t2"),
              (0.500, "t3"), (0.900, "t4"), (0.001, "t5")]
    for v, tid in values:
        h.observe(v, exemplar=tid)
    h.observe(2.0)  # no exemplar: sampled, never an exemplar entry
    ex = h.exemplars()
    # the largest exemplar-carrying observations, largest first; ties
    # keep arrival order (deterministic in the observation sequence)
    assert ex == [(0.9, "t4"), (0.5, "t1"), (0.5, "t3"), (0.02, "t2")]
    snap = reg.snapshot()
    assert snap["histograms"]["lat"]["exemplars"][0] == \
        {"v": 0.9, "trace": "t4"}
    # the exemplar rides the event log, so replay is still an identity
    replayed = replay(sink.events)
    assert replayed.histogram("lat").exemplars() == ex
    assert replayed.snapshot() == snap
    # a histogram without exemplars gains no snapshot key
    reg.histogram("plain").observe(1.0)
    assert "exemplars" not in reg.snapshot()["histograms"]["plain"]


def test_enable_disable_roundtrip_and_instrumented_pipeline():
    """End-to-end: a real aggregation run with obs enabled produces the
    hot-path spans, and the same run disabled produces none."""
    from gelly_streaming_tpu.core.stream import SimpleEdgeStream
    from gelly_streaming_tpu.core.window import CountWindow
    from gelly_streaming_tpu.library import ConnectedComponents

    rng = np.random.default_rng(5)
    src = rng.integers(0, 64, 600).astype(np.int32)
    dst = rng.integers(0, 64, 600).astype(np.int32)

    def run():
        stream = SimpleEdgeStream((src, dst), window=CountWindow(100))
        return list(stream.aggregate(ConnectedComponents()))

    sink = JsonlSink()
    obs.enable()
    obs.attach_sink(sink)
    run()
    names = {e["name"] for e in sink.events if e["kind"] == "span"}
    assert "window.pack" in names
    obs.reset()

    sink2 = JsonlSink()
    obs.attach_sink(sink2)  # sink attached but tracing DISABLED
    run()
    assert not [e for e in sink2.events if e["kind"] == "span"]


# --------------------------------------------------------------------- #
# Exporters
# --------------------------------------------------------------------- #
def test_jsonl_roundtrip_replays_to_identical_snapshot(tmp_path):
    reg = MetricRegistry()
    sink = JsonlSink()
    reg.add_sink(sink)
    rng = np.random.default_rng(11)
    lat = reg.histogram("lat", max_samples=64, cls="Q")
    for v in rng.random(500):
        lat.observe(float(v))
    reg.counter("served").inc(500)
    reg.gauge("pending").set(12)
    reg.gauge("pending").set(3)  # last write wins through replay too
    path = str(tmp_path / "events.jsonl")
    sink.write(path)
    events = read_jsonl(path)
    assert len(events) == 503
    replayed = replay(events)
    assert replayed.snapshot() == reg.snapshot()
    # eviction-dependent percentiles included: same bounded window
    assert (
        replayed.histogram("lat", max_samples=64, cls="Q").samples()
        == lat.samples()
    )


def test_replay_skips_span_and_meta_events():
    events = [
        {"kind": "meta", "bench": "x"},
        {"kind": "span", "name": "pack", "dur_s": 0.1, "sid": 1,
         "depth": 0, "ts": 0.0},
        {"kind": "counter", "name": "c", "v": 2},
    ]
    reg = replay(events)
    assert reg.snapshot()["counters"] == {"c": 2.0}


def test_prometheus_text_renderer():
    reg = MetricRegistry()
    reg.counter("serving.rejected").inc(3)
    reg.gauge("pipeline.queue_depth").set(2)
    h = reg.histogram("serving.query_seconds", cls="ConnectedQuery")
    for v in (0.001, 0.002, 0.003, 0.004):
        h.observe(v)
    text = prometheus_text(reg)
    assert "# TYPE serving_rejected counter" in text
    assert "serving_rejected 3" in text
    assert "# TYPE pipeline_queue_depth gauge" in text
    assert "pipeline_queue_depth 2" in text
    assert "# TYPE serving_query_seconds summary" in text
    # nearest-rank p50 over 4 samples: index round(0.5 * 3) = 2
    assert (
        'serving_query_seconds{cls="ConnectedQuery",quantile="0.5"} 0.003'
        in text
    )
    assert 'serving_query_seconds_sum{cls="ConnectedQuery"} 0.01' in text
    assert 'serving_query_seconds_count{cls="ConnectedQuery"} 4' in text


def test_snapshot_stream_composes_with_emissions():
    reg = MetricRegistry()
    c = reg.counter("windows")

    def emissions():
        for i in range(7):
            c.inc()
            yield i

    out = list(snapshot_stream(emissions(), every=3, registry=reg))
    assert [item for item, _ in out] == list(range(7))
    snaps = [(i, s) for i, (_, s) in enumerate(out) if s is not None]
    assert [i for i, _ in snaps] == [2, 5]  # every 3rd item
    assert snaps[0][1]["counters"]["windows"] == 3.0
    assert snaps[1][1]["counters"]["windows"] == 6.0


# --------------------------------------------------------------------- #
# ServingStats as a registry view + live server replay
# --------------------------------------------------------------------- #
def test_serving_stats_event_log_replay_unit():
    from gelly_streaming_tpu.serving.stats import ServingStats

    st = ServingStats()
    sink = JsonlSink()
    st.attach_sink(sink)
    rng = np.random.default_rng(3)
    for i in range(200):
        st.record("ConnectedQuery", float(rng.random()) * 1e-3, i % 3)
    for _ in range(5):
        st.record_batch()
    st.record_rejected()
    st.set_pending(4)
    st.record_drain(40)
    live = st.snapshot()
    assert live["queries"]["ConnectedQuery"]["count"] == 200
    assert ServingStats.from_events(sink.events).snapshot() == live


def test_live_server_event_log_replays_to_reported_snapshot():
    """The ISSUE 3 acceptance shape, in-miniature: a real StreamServer
    run with an attached event sink; the JSONL log replays to the exact
    ``snapshot()`` dict the live run reported."""
    from gelly_streaming_tpu.core.stream import SimpleEdgeStream
    from gelly_streaming_tpu.core.window import CountWindow
    from gelly_streaming_tpu.library import ConnectedComponents
    from gelly_streaming_tpu.serving import ConnectedQuery, StreamServer
    from gelly_streaming_tpu.serving.stats import ServingStats

    rng = np.random.default_rng(9)
    n_vertices = 64
    src = rng.integers(0, n_vertices, 800).astype(np.int32)
    dst = rng.integers(0, n_vertices, 800).astype(np.int32)
    stream = SimpleEdgeStream((src, dst), window=CountWindow(100))
    agg = ConnectedComponents()
    server = StreamServer(agg.servable(), stream, max_pending=4096)
    sink = JsonlSink()
    server.stats.attach_sink(sink)
    server.start()
    futures = [
        server.submit(
            ConnectedQuery(int(a), int(b))
        )
        for a, b in zip(
            rng.integers(0, n_vertices, 300),
            rng.integers(0, n_vertices, 300),
        )
    ]
    for f in futures:
        f.result(60)
    server.join(60)
    server.close()
    live = server.stats.snapshot()  # after close: the log is complete
    assert live["queries"]["ConnectedQuery"]["count"] == 300
    replayed = ServingStats.from_events(sink.events).snapshot()
    assert replayed == live


# --------------------------------------------------------------------- #
# Prefetch coupling metrics
# --------------------------------------------------------------------- #
def test_prefetch_records_coupling_metrics():
    from gelly_streaming_tpu.core.pipeline import prefetch

    obs.enable()

    def slow_producer():
        for i in range(5):
            time.sleep(0.01)
            yield i

    assert list(prefetch(slow_producer(), depth=2)) == list(range(5))
    reg = obs.get_registry()
    # slow producer, fast consumer: the consumer starved measurably
    assert reg.counter("pipeline.consumer_idle_s").value > 0.0

    obs.reset()
    obs.enable()

    def fast_producer():
        yield from range(5)

    slow_out = []
    for x in prefetch(fast_producer(), depth=1):
        time.sleep(0.01)
        slow_out.append(x)
    assert slow_out == list(range(5))
    assert obs.get_registry().counter(
        "pipeline.producer_blocked_s"
    ).value > 0.0


# --------------------------------------------------------------------- #
# Overhead guard. It used to compare two 12 ms passes by the wall clock
# (11% against a 10% limit in whole runs with six workers). What a
# regression would break is held by COUNT instead: with tracing off no
# instrumented site builds a Span or reads a clock, and with it on the
# events per window and per sweep are a small fixed number that does not
# grow with the edges of a window or the queries of a sweep.
# --------------------------------------------------------------------- #
class _CountingClock:
    """Stands in for the ``time`` module inside ``obs.trace``."""

    def __init__(self):
        self.reads = 0

    def perf_counter(self):
        self.reads += 1
        return time.perf_counter()

    def time(self):
        self.reads += 1
        return time.time()


class _Chunks:
    """A chunk source (the path a served stream's windower pulls on)."""

    def __init__(self, src, dst, size):
        self.src, self.dst, self.size = src, dst, size

    def iter_chunks(self):
        for a in range(0, len(self.src), self.size):
            yield self.src[a:a + self.size], self.dst[a:a + self.size]


def _guard_stream(window: int, n_windows: int = 4, n_vertices: int = 1 << 12):
    from gelly_streaming_tpu.core.stream import SimpleEdgeStream
    from gelly_streaming_tpu.core.window import CountWindow
    from gelly_streaming_tpu.datasets import IdentityDict

    rng = np.random.default_rng(13)
    # sources even, targets odd: bipartite, so the cover never latches
    src = (2 * rng.integers(0, n_vertices // 2, window * n_windows)
           ).astype(np.int32)
    dst = (2 * rng.integers(0, n_vertices // 2, window * n_windows) + 1
           ).astype(np.int32)
    return SimpleEdgeStream(
        _Chunks(src, dst, window), window=CountWindow(window),
        vertex_dict=IdentityDict(n_vertices),
    )


def _guard_windows(make_agg, size: int) -> int:
    """Folds 4 windows of ``size`` edges; returns how many it folded."""
    for _ in _guard_stream(size).aggregate(make_agg()):
        pass
    return 4


def _guard_sweeps(size: int) -> int:
    """Answers 3 batches of ``size`` queries on the engine's DEVICE path
    from a finished stream; returns how many it sent."""
    from gelly_streaming_tpu.library import ConnectedComponents
    from gelly_streaming_tpu.serving import ConnectedQuery, StreamServer
    from gelly_streaming_tpu.serving.query import QueryEngine

    server = StreamServer(
        ConnectedComponents(carry="forest").servable(), _guard_stream(256),
        max_pending=4096, engine=QueryEngine(prefer_host=False),
    )
    server.start()
    server.join(60)
    rng = np.random.default_rng(size)
    for _ in range(3):
        qs = [ConnectedQuery(int(a), int(b))
              for a, b in rng.integers(0, 1 << 12, (size, 2))]
        for f in server.submit_many(qs):
            f.result(60)
    server.close()
    return 3


def _cc_forest():
    from gelly_streaming_tpu.library import ConnectedComponents

    return ConnectedComponents(carry="forest")


def _cover_forest():
    from gelly_streaming_tpu.library.bipartiteness import BipartitenessCheck

    return BipartitenessCheck(carry="forest")


_WINDOW_SPANS = {"ingest.wait_source", "window.pack", "forest.window",
                 "forest.prep", "forest.dispatch"}
_SWEEP_SPANS = {"serving.queue_wait", "serving.answer",
                "serving.device_wait"}


@pytest.mark.parametrize("drive,sizes,per_unit,names", [
    pytest.param(lambda n: _guard_windows(_cc_forest, n), (256, 1 << 14),
                 5, _WINDOW_SPANS, id="cc-window"),
    pytest.param(lambda n: _guard_windows(_cover_forest, n), (256, 1 << 14),
                 5, _WINDOW_SPANS, id="cover-window"),
    pytest.param(_guard_sweeps, (8, 512), 3, _SWEEP_SPANS,
                 id="served-sweep"),
])
def test_overhead_guard_1m_edge_cpu_run(monkeypatch, drive, sizes,
                                        per_unit, names):
    from gelly_streaming_tpu.obs import trace as obs_trace

    drive(sizes[0])                       # warm: compiles stay out of it
    # off: the sites hand out the shared no-op and touch no clock
    built = []
    clock = _CountingClock()
    real_init = obs_trace.Span.__init__

    def counting_init(self, *a, **kw):
        built.append(a[0])
        real_init(self, *a, **kw)

    monkeypatch.setattr(obs_trace.Span, "__init__", counting_init)
    monkeypatch.setattr(obs_trace, "time", clock)
    assert obs_trace.span("x") is obs.NOOP_SPAN
    drive(sizes[0])
    assert built == [] and clock.reads == 0
    # on: a fixed small number of events per window (per sweep), the
    # same for a unit 64 times the size
    counts = []
    for size in sizes:
        sink = JsonlSink()
        obs.enable()
        obs.attach_sink(sink)
        try:
            units = drive(size)
        finally:
            obs.detach_sink(sink)
            obs.disable()
        got = [e["name"] for e in sink.events
               if e["kind"] == "span" and e["name"] in names]
        assert set(got) == names
        # + 1: the pull that finds the source at its end
        assert len(got) <= per_unit * units + 1, got
        counts.append(sorted(got))
    assert counts[0] == counts[1]
    assert clock.reads > 0 and built


def test_bench_serving_writes_replayable_obs_log(tmp_path):
    """The ISSUE 3 acceptance end-to-end, at test scale: a --serving
    bench run produces a JSONL event log that replays to the same
    ``ServingStats.snapshot()`` dict the live run reported (the bench
    itself asserts replay equality and would raise otherwise)."""
    import os
    import sys

    sys.path.insert(
        0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    import bench
    from gelly_streaming_tpu.serving.stats import ServingStats

    log_path = str(tmp_path / "serving_obs.jsonl")
    out = bench.bench_serving(
        n_vertices=1 << 10, window=1 << 12, n_win=3, burst=32,
        pace_s=0.0, obs_log=log_path,
    )
    assert out["obs"]["replay_ok"] is True
    assert out["obs"]["log"] == log_path
    events = read_jsonl(log_path)
    assert events[0]["kind"] == "meta"
    replayed = ServingStats.from_events(events).snapshot()
    assert replayed == out["serving"]["stats"]
    # on a tiny stream the paced client can race ingest completion and
    # answer zero queries; when any were answered the replayed count
    # must match the live report exactly
    if out["serving"]["queries_answered"]:
        assert (
            replayed["queries"]["ConnectedQuery"]["count"]
            == out["serving"]["queries_answered"]
        )


def test_stream_profiler_mirrors_into_registry():
    from gelly_streaming_tpu.utils.profiling import (
        StreamProfiler,
        WindowStats,
    )

    # explicit registry: mirrored regardless of the global enable flag
    reg = MetricRegistry()
    prof = StreamProfiler(registry=reg, name="ingest")
    prof.record(WindowStats(0, 0.5, 100))
    prof.record(WindowStats(1, 0.25, 50))
    assert reg.histogram("ingest.window_seconds").count == 2
    assert reg.counter("ingest.window_edges").value == 150.0
    # legacy list surface is unchanged
    assert prof.summary()["windows"] == 2
    assert prof.summary()["edges"] == 150

    # no registry + obs disabled: stays private, global registry clean
    prof2 = StreamProfiler()
    prof2.record(WindowStats(0, 0.1, 10))
    assert obs.get_registry().find("profiler.window_seconds") == []
