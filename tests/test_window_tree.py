"""The window's host life as one span tree (ISSUE 38): a root span a
window on the ingest thread (``serving.window``: an event for the sinks,
never a profiler annotation), the children it always had under it, the
two stretches nobody timed (``window.emit``, ``serving.publish``), the
depth in flight and the evicted window as attributes, and nothing at
all while tracing is off."""

import json
import os
import sys
import threading
import time

import numpy as np
import pytest

from gelly_streaming_tpu import obs
from gelly_streaming_tpu.obs import trace as obs_trace
from gelly_streaming_tpu.obs.export import JsonlSink
from gelly_streaming_tpu.serving import StreamServer
from gelly_streaming_tpu.serving.snapshot_store import SnapshotStore

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (REPO, os.path.join(REPO, "tools")):
    if _p not in sys.path:
        sys.path.insert(0, _p)


@pytest.fixture(autouse=True)
def _obs_hygiene():
    obs.reset()
    yield
    obs.reset()


@pytest.fixture
def spans():
    """Tracing on, finished spans collected."""
    sink = JsonlSink()
    obs.enable()
    obs_trace.add_sink(sink)
    yield lambda: [e for e in sink.events if e["kind"] == "span"]
    obs_trace.remove_sink(sink)
    obs.disable()


# --------------------------------------------------------------------- #
# three served per-window loops
# --------------------------------------------------------------------- #
class _Chunks:
    def __init__(self, cols, size):
        self.cols, self.size = cols, size

    def iter_chunks(self):
        for a in range(0, len(self.cols[0]), self.size):
            yield tuple(c[a:a + self.size] for c in self.cols)


def _stream(window=128, n_windows=3, n_vertices=1024, signed=False):
    from gelly_streaming_tpu.core.stream import SimpleEdgeStream
    from gelly_streaming_tpu.core.window import CountWindow
    from gelly_streaming_tpu.datasets import IdentityDict

    rng = np.random.default_rng(38)
    n = window * n_windows
    # sources even, targets odd: bipartite, so the cover never latches
    cols = [(2 * rng.integers(0, n_vertices // 2, n)).astype(np.int32),
            (2 * rng.integers(0, n_vertices // 2, n) + 1).astype(np.int32)]
    if signed:   # the degree path's events: the ±1 rides the val column
        cols.append(np.where(rng.random(n) < 0.25, -1, 1).astype(np.int32))
    return SimpleEdgeStream(
        _Chunks(cols, window), window=CountWindow(window),
        vertex_dict=IdentityDict(n_vertices))


def _cc():
    from gelly_streaming_tpu.library import ConnectedComponents

    return ConnectedComponents(carry="forest").servable(), _stream


def _cc_host():
    from gelly_streaming_tpu.library import ConnectedComponents

    return ConnectedComponents(carry="host").servable(), _stream


def _cover():
    from gelly_streaming_tpu.library.bipartiteness import BipartitenessCheck

    return BipartitenessCheck(carry="forest").servable(), _stream


def _degrees():
    from gelly_streaming_tpu.library.degrees import DegreeDistribution

    agg = DegreeDistribution(hist_capacity=1 << 10)
    return agg.servable(), lambda **kw: _stream(signed=True, **kw)


class _Probe:
    """A servable that notes the ingest thread's open spans on both
    sides of every ``yield`` of its payload iterator."""

    def __init__(self, inner):
        self._inner = inner
        self.query_classes = inner.query_classes
        self.before, self.after = [], []

    @staticmethod
    def _open():
        return [s.name for s in getattr(obs_trace._LOCAL, "stack", [])]

    def payloads(self, stream):
        for item in self._inner.payloads(stream):
            self.before.append(self._open())
            yield item
            self.after.append(self._open())

    def boot_payload(self):
        return None


def _serve(make, **stream_kw):
    servable, stream = make()
    probe = _Probe(servable)
    server = StreamServer(probe, stream(**stream_kw))
    server.start()
    server.join(120)
    assert server._ingest_error is None
    server.close()
    return server, probe


FOREST_KIDS = ["ingest.wait_source", "window.pack", "forest.window",
               "window.emit", "serving.publish"]
DEGREE_KIDS = ["ingest.wait_source", "window.pack", "degrees.window",
               "window.emit", "serving.publish"]


@pytest.mark.parametrize("make,kids,fold,grandkids", [
    (_cc, FOREST_KIDS, "forest.window", ["forest.prep", "forest.dispatch"]),
    (_cover, FOREST_KIDS, "forest.window",
     ["forest.prep", "forest.dispatch"]),
    (_degrees, DEGREE_KIDS, "degrees.window",
     ["degrees.prep", "degrees.dispatch"]),
], ids=["cc", "cover", "degrees"])
def test_one_root_a_window_parents_the_windows_whole_host_life(
        spans, make, kids, fold, grandkids):
    _server, probe = _serve(make)
    events = spans()
    roots = [e for e in events if e["name"] == "serving.window"]
    assert [r["attrs"]["window"] for r in roots] == [0, 1, 2]
    for r in roots:
        assert r["depth"] == 0 and "parent" not in r
        mine = [e for e in events if e.get("parent") == r["sid"]]
        # in the order the ingest thread lives them
        assert [e["name"] for e in mine] == kids
        assert all(e["depth"] == 1 for e in mine)
        # the children account for the root: what is left is its self
        # time, and every child lies inside it on the one clock
        spent = sum(e["dur_s"] for e in mine)
        assert 0.0 <= r["dur_s"] - spent < r["dur_s"]
        for a, b in zip(mine, mine[1:]):   # one after the other
            assert a["t0"] + a["dur_s"] <= b["t0"] + 1e-9
        assert r["t0"] <= mine[0]["t0"]
        assert (mine[-1]["t0"] + mine[-1]["dur_s"]
                <= r["t0"] + r["dur_s"] + 1e-9)
        folds = [e for e in mine if e["name"] == fold]
        assert [e["name"] for e in events
                if e.get("parent") == folds[0]["sid"]] == grandkids
        assert {"window", "in_flight", "ring"} <= set(r["attrs"])
    # 8 events a window where there were 5, and the pull that found the
    # stream at its end leaves its wait alone: no root for no window
    mine = [e for e in events if e["name"] in set(kids + grandkids)
            | {"serving.window"}]
    assert len(mine) == 8 * 3 + 1
    assert mine[-1]["name"] == "ingest.wait_source"
    # no span is open across a yield: on either side of it the ingest
    # thread holds the root alone
    assert probe.before == probe.after == [["serving.window"]] * 3


def test_the_host_carry_emits_under_the_same_names(spans):
    _serve(_cc_host)
    events = spans()
    for r in [e for e in events if e["name"] == "serving.window"]:
        names = [e["name"] for e in events if e.get("parent") == r["sid"]]
        assert names == ["ingest.wait_source", "window.pack", "window.emit",
                         "serving.publish"]


@pytest.mark.parametrize("make,has_log", [
    (_cc, True), (_cover, True), (_degrees, False)],
    ids=["cc", "cover", "degrees"])
def test_emit_says_how_many_ids_the_touch_log_took_in(spans, make, has_log):
    server, _probe = _serve(make)
    emits = [e for e in spans() if e["name"] == "window.emit"]
    assert len(emits) == 3
    if not has_log:
        assert all("attrs" not in e for e in emits)
        return
    fresh = [e["attrs"]["fresh"] for e in emits]
    assert fresh[0] > 0 and all(f >= 0 for f in fresh)
    # all the log holds came in through the three windows
    assert sum(fresh) == server.snapshot().payload["tcount"]


# --------------------------------------------------------------------- #
# the root is an event for the sinks, never a profiler annotation
# --------------------------------------------------------------------- #
def test_the_root_is_no_annotation_and_its_children_are(monkeypatch):
    import jax

    opened = []

    class Annotation:
        def __init__(self, name):
            opened.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    sink = JsonlSink()
    obs.enable(jax_annotations=True)
    obs_trace.add_sink(sink)
    _serve(_cc)
    names = {e["name"] for e in sink.events if e["kind"] == "span"}
    assert "serving.window" in names        # the sinks have it
    assert "serving.window" not in opened   # the profiler never does
    assert set(FOREST_KIDS) | {"forest.prep", "forest.dispatch"} <= set(
        opened)


def test_annotate_false_is_an_argument_of_every_span(monkeypatch):
    import jax

    opened = []
    monkeypatch.setattr(
        jax.profiler, "TraceAnnotation",
        lambda name: opened.append(name) or _NullContext())
    obs.enable(jax_annotations=True)
    with obs.span("seen"):
        with obs.span("sinks.only", annotate=False) as sp:
            assert sp.recording and sp.annotate is False
    assert opened == ["seen"]
    obs.disable()
    assert obs.span("off", annotate=False) is obs.NOOP_SPAN


class _NullContext:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


FIXTURE = os.path.join(REPO, "tests", "bench_harness", "fixtures",
                       "trace_cc_saturated_v5e.json")


def test_idle_gaps_name_what_they_named_with_the_root_among_the_spans():
    """The ledger's breakdown gives each idle gap of the device to the
    host annotation that covers most of it. The root's NAME is among the
    run's span names, its annotation is not in the trace: the gaps go
    where they went. Were it an annotation it would take every one."""
    from benchmarks.lib import trace_reduce as tr

    with open(FIXTURE) as f:
        planes = json.load(f)
    lo, hi = tr.window_bounds(planes)
    names = {"window.pack", "serving.answer"}
    without = tr.idle_gaps(planes, lo, hi, names)
    assert {n for n, _s in without} >= names
    assert tr.idle_gaps(planes, lo, hi, names | {"serving.window"}) == without
    # the trap: one enclosing annotation a window swallows the breakdown
    packs = next(ln["events"] for p in planes if p["name"] == "/host:CPU"
                 for ln in p["lines"]
                 if ln["events"] and ln["events"][0][0] == "window.pack")
    starts = [s for _n, s, _d in packs] + [hi]
    rooted = planes + [{"name": "/host:ingest", "lines": [{
        "name": "python3", "events": [
            ["serving.window", a - 1e6, b - a]
            for a, b in zip(starts, starts[1:])]}]}]
    swallowed = dict(tr.idle_gaps(rooted, lo, hi,
                                  names | {"serving.window"}))
    assert swallowed["serving.window"] > 0.9 * sum(
        s for n, s in without if n in names)
    assert "window.pack" not in swallowed


# --------------------------------------------------------------------- #
# the depth in flight: counted, never waited for
# --------------------------------------------------------------------- #
class _Table:
    """A device array's stand-in: ``is_ready`` answers, waiting is a
    failure of the test."""

    def __init__(self, ready: bool):
        self.ready = ready
        self.probes = 0

    def is_ready(self):
        self.probes += 1
        return self.ready

    def block_until_ready(self):
        raise AssertionError("the ingest thread waited for a table")

    __array__ = block_until_ready


def test_in_flight_counts_the_ring_with_is_ready_and_never_blocks():
    store = SnapshotStore()
    assert store.in_flight() == 0
    tables = [_Table(ready) for ready in (True, False, True, False, False)]
    for w, t in enumerate(tables):
        store.publish({"labels": t, "vdict": None, "tcount": w}, w, w)
    # the ring keeps 4: windows 4, 3, 2, 1, of which 2 is ready
    assert store.ring_depth() == 4 and store.ring() is store._recent
    assert store.in_flight() == 3
    assert tables[0].probes == 0 and all(t.probes for t in tables[1:])
    tables[4].ready = tables[3].ready = True
    assert store.in_flight() == 1


def test_the_root_carries_in_flight_and_the_gauge_follows_it(spans):
    ready_after = {0: True, 1: False, 2: False, 3: True}
    tables = {w: _Table(r) for w, r in ready_after.items()}
    server = StreamServer(
        iter([({"labels": tables[w], "vdict": None}, w) for w in tables]),
        None)
    server.start()
    server.join(30)
    assert server._ingest_error is None
    roots = [e for e in spans() if e["name"] == "serving.window"]
    # at each publish: the ring's tables that are not ready, the one
    # just published among them
    assert [r["attrs"]["in_flight"] for r in roots] == [0, 1, 2, 2]
    assert [r["attrs"]["ring"] for r in roots] == [1, 2, 3, 4]
    assert obs.get_registry().snapshot()["gauges"][
        "serving.windows_in_flight"] == 2.0
    pubs = [e for e in spans() if e["name"] == "serving.publish"]
    assert [p["attrs"] for p in pubs] == [{"evicted": -1}] * 4
    server.close()


def test_the_gauge_is_not_set_while_tracing_is_off():
    server = StreamServer(
        iter([({"labels": _Table(False), "vdict": None}, 0)]), None)
    server.start()
    server.join(30)
    server.close()
    assert "serving.windows_in_flight" not in obs.get_registry().snapshot()[
        "gauges"]


# --------------------------------------------------------------------- #
# the publish says which window's snapshot it pushed out of the ring
# --------------------------------------------------------------------- #
def test_publish_names_the_evicted_window_and_holds_no_table(spans):
    import weakref

    servable, stream = _cc()
    server = StreamServer(servable, stream(n_windows=7))
    tables = {}
    server.store.add_listener(lambda snap: tables.__setitem__(
        snap.window, weakref.ref(snap.payload["labels"])))
    server.start()
    server.join(120)
    assert server._ingest_error is None
    pubs = [e for e in spans() if e["name"] == "serving.publish"]
    # a ring of 4: window 4's publish is the first to push one out
    assert [p["attrs"] for p in pubs] == [
        {"evicted": w} for w in (-1, -1, -1, -1, 0, 1, 2)]
    # the span notes an index: the evicted tables are gone, as they are
    # with tracing off (the forest's update is functional, so a table a
    # window; the ring holds the last four)
    assert [w for w, ref in sorted(tables.items()) if ref() is not None] == [
        3, 4, 5, 6]
    server.close()


# --------------------------------------------------------------------- #
# accepted readings do not move: pack_ms is the pack's SELF time
# --------------------------------------------------------------------- #
def test_pack_ms_reads_under_the_root_what_it_read_without_it(spans):
    from benchmarks.lib import cellrun

    _serve(_cc)
    events = spans()
    child_s = {}
    for e in events:
        if "parent" in e:
            child_s[e["parent"]] = child_s.get(e["parent"], 0.0) + e["dur_s"]
    ctx = {"spans": events, "child_s": child_s}
    packs = [e for e in events if e["name"] == "window.pack"]
    roots = {e["sid"] for e in events if e["name"] == "serving.window"}
    # the pack has a parent now and still no child: self time == time
    assert {p["parent"] for p in packs} == roots
    assert not [e for e in events if e.get("parent") in
                {p["sid"] for p in packs}]
    read = cellrun.READERS["span_mean_ms"]
    want = 1e3 * float(np.mean([p["dur_s"] for p in packs]))
    assert read({"span": "window.pack", "self_time": True},
                ctx) == pytest.approx(want)
    # and the root's self time is what the tool calls unseen
    unseen = read({"span": "serving.window", "self_time": True}, ctx)
    whole = read({"span": "serving.window"}, ctx)
    assert 0.0 <= unseen < whole


# --------------------------------------------------------------------- #
# tracing off: one attribute check and the shared no-op, at every site
# --------------------------------------------------------------------- #
class _CountingClock:
    def __init__(self):
        self.reads = 0

    def perf_counter(self):
        self.reads += 1
        return time.perf_counter()

    def time(self):
        self.reads += 1
        return time.time()


@pytest.mark.parametrize("make,names", [
    (_cc, set(FOREST_KIDS) | {"serving.window", "forest.prep",
                              "forest.dispatch"}),
    (_cover, set(FOREST_KIDS) | {"serving.window", "forest.prep",
                                 "forest.dispatch"}),
    (_degrees, set(DEGREE_KIDS) | {"serving.window", "degrees.prep",
                                   "degrees.dispatch"}),
], ids=["cc", "cover", "degrees"])
def test_a_served_window_costs_nothing_off_and_eight_events_on(
        monkeypatch, make, names):
    """``tests/test_obs.py``'s zero-allocation pin at the new sites: no
    span object, no clock read, no ``is_ready`` probe, no address read
    and no gauge while tracing is off; a fixed 8 events a window while
    it is on, whatever the window's size."""
    _serve(make)                           # warm: compiles stay out of it
    built = []
    clock = _CountingClock()
    real_init = obs_trace.Span.__init__

    def counting_init(self, *a, **kw):
        built.append(a[0])
        real_init(self, *a, **kw)

    def never(*_a, **_kw):
        raise AssertionError("a tracing-only read ran with tracing off")

    monkeypatch.setattr(obs_trace.Span, "__init__", counting_init)
    monkeypatch.setattr(obs_trace, "time", clock)
    with monkeypatch.context() as off:
        off.setattr(SnapshotStore, "in_flight", never)
        off.setattr(SnapshotStore, "ring", never)
        assert obs_trace.span("x", annotate=False) is obs.NOOP_SPAN
        _serve(make)
        assert built == [] and clock.reads == 0
        assert "serving.windows_in_flight" not in (
            obs.get_registry().snapshot()["gauges"])
    counts = []
    for window in (128, 1 << 12):
        sink = JsonlSink()
        obs.enable()
        obs.attach_sink(sink)
        try:
            _serve(make, window=window, n_windows=4,
                   n_vertices=1 << 12 if window > 128 else 1024)
        finally:
            obs.detach_sink(sink)
            obs.disable()
        got = [e["name"] for e in sink.events
               if e["kind"] == "span" and e["name"] in names]
        assert set(got) == names
        # + 1: the pull that finds the source at its end
        assert len(got) == 8 * 4 + 1, got
        counts.append(sorted(got))
    assert counts[0] == counts[1]
    assert clock.reads > 0 and built


# --------------------------------------------------------------------- #
# obs.trace: a span that turned out to time nothing
# --------------------------------------------------------------------- #
def test_a_cancelled_span_leaves_the_stack_and_emits_nothing(spans):
    with obs.span("kept") as outer:
        with obs.span("dropped") as sp:
            assert obs.current_span() is sp
            with obs.span("orphan"):
                pass
            assert sp.cancel() is sp
        assert obs.current_span() is outer
    assert obs.current_span() is None
    events = {e["name"]: e for e in spans()}
    assert set(events) == {"kept", "orphan"}
    assert obs.get_registry().find("trace.span_seconds") and not [
        k for k in obs.get_registry().snapshot()["histograms"]
        if "dropped" in k]
    assert obs.NOOP_SPAN.cancel() is obs.NOOP_SPAN


def test_a_stopped_server_leaves_no_root_for_the_window_it_did_not_publish(
        spans):
    gate = threading.Event()

    def payloads():
        yield {"labels": _Table(True), "vdict": None}, 0
        gate.wait(30)
        yield {"labels": _Table(True), "vdict": None}, 1

    server = StreamServer(payloads(), None)
    server.start()
    assert server.store.wait_for(1, timeout=30) is not None
    server._stop_ingest.set()
    gate.set()
    server.join(30)
    server.close()
    roots = [e for e in spans() if e["name"] == "serving.window"]
    assert [r["attrs"]["window"] for r in roots] == [0]
    assert server.snapshot().window == 0


# --------------------------------------------------------------------- #
# tools/trace_phases.py: the window block
# --------------------------------------------------------------------- #
def _tool():
    import trace_phases

    return trace_phases


def test_the_tools_window_block_reads_the_tree(spans):
    _serve(_cc)
    events = spans()
    block = _tool().window_block(events, events)
    assert block["windows"] == 3 and block["events_per_window"] == 8.0
    assert list(block["children_ms"]) == sorted(FOREST_KIDS)
    assert block["root_ms"] == pytest.approx(
        sum(block["children_ms"].values()) + block["self_ms"])
    assert 0.0 <= block["self_ms_p50"] <= block["self_ms_max"]
    assert 0.0 <= block["self_ms"] <= block["self_ms_max"]
    # where the self time lies: before each child and after the last
    assert list(block["gaps_ms"]) == [
        "before:" + k for k in FOREST_KIDS] + ["after:last"]
    assert sum(block["gaps_ms"].values()) == pytest.approx(block["self_ms"])
    assert block["unseen_limit_ms"] >= 0.3
    assert sum(block["in_flight_hist"].values()) == 3
    assert 0.0 <= block["in_flight_mean"] <= block["ring"] == 3
    # a program without the root (the parent): nothing, and no error
    older = [e for e in events if e["name"] != "serving.window"]
    assert _tool().window_block(older, older) == {}


def test_the_unseen_limit_is_the_larger_of_its_two_terms():
    def root(sid, dur, wait):
        return [{"name": "serving.window", "sid": sid, "dur_s": dur,
                 "t0": 5.0, "attrs": {"in_flight": 2, "ring": 4}},
                {"name": "ingest.wait_source", "sid": sid + 1,
                 "parent": sid, "t0": 5.0001, "dur_s": wait},
                {"name": "forest.window", "sid": sid + 2, "parent": sid,
                 "t0": 5.0003 + wait, "dur_s": dur - wait - 0.0005}]

    paced = root(1, 0.200, 0.190)     # 10 ms of work: 3% is 0.3 ms
    block = _tool().window_block(paced, paced)
    assert block["unseen_limit_ms"] == pytest.approx(0.3)
    assert block["self_ms"] == pytest.approx(0.5)
    assert block["gaps_ms"] == pytest.approx({
        "before:ingest.wait_source": 0.1, "before:forest.window": 0.2,
        "after:last": 0.2})
    busy = root(1, 0.030, 0.0)        # 30 ms of work: 3% is 0.9 ms
    assert _tool().window_block(busy, busy)[
        "unseen_limit_ms"] == pytest.approx(0.9)
    assert block["in_flight_hist"] == {"2": 1}


def test_the_tool_counts_the_new_spans_among_a_windows_events():
    tool = _tool()
    assert {"serving.window", "window.emit", "serving.publish"} <= set(
        tool.INGEST_SPANS)
    entered = {"fold_host_ms.sat", "fold_prep_ms.sat",
               "fold_dispatch_ms.sat", "queue_wait_ms", "answer_wait_ms"}
    assert not entered & set(tool.PROPOSED)
    for name, span, self_time in [
            ("emit_ms", "window.emit", False),
            ("publish_ms", "serving.publish", False),
            ("window_unseen_ms", "serving.window", True)]:
        for tag, moves in (("", "edges_per_s"), (".paced", "window_p95_ms")):
            unit, _layer, got, cells, reader = tool.PROPOSED[name + tag]
            assert (unit, got) == ("ms", moves)
            assert reader["kind"] == "span_mean_ms"
            assert reader["span"] == span
            assert bool(reader.get("self_time")) is self_time
            assert len(cells) == (1 if tag else 5)
