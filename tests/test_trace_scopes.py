"""The tracing that reaches inside the forest step and the serving wait
(ISSUE 26): named scopes in the lowered programs, the host spans where a
window is folded and where a sweep waits, and the one clock (``t0``)."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gelly_streaming_tpu import obs
from gelly_streaming_tpu.obs import trace as obs_trace
from gelly_streaming_tpu.obs.export import JsonlSink


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
# device: the scopes are in the lowered programs, under their old names
# --------------------------------------------------------------------- #
TCAP, WCAP, VCAP, K = 16, 8, 64, 3
_I = jax.ShapeDtypeStruct
_CANON = _I((VCAP,), jnp.int32)
_TID, _TMASK = _I((TCAP,), jnp.int32), _I((TCAP,), jnp.bool_)
FOREST = {"forest.chase", "forest.group", "forest.fixpoint", "forest.commit"}


def _lower_cc_step():
    from gelly_streaming_tpu.summaries.forest import _forest_step_fn

    return _forest_step_fn(TCAP, WCAP, VCAP).lower(
        _CANON, _TID, _TMASK, _I((WCAP,), jnp.int32), _I((WCAP,), jnp.int32))


def _lower_cc_superbatch():
    from gelly_streaming_tpu.summaries.forest import _forest_superbatch_fn

    return _forest_superbatch_fn(TCAP, WCAP, VCAP, K).lower(
        _CANON, _TID, _TMASK,
        _I((K, WCAP), jnp.int32), _I((K, WCAP), jnp.int32))


def _lower_cover_step():
    from gelly_streaming_tpu.summaries.candidates import _cover_step_fn

    return _cover_step_fn(TCAP, WCAP, VCAP).lower(
        _I((2 * VCAP,), jnp.int32), _I((), jnp.bool_), _TID, _TMASK,
        _I((WCAP,), jnp.int32), _I((WCAP,), jnp.int32),
        _I((WCAP,), jnp.bool_))


def _lower_cover_superbatch():
    from gelly_streaming_tpu.summaries.candidates import _cover_superbatch_fn

    return _cover_superbatch_fn(TCAP, WCAP, VCAP, K).lower(
        _I((2 * VCAP,), jnp.int32), _I((), jnp.bool_), _TID, _TMASK,
        _I((K, WCAP), jnp.int32), _I((K, WCAP), jnp.int32),
        _I((K, WCAP), jnp.bool_))


def _lower_batch_roots():
    from gelly_streaming_tpu.serving.query import _batch_roots

    return _batch_roots.lower(_CANON, _I((8,), jnp.int32))


@pytest.mark.parametrize("lower,program,scopes", [
    (_lower_cc_step, "jit_step", FOREST),
    (_lower_cc_superbatch, "jit_step", FOREST),
    (_lower_cover_step, "jit_step", FOREST | {"forest.latch"}),
    (_lower_cover_superbatch, "jit_step", FOREST | {"forest.latch"}),
    (_lower_batch_roots, "jit__batch_roots", {"query.chase"}),
], ids=["cc-step", "cc-superbatch", "cover-step", "cover-superbatch",
        "batch-roots"])
def test_scopes_are_in_the_lowered_program_under_its_old_name(
        lower, program, scopes):
    lowered = lower()
    text = lowered.as_text(debug_info=True)
    for scope in scopes:
        assert f"{scope}/" in text, scope
    # the loops sit under their scopes: what the trace's reducer counts
    if "forest.chase" in scopes:
        assert "forest.chase/while/body" in text
        assert "forest.fixpoint/while/body" in text
        # the fixpoint's once-a-step part, nested in it and not in its
        # loop (ISSUE 33)
        assert "forest.fixpoint/forest.contract/gather" in text
        assert "forest.contract/while" not in text
        assert "while/body/forest.contract" not in text
    else:
        assert "query.chase/while/body" in text
    # the benchmark finds the programs by these names
    assert f"@{program}" in text.split("{", 1)[0]


# --------------------------------------------------------------------- #
# host: where a window is folded
# --------------------------------------------------------------------- #
class _Chunks:
    def __init__(self, src, dst, size):
        self.src, self.dst, self.size = src, dst, size

    def iter_chunks(self):
        for a in range(0, len(self.src), self.size):
            yield self.src[a:a + self.size], self.dst[a:a + self.size]


def _stream(window=128, n_windows=3, n_vertices=1024):
    from gelly_streaming_tpu.core.stream import SimpleEdgeStream
    from gelly_streaming_tpu.core.window import CountWindow
    from gelly_streaming_tpu.datasets import IdentityDict

    rng = np.random.default_rng(26)
    n = window * n_windows
    src = (2 * rng.integers(0, n_vertices // 2, n)).astype(np.int32)
    dst = (2 * rng.integers(0, n_vertices // 2, n) + 1).astype(np.int32)
    return SimpleEdgeStream(
        _Chunks(src, dst, window), window=CountWindow(window),
        vertex_dict=IdentityDict(n_vertices))


def _cc():
    from gelly_streaming_tpu.library import ConnectedComponents

    return ConnectedComponents(carry="forest")


def _cover():
    from gelly_streaming_tpu.library.bipartiteness import BipartitenessCheck

    return BipartitenessCheck(carry="forest")


@pytest.mark.parametrize("make_agg", [_cc, _cover], ids=["cc", "cover"])
def test_forest_window_covers_its_children(spans, make_agg):
    for _ in _stream().aggregate(make_agg()):
        pass
    events = spans()
    windows = [e for e in events if e["name"] == "forest.window"]
    assert len(windows) == 3
    for w in windows:
        kids = [e for e in events if e.get("parent") == w["sid"]]
        assert sorted(k["name"] for k in kids) == [
            "forest.dispatch", "forest.prep"]
        assert sum(k["dur_s"] for k in kids) <= w["dur_s"]
        for k in kids:   # inside the parent, on the one clock
            assert w["t0"] <= k["t0"]
            assert k["t0"] + k["dur_s"] <= w["t0"] + w["dur_s"] + 1e-9
        assert w["attrs"]["edges"] == 128
        assert 0 < w["attrs"]["touched"] <= w["attrs"]["tcap"]
        assert w["attrs"]["wcap"] == 128


def test_wait_source_wraps_the_pull_alone(spans):
    """The span is closed before the windower's generator yields: the
    pack and the fold that follow are no children of it."""
    for _ in _stream().aggregate(_cc()):
        pass
    events = spans()
    waits = [e for e in events if e["name"] == "ingest.wait_source"]
    assert len(waits) == 4               # 3 chunks and the end
    wait_sids = {e["sid"] for e in waits}
    assert all(e["depth"] == 0 for e in waits)
    assert not [e for e in events if e.get("parent") in wait_sids]
    packs = [e for e in events if e["name"] == "window.pack"]
    assert len(packs) == 3 and all("parent" not in e for e in packs)
    # nothing is opened inside window.pack: pack_ms.sat is its SELF time
    pack_sids = {e["sid"] for e in packs}
    assert not [e for e in events if e.get("parent") in pack_sids]


# --------------------------------------------------------------------- #
# one clock
# --------------------------------------------------------------------- #
def test_every_event_has_t0_on_the_perf_counter(spans):
    with obs.span("outer"):
        with obs.span("inner"):
            time.sleep(0.002)
        exit_inner = time.perf_counter()
    exit_outer = time.perf_counter()
    known = time.perf_counter() - 0.25
    obs.record_span("late", 0.25, t0=known)
    obs.record_span("derived", 0.125)
    exit_derived = time.perf_counter()
    by_name = {e["name"]: e for e in spans()}
    assert set(by_name) == {"outer", "inner", "late", "derived"}
    for e in by_name.values():
        assert isinstance(e["t0"], float) and "ts" in e
    assert abs(by_name["inner"]["t0"] + by_name["inner"]["dur_s"]
               - exit_inner) < 1e-3
    assert abs(by_name["outer"]["t0"] + by_name["outer"]["dur_s"]
               - exit_outer) < 1e-3
    assert by_name["late"]["t0"] == known
    # no start passed: the span is taken to end at the call
    assert abs(by_name["derived"]["t0"] + 0.125 - exit_derived) < 1e-3
    assert by_name["outer"]["t0"] <= by_name["inner"]["t0"]


# --------------------------------------------------------------------- #
# serving: the queue wait and the device wait of a sweep
# --------------------------------------------------------------------- #
def test_a_sweep_without_a_trace_context_says_how_long_it_waited(spans):
    from gelly_streaming_tpu.serving import ConnectedQuery, StreamServer
    from gelly_streaming_tpu.serving.query import QueryEngine

    server = StreamServer(_cc().servable(), _stream(), max_pending=4096,
                          engine=QueryEngine(prefer_host=False))
    server.start()
    server.join(60)
    t_submit = time.perf_counter()
    futures = server.submit_many(
        [ConnectedQuery(2 * i, 2 * i + 1) for i in range(40)])   # no ctx
    for f in futures:
        f.result(60)
    t_done = time.perf_counter()
    server.close()
    events = spans()
    assert not [e for e in events if e["name"] == "serving.query"]
    waits = [e for e in events if e["name"] == "serving.queue_wait"]
    answers = [e for e in events if e["name"] == "serving.answer"]
    assert len(waits) == len(answers) >= 1
    assert sum(e["attrs"]["batch"] for e in waits) == 40
    for w in waits:
        assert "trace" not in w
        assert t_submit - 1e-3 <= w["t0"] <= t_done
        assert w["t0"] + w["dur_s"] <= t_done
    # the device wait is a child of the sweep's answer and inside it
    dev = [e for e in events if e["name"] == "serving.device_wait"]
    assert len(dev) == len(answers)
    for d in dev:
        a = next(a for a in answers if a["sid"] == d["parent"])
        assert d["dur_s"] <= a["dur_s"]
        assert d["attrs"]["n"] == 2 * a["attrs"]["batch"]


def _degree_sweep():
    from gelly_streaming_tpu.core.stream import SimpleEdgeStream
    from gelly_streaming_tpu.core.window import CountWindow
    from gelly_streaming_tpu.datasets import IdentityDict
    from gelly_streaming_tpu.library.degrees import DegreeDistribution
    from gelly_streaming_tpu.serving import DegreeCountQuery, DegreeQuery

    rng = np.random.default_rng(39)
    src, dst = (rng.integers(0, 64, 256).astype(np.int32) for _ in "sd")
    stream = SimpleEdgeStream(
        (src, dst, np.ones(256, np.int32)), window=CountWindow(64),
        vertex_dict=IdentityDict(64))
    return (DegreeDistribution(hist_capacity=64).servable(), stream,
            [DegreeQuery(v) for v in range(24)]
            + [DegreeCountQuery(d) for d in range(1, 8)])


def _pair_sweep():
    from gelly_streaming_tpu.serving import ConnectedQuery

    return (_cc().servable(), _stream(),
            [ConnectedQuery(2 * i, 2 * i + 1) for i in range(20)])


def _sized_sweep():
    from gelly_streaming_tpu.library import ConnectedComponents
    from gelly_streaming_tpu.serving import ComponentSizeQuery, ConnectedQuery

    return (ConnectedComponents(component_sizes=True).servable(), _stream(),
            [ComponentSizeQuery(v) for v in range(12)]
            + [ConnectedQuery(2 * i, 2 * i + 1) for i in range(4)])


@pytest.mark.parametrize("make,waits,under", [
    (_degree_sweep, [24, 7], "serving.answer"),
    (_pair_sweep, [40], "serving.answer"),
    (_sized_sweep, [20], "serving.size_lookup"),
], ids=["two_degree_classes", "pairs_alone", "sized_pair"])
def test_a_sweeps_answer_span_counts_its_reads(spans, make, waits, under):
    """``serving.answer`` says how many device reads the sweep enqueued
    before its first fetch (``reads``) and how many of them, the first
    aside, still waited (``late_reads``); every read keeps a
    ``serving.device_wait`` of its own, with its lanes."""
    from gelly_streaming_tpu.serving import StreamServer
    from gelly_streaming_tpu.serving.query import QueryEngine

    servable, stream, queries = make()
    server = StreamServer(servable, stream, max_pending=4096,
                          engine=QueryEngine(prefer_host=False))
    server.start()
    server.join(60)
    for f in server.submit_many(queries):
        f.result(60)
    server.close()
    events = spans()
    (answer,) = [e for e in events if e["name"] == "serving.answer"]
    assert answer["attrs"]["batch"] == len(queries)
    assert answer["attrs"]["reads"] == len(waits)
    assert 0 <= answer["attrs"]["late_reads"] < len(waits)
    parent = answer
    if under != "serving.answer":
        (parent,) = [e for e in events if e["name"] == under]
        assert parent["parent"] == answer["sid"]
    dev = [e for e in events if e["name"] == "serving.device_wait"]
    assert [d["attrs"]["n"] for d in dev] == waits
    assert all(d["parent"] == parent["sid"] for d in dev)
    assert sum(d["dur_s"] for d in dev) <= answer["dur_s"]
    # tools/trace_phases.py prints both a sweep, and the first read's
    # wait beside a later one's
    block = _trace_phases().serving_block(events, events)
    assert block["sweeps"] == 1
    assert block["reads_per_sweep"] == len(waits)
    assert block["late_reads_per_sweep"] == answer["attrs"]["late_reads"]
    assert block["first_wait_ms"] == pytest.approx(1e3 * dev[0]["dur_s"])
    assert (block["later_wait_ms"] is None) == (len(waits) == 1)
    # a program whose sweeps do not count their reads: the waits alone
    older = [dict(e, attrs={"batch": 1}) if e is answer else e
             for e in events]
    assert "reads_per_sweep" not in _trace_phases().serving_block(
        older, older)
    assert _trace_phases().serving_block([], events) == {}


def _trace_phases():
    import os
    import sys

    tools = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools")
    if tools not in sys.path:
        sys.path.insert(0, tools)
    import trace_phases

    return trace_phases


# --------------------------------------------------------------------- #
# the degree step's histogram: three scopes nested in `degrees.hist`
# --------------------------------------------------------------------- #
def test_the_histograms_parts_are_scopes_inside_degrees_hist():
    """The head's contraction, the tail's slab and the fallback branch
    carry their scopes under ``degrees.hist``, where the tool reads
    them; a histogram no larger than the head has neither tail nor
    fallback."""
    from gelly_streaming_tpu.library.degrees import degree_step

    def lowered(hcap):
        return degree_step.lower(
            _I((VCAP,), jnp.int32), _I((hcap,), jnp.int32),
            _I((8,), jnp.int32), _I((8,), jnp.int32), _I((8,), jnp.int32),
            _I((8,), jnp.bool_)).as_text(debug_info=True)

    text = lowered(1 << 15)
    assert "degrees.hist/degrees.hist.head/dot_general" in text
    assert "degrees.hist/degrees.hist.tail/" in text
    assert "degrees.hist.tail/scatter-add" in text
    assert "degrees.hist.fallback/scatter-add" in text
    fallback = [ln for ln in text.splitlines()
                if "degrees.hist.fallback/" in ln]
    assert all("degrees.hist/" in ln for ln in fallback)
    small = lowered(64)
    assert "degrees.hist.head/dot_general" in small
    assert "degrees.hist.tail" not in small
    assert "degrees.hist.fallback" not in small


def _op(name, scope_path, start, dur=10.0):
    return [f'%{name} = s32[8]{{0}} fusion(), metadata={{op_name='
            f'"jit(degree_step)/{scope_path}/add"}}', start, dur]


def test_the_tool_reads_the_histograms_parts_and_the_fallbacks_share():
    """Three executions of the step, the second of which took the
    fallback: the share is a third, and the histogram's parts stand
    beside the phases' sum, not in it."""
    tp = _trace_phases()
    ops = []
    for k, took in enumerate((False, True, False)):
        a = 1000.0 * k
        ops += [_op("f1", "degrees.sort", a + 10),
                _op("f2", "degrees.hist/degrees.hist.head", a + 100, 20),
                _op("f3", "degrees.hist/degrees.hist.tail", a + 200, 30)]
        if took:
            ops.append(_op("f4", "degrees.hist/cond/branch_0_fun/"
                           "degrees.hist.fallback", a + 300, 200))
    planes = [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules",
         "events": [["jit_degree_step(1)", 1000.0 * k, 900.0]
                    for k in range(3)]},
        {"name": "XLA Ops", "events": ops}]}]
    block = tp.hist_fallback_block(planes, 0.0, 3000.0)
    assert block == {"executions": 3, "hist_fallback_executions": 1,
                     "hist_fallback_share": pytest.approx(1 / 3)}
    from benchmarks.lib import scope_reduce

    fb = scope_reduce.scope_seconds(planes, "jit_degree_step",
                                    "degrees.hist.fallback", 0.0, 3000.0)
    assert [round(x * 1e9) for x in fb] == [0, 200, 0]
    hist = scope_reduce.scope_seconds(planes, "jit_degree_step",
                                      "degrees.hist", 0.0, 3000.0)
    assert [round(x * 1e9) for x in hist] == [50, 250, 50]
    # a step without the scope (the parent's): no execution took it
    older = [dict(planes[0], lines=[planes[0]["lines"][0], {
        "name": "XLA Ops", "events": ops[:3]}])]
    assert tp.hist_fallback_block(older, 0.0, 3000.0)[
        "hist_fallback_executions"] == 0
    m = {"degree_step_ms.dyn": {"value": 0.9},
         "degree_step_sort_ms.dyn": {"value": 0.01},
         "degree_step_hist_ms.dyn": {"value": 0.117},
         "degree_step_hist_head_ms.dyn": {"value": 0.02},
         "degree_step_hist_tail_ms.dyn": {"value": 0.03},
         "degree_step_hist_fallback_ms.dyn": {"value": 0.067}}
    phases = tp.phases_block(m)
    assert phases["sum_ms"] == pytest.approx(0.127)
    assert phases["hist_head_ms"] == 0.02
    assert phases["hist_tail_ms"] == 0.03
    assert phases["hist_fallback_ms"] == 0.067
    assert set(tp.DEGREE_HIST_PARTS) == {"head", "tail", "fallback"}
    assert {f"degree_step_hist_{p}_ms.dyn" for p in tp.DEGREE_HIST_PARTS} \
        <= set(tp.PROPOSED)


# --------------------------------------------------------------------- #
# the operator's device trace
# --------------------------------------------------------------------- #
def test_device_trace_turns_annotations_on_for_the_block_and_back(tmp_path):
    import glob

    assert not obs.enabled()
    with obs.device_trace(str(tmp_path)):
        assert obs.enabled() and obs_trace._CFG.annotate_jax
        assert jax.config.jax_compilation_cache_include_metadata_in_key
        with obs.span("forest.window"):
            jnp.arange(8).block_until_ready()
    assert not obs.enabled() and not obs_trace._CFG.annotate_jax
    assert glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                         "*.xplane.pb"))
    # a run that already traces keeps tracing afterwards
    obs.enable()
    with obs.device_trace(str(tmp_path / "second")):
        pass
    assert obs.enabled() and not obs_trace._CFG.annotate_jax


def test_the_compile_caches_key_holds_metadata_while_annotations_are_on():
    """A device trace names ops by the executable's metadata; a cache
    hit under a key without it would hand back another checkout's."""
    key = "jax_compilation_cache_include_metadata_in_key"
    assert getattr(jax.config, key) is False
    obs.enable(jax_annotations=True)
    assert getattr(jax.config, key) is True
    obs.enable()                     # spans stay, annotations go
    assert getattr(jax.config, key) is False
    obs.enable(jax_annotations=True)
    obs.disable()
    assert getattr(jax.config, key) is False
    # a setting the operator made is put back, not overwritten
    jax.config.update(key, True)
    try:
        obs.enable(jax_annotations=True)
        obs.disable()
        assert getattr(jax.config, key) is True
    finally:
        jax.config.update(key, False)


def test_utils_profiling_device_trace_is_gone():
    from gelly_streaming_tpu import utils
    from gelly_streaming_tpu.utils import profiling

    assert not hasattr(profiling, "device_trace")
    assert not hasattr(utils, "device_trace")
