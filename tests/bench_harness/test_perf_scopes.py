"""The reduction from a profiler trace to a program's phases by named
scope (``benchmarks/lib/scope_reduce.py``), on a trimmed recording of
this repository's forest step with its scopes: three executions of
``jit_step`` and the query kernels between them, from a traced run of
``cc-g500-s28.ingest-saturated`` on a TPU v5e (PR 26, call p3). Event
names are cut to 60 characters and keep their ``op_name``. The numbers
asserted here are properties of the reduction, not measurements."""

from __future__ import annotations

import copy
import json
import math
import os
import struct
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks.lib import cellrun, scope_reduce as sr, spec  # noqa: E402
from benchmarks.lib import trace_reduce as tr  # noqa: E402

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "trace_cc_scopes_v5e.json")
PHASES = ("forest.chase", "forest.group", "forest.fixpoint", "forest.commit")


@pytest.fixture(scope="module")
def planes():
    with open(FIXTURE) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def window(planes):
    return tr.window_bounds(planes)


def _ops(planes):
    return tr.line_of(tr.device_planes(planes)[0], tr.OPS_LINE)["events"]


def test_the_recording_holds_three_whole_steps_under_their_old_names(
        planes, window):
    lo, hi = window
    runs = sr.executions(planes, "jit_step", lo, hi)
    assert len(runs) == 3
    assert [b - a for a, b in runs] == pytest.approx(
        [1e9 * d for d in tr.program_durations(planes, "jit_step", lo, hi)])
    assert len(sr.executions(planes, "jit__batch_roots", lo, hi)) == 4
    with pytest.raises(tr.TraceError, match="no execution of program"):
        sr.executions(planes, "jit_forest_step", lo, hi)


@pytest.mark.parametrize("scope", PHASES)
def test_a_phase_is_above_zero_in_every_execution(planes, window, scope):
    secs = sr.scope_seconds(planes, "jit_step", scope, *window)
    assert len(secs) == 3 and all(0.001 < s < 0.1 for s in secs)


def test_the_phases_of_an_execution_fill_it_and_never_exceed_it(
        planes, window):
    """Scopes are disjoint in time, so their unions add up to at most
    the program's own duration; what is missing is the compiler's own
    copy of the table, which carries no scope."""
    lo, hi = window
    per_phase = [sr.scope_seconds(planes, "jit_step", s, lo, hi)
                 for s in PHASES]
    durs = tr.program_durations(planes, "jit_step", lo, hi)
    for k, whole in enumerate(durs):
        parts = sum(p[k] for p in per_phase)
        assert 0.95 * whole <= parts <= whole
    unscoped = [e for e in _ops(planes)
                if not sr.op_path(e[0]) and e[2] > 1e6
                and "while" not in sr.op_id(e[0])]
    assert {sr.op_id(e[0]).split(".")[0] for e in unscoped} == {"%copy"}


def test_a_phase_is_a_union_a_while_and_its_body_are_not_counted_twice(
        planes, window):
    """The trace's ``while`` events carry no scope of their own. Give
    them their body's, as a trace that named them would: the phase
    must not double."""
    lo, hi = window
    before = sr.scope_seconds(planes, "jit_step", "forest.fixpoint", lo, hi)
    tagged = copy.deepcopy(planes)
    ops = _ops(tagged)
    n_tagged = 0
    for e in ops:
        if sr.op_id(e[0]).startswith("%while") and not sr.op_path(e[0]):
            inside = [x for x in ops if x is not e
                      and e[1] <= x[1] and x[1] + x[2] <= e[1] + e[2]
                      and "forest.fixpoint" in sr.op_path(x[0])]
            if inside:
                e[0] += ', metadata={op_name="jit(step)/forest.fixpoint/while"}'
                n_tagged += 1
    assert n_tagged == 3
    after = sr.scope_seconds(tagged, "jit_step", "forest.fixpoint", lo, hi)
    naive = [sum(x[2] for x in evs) / 1e9 for _run, evs in sr.scope_events(
        tagged, "jit_step", "forest.fixpoint", lo, hi)]
    for b, a, n in zip(before, after, naive):
        assert b <= a < 1.01 * b        # the loop's own overhead, no more
        assert n > 1.9 * b              # what a sum would have said
    # and the trips do not count the while itself
    assert sr.scope_rounds(tagged, "jit_step", "forest.fixpoint", lo, hi) \
        == sr.scope_rounds(planes, "jit_step", "forest.fixpoint", lo, hi)


def test_an_execution_straddling_the_windows_end_is_left_out(
        planes, window):
    lo, hi = window
    runs = sr.executions(planes, "jit_step", lo, hi)
    cut = (runs[2][0] + runs[2][1]) / 2
    assert len(sr.executions(planes, "jit_step", lo, cut)) == 2
    secs = sr.scope_seconds(planes, "jit_step", "forest.commit", lo, cut)
    assert secs == sr.scope_seconds(
        planes, "jit_step", "forest.commit", lo, hi)[:2]
    assert len(sr.scope_rounds(
        planes, "jit_step", "forest.chase", runs[0][0] + 1, hi)) == 2


@pytest.mark.parametrize("program,scope", [
    ("jit_step", "forest.sort"),            # no such scope
    ("jit_step", "forest"),                 # a prefix is not a segment
    ("jit_step", "query.chase"),            # another program's
    ("jit__batch_roots", "forest.chase"),
])
def test_a_scope_no_op_carries_is_an_error_not_a_zero(
        planes, window, program, scope):
    with pytest.raises(tr.TraceError, match="carries the scope") as e:
        sr.scope_seconds(planes, program, scope, *window)
    assert "forest.fixpoint" in str(e.value)     # says what it did see
    with pytest.raises(tr.TraceError):
        sr.scope_rounds(planes, program, scope, *window)


@pytest.mark.parametrize("scope,body_op", [
    ("forest.chase", "%fusion"), ("forest.fixpoint", "%fusion.2")])
def test_rounds_are_the_trips_of_the_loops_body(planes, window, scope,
                                                body_op):
    """Counted here another way: occurrences of the recording's body
    gather per execution. The chase's condition gathers once more."""
    lo, hi = window
    rounds = sr.scope_rounds(planes, "jit_step", scope, lo, hi)
    want = []
    for a, b in sr.executions(planes, "jit_step", lo, hi):
        want.append(sum(1 for n, s, _d in _ops(planes)
                        if a <= s < b and sr.op_id(n) == body_op
                        and "body" in sr.op_path(n)))
    assert rounds == want and all(4 <= r <= 7 for r in rounds)
    if scope == "forest.chase":
        conds = [sum(1 for n, s, _d in _ops(planes) if a <= s < b
                     and sr.op_path(n)[-2:-1] == ["cond"]
                     and sr.op_id(n) == "%fusion.1")
                 for a, b in sr.executions(planes, "jit_step", lo, hi)]
        assert conds == [r + 1 for r in rounds]


def test_a_scope_without_a_loop_has_no_rounds(planes, window):
    assert sr.scope_rounds(planes, "jit_step", "forest.commit",
                           *window) == [0, 0, 0]


def test_the_query_kernel_has_its_scope_too(planes, window):
    secs = sr.scope_seconds(planes, "jit__batch_roots", "query.chase",
                            *window)
    durs = tr.program_durations(planes, "jit__batch_roots", *window)
    assert len(secs) == 4
    assert all(0 < s <= d for s, d in zip(secs, durs))


def test_the_host_spans_are_on_the_trace_beside_the_steps(planes, window):
    """The recording's host plane holds the new spans as annotations:
    a window's fold contains its prep and its dispatch."""
    host = [e for p in planes if not p["name"].startswith("/device")
            for ln in p["lines"] for e in ln["events"]]
    by_name = {}
    for n, s, d in host:
        by_name.setdefault(n, []).append((s, s + d))
    for name in ("ingest.wait_source", "forest.window", "forest.prep",
                 "forest.dispatch", "serving.answer", "serving.device_wait"):
        assert by_name.get(name), name
    for child in ("forest.prep", "forest.dispatch"):
        for a, b in by_name[child]:
            assert any(wa <= a and b <= wb
                       for wa, wb in by_name["forest.window"])
    for a, b in by_name["serving.device_wait"]:
        assert any(wa <= a and b <= wb
                   for wa, wb in by_name["serving.answer"])


# --------------------------------------------------------------------- #
# the readers, as the harness calls them
# --------------------------------------------------------------------- #
def test_the_reader_kinds_resolve_and_are_not_the_harnesss_yet(
        planes, window):
    lo, hi = window
    ctx = {"scoped_planes": planes, "lo": lo, "hi": hi}
    assert set(sr.READERS) == {"scope_mean_ms", "scope_rounds_mean"}
    assert not set(sr.READERS) & set(cellrun.READERS)
    ms = sr.READERS["scope_mean_ms"](
        {"program": "jit_step", "scope": "forest.group"}, ctx)
    assert ms == pytest.approx(15.7, abs=0.1)
    trips = sr.READERS["scope_rounds_mean"](
        {"program": "jit_step", "scope": "forest.fixpoint"}, ctx)
    assert trips == pytest.approx(16 / 3)
    with pytest.raises(tr.TraceError):
        sr.READERS["scope_mean_ms"](
            {"program": "jit_step", "scope": "forest.gone"}, ctx)


def _proposed():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import trace_phases

    return trace_phases.PROPOSED


@pytest.mark.parametrize("name", sorted(_proposed()))
def test_a_proposed_metric_would_fit_the_benchmark_as_it_is(name):
    """What ``tools/trace_phases.py`` reads beside the accepted
    metrics is shaped as an entry a ``benchmark`` PR could take over:
    cells that exist and report the metric it moves, a layer the
    benchmark names, a reader kind that exists."""
    unit, layer, moves, cells, reader = _proposed()[name]
    bench = spec.load_benchmark()
    assert name not in {m["name"] for m in bench["per_layer"]}
    assert layer in {m["layer"] for m in bench["per_layer"]}
    assert reader["kind"] in {**cellrun.READERS, **sr.READERS}
    for cell_name in cells:
        assert moves in spec.load_cell(cell_name).end_to_end
    assert unit in ("ms", "count")


# --------------------------------------------------------------------- #
# the second read: the scope is a stat of an event's METADATA record,
# which jax.profiler.ProfileData does not hand out
# --------------------------------------------------------------------- #
def _varint(n: int) -> bytes:
    out = b""
    while True:
        b, n = n & 0x7F, n >> 7
        out += bytes([b | (0x80 if n else 0)])
        if not n:
            return out


def _ld(field: int, payload: bytes) -> bytes:
    return _varint(field << 3 | 2) + _varint(len(payload)) + payload


def _vi(field: int, value: int) -> bytes:
    return _varint(field << 3) + _varint(value)


def _xspace(interned: bool) -> bytes:
    """A two-plane XSpace by hand: a device plane with two ops (one
    scoped, one not) in one module, and a host plane."""
    stat_md = _ld(5, _vi(1, 7) + _ld(2, _vi(1, 7) + _ld(2, b"tf_op")))
    stat_md += _ld(5, _vi(1, 8) + _ld(2, _vi(1, 8) + _ld(2, b"flops")))
    path = b"jit(step)/forest.chase/while/body/gather:"
    if interned:    # the string is a stat metadata's name, referred to
        stat_md += _ld(5, _vi(1, 9) + _ld(2, _vi(1, 9) + _ld(2, path)))
        scope_stat = _vi(1, 7) + _vi(7, 9)
    else:
        scope_stat = _vi(1, 7) + _ld(5, path)
    flops = _vi(1, 8) + _varint(2 << 3 | 1) + struct.pack("<d", 3.0)
    md = [
        (1, b"jit_step(123)", b""),
        (2, b"%fusion.1 = s32[8] fusion(...)",
         _ld(5, flops) + _ld(5, scope_stat)),
        (3, b"%copy.2 = s32[8] copy(...)", _ld(5, flops)),
    ]
    event_md = b"".join(
        _ld(4, _vi(1, i) + _ld(2, _vi(1, i) + _ld(2, name) + stats))
        for i, name, stats in md)

    def event(mid, offset_ps, dur_ps):
        return _ld(4, _vi(1, mid) + _vi(2, offset_ps) + _vi(3, dur_ps))

    modules = _ld(3, _vi(1, 1) + _ld(2, b"XLA Modules") + _vi(3, 1000)
                  + event(1, 0, 9_000_000))
    ops = _ld(3, _vi(1, 2) + _ld(2, b"XLA Ops") + _vi(3, 1000)
              + event(2, 1_000_000, 2_000_000)
              + event(3, 4_000_000, 500_000)
              + event(2, 5_000_000, 2_500_000))
    other = _ld(3, _vi(1, 3) + _ld(2, b"Async XLA Ops") + _vi(3, 1000)
                + event(3, 0, 1))
    device = _ld(2, b"/device:TPU:0") + modules + ops + other \
        + event_md + stat_md
    host = _ld(2, b"/host:CPU") + _ld(3, _vi(1, 1) + _ld(2, b"python3"))
    return _ld(1, device) + _ld(1, host)


@pytest.mark.parametrize("interned", [False, True],
                         ids=["str_value", "ref_value"])
def test_the_scope_is_read_from_the_event_metadatas_stat(tmp_path, interned):
    log_dir = tmp_path / "plugins" / "profile" / "2026_09_30"
    log_dir.mkdir(parents=True)
    (log_dir / "host.xplane.pb").write_bytes(_xspace(interned))
    path = tr.find_xplane(str(tmp_path))
    got = sr.scoped_planes(path)
    assert [p["name"] for p in got] == ["/device:TPU:0"]
    lines = {ln["name"]: ln["events"] for ln in got[0]["lines"]}
    assert set(lines) == {tr.MODULES_LINE, tr.OPS_LINE}
    assert lines[tr.MODULES_LINE] == [["jit_step(123)", 1000.0, 9000.0]]
    names = [e[0] for e in lines[tr.OPS_LINE]]
    assert sr.op_path(names[0]) == [
        "jit(step)", "forest.chase", "while", "body", "gather:"]
    assert sr.op_id(names[0]) == "%fusion.1"
    assert sr.op_path(names[1]) == [] and names[2] == names[0]
    assert [e[1:] for e in lines[tr.OPS_LINE]] == [
        [2000.0, 2000.0], [5000.0, 500.0], [6000.0, 2500.0]]
    # the same file through jax's own reader: same events, same clock,
    # and no scope anywhere in what it hands out
    from jax.profiler import ProfileData

    dev = ProfileData.from_file(path).find_plane_with_name("/device:TPU:0")
    theirs = {ln.name: [(e.name, e.start_ns, e.duration_ns)
                        for e in ln.events] for ln in dev.lines}
    assert [(n.split(", metadata=")[0], s, d)
            for n, s, d in lines[tr.OPS_LINE]] == theirs[tr.OPS_LINE]
    assert not any("forest.chase" in n for n, _s, _d in theirs[tr.OPS_LINE])
    # and the readers find the file through the traced run's directory
    ctx = {"traced": {"dir": str(tmp_path)}, "lo": 0.0, "hi": 20000.0}
    ms = sr.READERS["scope_mean_ms"](
        {"program": "jit_step", "scope": "forest.chase"}, ctx)
    assert ms == pytest.approx(4.5e-3)
    assert sr.READERS["scope_rounds_mean"](
        {"program": "jit_step", "scope": "forest.chase"}, ctx) == 2
    assert math.isfinite(ms) and "scoped_planes" in ctx   # read once
