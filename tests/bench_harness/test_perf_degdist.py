"""The degree-distribution cell's benchmark files (``degdist``,
``graph500_dynamic``, ISSUE 34) end to end on the CPU at a tiny scale:
a run comes out correct on two seeds, the control and a histogram too
small for the stream come out NOT correct, the packed sign is decoded
alike by the stream, the reference and the queries, the reference folds
what upstream folds event by event, the event stream has the make-up
the configuration states, and the new entries of ``BENCHMARK.json``
resolve. Times here are of the CPU and are never a device number."""

from __future__ import annotations

import importlib
import json
import os
import sys
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
HERE = os.path.dirname(os.path.abspath(__file__))
for p in (REPO, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)

import _contract  # noqa: E402
from _tiny import make_tiny_root  # noqa: E402

from benchmarks.algorithms import degdist  # noqa: E402
from benchmarks.generators import graph500, graph500_dynamic  # noqa: E402
from benchmarks.lib import (  # noqa: E402
    bytes_model,
    cellrun,
    lastline,
    scope_reduce,
    spec,
    trace_reduce,
)

CELL = "dd-g500-s28.ingest-saturated-dyn"
EVENTS = {"additions": 192, "deletions_of_added": 60,
          "deletions_never_added": 4, "lag_windows": 3,
          "closing_lag_windows": 1}


def _tiny_cell(tmp_path, hist_capacity=1 << 16):
    """``_tiny.make_tiny_root``'s cell (scale 12, windows of 256) with
    this PR's algorithm and generator in the place of its own."""
    root = make_tiny_root(str(tmp_path), algorithm="degdist")
    cell = spec.load_cell("tiny.tiny-mix", root)
    cell.config.update({
        "generator": "graph500_dynamic", "events": EVENTS,
        "aggregation_args": {"hist_capacity": hist_capacity}})
    cell.config["guarantees"]["per_vertex_event_order_kept"] = True
    cell.traffic["ingest"] = {"mode": "closed", "outstanding": 2}
    cell.traffic["stream_edges_per_s"] = 256 * 6000
    return cell


def _run(tmp_path, *, control=None, seed=17, **kw):
    cell = _tiny_cell(tmp_path, **kw)
    return cell, cellrun.run_cell(
        cell, seed, 1.2, False, t_process=time.perf_counter(),
        backend=(cellrun.describe_device(), 0.0), require_tpu=False,
        control=control, work_root=str(tmp_path))


def _failing(doc) -> set:
    return {n for n, c in doc["compared"].items() if c["value"] > c["limit"]}


@pytest.mark.parametrize("seed", [17, 2**31 + 5])
def test_a_tiny_run_is_correct_on_both_query_kinds(tmp_path, seed):
    cell, doc = _run(tmp_path, seed=seed)
    assert doc["correct"] is True, doc["compared"]
    assert doc["attempted"] > 0 and doc["failed"] == 0
    assert set(doc["compared"]) == {
        "answers_compared", "answer_mismatches", "stale_answers",
        "stamp_errors", "unanswered", "windows_unpublished",
        "table_mismatches", "hist_overflow", "reference_hist_drift"}
    assert all(c["value"] == 0 for c in doc["compared"].values())
    assert doc["windows"]["ready_in_window"] > 5
    assert doc["windows"]["closing"] == 2
    assert doc["windows"]["compiles_in_window"] == 0
    doc["device"]["memory_peak_bytes"] = 1   # the CPU reports none
    assert lastline.validate(
        json.dumps(doc), required=cell.units("end_to_end"), trace=False,
        chips=doc["device"]["count"]) == []


def test_the_control_comes_out_not_correct(tmp_path):
    _cell, doc = _run(tmp_path, control="stale_prefix")
    assert doc["correct"] is False
    assert {"table_mismatches", "answer_mismatches"} <= _failing(doc)


def test_a_histogram_too_small_for_the_stream_is_not_correct(tmp_path):
    """``hist_overflow`` used: rows of the final table at or past the
    capacity, where the last bin no longer holds one degree."""
    _cell, doc = _run(tmp_path, hist_capacity=64)
    assert doc["correct"] is False
    assert "hist_overflow" in _failing(doc)
    assert doc["compared"]["table_mismatches"]["value"] == 0


def test_a_program_without_the_query_class_fails_when_the_module_loads(
        monkeypatch):
    """The parent commit on the new cell: the harness loads the
    algorithm module before it makes the stream, the import fails there
    and ``run.py`` turns that into exit code 2."""
    from gelly_streaming_tpu import serving

    monkeypatch.delattr(serving, "DegreeCountQuery")
    try:
        with pytest.raises(ImportError):
            importlib.reload(degdist)
    finally:
        monkeypatch.undo()
        importlib.reload(degdist)


def test_the_aggregation_is_built_with_a_fixed_histogram():
    agg = degdist.build(spec.load_cell(CELL).config)
    assert agg.hist_capacity == 1 << 20

    class Server:
        class engine:
            prefer_host = False

    assert degdist.chip_paths_problem(agg, Server) is None
    Server.engine.prefer_host = True
    assert "host" in degdist.chip_paths_problem(agg, Server)
    agg.hist_capacity = None
    Server.engine.prefer_host = False
    assert "grows" in degdist.chip_paths_problem(agg, Server)


# ---- the packed sign ------------------------------------------------- #
def _packed(rows):
    """``(src, dst)`` columns of ``(u, v, sign)`` rows, packed."""
    u, v, c = (np.asarray(x) for x in zip(*rows))
    return (np.where(c < 0, u | graph500_dynamic.DELETE_BIT, u).astype(
        np.int32), v.astype(np.int32))


def test_stream_reference_and_queries_decode_the_sign_alike():
    rows = [(1, 2, 1), (3, 4, -1), (5, 5, 1), (6, 7, -1), (1, 4, 1)]
    src, dst = _packed(rows)
    want = tuple(np.asarray(x) for x in zip(*rows))

    class Source:
        def iter_chunks(self):
            yield src, dst

    # the stream the program ingests
    [(s, d, c)] = list(degdist._Unpacked(Source()).iter_chunks())
    for got, w in zip((s, d, c), want):
        assert got.tolist() == w.tolist()
    assert c.dtype == np.int32 and int(s.max()) < graph500_dynamic.DELETE_BIT
    # the reference
    config = {"id_space": 16, "aggregation_args": {"hist_capacity": 8}}
    ref = degdist.Reference(config)
    ref.fold(src, dst)
    assert ref.deg.tolist()[:8] == [0, 2, 1, 0, 1, 2, 0, 0]
    # the queries: endpoints of additions, then of deletions
    queries, records = degdist.draw_queries(
        np.random.default_rng(0), 256, src, dst, config)
    kinds, keys = records[:, 0], records[:, 1]
    assert (kinds[:192] == degdist.DEGREE_OF).all()
    assert (kinds[192:] == degdist.COUNT_AT).all()
    assert set(keys[:64].tolist()) <= {1, 2, 5, 4}
    assert set(keys[64:128].tolist()) <= {3, 4, 6, 7}
    assert keys[:192].max() < 16 and keys[192:].min() >= 1
    assert keys[192:].max() < 2 ** degdist.COUNT_LOG2_BOUND
    assert [type(q).__name__ for q in queries[191:193]] == [
        "DegreeQuery", "DegreeCountQuery"]
    assert [q.v for q in queries[:192]] == keys[:192].tolist()
    assert [q.d for q in queries[192:]] == keys[192:].tolist()
    # the byte model's shapes
    assert degdist.fold_shape(config, src, dst) == {
        "rows": 16, "window_edges": 5, "touched": 7}


# ---- the reference against upstream, event by event ------------------ #
def _replay(src, dst, sign, rows):
    deg = np.zeros(rows, np.int64)
    for s, d, c in zip(src.tolist(), dst.tolist(), sign.tolist()):
        for v in (s, d):
            deg[v] = max(0, deg[v] + c)
    return deg


@pytest.mark.parametrize("window", [1, 5, 64, 400])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_reference_folds_what_upstream_folds(window, seed):
    """Few ids, half the events deletions: most vertices meet the clamp
    (the walked path), the rest are summed; the kept histogram is the
    bincount of the vector, bin by bin, after every window."""
    rng = np.random.default_rng(seed)
    n, rows = 400, 24
    u = rng.integers(0, rows, n)
    v = np.where(rng.random(n) < 0.1, u, rng.integers(0, rows, n))
    c = np.where(rng.random(n) < 0.5, 1, -1)
    ref = degdist.Reference(
        {"id_space": rows, "aggregation_args": {"hist_capacity": 1 << 10}})
    for lo in range(0, n, window):
        src, dst = _packed(list(zip(u[lo:lo + window], v[lo:lo + window],
                                    c[lo:lo + window])))
        ref.fold(src, dst)
        want = _replay(u[:lo + window], v[:lo + window], c[:lo + window],
                       rows)
        assert np.array_equal(ref.deg, want)
        bins = np.bincount(want, minlength=len(ref.hist))
        bins[0] = 0
        assert np.array_equal(ref.hist, bins[:len(ref.hist)])
        recs = np.asarray([[degdist.COUNT_AT, d] for d in range(1, 9)]
                          + [[degdist.DEGREE_OF, x] for x in range(rows)])
        assert ref.expected(recs).tolist() == (
            [int(np.sum(want == d)) for d in range(1, 9)] + want.tolist())
    final = ref.compare_final(ref.table())
    assert final == {"table_mismatches": 0, "hist_overflow": 0,
                     "reference_hist_drift": 0}
    wrong = ref.table()
    wrong[3] += 1
    assert ref.compare_final(wrong)["table_mismatches"] == 1
    ref.hist_capacity = 2
    assert ref.compare_final(ref.table())["hist_overflow"] == int(
        np.sum(ref.deg >= 2))


# ---- the event stream's make-up -------------------------------------- #
TINY = {"scale": 12, "window_edges": 256, "events": EVENTS,
        "graph500": {"graph_seed": 77, "a": 0.57, "b": 0.19, "c": 0.19,
                     "scrambled": True, "seeded_closing_windows": 4}}


@pytest.mark.parametrize("seed", [5, 2**31 + 9])
def test_every_window_holds_the_stated_make_up(seed):
    w, a, d, f, lag = 256, 192, 60, 4, 3
    n_w = 9
    src, dst = graph500_dynamic.edges(TINY, n_w * w, seed, 0)
    ids, dst_, sign = graph500_dynamic.unpack(src, dst)
    assert src.dtype == np.int32 and int(ids.max()) < 1 << 12
    dels = (sign < 0).reshape(n_w, w).sum(axis=1)
    assert dels.tolist() == [f] * lag + [d + f] * (n_w - lag)
    # before the seed orders it: the layout, window by window
    pool = graph500.kronecker_edges(
        77, 12, graph500_dynamic.fresh_edges_needed(n_w, lag, w, a, f),
        a=0.57, b=0.19, c=0.19)
    ls, ld = graph500_dynamic.lay_out(*pool, n_w, lag, (w, a, d, f))
    for k in range(lag, n_w):
        # the deletions of added edges name window k - lag's first ones
        assert (ls[k, a:a + d] & ~graph500_dynamic.DELETE_BIT).tolist() == (
            ls[k - lag, :d].tolist())
        assert ld[k, a:a + d].tolist() == ld[k - lag, :d].tolist()
        assert (ls[k, :a] < graph500_dynamic.DELETE_BIT).all()
    # the seed permutes the places of every window alike, no more
    for k in range(n_w):
        for col, lay in ((src, ls), (dst, ld)):
            assert sorted(col[k * w:(k + 1) * w].tolist()) == sorted(
                lay[k].tolist())
    other = graph500_dynamic.edges(TINY, n_w * w, seed + 1, 0)
    assert not np.array_equal(other[0], src)
    assert sorted(other[0][:w].tolist()) == sorted(src[:w].tolist())
    # a longer stream is the shorter one and a tail
    longer = graph500_dynamic.edges(TINY, (n_w + 5) * w, seed, 0)
    assert np.array_equal(longer[0][:n_w * w], src)
    assert np.array_equal(longer[1][:n_w * w], dst)


def test_the_closing_windows_are_the_seeds_own_with_deletions():
    a = graph500_dynamic.closing_edges(TINY, 5)
    b = graph500_dynamic.closing_edges(TINY, 6)
    assert len(a[0]) == 4 * 256 and not np.array_equal(a[0], b[0])
    sign = graph500_dynamic.unpack(*a)[2].reshape(4, 256)
    assert (sign < 0).sum(axis=1).tolist() == [4, 64, 64, 64]
    with pytest.raises(ValueError):
        graph500_dynamic.edges(TINY, 300, 5, 0)         # not whole windows
    with pytest.raises(ValueError):
        graph500_dynamic.edges({**TINY, "scale": 30}, 256, 5, 0)


# ---- the new entries of BENCHMARK.json ------------------------------- #
V4_CELL = _contract.V4_CELL


def test_the_new_entries_resolve_and_keep_to_the_contract():
    """Found by NAME: the cell, its nine per-layer entries whole (reader
    kinds and files, units, ``moves``), together and in their order
    after the four-chip cell's, and the end-to-end metrics it reports;
    a later PR's entries after them fit in."""
    _contract.entries_stand(REPO, CELL)
    _contract.cells_stand(REPO)
    _contract.four_chip_cells_fit(REPO)


def test_pr_28s_entries_stand_as_they_stood():
    """The four-chip cell's entries by name, as
    ``test_perf_vsharded.py``'s
    ``test_the_new_entries_resolve_and_keep_to_the_contract`` holds them:
    four chips, its end-to-end metrics, its seven per-layer entries
    whole, together and in their order, its configuration, and among
    the four-chip cells, which are no more than a quarter of the
    cells."""
    _contract.entries_stand(REPO, V4_CELL)
    _contract.four_chip_cells_fit(REPO)


def test_the_configuration_states_its_cut_and_its_stream():
    cfg = spec.load_cell(CELL).config
    assert cfg["algorithm"] == "degdist" and cfg["scale"] == 28
    assert cfg["generator"] == "graph500_dynamic"
    assert cfg["id_space"] == 1 << 28 and cfg["window_edges"] == 1 << 16
    assert cfg["aggregation_args"] == {"hist_capacity": 1 << 20}
    assert cfg["reduced"] == ["scale"] and "scale" in cfg["reduced_why"]
    s28 = spec.load_cell("cc-g500-s28.ingest-saturated").config
    assert cfg["guarantees"] == {**s28["guarantees"],
                                 "per_vertex_event_order_kept": True}
    g = dict(cfg["graph500"])
    assert g.pop("graph_seed") != s28["graph500"]["graph_seed"]
    assert g == {k: v for k, v in s28["graph500"].items()
                 if k != "graph_seed"}
    ev = cfg["events"]
    assert (ev["additions"], ev["deletions_of_added"],
            ev["deletions_never_added"]) == (49152, 15360, 1024)
    assert ev["lag_windows"] == 8
    # a quarter of the events are deletions
    assert 4 * (ev["deletions_of_added"] + ev["deletions_never_added"]) == (
        cfg["window_edges"])
    for key in ("events", "events_sources", "sign_in_bit_30",
                "hist_capacity", "graph_seed"):
        assert key in cfg["assumed"]
    # the table is the 1 GiB that cc-g500-s28 carries
    assert 4 * degdist.table_rows(cfg) == 1 << 30
    traffic = spec.load_cell(CELL).traffic
    assert traffic["ingest"] == {"mode": "closed", "outstanding": 2}
    assert traffic["queries"]["batch"] == 256
    assert traffic["queries"]["period_ms"] == 100
    # the stream outlasts the program: room for twice the rate on record
    assert cellrun.stream_length(spec.load_cell(CELL), 45) // (1 << 16) >= 4000


def test_the_byte_model_reads_the_degree_table():
    cfg = spec.load_cell(CELL).config
    src, dst = _packed([(1, 2, 1), (2, 3, -1), (3, 4, 1), (3, 9, -1)])
    shape = degdist.fold_shape(cfg, src, dst)
    assert shape == {"rows": 1 << 28, "window_edges": 4, "touched": 5}
    assert bytes_model.forest_step(**shape) == 8 * (1 << 28) + 8 * 4 + 8 * 5


# ---- the cell's trace readers, on a recording of the cell ------------ #
RECORDING = os.path.join(HERE, "fixtures", "trace_dd_saturated_v5e.json")


@pytest.fixture(scope="module")
def recording():
    """Three executions of ``jit_degree_step`` and two of ``jit__gather``
    (TPU v5e, PR 34, ``tools/trace_phases.py --dump``): the device's
    modules and ops, names uncut and scopes in them, and the host's
    annotations of the program's spans."""
    with open(RECORDING) as f:
        return json.load(f)


def test_every_trace_metric_of_the_cell_resolves_on_its_recording(recording):
    """What ``test_perf_trace.py`` asks of every cell against the CC
    recording, asked of this cell against its own."""
    cell = spec.load_cell(CELL)
    ctx = {"planes": recording["planes"], "lo": recording["lo"],
           "hi": recording["hi"]}
    got = {}
    for name, reader in cell.readers.items():
        r = reader["reader"]
        if r["kind"] == "program_mean_ms":
            got[name] = cellrun.READERS[r["kind"]](r, ctx)
    assert sorted(got) == ["degree_gather_ms.dyn", "degree_step_ms.dyn"]
    assert 11.0 < got["degree_step_ms.dyn"] < 12.0
    assert 0 < got["degree_gather_ms.dyn"] < 0.01
    with pytest.raises(trace_reduce.TraceError):
        trace_reduce.program_durations(
            recording["planes"], "jit_step", ctx["lo"], ctx["hi"])


def test_the_scopes_and_the_tables_copy_fill_the_recorded_step(recording):
    planes, lo, hi = recording["planes"], recording["lo"], recording["hi"]
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import trace_phases

    step = 1e3 * np.mean(trace_reduce.program_durations(
        planes, "jit_degree_step", lo, hi))
    scopes = {p: 1e3 * np.mean(scope_reduce.scope_seconds(
        planes, "jit_degree_step", f"degrees.{p}", lo, hi))
        for p in trace_phases.DEGREE_PHASES}
    assert all(v > 0 for v in scopes.values())
    # the instructions of one execution with a row per vertex in an
    # operand or a result: the gather, the scatter, the compiler's copy
    a, b = scope_reduce.executions(planes, "jit_degree_step", lo, hi)[0]
    ops = trace_reduce.line_of(trace_reduce.device_planes(planes)[0],
                               trace_reduce.OPS_LINE)["events"]
    rows = [(scope_reduce.op_id(n), scope_reduce.op_path(n), d / 1e6)
            for n, s, d in ops if a <= s < b and f"[{1 << 28}]" in n]
    assert [r[0] for r in rows] == ["%copy.7", "%fusion", "%fusion.1"]
    assert rows[0][1] == [] and "degrees.gather" in rows[1][1]
    assert "degrees.scatter" in rows[2][1]
    assert sum(scopes.values()) + rows[0][2] == pytest.approx(step, rel=0.02)
    # the tool's block for this family of metrics
    m = {"degree_step_ms.dyn": {"value": step},
         **{f"degree_step_{p}_ms.dyn": {"value": v}
            for p, v in scopes.items()}}
    block = trace_phases.phases_block(m)
    assert block["step_ms"] == step
    assert block["sum_ms"] == pytest.approx(sum(scopes.values()))
    assert 0.6 < block["share"] < 0.8 and block["scatter_ms"] > 3.5
