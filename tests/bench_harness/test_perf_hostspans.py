"""The host fold's spans and the serving waits as per-layer metrics
(ISSUE 38): five entries over spans every program since PR 26 emits,
added as data files alone. They resolve, they stand at the end of
``per_layer``, they use reader kinds the harness has, their cells
report what they move, and a tiny traced run on the CPU gives each of
them a number (a time of the CPU's: never a device number)."""

from __future__ import annotations

import json
import math
import os
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
HERE = os.path.dirname(os.path.abspath(__file__))
for p in (REPO, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)

from _tiny import make_tiny_root  # noqa: E402

from benchmarks.lib import cellrun, spec, trace_reduce  # noqa: E402

SAT = ["cc-g500-s28.ingest-saturated", "bip-g500-s27.ingest-saturated-poll"]
CC = ["cc-g500-s28.ingest-saturated", "cc-g500-s28.paced-query-heavy"]
#: name -> (span, layer, moves, cells), in the order they were entered
ENTERED = {
    "fold_host_ms.sat": ("forest.window", "window host step",
                         "edges_per_s", SAT),
    "fold_prep_ms.sat": ("forest.prep", "window host step",
                         "edges_per_s", SAT),
    "fold_dispatch_ms.sat": ("forest.dispatch", "window host step",
                             "edges_per_s", SAT),
    "queue_wait_ms": ("serving.queue_wait", "serving", "query_p95_ms", CC),
    "answer_wait_ms": ("serving.device_wait", "serving", "query_p95_ms", CC),
}
FIXTURE = os.path.join(HERE, "fixtures", "trace_cc_saturated_v5e.json")


def test_the_new_entries_resolve_and_stand_at_the_end():
    assert spec.check_names_resolve() == []
    bench = spec.load_benchmark()
    names = [m["name"] for m in bench["per_layer"]]
    # found by NAME: together, in order, after everything PR 36 entered
    first = names.index("fold_host_ms.sat")
    assert names[first:first + len(ENTERED)] == list(ENTERED)
    assert first > names.index("compiles_in_window.size")
    layers = {m["layer"] for m in bench["per_layer"][:first]}
    for m in bench["per_layer"][first:first + len(ENTERED)]:
        span, layer, moves, cells = ENTERED[m["name"]]
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert (m["unit"], m["better"], m["source"]) == (
            "ms", "lower", "program_span")
        assert (m["layer"], m["moves"], m["workloads"]) == (
            layer, moves, cells)
        assert layer in layers          # a layer the benchmark names
        with open(os.path.join(REPO, "benchmarks", "layer_metrics",
                               m["name"] + ".json")) as f:
            assert json.load(f) == {
                "reader": {"kind": "span_mean_ms", "span": span}}


@pytest.mark.parametrize("name", list(ENTERED))
def test_an_entrys_cells_report_what_it_moves(name):
    span, _layer, moves, cells = ENTERED[name]
    for cell_name in cells:
        cell = spec.load_cell(cell_name)
        assert moves in cell.end_to_end
        assert cell.per_layer[name]["moves"] == moves
        reader = cell.readers[name]["reader"]
        assert reader["kind"] in cellrun.READERS    # no new reader kind
        assert reader == {"kind": "span_mean_ms", "span": span}


def test_only_entries_were_added_and_no_cell_lost_a_metric():
    """Nothing but the end of ``per_layer`` grew: no configuration, no
    cell, no traffic file, no end-to-end entry; the cells whose SET of
    per-layer metrics other tests of this directory hold (four chips,
    the degree cell, the sized cell) have what they had."""
    bench = spec.load_benchmark()
    assert len(bench["configs"]) == 5 and len(bench["workloads"]) == 6
    assert [m["name"] for m in bench["end_to_end"]] == [
        "edges_per_s", "window_p95_ms", "query_p95_ms", "answer_age_p95_ms",
        "setup_s"]
    assert bench["run_seconds"] == 45
    grown = {c for _s, _l, _m, cells in ENTERED.values() for c in cells}
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        mine = set(cell.per_layer) & set(ENTERED)
        assert bool(mine) == (w["name"] in grown)
    # by name, so that a later PR's entries for these cells fit in
    assert {"pack_ms.sat", "ingest_host_ms.sat", "forest_step_ms.sat",
            "answer_ms", "query_kernel_ms"} <= set(
        spec.load_cell(CC[0]).per_layer)
    assert {"forest_step_ms.paced", "answer_ms", "query_kernel_ms"} <= set(
        spec.load_cell(CC[1]).per_layer)
    assert {"pack_ms.sat", "ingest_host_ms.sat", "verdict_query_ms"} <= set(
        spec.load_cell(SAT[1]).per_layer)


def test_the_span_reader_means_a_span_and_leaves_out_what_is_not_there():
    read = cellrun.READERS["span_mean_ms"]
    ctx = {"spans": [
        {"name": "forest.window", "sid": 1, "dur_s": 0.004},
        {"name": "forest.prep", "sid": 2, "parent": 1, "dur_s": 0.002},
        {"name": "forest.window", "sid": 3, "dur_s": 0.002},
    ], "child_s": {1: 0.002}}
    assert read({"span": "forest.window"}, ctx) == pytest.approx(3.0)
    assert read({"span": "forest.prep"}, ctx) == pytest.approx(2.0)
    # a program that lacks the span (an older parent): no number, no error
    assert read({"span": "serving.device_wait"}, ctx) is None


def test_a_tiny_traced_run_gives_every_entry_a_number(tmp_path, monkeypatch):
    """The five entries read through the harness's own traced pass, the
    tiny cell appended to their cells in the tiny tree's copy of
    ``BENCHMARK.json``. The CPU has no device plane, so the profiler is
    left out and the recording of cell 1 on a v5e stands in for the
    trace: the spans are the program's own, from this run."""
    root = make_tiny_root(str(tmp_path))
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    for m in bench["per_layer"]:
        if m["name"] in ENTERED:
            m["workloads"].append("tiny.tiny-mix")
    with open(path, "w") as f:
        json.dump(bench, f)
    assert spec.check_names_resolve(root) == []
    cell = spec.load_cell("tiny.tiny-mix", root)
    assert set(cell.per_layer) == set(ENTERED) | {"tiny_answer_ms"}
    # the warm-up folds on while the device path's query kernels
    # compile: a stream long enough to outlast that
    cell.traffic["stream_edges_per_s"] = 256 * 4000

    def slice_without_a_profiler(t0, t_end, work_root):
        a = time.perf_counter()
        time.sleep(max(0.0, min(0.4, t_end - a - 0.2)))
        return {"dir": str(tmp_path / "no-trace"), "lo": a,
                "hi": time.perf_counter()}

    with open(FIXTURE) as f:
        recording = json.load(f)
    # the chip's path through the query engine (the CPU's default is the
    # host path, which waits for no device): the test steers, the
    # program has no option for it
    from gelly_streaming_tpu.serving import query

    real_init = query.QueryEngine.__init__
    monkeypatch.setattr(
        query.QueryEngine, "__init__",
        lambda self, prefer_host=False: real_init(self, prefer_host))
    monkeypatch.setattr(cellrun, "_trace_slice", slice_without_a_profiler)
    monkeypatch.setattr(trace_reduce, "find_xplane", lambda d: FIXTURE)
    monkeypatch.setattr(trace_reduce, "load",
                        lambda path, keep_line=None: recording)
    doc = cellrun.run_cell(
        cell, 38, 1.2, True, t_process=time.perf_counter(),
        backend=(cellrun.describe_device(), 0.0), require_tpu=False,
        work_root=str(tmp_path))
    assert doc["correct"] is True, doc["compared"]
    assert doc["failed"] == 0
    for name in ENTERED:
        value = doc["metrics"][name]["value"]
        assert math.isfinite(value) and value > 0, name
        assert doc["metrics"][name]["unit"] == "ms"
    m = {k: v["value"] for k, v in doc["metrics"].items()}
    # the fold's two children lie inside it, the sweep's wait inside
    # its answer
    assert m["fold_prep_ms.sat"] + m["fold_dispatch_ms.sat"] <= (
        m["fold_host_ms.sat"])
    assert m["answer_wait_ms"] <= m["tiny_answer_ms"]
    # the breakdown still names the recording's spans, never the root
    assert "serving.window" not in {n for n, _s in
                                    doc["breakdown"]["idle_gaps"]}
