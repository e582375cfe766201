"""The by-name contract checks of ``_contract.py`` on trees that grew.

A later PR adds a cell as new files and new entries at the end of their
lists. Two such trees are built from ``_tiny.make_tiny_root`` (the
repository's ``BENCHMARK.json`` plus a configuration, a one-chip cell
in ``edges_per_s``'s cells and per-layer entries with their reader
files): the first as it is, with a recording of the new cell's program
written beside it; the second with a second four-chip cell too, and as
many one-chip cells as a quarter needs to allow it. Every check holds
on both. Each fault a check is there to catch, planted in a copy of one
of them, fails it."""

from __future__ import annotations

import json
import os
import re
import shutil
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
HERE = os.path.dirname(os.path.abspath(__file__))
for p in (REPO, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)

import _contract  # noqa: E402
from _tiny import make_tiny_root  # noqa: E402

from benchmarks.lib import spec  # noqa: E402

TINY = "tiny.tiny-mix"
PROGRAM = "jit_tiny_step"
#: a recording of the new cell's program: two executions of 4 ms
RECORDING = {
    "planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [
            [f"{PROGRAM}(123)", 1e6, 4e6], [f"{PROGRAM}(123)", 6e6, 4e6]]},
        {"name": "XLA Ops", "events": [
            ["%fusion = s32[4096] fusion()", 1e6, 4e6],
            ["%fusion = s32[4096] fusion()", 6e6, 4e6]]}]}],
    "lo": 0.0, "hi": 2e7,
}


def _load(root):
    return spec.load_benchmark(root)


def _save(root, bench):
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)


def _put(root, rel, doc):
    with open(os.path.join(root, spec.BENCH_DIR, rel), "w") as f:
        json.dump(doc, f)


def _metric(bench, name):
    return next(m for m in bench["per_layer"] if m["name"] == name)


def _add_cell(root, bench, traffic, chips):
    """A cell of the tiny configuration under a traffic mix of its own,
    in ``edges_per_s``'s cells and the tiny trace entry's."""
    with open(os.path.join(root, spec.BENCH_DIR, "traffic",
                           "tiny-mix.json")) as f:
        _put(root, f"traffic/{traffic}.json", json.load(f))
    name = f"tiny.{traffic}"
    bench["workloads"].append({"name": name, "config": "tiny",
                               "traffic": traffic, "chips": chips,
                               "why": "test"})
    next(m for m in bench["end_to_end"]
         if m["name"] == "edges_per_s")["workloads"].append(name)
    _metric(bench, "tiny_step_ms")["workloads"].append(name)


def _grow(tmp, *, second_four_chip):
    root = make_tiny_root(tmp)
    bench = _load(root)
    # the new cell's own program, read from the trace
    _put(root, "layer_metrics/tiny_step_ms.json",
         {"reader": {"kind": "program_mean_ms", "program": PROGRAM}})
    bench["per_layer"].append({
        "name": "tiny_step_ms", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "forest step",
        "moves": "edges_per_s", "workloads": [TINY]})
    if second_four_chip:
        _add_cell(root, bench, "tiny-mix-v4", 4)
        k = 0
        while sum(w["chips"] == 4 for w in bench["workloads"]) > (
                len(bench["workloads"]) // 4):
            k += 1
            _add_cell(root, bench, f"tiny-mix-{k}", 1)
    _save(root, bench)
    fixtures = os.path.join(tmp, "fixtures")
    os.makedirs(fixtures)
    with open(os.path.join(fixtures, "trace_tiny_v5e.json"), "w") as f:
        json.dump(RECORDING, f)
    return root


TREES = {"one_cell_added": False, "a_second_four_chip_cell": True}


@pytest.fixture(scope="module")
def grown(tmp_path_factory):
    """tree name -> its root; its recordings beside it, under ``fixtures``."""
    return {tree: _grow(str(tmp_path_factory.mktemp(tree)),
                        second_four_chip=four)
            for tree, four in TREES.items()}


def _recordings(root):
    return _contract.index_recordings(
        _contract.FIXTURES, os.path.join(os.path.dirname(root), "fixtures"))


def _trace_readers_resolve(root):
    recordings = _recordings(root)
    for w in _load(root)["workloads"]:
        _contract.trace_readers_resolve(root, w["name"], recordings)


CHECKS = {
    "cells_stand": _contract.cells_stand,
    "host_span_entries_stand": _contract.host_span_entries_stand,
    "four_chip_entries_stand":
        lambda root: _contract.entries_stand(root, _contract.V4_CELL),
    "degree_entries_stand":
        lambda root: _contract.entries_stand(root, _contract.DD_CELL),
    "sized_entries_stand":
        lambda root: _contract.entries_stand(root, _contract.SIZE_CELL),
    "four_chip_cells_fit": _contract.four_chip_cells_fit,
    "trace_readers_resolve": _trace_readers_resolve,
}


def test_the_trees_grew_as_a_later_pr_grows_the_benchmark(grown):
    one, two = (_load(grown[t]) for t in TREES)
    repo = _load(REPO)
    for bench in (one, two):
        for key in ("configs", "workloads", "end_to_end", "per_layer"):
            names = [m["name"] for m in bench[key]]
            assert names[:len(repo[key])] == [m["name"] for m in repo[key]]
        assert bench["workloads"][len(repo["workloads"])]["name"] == TINY
        eps = next(m for m in bench["end_to_end"]
                   if m["name"] == "edges_per_s")
        assert TINY in eps["workloads"]
    assert one["configs"][len(repo["configs"])]["name"] == "tiny"

    def four(bench):
        return [w["name"] for w in bench["workloads"] if w["chips"] == 4]

    assert four(one) == four(repo)
    assert four(two) == four(repo) + ["tiny.tiny-mix-v4"]
    assert len(four(two)) <= len(two["workloads"]) // 4
    # the new cell reads its program from the recording written for it
    assert _recordings(grown["one_cell_added"])[PROGRAM][0] == (
        "trace_tiny_v5e.json")


@pytest.mark.parametrize("check", list(CHECKS))
@pytest.mark.parametrize("tree", list(TREES))
def test_every_check_holds_on_a_tree_with_entries_added(grown, tree, check):
    CHECKS[check](grown[tree])


# ---- the faults each check is there to catch ------------------------- #
def _drop_metric(bench, root):
    bench["per_layer"].remove(_metric(bench, "answer_ms.dyn"))


def _drop_end_to_end(bench, root):
    m = next(m for m in bench["end_to_end"]
             if m["name"] == "answer_age_p95_ms")
    m["workloads"].remove(_contract.PACED)


def _reorder_four_chip_metrics(bench, root):
    names = [m["name"] for m in bench["per_layer"]]
    i, j = names.index("place_ms.v4"), names.index("ingest_host_ms.v4")
    pl = bench["per_layer"]
    pl[i], pl[j] = pl[j], pl[i]


def _four_chip_past_the_quarter(bench, root):
    _add_cell(root, bench, "tiny-mix-v4b", 4)


def _program_in_no_recording(bench, root):
    _put(root, "layer_metrics/tiny_step_ms.json", {"reader": {
        "kind": "program_mean_ms", "program": "jit_tiny_fold"}})


def _reader_changed(bench, root):
    _put(root, "layer_metrics/size_lookup_ms.size.json", {"reader": {
        "kind": "span_mean_ms", "span": "serving.answer"}})


def _new_cell_first(bench, root):
    bench["workloads"].insert(0, bench["workloads"].pop(
        [w["name"] for w in bench["workloads"]].index(TINY)))


def _host_span_on_another_cell(bench, root):
    _metric(bench, "queue_wait_ms")["workloads"].append(_contract.DD_CELL)


#: fault -> (the tree it is planted in, what it does, the checks it
#: fails and how they say it)
FAULTS = {
    "a_cell_lost_a_per_layer_metric": (
        "one_cell_added", _drop_metric,
        {"cells_stand": "lost", "degree_entries_stand": "lost"}),
    "a_cell_lost_an_end_to_end_metric": (
        "one_cell_added", _drop_end_to_end,
        {"cells_stand": "answer_age_p95_ms lost"}),
    "the_four_chip_metrics_reordered": (
        "one_cell_added", _reorder_four_chip_metrics,
        {"cells_stand": "moved", "four_chip_entries_stand": "moved"}),
    "a_four_chip_cell_past_the_quarter": (
        "a_second_four_chip_cell", _four_chip_past_the_quarter,
        {"four_chip_cells_fit": "a quarter allows"}),
    "a_program_in_no_recording": (
        "one_cell_added", _program_in_no_recording,
        {"trace_readers_resolve": "'jit_tiny_fold', which no recording"}),
    "a_reader_file_changed": (
        "one_cell_added", _reader_changed,
        {"sized_entries_stand": "size_lookup_ms.size"}),
    "a_new_cell_put_first": (
        "one_cell_added", _new_cell_first,
        {"cells_stand": "no longer first"}),
    "a_host_span_entry_on_another_cell": (
        "one_cell_added", _host_span_on_another_cell,
        {"cells_stand": "queue_wait_ms",
         "host_span_entries_stand": "queue_wait_ms"}),
}


@pytest.mark.parametrize("fault,check", [
    (fault, check) for fault, (_t, _f, checks) in FAULTS.items()
    for check in checks])
def test_a_fault_fails_the_check_written_to_catch_it(
        grown, tmp_path, fault, check):
    tree, plant, says = FAULTS[fault]
    src = grown[tree]
    root = str(tmp_path / "root")
    shutil.copytree(src, root)
    shutil.copytree(os.path.join(os.path.dirname(src), "fixtures"),
                    str(tmp_path / "fixtures"))
    bench = _load(root)
    plant(bench, root)
    _save(root, bench)
    assert spec.check_names_resolve(root) == []    # the fault is no other
    with pytest.raises(AssertionError, match=re.escape(says[check])):
        CHECKS[check](root)
