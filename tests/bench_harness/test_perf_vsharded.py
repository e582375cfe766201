"""The four-chip cell's algorithm module (``cc_vsharded``, ISSUE 28) end
to end on the CPU at a tiny scale, on four of the suite's virtual
devices: a run comes out correct with its table laid out as the
configuration says, a planted fault (two chips' blocks swapped before
the final comparison) and the control come out NOT correct, and the new
entries of ``BENCHMARK.json`` resolve. Times here are of the CPU and are
never a device number."""

from __future__ import annotations

import dataclasses
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

from _tiny import make_tiny_root  # noqa: E402

from benchmarks.algorithms import cc_vsharded  # noqa: E402
from benchmarks.lib import bytes_model, cellrun, lastline, spec  # noqa: E402

CELL = "cc-g500-s30-v4.ingest-saturated"
LAYOUT = {"chips": 4, "axis": "vertices",
          "partition": "contiguous blocks of 2^10 rows"}


def _run(tmp_path, *, control=None, seed=17):
    root = make_tiny_root(str(tmp_path), algorithm="cc_vsharded")
    cell = spec.load_cell("tiny.tiny-mix", root)
    cell.config["layout"] = LAYOUT
    cell.traffic["ingest"] = {"mode": "closed", "outstanding": 2}
    cell.traffic["stream_edges_per_s"] = 256 * 6000
    return cell, cellrun.run_cell(
        cell, seed, 1.2, False, t_process=time.perf_counter(),
        backend=(cellrun.describe_device(), 0.0), require_tpu=False,
        control=control, work_root=str(tmp_path))


def _failing(doc) -> set:
    return {n for n, c in doc["compared"].items() if c["value"] > c["limit"]}


@pytest.fixture
def published(monkeypatch):
    """The tables the served path published, as the harness's final
    ``server.snapshot()`` saw them."""
    from gelly_streaming_tpu.serving import StreamServer

    seen = []
    real = StreamServer.snapshot

    def snapshot(self, *a, **kw):
        snap = real(self, *a, **kw)
        if snap is not None:
            seen.append(snap.payload["labels"])
        return snap

    monkeypatch.setattr(StreamServer, "snapshot", snapshot)
    return seen


@pytest.mark.parametrize("seed", [17, 2**31 + 5])
def test_a_tiny_run_on_four_devices_is_correct_and_sharded(
        tmp_path, published, seed):
    cell, doc = _run(tmp_path, seed=seed)
    assert doc["correct"] is True, doc["compared"]
    assert doc["attempted"] > 0 and doc["failed"] == 0
    assert all(c["value"] == 0 for c in doc["compared"].values())
    assert doc["windows"]["ready_in_window"] > 5
    assert doc["windows"]["closing"] == 2
    doc["device"]["memory_peak_bytes"] = 1   # the CPU reports none
    assert lastline.validate(
        json.dumps(doc), required=cell.units("end_to_end"), trace=False,
        chips=doc["device"]["count"]) == []
    # the table the comparison read lay on four devices, a block on each
    table = published[-1]
    assert cc_vsharded.layout_problem(table, cell.config) is None
    assert {s.data.shape for s in table.addressable_shards} == {(1024,)}


def test_a_replicated_or_gathered_table_is_a_reason_to_fail(tmp_path):
    import jax

    from gelly_streaming_tpu.parallel.mesh import (
        make_mesh,
        replicated,
        vertex_sharding,
    )

    config = {"id_space": 4096, "layout": LAYOUT}
    mesh = make_mesh(n_edge_shards=1, n_vertex_shards=4)
    rows = np.arange(4096, dtype=np.int32)
    assert cc_vsharded.layout_problem(
        jax.device_put(rows, vertex_sharding(mesh)), config) is None
    for table, says in (
            (jax.device_put(rows, replicated(mesh)), "replicated or gathered"),
            (jax.device_put(rows, jax.devices()[0]), "1 devices"),
            (rows, "on the host"),
            (jax.device_put(rows, vertex_sharding(make_mesh(
                n_edge_shards=1, n_vertex_shards=2))), "2 devices")):
        assert says in cc_vsharded.layout_problem(table, config)


def _swap_two_blocks(table):
    """The same sharded array with the rows of chips 1 and 2 swapped."""
    import jax

    shards = sorted(table.addressable_shards,
                    key=lambda s: s.index[0].start or 0)
    order = [0, 2, 1, 3]
    return jax.make_array_from_single_device_arrays(
        table.shape, table.sharding,
        [jax.device_put(shards[j].data, shards[i].device)
         for i, j in enumerate(order)])


def test_two_chips_blocks_swapped_before_the_comparison_is_not_correct(
        tmp_path, monkeypatch):
    """The planted fault: every answer was right, and the final table,
    its blocks laid end to end in another order than their rows, is not
    the reference's."""
    from gelly_streaming_tpu.serving import StreamServer

    real = StreamServer.snapshot

    def snapshot(self, *a, **kw):
        snap = real(self, *a, **kw)
        return dataclasses.replace(snap, payload={
            **snap.payload,
            "labels": _swap_two_blocks(snap.payload["labels"])})

    monkeypatch.setattr(StreamServer, "snapshot", snapshot)
    _cell, doc = _run(tmp_path)
    assert doc["correct"] is False
    assert _failing(doc) == {"table_mismatches"}, doc["compared"]


def test_the_control_comes_out_not_correct(tmp_path):
    _cell, doc = _run(tmp_path, control="stale_prefix")
    assert doc["correct"] is False
    assert {"table_mismatches", "answer_mismatches"} <= _failing(doc)


def test_a_program_without_the_axis_fails_before_anything_is_allocated(
        monkeypatch):
    """The parent commit on the new cell: ``make_stream`` fails at its
    import of the axis, and ``run.py`` turns that into exit code 2."""
    from gelly_streaming_tpu.parallel import mesh

    monkeypatch.delattr(mesh, "VERTEX_AXIS")
    with pytest.raises(ImportError):
        cc_vsharded.make_stream({"layout": LAYOUT}, None)


# ---- the new entries of BENCHMARK.json ------------------------------- #
def test_the_new_entries_resolve_and_keep_to_the_contract():
    assert spec.check_names_resolve() == []
    bench = spec.load_benchmark()
    cell = spec.load_cell(CELL)
    assert cell.chips == 4 and cell.traffic_name == "ingest-saturated"
    assert set(cell.end_to_end) == {"edges_per_s", "query_p95_ms", "setup_s"}
    assert sorted(cell.per_layer) == [
        "answer_ms.v4", "compiles_in_window.v4", "forest_step_ms.v4",
        "forest_step_roofline.v4", "ingest_host_ms.v4", "place_ms.v4",
        "query_kernel_ms.v4"]
    for name, m in cell.per_layer.items():
        assert m["workloads"] == [CELL]
        assert cell.readers[name]["reader"]["kind"] in cellrun.READERS
    # one four-chip cell in four: the most a benchmark of this size may have
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert [w["name"] for w in four] == [CELL]
    assert len(four) <= max(1, len(bench["workloads"]) // 4)
    assert all(len(w["why"]) <= 200 for w in bench["workloads"])
    # new entries stand at the end of their lists
    assert bench["workloads"][-1]["name"] == CELL
    assert bench["configs"][-1]["name"] == "cc-g500-s30-v4"
    assert [m["name"] for m in bench["per_layer"]][-7:] == [
        "forest_step_ms.v4", "forest_step_roofline.v4", "place_ms.v4",
        "ingest_host_ms.v4", "answer_ms.v4", "query_kernel_ms.v4",
        "compiles_in_window.v4"]


def test_the_configuration_states_its_layout_and_its_cut():
    cfg = spec.load_cell(CELL).config
    assert cfg["algorithm"] == "cc_vsharded" and cfg["scale"] == 30
    assert cfg["id_space"] == 1 << 30 and cfg["window_edges"] == 1 << 16
    assert cfg["layout"]["chips"] == 4 and cfg["layout"]["axis"] == "vertices"
    assert cfg["reduced"] == ["scale"] and "scale" in cfg["reduced_why"]
    s28 = spec.load_cell("cc-g500-s28.ingest-saturated").config
    assert cfg["guarantees"] == s28["guarantees"]
    g = dict(cfg["graph500"])
    assert g.pop("graph_seed") != s28["graph500"]["graph_seed"]
    assert g == {k: v for k, v in s28["graph500"].items()
                 if k != "graph_seed"}
    # a graph that no one chip holds: the table is 4 GiB, a block 1 GiB
    assert 4 * cc_vsharded.table_rows(cfg) == 4 << 30


def test_the_byte_model_reads_one_chips_share():
    cfg = spec.load_cell(CELL).config
    src = np.asarray([1, 2, 3, 3], np.int32)
    dst = np.asarray([2, 3, 4, 9], np.int32)
    shape = cc_vsharded.fold_shape(cfg, src, dst)
    assert shape == {"rows": 1 << 28, "window_edges": 4, "touched": 5}
    assert bytes_model.forest_step(**shape) == 8 * (1 << 28) + 8 * 4 + 8 * 5
