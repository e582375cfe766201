"""The harness end to end on the CPU at a tiny scale: names resolve, a
configuration, a traffic mix, a cell and a per-layer metric are added
as new files only, a run comes out correct, and the control and each
planted fault come out NOT correct. Times printed here are of the CPU
and are never a device number."""

from __future__ import annotations

import json
import os
import subprocess
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

from benchmarks.lib import cellrun, lastline, spec  # noqa: E402

NAME, UNIT = lastline.NAME_RE, lastline.UNIT_RE


def _bench():
    return spec.load_benchmark()


# ---- BENCHMARK.json against the contract ----------------------------- #
def test_every_name_in_benchmark_json_resolves():
    assert spec.check_names_resolve() == []


def test_benchmark_json_keeps_to_the_contract():
    b = _bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert isinstance(b["run_seconds"], int) and 1 <= b["run_seconds"] <= 51
    assert 1 <= len(b["paths"]) <= 16
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 64 << 10
    for word in b["command"]:
        assert not word.startswith("/") and ".." not in word
        if "/" in word:
            assert any(word.startswith(p + "/") for p in b["paths"])
    names = []
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert any(c["file"].startswith(p + "/") for p in b["paths"])
        assert len(c["reduced"]) <= 16 and len(c["source"]) <= 200
        names.append(c["name"])
    used = {w["config"] for w in b["workloads"]}
    assert used == set(names)
    pairs = [(w["config"], w["traffic"]) for w in b["workloads"]]
    assert len(set(pairs)) == len(pairs)
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        names += [w["name"], w["traffic"]]
    four = sum(w["chips"] == 4 for w in b["workloads"])
    assert four <= max(1, len(b["workloads"]) // 4)
    assert any(m["name"] == "setup_s" and m["bound"] <= 0.1
               for m in b["end_to_end"])
    for m in b["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    layers = set()
    for m in b["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        layers.add(m["layer"])
    for m in b["end_to_end"] + b["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        names.append(m["name"])
    assert all(NAME.match(n) for n in names), names
    metric_names = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert len(set(metric_names)) == len(metric_names)


def test_configuration_files_state_source_cuts_and_guarantees():
    for c in _bench()["configs"]:
        with open(os.path.join(REPO, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
        assert set(cfg["reduced"]) <= set(cfg["reduced_why"])
        assert cfg["guarantees"]["max_staleness_windows"] >= 0
        for kind in ("algorithm", "generator"):
            assert os.path.exists(os.path.join(
                REPO, "benchmarks", kind + "s", cfg[kind] + ".py"))
        if cfg["generator"] == "graph500":
            g = cfg["graph500"]
            # the source's shapes are never cut: Graph500's A, B, C, factor
            assert (g["a"], g["b"], g["c"], g["edge_factor"]) == (
                0.57, 0.19, 0.19, 16)
            assert cfg["id_space"] == 1 << cfg["scale"]
        algo = spec.load_cell(next(
            w["name"] for w in _bench()["workloads"]
            if w["config"] == c["name"])).algorithm()
        if hasattr(algo, "table_rows"):
            # the carried int32 table is a quarter of the chip or more
            assert 4 * algo.table_rows(cfg) >= 1 << 30


# ---- adding one of each as new files only ---------------------------- #
def test_a_config_a_mix_a_cell_and_a_metric_are_added_as_new_files(tmp_path):
    root = make_tiny_root(str(tmp_path))
    # nothing that existed was edited
    for rel in ("benchmarks/run.py", "benchmarks/lib/spec.py",
                "benchmarks/configs/cc-g500-s28.json",
                "benchmarks/traffic/ingest-saturated.json"):
        with open(os.path.join(REPO, rel), "rb") as a, \
                open(os.path.join(root, rel), "rb") as b:
            assert a.read() == b.read()
    # (an algorithm and a generator come as new files too: see
    # test_a_configuration_of_another_kind_is_added_as_new_files_only)
    assert spec.check_names_resolve(root) == []
    cell = spec.load_cell("tiny.tiny-mix", root)
    assert cell.config["window_edges"] == 256
    assert cell.traffic["queries"]["batch"] == 16
    assert "tiny_answer_ms" in cell.per_layer
    assert cell.readers["tiny_answer_ms"]["reader"]["span"] == "serving.answer"
    assert "edges_per_s" in cell.end_to_end and "setup_s" in cell.end_to_end
    # the cells that were there are untouched by the addition
    old = spec.load_cell("cc-g500-s28.ingest-saturated", root)
    assert "tiny_answer_ms" not in old.per_layer
    with pytest.raises(KeyError):
        spec.load_cell("no-such.cell", root)


# ---- the harness end to end ------------------------------------------ #
def _run(tmp_path, *, algorithm="cc", control=None, seed=11, closed=False):
    root = make_tiny_root(str(tmp_path), algorithm=algorithm)
    cell = spec.load_cell("tiny.tiny-mix", root)
    if closed:
        cell.traffic["ingest"] = {"mode": "closed", "outstanding": 2}
        cell.traffic["stream_edges_per_s"] = 256 * 6000
    return cell, cellrun.run_cell(
        cell, seed, 1.2, False, t_process=time.perf_counter(),
        backend=(cellrun.describe_device(), 0.0), require_tpu=False, control=control, work_root=str(tmp_path))


@pytest.mark.parametrize("algorithm,closed,seed", [
    ("cc", False, 2**31 + 7), ("cc", True, 5), ("bipartite", False, 9)])
def test_a_run_is_correct_and_its_line_passes_the_validator(
        tmp_path, algorithm, closed, seed):
    cell, doc = _run(tmp_path, algorithm=algorithm, closed=closed, seed=seed)
    assert doc["correct"] is True, doc["compared"]
    assert doc["attempted"] > 0 and doc["failed"] == 0
    assert list(doc)[-1] == "compared"
    assert all(c["value"] <= c["limit"] for c in doc["compared"].values())
    doc["device"]["memory_peak_bytes"] = 1   # the CPU reports none
    assert lastline.validate(
        json.dumps(doc), required=cell.units("end_to_end"), trace=False,
        chips=doc["device"]["count"]) == []
    assert doc["windows"]["max_outstanding"] <= 2 or not closed
    assert doc["windows"]["ready_in_window"] > 5
    # the seed's own closing windows were folded and compared too
    assert doc["windows"]["closing"] == 2
    assert doc["compared"]["windows_unpublished"]["value"] == 0


def test_an_algorithm_module_can_give_the_stream_a_mesh(tmp_path, monkeypatch):
    """The hook a four-chip cell needs (PERF.md, Open questions, row 1):
    an algorithm module's ``make_stream`` puts the served path under a
    mesh, here of four of the suite's virtual CPU devices."""
    import types

    from benchmarks.algorithms import cc

    def make_stream(config, source):
        from gelly_streaming_tpu.core.stream import StreamContext
        from gelly_streaming_tpu.parallel.mesh import make_mesh

        return cellrun.default_stream(config, source, StreamContext(
            mesh=make_mesh(n_edge_shards=config["mesh_edge_shards"])))

    mod = types.ModuleType("benchmarks.algorithms.cc_mesh_for_test")
    mod.__dict__.update({k: v for k, v in vars(cc).items()
                         if not k.startswith("__")})
    mod.make_stream = make_stream
    monkeypatch.setitem(sys.modules, mod.__name__, mod)
    root = make_tiny_root(str(tmp_path), algorithm="cc_mesh_for_test")
    cell = spec.load_cell("tiny.tiny-mix", root)
    cell.config["mesh_edge_shards"] = 4
    doc = cellrun.run_cell(cell, 13, 1.2, False,
                           t_process=time.perf_counter(),
                           backend=(cellrun.describe_device(), 0.0),
                           require_tpu=False,
                           work_root=str(tmp_path))
    assert doc["correct"] is True, doc["compared"]
    assert doc["attempted"] > 0 and doc["windows"]["ready_in_window"] > 5


@pytest.mark.parametrize("algorithm", ["cc", "bipartite"])
def test_the_control_comes_out_not_correct(tmp_path, algorithm):
    """The reference, answering and publishing one window staler than
    its stamp, in the program's place: its table goes through the same
    comparison as the program's, which has to fail it."""
    _cell, doc = _run(tmp_path, algorithm=algorithm, control="stale_prefix")
    assert doc["correct"] is False
    failing = {n for n, c in doc["compared"].items()
               if c["value"] > c["limit"]}
    assert "table_mismatches" in failing
    assert "answer_mismatches" in failing or algorithm == "bipartite"


def _run_from_its_own_tree(root, seed, *control):
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "_run_tiny.py"), root, str(seed),
         *control], cwd=root, capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO))
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.splitlines()[-1])


def test_a_configuration_of_another_kind_is_added_as_new_files_only(tmp_path):
    """Served degree counts over a uniform record stream: an algorithm
    module with a reference that is no union-find and a table that is no
    forest, a generator module that is no Graph500, a stream that is no
    ``SimpleEdgeStream``. All come as new files; the tree's own
    ``benchmarks`` package, run from its root, finds them by name,
    proves a run correct and fails the control through the algorithm's
    own numbers."""
    root = make_tiny_root(str(tmp_path), algorithm="tinydeg")
    for folder, _dirs, files in os.walk(os.path.join(REPO, "benchmarks")):
        if "__pycache__" in folder:
            continue
        for name in files:
            path = os.path.join(folder, name)
            with open(path, "rb") as a, open(os.path.join(
                    root, os.path.relpath(path, REPO)), "rb") as b:
                assert a.read() == b.read(), path
    doc = _run_from_its_own_tree(root, 2**31 + 5)
    assert doc["correct"] is True, doc["compared"]
    assert doc["attempted"] > 0 and doc["failed"] == 0
    assert doc["compared"]["degree_mismatches"] == {"value": 0, "limit": 0}
    assert "table_mismatches" not in doc["compared"]
    doc = _run_from_its_own_tree(root, 6, "stale_prefix")
    assert doc["correct"] is False
    assert doc["compared"]["degree_mismatches"]["value"] > 0
    assert doc["compared"]["answer_mismatches"]["value"] > 0


def _plant_state_unchanged(monkeypatch):
    import gelly_streaming_tpu.library.connected_components as cc

    def frozen(canon, src_h, dst_h, vcap, prep, **kw):
        _new, tids = real(canon, src_h, dst_h, vcap, prep, **kw)
        return canon, tids
    real = cc.forest_window
    monkeypatch.setattr(cc, "forest_window", frozen)


def _plant_half_the_window_left_out(monkeypatch):
    import gelly_streaming_tpu.library.connected_components as cc

    def half(canon, src_h, dst_h, vcap, prep, **kw):
        n = len(src_h) // 2
        return real(canon, src_h[:n], dst_h[:n], vcap, prep, **kw)
    real = cc.forest_window
    monkeypatch.setattr(cc, "forest_window", half)


def _plant_the_closing_windows_left_out(monkeypatch):
    """Sound all through the measured window; the windows of the seed's
    own graph that follow it leave the state unchanged."""
    import gelly_streaming_tpu.library.connected_components as cc
    from benchmarks.generators import graph500

    tiny = {"scale": 12, "window_edges": 256, "graph500": {
        "a": 0.57, "b": 0.19, "c": 0.19, "seeded_closing_windows": 2}}
    own = graph500.closing_edges(tiny, 11)[0].reshape(2, 256)

    def skipping(canon, src_h, dst_h, vcap, prep, **kw):
        new, tids = real(canon, src_h, dst_h, vcap, prep, **kw)
        if any(np.array_equal(src_h[:256], w) for w in own):
            return canon, tids
        return new, tids
    real = cc.forest_window
    monkeypatch.setattr(cc, "forest_window", skipping)


def _plant_an_answer_altered(monkeypatch):
    from gelly_streaming_tpu.serving.query import QueryEngine

    def flipped(self, snap, us, vs):
        out = np.array(real(self, snap, us, vs))
        out[0] = ~out[0]
        return out
    real = QueryEngine.connected
    monkeypatch.setattr(QueryEngine, "connected", flipped)


def _plant_a_stale_snapshot(monkeypatch):
    """Answers computed from an older snapshot than their stamp."""
    from gelly_streaming_tpu.serving.query import QueryEngine

    history = []

    def stale(self, snap, us, vs):
        history.append(snap)
        return real(self, history[max(0, len(history) - 6)], us, vs)
    real = QueryEngine.connected
    monkeypatch.setattr(QueryEngine, "connected", stale)


def _plant_cover_state_unchanged(monkeypatch):
    import gelly_streaming_tpu.library.bipartiteness as bp

    def frozen(canon, failed, src_h, dst_h, vcap, prep, **kw):
        _new, new_failed, tids = real(canon, failed, src_h, dst_h, vcap,
                                      prep, **kw)
        return canon, new_failed, tids
    real = bp.cover_forest_window
    monkeypatch.setattr(bp, "cover_forest_window", frozen)


def _plant_half_the_cover_window_left_out(monkeypatch):
    import gelly_streaming_tpu.library.bipartiteness as bp

    def half(canon, failed, src_h, dst_h, vcap, prep, **kw):
        n = len(src_h) // 2
        return real(canon, failed, src_h[:n], dst_h[:n], vcap, prep, **kw)
    real = bp.cover_forest_window
    monkeypatch.setattr(bp, "cover_forest_window", half)


def _plant_a_verdict_altered(monkeypatch):
    from gelly_streaming_tpu.serving.query import QueryEngine

    def flipped(self, snap):
        doc = dict(real(self, snap))
        return {"bipartite": not doc["bipartite"],
                "witness": 0 if doc["bipartite"] else None}
    real = QueryEngine.bipartite
    monkeypatch.setattr(QueryEngine, "bipartite", flipped)


@pytest.mark.parametrize("algorithm,plant,numbers", [
    ("cc", _plant_state_unchanged,
     {"table_mismatches", "answer_mismatches"}),
    ("cc", _plant_half_the_window_left_out, {"table_mismatches"}),
    ("cc", _plant_the_closing_windows_left_out, {"table_mismatches"}),
    ("cc", _plant_an_answer_altered, {"answer_mismatches"}),
    ("cc", _plant_a_stale_snapshot, {"answer_mismatches"}),
    ("bipartite", _plant_cover_state_unchanged, {"table_mismatches"}),
    ("bipartite", _plant_half_the_cover_window_left_out,
     {"table_mismatches"}),
    ("bipartite", _plant_a_verdict_altered, {"answer_mismatches"}),
])
def test_a_broken_timed_path_comes_out_not_correct(
        tmp_path, monkeypatch, algorithm, plant, numbers):
    """The rest of a run, with the timed path broken underneath."""
    plant(monkeypatch)
    _cell, doc = _run(tmp_path, algorithm=algorithm)
    assert doc["correct"] is False
    failing = {n for n, c in doc["compared"].items()
               if c["value"] > c["limit"]}
    assert numbers <= failing, doc["compared"]


def test_the_backend_is_started_before_the_program_is_imported():
    """``setup_s`` leaves the runtime's start-up out, so nothing of the
    program may run inside it: the harness refuses to time a backend
    start that the program's import could already have made."""
    import gelly_streaming_tpu  # noqa: F401

    with pytest.raises(cellrun.RunError, match="before the backend"):
        cellrun.start_backend()


# ---- the command itself ---------------------------------------------- #
def test_the_command_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks", "run.py"),
         "--workload", "cc-g500-s28.ingest-saturated", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode == 2
    assert out.stdout == ""
    assert "no TPU" in out.stderr


def test_the_command_prints_nothing_for_an_unknown_cell():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks", "run.py"),
         "--workload", "nope", "--seed", "1", "--seconds", "1"],
        cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=120)
    assert out.returncode == 2 and out.stdout == ""
