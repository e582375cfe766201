"""The sized connected-components cell's benchmark files (``ccsize``,
ISSUE 36) end to end on the CPU at a tiny scale: a run from the tiny
tree's own ``benchmarks`` package comes out correct on two seeds and
the control NOT, the module keeps the harness's contract, its reference
is held to ``tests/_size_ref.py`` edge by edge, and the new entries of
``BENCHMARK.json`` resolve by name. Times here are of the CPU and are
never a device number."""

from __future__ import annotations

import importlib
import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
HERE = os.path.dirname(os.path.abspath(__file__))
for p in (REPO, HERE, os.path.dirname(HERE)):
    if p not in sys.path:
        sys.path.insert(0, p)

import _contract  # noqa: E402
from _size_ref import SizeRef  # noqa: E402
from _tiny import make_tiny_root  # noqa: E402

from benchmarks.algorithms import cc, ccsize  # noqa: E402
from benchmarks.lib import bytes_model, cellrun, lastline, spec  # noqa: E402

CELL = "ccsize-g500-s28.ingest-saturated-size"
CONTROL_CELL = "cc-g500-s28.ingest-saturated"

# ---- the module's contract, from a tiny tree of its own -------------- #
def _tiny_root(tmp_path) -> str:
    """``_tiny.make_tiny_root``'s tree (scale 12, windows of 256) with
    this PR's algorithm, its aggregation built as the cell builds it and
    the cell's query mix."""
    root = make_tiny_root(str(tmp_path), algorithm="ccsize")
    path = os.path.join(root, "benchmarks", "configs", "tiny.json")
    with open(path) as f:
        cfg = json.load(f)
    cfg["aggregation_args"] = {"component_sizes": True}
    with open(path, "w") as f:
        json.dump(cfg, f)
    path = os.path.join(root, "benchmarks", "traffic", "tiny-mix.json")
    with open(path) as f:
        traffic = json.load(f)
    traffic["queries"]["closing_batches"] = 4
    with open(path, "w") as f:
        json.dump(traffic, f)
    return root


def _run_tiny(root, seed, *control):
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "_run_tiny.py"), root, str(seed),
         *control], cwd=root, capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO))
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.splitlines()[-1])


def _failing(doc) -> set:
    return {n for n, c in doc["compared"].items() if c["value"] > c["limit"]}


@pytest.mark.parametrize("seed", [17, 2**31 + 5])
def test_a_tiny_run_is_correct_on_both_query_kinds(tmp_path, seed):
    root = _tiny_root(tmp_path)
    doc = _run_tiny(root, seed)
    assert doc["correct"] is True, doc["compared"]
    assert doc["attempted"] > 0 and doc["failed"] == 0
    assert set(doc["compared"]) == {
        "answers_compared", "answer_mismatches", "stale_answers",
        "stamp_errors", "unanswered", "windows_unpublished",
        "table_mismatches"}
    assert all(c["value"] == 0 for c in doc["compared"].values())
    assert doc["windows"]["closing"] == 2
    assert doc["windows"]["compiles_in_window"] == 0
    doc["device"]["memory_peak_bytes"] = 1   # the CPU reports none
    cell = spec.load_cell("tiny.tiny-mix", root)
    assert lastline.validate(
        json.dumps(doc), required=cell.units("end_to_end"), trace=False,
        chips=doc["device"]["count"]) == []


def test_the_control_comes_out_not_correct(tmp_path):
    doc = _run_tiny(_tiny_root(tmp_path), 17, "stale_prefix")
    assert doc["correct"] is False
    assert {"table_mismatches", "answer_mismatches"} <= _failing(doc)


def test_a_program_without_the_size_table_fails_when_the_module_loads(
        monkeypatch):
    """The parent commit on the new cell: the harness loads the
    algorithm module before it makes the stream, the import fails there
    and ``run.py`` turns that into exit code 2."""
    from gelly_streaming_tpu.summaries import forest

    monkeypatch.delattr(forest, "fold_sizes")
    try:
        with pytest.raises(ImportError):
            importlib.reload(ccsize)
    finally:
        monkeypatch.undo()
        importlib.reload(ccsize)


def test_the_aggregation_carries_sizes_and_the_chips_paths_are_checked():
    agg = ccsize.build(spec.load_cell(CELL).config)
    assert agg.component_sizes is True and agg.carry == "auto"

    class Snap:
        payload = {"labels": 0, "sizes": 0}

    class Server:
        class engine:
            prefer_host = False

        @staticmethod
        def snapshot():
            return Snap

    agg._cc_mode = "forest"
    assert ccsize.chip_paths_problem(agg, Server) is None
    Snap.payload = {"labels": 0}
    assert "no size table" in ccsize.chip_paths_problem(agg, Server)
    Server.engine.prefer_host = True
    assert "host" in ccsize.chip_paths_problem(agg, Server)
    agg._cc_mode = "host"
    assert "forest" in ccsize.chip_paths_problem(agg, Server)


def test_a_batch_is_three_quarters_sizes_and_a_quarter_pairs():
    config = {"id_space": 1 << 20}
    rng = np.random.default_rng(3)
    src = rng.integers(0, 50, 400) + 1000
    dst = rng.integers(0, 50, 400) + 2000
    queries, records = ccsize.draw_queries(
        np.random.default_rng(0), 256, src, dst, config)
    assert len(queries) == 256 and records.shape == (256, 3)
    kind, u, v = records.T
    assert (kind[:192] == ccsize.SIZE_OF).all()
    assert (kind[192:] == ccsize.CONNECTED).all()
    assert [type(q).__name__ for q in queries[191:193]] == [
        "ComponentSizeQuery", "ConnectedQuery"]
    assert [q.v for q in queries[:192]] == u[:192].tolist()
    assert (u[:192] == v[:192]).all()
    assert [(q.u, q.v) for q in queries[192:]] == list(
        zip(u[192:].tolist(), v[192:].tolist()))
    # half the sized vertices are endpoints of the recent windows' edges
    recent = set(src.tolist()) | set(dst.tolist())
    assert set(u[:96].tolist()) <= recent
    assert len(set(u[96:192].tolist()) & recent) < 5
    # the pairs are cc's own draw, on the generator's next numbers
    rng_a, rng_b = np.random.default_rng(9), np.random.default_rng(9)
    _q, recs = ccsize.draw_queries(rng_a, 256, src, dst, config)
    rng_b.integers(0, len(src), 96), rng_b.integers(0, 2, 96)
    rng_b.integers(0, 1 << 20, 96)
    _pairs, want = cc.draw_queries(rng_b, 64, src, dst, config)
    assert np.array_equal(recs[192:, 1:], want)
    # a sweep of 1, 2 or 4 batches chases 320, 640 or 1,280 ids
    assert 192 + 2 * 64 == 320


# ---- the reference against the sequential union-find, edge by edge --- #
def _streams():
    rng = np.random.default_rng(11)
    n = 96
    u = rng.integers(0, n, 600)
    yield "random", n, u, np.where(rng.random(600) < 0.15, u,
                                   rng.integers(0, n, 600))
    a = np.arange(n - 1, 0, -1)
    yield "worst_order_path", n, a, a - 1
    few = rng.integers(0, n, (6, 2))[rng.integers(0, 6, 300)]
    yield "duplicates", n, few[:, 0], few[:, 1]


@pytest.mark.parametrize("window", [1, 7, 64, 1000])
@pytest.mark.parametrize("name", ["random", "worst_order_path", "duplicates"])
def test_the_reference_keeps_the_sizes_a_sequential_union_find_keeps(
        name, window):
    n, u, v = next(s[1:] for s in _streams() if s[0] == name)
    ref, plain = ccsize.Reference({"id_space": n}), SizeRef(n)
    every = np.arange(n)
    for lo in range(0, len(u), window):
        s, d = u[lo:lo + window], v[lo:lo + window]
        ref.fold(s, d)
        plain.fold(s, d)
        recs = np.concatenate([
            np.stack([np.full(n, ccsize.SIZE_OF), every, every], axis=1),
            np.stack([np.full(n, ccsize.CONNECTED), every,
                      (every * 5 + 1) % n], axis=1)])
        want = plain.sizes().tolist() + [
            int(plain.connected(x, (x * 5 + 1) % n)) for x in range(n)]
        assert ref.expected(recs).tolist() == want, lo
        roots = np.flatnonzero(ref.uf.find(every) == every)
        assert int(ref.size[roots].sum()) == n
    assert ref.compare_final(ref.table()) == {"table_mismatches": 0}
    # a touched row cut loose from its component shows in the final table
    hooked = np.flatnonzero(ref.uf.find(every) != every)
    wrong = ref.table()
    wrong[hooked[-1]] = hooked[-1]
    assert ref.compare_final(wrong)["table_mismatches"] >= 1


# ---- the new entries of BENCHMARK.json ------------------------------- #
def test_the_new_entries_resolve_and_keep_to_the_contract():
    """Found by NAME: the cell, its nine per-layer entries whole,
    together and in their order after the degree cell's, and the
    end-to-end metrics it reports; a later PR's entries after them fit
    in."""
    _contract.entries_stand(REPO, CELL)
    _contract.cells_stand(REPO)
    _contract.four_chip_cells_fit(REPO)
    # the device programs the trace readers name include the CC cells'
    cell = spec.load_cell(CELL)
    assert {r["reader"]["program"] for r in cell.readers.values()
            if r["reader"]["kind"].startswith("program_")} >= {
        "jit_step", "jit__batch_roots"}


def test_the_configuration_is_its_control_plus_the_size_table():
    cfg = spec.load_cell(CELL).config
    control = spec.load_cell(CONTROL_CELL).config
    assert cfg["algorithm"] == "ccsize" and cfg["generator"] == "graph500"
    assert cfg["aggregation_args"] == {"component_sizes": True}
    for key in ("scale", "id_space", "window_edges", "reduced"):
        assert cfg[key] == control[key]
    assert cfg["reduced"] == ["scale"] and "scale" in cfg["reduced_why"]
    assert "memory_analysis()" in cfg["reduced_why"]["scale"]
    g = dict(cfg["graph500"])
    assert g.pop("graph_seed") != control["graph500"]["graph_seed"]
    assert g == {k: v for k, v in control["graph500"].items()
                 if k != "graph_seed"}
    assert cfg["guarantees"] == {
        **control["guarantees"],
        "sizes_exact_at_roots_for_stamped_prefix": True,
        "size_and_root_from_one_snapshot": True}
    for key in ("window_edges", "scramble", "graph_seed",
                "seeded_closing_windows", "size_point_query", "query_mix"):
        assert key in cfg["assumed"]
    # two carried tables of the 1 GiB that cc-g500-s28 carries one of
    assert 4 * ccsize.table_rows(cfg) == 1 << 30
    traffic = spec.load_cell(CELL).traffic
    ctl = spec.load_cell(CONTROL_CELL).traffic
    assert traffic["ingest"] == ctl["ingest"] == {
        "mode": "closed", "outstanding": 2}
    assert traffic["queries"] == {**ctl["queries"], "closing_batches": 4}
    assert traffic["warm_windows"] == ctl["warm_windows"]
    # the stream outlasts the program: room for twice the rate on record
    seconds = spec.load_benchmark()["run_seconds"]
    n = cellrun.stream_length(spec.load_cell(CELL), seconds) / (1 << 16)
    assert (n - 28 - 2) * (1 << 16) / seconds >= 2.9e6


def test_the_byte_model_counts_both_tables():
    cfg = spec.load_cell(CELL).config
    src, dst = np.asarray([1, 2, 3, 3]), np.asarray([2, 3, 4, 9])
    shape = ccsize.fold_shape(cfg, src, dst)
    assert shape == {"rows": 2 << 28, "window_edges": 4, "touched": 5}
    assert bytes_model.forest_step(**shape) == 2 * 8 * (1 << 28) + 8 * 4 + 8 * 5
    assert cc.fold_shape(cfg, src, dst)["rows"] == 1 << 28
