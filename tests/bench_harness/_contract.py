"""What the cells of ``BENCHMARK.json`` were given, held by name.

Every check here is a function of a tree's root (the repository, or a
copy with more files and entries) and raises ``AssertionError`` when an
entry that stands in the benchmark was lost, moved or changed. A later
PR adds configurations, cells, traffic mixes and per-layer metrics as
new files and as new entries at the end of their lists: every check
holds on such a tree. ``test_perf_growth.py`` builds two of them, and a
faulty tree for each kind of fault the checks are there to catch.

The trace readers of a cell are read against the recording that holds
their program: any ``*.json`` under ``fixtures/`` that is a recording
(a bare list of planes, with the host's ``bench.window`` annotation, or
``{"planes", "lo", "hi"}``), indexed by the program names on its
``XLA Modules`` line. A cell whose program is in no recording fails its
trace check: the PR that adds the cell brings a recording of it as a
new file there."""

from __future__ import annotations

import json
import math
import os

from benchmarks.lib import cellrun, spec, trace_reduce

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")

CELL1 = "cc-g500-s28.ingest-saturated"
PACED = "cc-g500-s28.paced-query-heavy"
BIP = "bip-g500-s27.ingest-saturated-poll"
V4_CELL = "cc-g500-s30-v4.ingest-saturated"
DD_CELL = "dd-g500-s28.ingest-saturated-dyn"
SIZE_CELL = "ccsize-g500-s28.ingest-saturated-size"

#: the configurations and cells that stand, in the order they were entered
CONFIGS = ["cc-g500-s28", "bip-g500-s27", "cc-g500-s30-v4", "dd-g500-s28",
           "ccsize-g500-s28"]
CELLS = [CELL1, PACED, BIP, V4_CELL, DD_CELL, SIZE_CELL]
E2E_NAMES = ["edges_per_s", "window_p95_ms", "query_p95_ms",
             "answer_age_p95_ms", "setup_s"]
RUN_SECONDS = 45

EPS, Q95 = "edges_per_s", "query_p95_ms"
_SAT = {"edges_per_s", "query_p95_ms", "setup_s"}
#: cell -> (configuration, traffic, chips, end-to-end metrics)
SHAPES = {
    CELL1: ("cc-g500-s28", "ingest-saturated", 1, _SAT),
    PACED: ("cc-g500-s28", "paced-query-heavy", 1,
            {"window_p95_ms", "query_p95_ms", "answer_age_p95_ms",
             "setup_s"}),
    BIP: ("bip-g500-s27", "ingest-saturated-poll", 1,
          {"edges_per_s", "setup_s"}),
    V4_CELL: ("cc-g500-s30-v4", "ingest-saturated", 4, _SAT),
    DD_CELL: ("dd-g500-s28", "ingest-saturated-dyn", 1, _SAT),
    SIZE_CELL: ("ccsize-g500-s28", "ingest-saturated-size", 1, _SAT),
}

#: the host fold's spans and the serving waits: name -> (span, layer,
#: moves, cells), in the order they were entered
SAT = [CELL1, BIP]
CC = [CELL1, PACED]
HOST_SPANS = {
    "fold_host_ms.sat": ("forest.window", "window host step", EPS, SAT),
    "fold_prep_ms.sat": ("forest.prep", "window host step", EPS, SAT),
    "fold_dispatch_ms.sat": ("forest.dispatch", "window host step", EPS,
                             SAT),
    "queue_wait_ms": ("serving.queue_wait", "serving", Q95, CC),
    "answer_wait_ms": ("serving.device_wait", "serving", Q95, CC),
}

#: cell -> the per-layer metrics it reports, in their order
PER_LAYER = {
    CELL1: ["generator_late_p95_ms", "pack_ms.sat", "ingest_host_ms.sat",
            "forest_step_ms.sat", "forest_step_roofline.sat", "answer_ms",
            "query_kernel_ms", "compiles_in_window", *HOST_SPANS],
    PACED: ["generator_late_p95_ms", "forest_step_ms.paced", "answer_ms",
            "query_kernel_ms", "compiles_in_window", "queue_wait_ms",
            "answer_wait_ms"],
    BIP: ["pack_ms.sat", "ingest_host_ms.sat", "forest_step_ms.sat",
          "forest_step_roofline.sat", "verdict_query_ms",
          "compiles_in_window.ingest", "fold_host_ms.sat",
          "fold_prep_ms.sat", "fold_dispatch_ms.sat"],
}


def _prog(program):
    return {"kind": "program_mean_ms", "program": program}


def _share(program):
    return {"kind": "program_bytes_share", "program": program,
            "bytes_model": "forest_step"}


def _span(span, self_time=False):
    return {"kind": "span_mean_ms", "span": span,
            **({"self_time": True} if self_time else {})}


def _stat(key):
    return {"kind": "harness_stat", "key": key}


TRACE, SPAN, HOST, COUNTER = ("device_trace", "program_span", "host_clock",
                              "program_counter")
#: the cells whose every per-layer entry is held whole: cell -> (the entry
#: its block follows, {name: (unit, source, layer, moves, reader)})
BLOCKS = {
    V4_CELL: ("compiles_in_window.ingest", {
        "forest_step_ms.v4": ("ms", TRACE, "forest step", EPS,
                              _prog("jit_step")),
        "forest_step_roofline.v4": ("%", TRACE, "forest step", EPS,
                                    _share("jit_step")),
        "place_ms.v4": ("ms", SPAN, "window host step", EPS,
                        _span("forest.place")),
        "ingest_host_ms.v4": ("ms", HOST, "window host step", EPS,
                              _stat("ingest_host_ms")),
        "answer_ms.v4": ("ms", SPAN, "serving", Q95,
                         _span("serving.answer")),
        "query_kernel_ms.v4": ("ms", TRACE, "query kernels", Q95,
                               _prog("jit__batch_roots")),
        "compiles_in_window.v4": ("count", COUNTER, "device", EPS,
                                  _stat("compiles_in_window")),
    }),
    DD_CELL: ("compiles_in_window.v4", {
        "degree_step_ms.dyn": ("ms", TRACE, "degree step", EPS,
                               _prog("jit_degree_step")),
        "degree_step_roofline.dyn": ("%", TRACE, "degree step", EPS,
                                     _share("jit_degree_step")),
        "degree_window_ms.dyn": ("ms", SPAN, "window host step", EPS,
                                 _span("degrees.window")),
        "pack_ms.dyn": ("ms", SPAN, "windower", EPS,
                        _span("window.pack", self_time=True)),
        "ingest_host_ms.dyn": ("ms", HOST, "window host step", EPS,
                               _stat("ingest_host_ms")),
        "answer_ms.dyn": ("ms", SPAN, "serving", Q95,
                          _span("serving.answer")),
        "degree_gather_ms.dyn": ("ms", TRACE, "query kernels", Q95,
                                 _prog("jit__gather")),
        "generator_late_p95_ms.dyn": ("ms", HOST, "generator", Q95,
                                      _stat("generator_late_p95_ms")),
        "compiles_in_window.dyn": ("count", COUNTER, "device", EPS,
                                   _stat("compiles_in_window")),
    }),
    SIZE_CELL: ("compiles_in_window.dyn", {
        "forest_step_ms.size": ("ms", TRACE, "forest step", EPS,
                                _prog("jit_step")),
        "forest_step_roofline.size": ("%", TRACE, "forest step", EPS,
                                      _share("jit_step")),
        "ingest_host_ms.size": ("ms", HOST, "window host step", EPS,
                                _stat("ingest_host_ms")),
        "pack_ms.size": ("ms", SPAN, "windower", EPS,
                         _span("window.pack", self_time=True)),
        "answer_ms.size": ("ms", SPAN, "serving", Q95,
                           _span("serving.answer")),
        "size_lookup_ms.size": ("ms", SPAN, "serving", Q95,
                                _span("serving.size_lookup")),
        "query_kernel_ms.size": ("ms", TRACE, "query kernels", Q95,
                                 _prog("jit__batch_roots")),
        "generator_late_p95_ms.size": ("ms", HOST, "generator", Q95,
                                       _stat("generator_late_p95_ms")),
        "compiles_in_window.size": ("count", COUNTER, "device", EPS,
                                    _stat("compiles_in_window")),
    }),
}
PER_LAYER.update({cell: list(block) for cell, (_a, block) in BLOCKS.items()})


def _names(bench, key):
    return [entry["name"] for entry in bench[key]]


def _reader_file(root, name):
    with open(os.path.join(root, spec.BENCH_DIR, "layer_metrics",
                           name + ".json")) as f:
        return json.load(f)


def _in_order(names, run, after):
    """``run`` stands together and in its order in ``names``, after
    ``after``."""
    assert run[0] in names, f"{run[0]} is no longer in per_layer"
    first = names.index(run[0])
    assert names[first:first + len(run)] == run, (
        f"moved or split: {run} against {names[first:first + len(run)]}")
    assert after in names, f"{after} is no longer in per_layer"
    assert first > names.index(after), f"{run[0]} stands before {after}"


# ---- the checks ------------------------------------------------------ #
def cells_stand(root: str = spec.ROOT) -> None:
    """Every configuration and cell that stands is there, by name and
    ahead of any added later; each cell reports the end-to-end metrics
    it had (and no other of those the benchmark had), at least the
    per-layer metrics it had in their order, and a host-span entry
    exactly where it was entered."""
    problems = spec.check_names_resolve(root)
    assert problems == [], problems
    bench = spec.load_benchmark(root)
    for key, had in (("configs", CONFIGS), ("workloads", CELLS),
                     ("end_to_end", E2E_NAMES)):
        assert _names(bench, key)[:len(had)] == had, (
            f"the {key} that stand are no longer first, in their order")
    assert bench["run_seconds"] == RUN_SECONDS
    assert all(len(w["why"]) <= 200 for w in bench["workloads"])
    assert all(len(c["source"]) <= 200 for c in bench["configs"])
    assert len({c["source"] for c in bench["configs"]}) == len(
        bench["configs"])
    # an end-to-end metric's cells: those it had first, in their order
    for m in bench["end_to_end"][:len(E2E_NAMES)]:
        if "workloads" in m:
            had = [c for c in CELLS if m["name"] in SHAPES[c][3]]
            assert m["workloads"][:len(had)] == had, (
                f"{m['name']} lost or moved a cell it had: {had}")
    for name in CELLS:
        config, traffic, chips, e2e = SHAPES[name]
        cell = spec.load_cell(name, root)
        assert (cell.config_name, cell.traffic_name, cell.chips) == (
            config, traffic, chips), name
        for metric in E2E_NAMES:
            assert (metric in cell.end_to_end) == (metric in e2e), (
                f"{name} {'lost' if metric in e2e else 'gained'} {metric}")
        got = [n for n in cell.per_layer if n in PER_LAYER[name]]
        lost = [n for n in PER_LAYER[name] if n not in cell.per_layer]
        assert not lost, f"{name} lost {lost}"
        assert got == PER_LAYER[name], f"{name}'s metrics moved: {got}"
        for metric, (_s, _l, _m, cells) in HOST_SPANS.items():
            assert (metric in cell.per_layer) == (name in cells), (
                name, metric)


def host_span_entries_stand(root: str = spec.ROOT) -> None:
    """The five host-span entries stand together, in their order, after
    the sized cell's; each is whole, with its reader file."""
    bench = spec.load_benchmark(root)
    names = _names(bench, "per_layer")
    _in_order(names, list(HOST_SPANS), "compiles_in_window.size")
    first = names.index(next(iter(HOST_SPANS)))
    layers = {m["layer"] for m in bench["per_layer"][:first]}
    for m in bench["per_layer"][first:first + len(HOST_SPANS)]:
        span, layer, moves, cells = HOST_SPANS[m["name"]]
        assert m == {"name": m["name"], "unit": "ms", "better": "lower",
                     "source": SPAN, "layer": layer, "moves": moves,
                     "workloads": cells}, m
        assert layer in layers          # a layer the benchmark names
        assert _reader_file(root, m["name"]) == {"reader": _span(span)}


def entries_stand(root: str, cell_name: str) -> None:
    """A cell of ``BLOCKS`` keeps every per-layer entry it was given,
    whole (unit, source, layer, ``moves``, its cells, its reader file),
    together and in its order, wherever later entries stand."""
    problems = spec.check_names_resolve(root)
    assert problems == [], problems
    config, traffic, chips, e2e = SHAPES[cell_name]
    after, block = BLOCKS[cell_name]
    cell = spec.load_cell(cell_name, root)
    assert (cell.config_name, cell.traffic_name, cell.chips) == (
        config, traffic, chips)
    assert e2e <= set(cell.end_to_end), f"{cell_name} lost an end-to-end"
    lost = [n for n in block if n not in cell.per_layer]
    assert not lost, f"{cell_name} lost {lost}"
    for name, (unit, source, layer, moves, reader) in block.items():
        assert cell.per_layer[name] == {
            "name": name, "unit": unit,
            "better": "higher" if unit == "%" else "lower",
            "source": source, "layer": layer, "moves": moves,
            "workloads": [cell_name]}, cell.per_layer[name]
        assert moves in cell.end_to_end
        assert reader["kind"] in cellrun.READERS
        assert _reader_file(root, name) == {"reader": reader}, name
    bench = spec.load_benchmark(root)
    _in_order(_names(bench, "per_layer"), list(block), after)
    assert config in _names(bench, "configs")


def four_chip_cells_fit(root: str = spec.ROOT) -> None:
    """The four-chip cell is one of the four-chip cells, and those are
    no more than a quarter of the cells (one always may)."""
    cells = spec.load_benchmark(root)["workloads"]
    four = [w["name"] for w in cells if w["chips"] == 4]
    assert V4_CELL in four
    allowed = max(1, len(cells) // 4)
    assert len(four) <= allowed, (
        f"{len(four)} four-chip cells of {len(cells)}: a quarter allows "
        f"{allowed}")


# ---- the recordings -------------------------------------------------- #
def index_recordings(*dirs: str) -> dict:
    """program name -> ``(file, planes, lo, hi)``: the first recording,
    by file name, whose device's ``XLA Modules`` line runs the program."""
    index: dict = {}
    for d in dirs:
        for fn in sorted(os.listdir(d)):
            if not fn.endswith(".json"):
                continue
            with open(os.path.join(d, fn)) as f:
                doc = json.load(f)
            if isinstance(doc, dict) and "planes" in doc:
                planes, lo, hi = doc["planes"], doc["lo"], doc["hi"]
            elif isinstance(doc, list):
                planes = doc
                lo, hi = trace_reduce.window_bounds(planes)
            else:
                continue
            modules = trace_reduce.line_of(
                trace_reduce.device_planes(planes)[0],
                trace_reduce.MODULES_LINE)["events"]
            for program in sorted({n.split("(")[0] for n, _s, _d in modules}):
                index.setdefault(program, (fn, planes, lo, hi))
    return index


def trace_readers_resolve(root: str, cell_name: str,
                          recordings: dict) -> None:
    """Each per-layer metric of the cell that reads a program's time
    from the trace gives a finite number above 0 on the recording that
    holds the program; a cell has at least one."""
    cell = spec.load_cell(cell_name, root)
    seen = 0
    for name, reader in cell.readers.items():
        r = reader["reader"]
        assert r["kind"] in cellrun.READERS, name
        if r["kind"] != "program_mean_ms":
            continue
        program = r["program"]
        assert program in recordings, (
            f"{cell_name}: {name} reads program {program!r}, which no "
            f"recording under fixtures/ holds (held: {sorted(recordings)}); "
            "the PR that adds the cell brings a recording of the program "
            "as a new fixture file")
        _fn, planes, lo, hi = recordings[program]
        value = cellrun.READERS[r["kind"]](
            r, {"planes": planes, "lo": lo, "hi": hi})
        assert math.isfinite(value) and value > 0, name
        seen += 1
    assert seen >= 1, f"{cell_name} reads no program's time from the trace"
