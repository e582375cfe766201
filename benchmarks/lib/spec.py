"""Finds everything a cell needs by the names in ``BENCHMARK.json``.

A cell names a configuration and a traffic mix; the configuration's
file names an algorithm module and a generator module; every per-layer
metric has a reader file of its own, which says only how the number is
read (unit, layer, ``moves`` and cells are ``BENCHMARK.json``'s to say,
once). Nothing here lists them: a later PR adds a configuration, an
algorithm, a generator, a traffic mix, a cell or a per-layer metric as
new files and new entries, and edits no file that exists.

    <root>/BENCHMARK.json
    <root>/benchmarks/configs/<config>.json        (the entry's "file")
    <root>/benchmarks/algorithms/<algorithm>.py    (config["algorithm"])
    <root>/benchmarks/generators/<generator>.py    (config["generator"])
    <root>/benchmarks/traffic/<traffic>.json
    <root>/benchmarks/layer_metrics/<metric>.json  ({"reader": {...}})
"""

from __future__ import annotations

import importlib
import json
import os
from dataclasses import dataclass, field

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH_DIR = "benchmarks"


def _load(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict
    traffic: dict
    end_to_end: dict       # name -> metric entry of BENCHMARK.json
    per_layer: dict        # name -> metric entry
    readers: dict = field(default_factory=dict)  # name -> reader file

    def units(self, which: str) -> dict:
        return {n: m["unit"] for n, m in getattr(self, which).items()}

    def algorithm(self):
        return importlib.import_module(
            f"{BENCH_DIR}.algorithms.{self.config['algorithm']}")

    def generator(self):
        return importlib.import_module(
            f"{BENCH_DIR}.generators.{self.config['generator']}")


def load_benchmark(root: str = ROOT) -> dict:
    return _load(os.path.join(root, "BENCHMARK.json"))


def load_cell(name: str, root: str = ROOT) -> Cell:
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(known: {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _load(os.path.join(root, configs[w["config"]]["file"]))
    traffic = _load(os.path.join(
        root, BENCH_DIR, "traffic", w["traffic"] + ".json"))
    # an end-to-end metric without the key is every cell's; a per-layer
    # metric lists its cells
    e2e = {m["name"]: m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])}
    per_layer = {m["name"]: m for m in bench["per_layer"]
                 if name in m["workloads"]}
    readers = {
        n: _load(os.path.join(root, BENCH_DIR, "layer_metrics", n + ".json"))
        for n in per_layer
    }
    return Cell(name=name, chips=int(w["chips"]), config_name=w["config"],
                traffic_name=w["traffic"], config=config, traffic=traffic,
                end_to_end=e2e, per_layer=per_layer, readers=readers)


def check_names_resolve(root: str = ROOT) -> list:
    """Every name in ``BENCHMARK.json`` resolves to its file. Returns
    the problems."""
    problems = []
    bench = load_benchmark(root)
    e2e_names = {m["name"] for m in bench["end_to_end"]}
    cell_names = {w["name"] for w in bench["workloads"]}
    for m in bench["per_layer"]:
        if "workloads" not in m:
            problems.append(f"per-layer metric {m['name']} lists no cells")
            m["workloads"] = []
        if m["moves"] not in e2e_names:
            problems.append(f"{m['name']} moves unknown {m['moves']}")
        for c in m["workloads"]:
            if c not in cell_names:
                problems.append(f"{m['name']} names unknown cell {c}")
    if problems:
        return problems
    for w in bench["workloads"]:
        try:
            cell = load_cell(w["name"], root)
        except (OSError, KeyError, ValueError) as e:
            problems.append(f"cell {w['name']}: {e!r}")
            continue
        for kind in ("algorithm", "generator"):
            path = os.path.join(root, BENCH_DIR, kind + "s",
                                str(cell.config.get(kind)) + ".py")
            if not os.path.exists(path):
                problems.append(f"cell {w['name']}: no {kind} module {path}")
        if set(cell.end_to_end) <= {"setup_s"}:
            problems.append(f"cell {w['name']} reports no end-to-end "
                            "metric besides setup_s")
        if not cell.per_layer:
            problems.append(f"cell {w['name']} reports no per-layer metric")
        for n, m in cell.per_layer.items():
            if m["moves"] not in cell.end_to_end:
                problems.append(f"{n} moves {m['moves']}, which cell "
                                f"{w['name']} does not report")
            if "kind" not in cell.readers[n].get("reader", {}):
                problems.append(f"{n}: its file names no reader kind")
    return problems
