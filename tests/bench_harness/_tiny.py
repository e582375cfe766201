"""A tiny benchmark tree for CPU tests: the real harness and the real
data files copied to a temporary root, plus one configuration, one
traffic mix, one cell and one per-layer metric ADDED as new files and
new entries (no file that exists is edited). ``algorithm="tinydeg"``
adds a configuration of another kind altogether, again as new files
only: served degree counts (no forest, no union-find) over a uniform
record stream (no Graph500), with an algorithm module and a generator
module of its own."""

from __future__ import annotations

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = "benchmarks"

#: an algorithm the harness has never heard of: degree counts served by
#: ``DegreeDistribution`` over a RECORD stream, its reference a bincount
TINYDEG = '''
import numpy as np

PAYLOAD_KEY = "deg"


def build(config):
    from gelly_streaming_tpu.core.window import CountWindow
    from gelly_streaming_tpu.datasets import IdentityDict
    from gelly_streaming_tpu.library.degrees import DegreeDistribution

    return DegreeDistribution(
        window=CountWindow(int(config["window_edges"])),
        vertex_dict=IdentityDict(int(config["id_space"])))


def make_stream(config, source):
    def records():
        for src, dst in source.iter_chunks():
            for u, v in zip(src.tolist(), dst.tolist()):
                yield u, v, "+"
    return records()


def draw_queries(rng, n, recent_src, recent_dst, config):
    from gelly_streaming_tpu.serving import DegreeQuery

    vs = recent_src[rng.integers(0, len(recent_src), n)].astype(np.int64)
    return [DegreeQuery(int(v)) for v in vs], vs[:, None]


def answer_value(answer):
    return int(answer.value)


class Reference:
    def __init__(self, config):
        self.deg = np.zeros(int(config["id_space"]), np.int64)

    def fold(self, src, dst):
        np.add.at(self.deg, src, 1)
        np.add.at(self.deg, dst, 1)

    def expected(self, records):
        return self.deg[records[:, 0]]

    def table(self):
        return self.deg.copy()

    def compare_final(self, table):
        n = min(len(table), len(self.deg))
        return {"degree_mismatches": int(
            np.sum(table[:n] != self.deg[:n]) + np.sum(self.deg[n:] != 0))}
'''

UNIFORM = '''
import numpy as np


def edges(config, n_edges, seed, warm_edges):
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32])
    ids = rng.integers(0, int(config["id_space"]), (2, n_edges))
    return ids[0].astype(np.int32), ids[1].astype(np.int32)
'''


def make_tiny_root(tmp: str, *, algorithm: str = "cc",
                   carry: str = "forest") -> str:
    root = os.path.join(tmp, "root")
    shutil.copytree(os.path.join(REPO, "benchmarks"),
                    os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bip = algorithm == "bipartite"
    config = {
        "name": "tiny", "algorithm": algorithm, "generator": "graph500",
        "source": "test", "scale": 12,
        "graph500": {"graph_seed": 77, "edge_factor": 16, "a": 0.57,
                     "b": 0.19, "c": 0.19, "scrambled": True,
                     "bipartite_even_odd": bip,
                     "seeded_closing_windows": 2},
        "id_space": 4096, "window_edges": 256,
        "aggregation_args": {"carry": carry},
        "guarantees": {"answers_exact_for_stamped_prefix": True,
                       "max_staleness_windows": 3,
                       "final_table_equals_reference": True},
        "reduced": ["scale"],
    }
    traffic = {
        "ingest": {"mode": "open", "edges_per_s": 256 * 40,
                   "max_backlog": 8},
        "queries": {"batch": 16, "period_ms": 25, "recent_windows": 4,
                    "warm_sweeps": [1, 2], "closing_batches": 1},
        "warm_windows": 3, "stream_edges_per_s": 256 * 60,
    }
    metric = {
        "name": "tiny_answer_ms", "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "serving",
        "moves": "query_p95_ms", "workloads": ["tiny.tiny-mix"],
    }
    reader = {"reader": {"kind": "span_mean_ms", "span": "serving.answer"}}
    if algorithm == "tinydeg":
        config = {
            "name": "tiny", "algorithm": "tinydeg", "generator": "uniform",
            "source": "test", "id_space": 512, "window_edges": 64,
            "guarantees": {"max_staleness_windows": 3}, "reduced": [],
        }
        traffic["ingest"] = {"mode": "open", "edges_per_s": 64 * 40}
        traffic["stream_edges_per_s"] = 64 * 60
        for rel, text in ((f"{BENCH}/algorithms/tinydeg.py", TINYDEG),
                          (f"{BENCH}/generators/uniform.py", UNIFORM)):
            with open(os.path.join(root, rel), "w") as f:
                f.write(text)

    def put(rel, doc):
        with open(os.path.join(root, rel), "w") as f:
            json.dump(doc, f)

    put("benchmarks/configs/tiny.json", config)
    put("benchmarks/traffic/tiny-mix.json", traffic)
    put("benchmarks/layer_metrics/tiny_answer_ms.json", reader)
    bench["configs"].append({"name": "tiny", "source": "test",
                             "file": "benchmarks/configs/tiny.json",
                             "reduced": ["scale"], "why": "test"})
    bench["workloads"].append({"name": "tiny.tiny-mix", "config": "tiny",
                               "traffic": "tiny-mix", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"]:
        if "workloads" in m:
            m["workloads"].append("tiny.tiny-mix")
    bench["per_layer"].append(metric)
    put("BENCHMARK.json", bench)
    return root
