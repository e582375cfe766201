"""One traced run of a benchmark cell, read by named scope and by the
spans inside the program.

    chiprun -- python3 tools/trace_phases.py --workload <cell> --seed <n> \\
        --seconds <s> [--dump chiprun_out/<dir>]

Runs the cell exactly as ``benchmarks/run.py --trace 1`` does (the same
``cellrun.run_cell``) and adds, to the cell's per-layer metrics, the
ones ``PROPOSED`` lists: the forest step's phases and loop trips
(``benchmarks/lib/scope_reduce.py``; of the chase's two loops the trips
of each, ``forest_wide_rounds`` and ``forest_narrow_rounds``), the
degree step's phases (the
``dd-g500-s28`` cell: ``degrees.sort|scan|gather|scatter|hist`` inside
``jit_degree_step``, and inside ``degrees.hist`` its head's contraction,
its tail's slab and the slab's fallback, ``degrees.hist.head|tail|
fallback``), the size table's back phase (the
``ccsize-g500-s28`` cell: ``forest.sizes`` inside ``jit_step``, and the
size lanes' ``jit__gather``), the window's host life under its root
span (``window.emit``, ``serving.publish`` and the root's self time) and,
in the four-chip, the sized and the degree cell, the host fold's spans
and the serving waits. What still keeps a metric HERE and out of
``BENCHMARK.json`` (PERF.md, section 7, "What the harness needs"):

- its span is one the PARENT of the PR that adds it does not emit: the
  harness requires every per-layer metric of a cell on the line of a
  traced run, so the parent would print no line at all. The first later
  PR whose parent emits the span enters it, with data files alone (the
  kind ``span_mean_ms`` is the harness's own). PR 38 entered so the
  host fold's spans and the serving waits of cell 1, the cover and the
  paced cell, which every parent since PR 26 emits, and left the three
  new spans of its own here;
- its kind is one of the ``scope_*`` kinds (``scope_reduce.READERS``),
  which the harness does not know yet;
- its cell is the four-chip, the degree or the sized cell: tests of
  ``tests/bench_harness/`` (``test_perf_degdist.py``,
  ``test_perf_ccsize.py``) hold the SET of each of those cells'
  per-layer metrics, and ``test_perf_trace.py`` reads every
  ``program_mean_ms`` entry of every cell in a recording of cell 1, so
  an entry more in one of them fails a test only a ``benchmark`` PR
  may edit.

The reader kinds are registered here, at run time; no file of the
benchmark is edited.

The last line of standard output is the result document, with
``phases`` (the phases' sum against the whole step, the ms of the chase
and of the fixpoint over their loops' rounds, the chase's slab under
``forest.narrow``, the sorts ahead of the table's scatters under
``forest.sort``, the fixpoint's once-a-step
contraction under ``forest.contract`` and, where the table is
sharded over chips, the collectives of a step under ``forest.exchange``:
their ms, their count and the span attributes ``shards`` and
``owner_max_share``; for the degree step the histogram's three parts
and how many executions took the fallback), ``events`` (span
events per window and per sweep), ``window`` (the host life of a window
under its root span ``serving.window``: the mean of the root, of each
child and of the root's SELF time, which is the host time no span names;
the mean and the histogram of ``in_flight``, the published tables still
being computed at a publish; span events a window), ``serving`` (a
sweep's ``reads`` and ``late_reads`` as ``serving.answer`` says them,
and the wait of its first read beside that of a later one) and ``clock`` (how far a
span's ``t0``,
mapped through the traced slice's bracket, lies from the same span's
annotation on the profiler's clock) beside the harness's keys.
``--dump`` writes the first executions' stretch of the trace as plain
lists, names uncut and scopes in them, for a by-hand look and for
cutting a test recording, and beside it (``spans.json``) the program's
span events of the measured window with the collector's pauses.
"""

from __future__ import annotations

import time

_T_PROCESS = time.perf_counter()

import argparse
import bisect
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

SAT = ["cc-g500-s28.ingest-saturated", "bip-g500-s27.ingest-saturated-poll"]
CC = ["cc-g500-s28.ingest-saturated", "cc-g500-s28.paced-query-heavy"]
V4 = ["cc-g500-s30-v4.ingest-saturated"]
DYN = ["dd-g500-s28.ingest-saturated-dyn"]
SIZE = ["ccsize-g500-s28.ingest-saturated-size"]
#: the per-window program of a cell (the one whose phases are read)
STEP_PROGRAM = {**dict.fromkeys(SAT + CC + V4 + SIZE, "jit_step"),
                **dict.fromkeys(DYN, "jit_degree_step")}
DEGREE_PHASES = ("sort", "scan", "gather", "scatter", "hist")
#: the scopes nested in ``degrees.hist``: the head's one contraction, the
#: tail's slab, and the fallback a window takes where the slab is short
DEGREE_HIST_PARTS = ("head", "tail", "fallback")


#: every span the ingest thread emits for a window of a cell (5.0 events
#: a window on one chip and 6.0 on four before PR 38; 8.0 and 9.0 since)
INGEST_SPANS = (
    "serving.window", "ingest.wait_source", "window.pack", "forest.window",
    "forest.prep", "forest.place", "forest.dispatch", "degrees.window",
    "degrees.prep", "degrees.dispatch", "window.emit", "serving.publish")
#: the root's self time is held under the larger of these two
UNSEEN_LIMIT_MS, UNSEEN_LIMIT_SHARE = 0.3, 0.03


def _scope(kind: str, scope: str, program: str = "jit_step") -> dict:
    return {"kind": kind, "program": program, "scope": scope}


#: name -> (unit, layer, moves, cells, reader): what a ``benchmark`` PR
#: would enter in BENCHMARK.json and benchmarks/layer_metrics/
PROPOSED = {
    **{f"forest_{p}_ms.sat": ("ms", "forest step", "edges_per_s", SAT,
                              _scope("scope_mean_ms", f"forest.{p}"))
       for p in ("chase", "group", "fixpoint", "commit")},
    "forest_latch_ms.sat": ("ms", "forest step", "edges_per_s", SAT[1:],
                            _scope("scope_mean_ms", "forest.latch")),
    # the group's two sorts of the lanes and the sorts ahead of the
    # commit's two table-sized scatters: inside group and commit, so
    # NOT a phase to add to their sum
    "forest_sort_ms.sat": ("ms", "forest step", "edges_per_s", SAT,
                           _scope("scope_mean_ms", "forest.sort")),
    # the fixpoint's once-a-step part (the endpoints relabelled through
    # their groups' representatives, the labels read back): inside the
    # fixpoint, so NOT a phase to add to the sum
    "forest_contract_ms.sat": ("ms", "forest step", "edges_per_s", SAT,
                               _scope("scope_mean_ms", "forest.contract")),
    # the chase's two loops (PR 35), nested in ``forest.chase``: how often
    # the full-width body ran (``forest.wide``: every round of the chase
    # before the slab, a fraction of one since) and the slab's
    # (``forest.narrow``: its compaction, its loop and its write-back;
    # inside the chase, so NOT a phase to add to the sum)
    "forest_narrow_ms.sat": ("ms", "forest step", "edges_per_s", SAT,
                             _scope("scope_mean_ms", "forest.narrow")),
    **{f"forest_{p}_rounds.sat": ("count", "forest step", "edges_per_s", SAT,
                                  _scope("scope_rounds_mean", f"forest.{p}"))
       for p in ("chase", "fixpoint", "wide", "narrow")},
    # the window's host life under its root span (PR 38): the spans are
    # new, so the first later PR whose parent emits them enters these
    # (kind ``span_mean_ms``, data files alone). The root's SELF time is
    # the host time of a window that no span names
    **{name + tag: ("ms", layer, moves, cells,
                    {"kind": "span_mean_ms", "span": span, **extra})
       for tag, moves, cells in ((
           "", "edges_per_s", SAT + V4 + DYN + SIZE),
           (".paced", "window_p95_ms", CC[1:]))
       for name, layer, span, extra in (
           ("emit_ms", "window host step", "window.emit", {}),
           ("publish_ms", "serving", "serving.publish", {}),
           ("window_unseen_ms", "window host step", "serving.window",
            {"self_time": True}))},
    # the vertex-sharded cell: the same phases on chip 0's line, the
    # sorts, and the collectives that make the lanes whole (inside the
    # chase, so NOT a phase to add to the sum)
    **{f"forest_{p}_ms.v4": ("ms", "forest step", "edges_per_s", V4,
                             _scope("scope_mean_ms", f"forest.{p}"))
       for p in ("chase", "group", "fixpoint", "commit", "sort",
                 "exchange", "contract", "narrow")},
    **{f"forest_{p}_rounds.v4": ("count", "forest step", "edges_per_s", V4,
                                 _scope("scope_rounds_mean", f"forest.{p}"))
       for p in ("chase", "fixpoint", "wide", "narrow")},
    "fold_host_ms.v4": ("ms", "window host step", "edges_per_s", V4,
                        {"kind": "span_mean_ms", "span": "forest.window"}),
    "fold_dispatch_ms.v4": ("ms", "window host step", "edges_per_s", V4,
                            {"kind": "span_mean_ms",
                             "span": "forest.dispatch"}),
    "answer_wait_ms.v4": ("ms", "serving", "query_p95_ms", V4,
                          {"kind": "span_mean_ms",
                           "span": "serving.device_wait"}),
    # the degree step (dd-g500-s28): its five scopes partition it, but
    # for the compiler's copy of the table; `degree_gather_ms.dyn` is
    # the accepted metric of the QUERY gather, hence `degree_step_*`
    **{f"degree_step_{p}_ms.dyn": (
        "ms", "degree step", "edges_per_s", DYN,
        _scope("scope_mean_ms", f"degrees.{p}", "jit_degree_step"))
       for p in DEGREE_PHASES},
    # inside `degrees.hist`, so NOT phases to add to the sum; the
    # fallback's mean is over every execution, those that did not take
    # it at 0 (none took it: no reading)
    **{f"degree_step_hist_{p}_ms.dyn": (
        "ms", "degree step", "edges_per_s", DYN,
        _scope("scope_mean_ms", f"degrees.hist.{p}", "jit_degree_step"))
       for p in DEGREE_HIST_PARTS},
    "degree_prep_ms.dyn": ("ms", "window host step", "edges_per_s", DYN,
                           {"kind": "span_mean_ms", "span": "degrees.prep"}),
    "degree_dispatch_ms.dyn": ("ms", "window host step", "edges_per_s", DYN,
                               {"kind": "span_mean_ms",
                                "span": "degrees.dispatch"}),
    "queue_wait_ms.dyn": ("ms", "serving", "query_p95_ms", DYN,
                          {"kind": "span_mean_ms",
                           "span": "serving.queue_wait"}),
    "answer_wait_ms.dyn": ("ms", "serving", "query_p95_ms", DYN,
                           {"kind": "span_mean_ms",
                            "span": "serving.device_wait"}),
    # the sized CC cell (ccsize-g500-s28): the forest step's phases and
    # the size table's back phase (``forest.sizes``: the gather of the
    # old roots' sizes, the sum in fast memory, the sorted scatter; its
    # sort is one more under ``forest.sort``), the sweep's one wait and
    # the size lanes' gather behind the chase
    **{f"forest_{p}_ms.size": ("ms", "forest step", "edges_per_s", SIZE,
                               _scope("scope_mean_ms", f"forest.{p}"))
       for p in ("chase", "group", "fixpoint", "commit", "sizes", "sort",
                 "contract", "narrow")},
    **{f"forest_{p}_rounds.size": ("count", "forest step", "edges_per_s",
                                   SIZE,
                                   _scope("scope_rounds_mean", f"forest.{p}"))
       for p in ("chase", "fixpoint", "wide", "narrow")},
    "fold_host_ms.size": ("ms", "window host step", "edges_per_s", SIZE,
                          {"kind": "span_mean_ms", "span": "forest.window"}),
    "fold_dispatch_ms.size": ("ms", "window host step", "edges_per_s", SIZE,
                              {"kind": "span_mean_ms",
                               "span": "forest.dispatch"}),
    "queue_wait_ms.size": ("ms", "serving", "query_p95_ms", SIZE,
                           {"kind": "span_mean_ms",
                            "span": "serving.queue_wait"}),
    "answer_wait_ms.size": ("ms", "serving", "query_p95_ms", SIZE,
                            {"kind": "span_mean_ms",
                             "span": "serving.device_wait"}),
    "size_gather_ms.size": ("ms", "query kernels", "query_p95_ms", SIZE,
                            {"kind": "program_mean_ms",
                             "program": "jit__gather"}),
}


def add_proposed(cell) -> list:
    added = []
    for name, (unit, layer, moves, cells, reader) in PROPOSED.items():
        if cell.name in cells and name not in cell.per_layer:
            cell.per_layer[name] = {"name": name, "unit": unit,
                                    "layer": layer, "moves": moves}
            cell.readers[name] = {"reader": reader}
            added.append(name)
    return added


def phases_block(m: dict) -> dict:
    """The result document's ``phases``: the scopes' sum against the
    whole step, and the scope's ms over the scope's rounds for the two
    loops. ``fixpoint_ms_per_round`` is what one trip costs (the
    contraction, which runs once a step under a scope of its own, is
    taken out first). ``chase_ms_per_round`` is NOT one trip's cost
    since the chase has two loops (PR 35): ``forest_chase_rounds`` reads
    the more frequent body under ``forest.chase``, the slab's wherever
    it engages, and the chase's ms hold the two full-width opening
    gathers, the wide trips and the compaction beside it; a narrow
    trip costs at most ``narrow_ms`` (compaction and write-back in it)
    over ``forest_narrow_rounds``."""
    if "degree_step_ms.dyn" in m:
        # the degree step has no loop: the scopes' sum against the whole
        # step (the rest is the table's copy), and beside it the
        # histogram's parts, nested in `degrees.hist`
        step = m["degree_step_ms.dyn"]["value"]
        parts = {p: m[f"degree_step_{p}_ms.dyn"]["value"]
                 for p in DEGREE_PHASES if f"degree_step_{p}_ms.dyn" in m}
        if not parts:
            return {}
        nested = {p: m[f"degree_step_hist_{p}_ms.dyn"]["value"]
                  for p in DEGREE_HIST_PARTS
                  if f"degree_step_hist_{p}_ms.dyn" in m}
        return {"sum_ms": sum(parts.values()), "step_ms": step,
                "share": sum(parts.values()) / step,
                **{f"{p}_ms": v for p, v in parts.items()},
                **{f"hist_{p}_ms": v for p, v in nested.items()}}
    step = next((m[k]["value"] for k in m if k.startswith("forest_step_ms")),
                None)
    tag = next((t for t in (".sat", ".v4", ".size")
                if f"forest_chase_ms{t}" in m), ".sat")
    # the exchanges run inside the chase, the sorts inside group,
    # commit and sizes, the contraction inside the fixpoint, the slab
    # inside the chase: beside the sum, not in it
    nested = {f"forest_{p}_ms{tag}": p
              for p in ("exchange", "sort", "contract", "narrow")}
    parts = {k: m[k]["value"] for k in m
             if k.startswith("forest_") and k.endswith("_ms" + tag)
             and k != "forest_step_ms" + tag and k not in nested}
    if not (step and parts):
        return {}
    out = {"sum_ms": sum(parts.values()), "step_ms": step,
           "share": sum(parts.values()) / step}
    # what a scope holds beside its loop under a scope of its own
    once = {"fixpoint": m.get(f"forest_contract_ms{tag}", {"value": 0.0})}
    for p in ("chase", "fixpoint"):
        ms = m.get(f"forest_{p}_ms{tag}")
        rounds = m.get(f"forest_{p}_rounds{tag}")
        if ms and rounds and rounds["value"]:
            beside = once.get(p, {"value": 0.0})["value"]
            out[f"{p}_ms_per_round"] = (ms["value"] - beside) / rounds["value"]
    for key, p in nested.items():
        if m.get(key):
            out[f"{p}_ms"] = m[key]["value"]
            out[f"{p}_share"] = m[key]["value"] / step
    return out


def _exchanges(ctx: dict) -> dict:
    """The collectives of a step, counted: per execution of ``jit_step``
    the ops events under ``forest.exchange`` on chip 0's line (one
    ``%psum`` event an all-reduce on a v5e), and from the
    ``forest.window`` spans the shard count and the largest owner's
    share of a window's touched ids."""
    from statistics import fmean

    from benchmarks.lib import scope_reduce

    runs = scope_reduce.scope_events(
        scope_reduce._scoped(ctx), "jit_step", "forest.exchange",
        ctx["lo"], ctx["hi"])
    ids = [[scope_reduce.op_id(e[0]) for e in events] for _r, events in runs]
    out = {"exchanges_per_step": fmean(len(x) for x in ids),
           "exchange_instructions": sorted({i for x in ids for i in x})[:12]}
    shares = [e["attrs"]["owner_max_share"] for e in ctx["spans"]
              if e["name"] == "forest.window"
              and "owner_max_share" in e.get("attrs", {})]
    if shares:
        out["owner_max_share_mean"] = fmean(shares)
        out["owner_max_share_max"] = max(shares)
        out["shards"] = next(
            e["attrs"]["shards"] for e in ctx["spans"]
            if e["name"] == "forest.window" and "shards" in e.get("attrs", {}))
    return out


def hist_fallback_block(planes: list, lo: float, hi: float) -> dict:
    """How many executions of the degree step took the histogram's
    fallback: those with an op under ``degrees.hist.fallback`` (the
    branch that did not run leaves no op in the trace). A step without
    the scope reads 0 of its executions."""
    from benchmarks.lib import scope_reduce, trace_reduce

    runs = scope_reduce.executions(planes, "jit_degree_step", lo, hi)
    ops = trace_reduce.line_of(trace_reduce.device_planes(planes)[0],
                               trace_reduce.OPS_LINE)["events"]
    starts = sorted(s for n, s, _d in ops
                    if "degrees.hist.fallback" in scope_reduce.op_path(n))
    took = 0
    for a, b in runs:
        i = bisect.bisect_left(starts, a)
        took += i < len(starts) and starts[i] < b
    return {"executions": len(runs), "hist_fallback_executions": took,
            "hist_fallback_share": took / len(runs)}


def _hist_fallback(ctx: dict) -> dict:
    from benchmarks.lib import scope_reduce

    return hist_fallback_block(scope_reduce._scoped(ctx), ctx["lo"],
                               ctx["hi"])


def _table_ops(ctx: dict) -> list:
    """The ops of the per-window program that have an operand or a
    result with a row per vertex (the instruction's text names the
    shape ``[<rows>]``): ``[instruction, scope path, ms]`` over the
    first traced execution. What shows whether a step's cost follows
    the window: for the degree step these are the gather, the scatter
    and the compiler's copy of the table, and nothing else."""
    from benchmarks.lib import scope_reduce, trace_reduce

    cell = ctx["cell"]
    rows = f"[{cell.algorithm().table_rows(cell.config)}]"
    scoped = scope_reduce._scoped(ctx)
    a, b = scope_reduce.executions(
        scoped, STEP_PROGRAM[cell.name], ctx["lo"], ctx["hi"])[0]
    ops = trace_reduce.line_of(trace_reduce.device_planes(scoped)[0],
                               trace_reduce.OPS_LINE)["events"]
    return [[scope_reduce.op_id(n), "/".join(scope_reduce.op_path(n)),
             d / 1e6] for n, s, d in ops if a <= s < b and rows in n]


def _span_counts(ctx: dict) -> dict:
    """Span events per window and per sweep inside the measured window."""
    by_name: dict = {}
    for e in ctx["spans"]:
        by_name[e["name"]] = by_name.get(e["name"], 0) + 1
    windows = by_name.get("forest.window", 0) + by_name.get(
        "degrees.window", 0)
    sweeps = by_name.get("serving.answer", 0)
    ingest = sum(by_name.get(n, 0) for n in INGEST_SPANS)
    serve = sum(by_name.get(n, 0) for n in (
        "serving.queue_wait", "serving.answer", "serving.size_lookup",
        "serving.device_wait"))
    return {"by_name": by_name,
            "per_window": ingest / windows if windows else None,
            "per_sweep": serve / sweeps if sweeps else None}


def _children(every: list) -> dict:
    """Span id -> the span events whose ``parent`` it is."""
    kids: dict = {}
    for e in every:
        if "parent" in e:
            kids.setdefault(e["parent"], []).append(e)
    return kids


def window_block(spans: list, every: list) -> dict:
    """The result document's ``window``: the host life of a window under
    its root span ``serving.window``, over the roots in ``spans`` (those
    that ended inside the measured window), their descendants looked up
    in ``every`` (all the run's span events). Per root, in ms: the mean
    of the root, of each child by name, and of the root's SELF time,
    which is the host time no span names (``unseen_limit_ms`` is what
    it is held under: the larger of 0.3 ms and 3% of the root less
    ``ingest.wait_source``), with its median and its largest; WHERE the
    self time lies (``gaps_ms``: the mean stretch before each child,
    from the end of the child before it, and after the last); the mean
    and the histogram of the attribute ``in_flight``; span events a
    window (the root, its children and theirs). Empty for a program
    without the root."""
    from statistics import fmean, median

    roots = [e for e in spans if e["name"] == "serving.window"]
    if not roots:
        return {}
    kids = _children(every)
    by_child: dict = {}
    gaps: dict = {}
    self_ms, events = [], 0
    for r in roots:
        mine = sorted(kids.get(r["sid"], []), key=lambda c: c["t0"])
        events += 1 + len(mine) + sum(
            len(kids.get(c["sid"], [])) for c in mine)
        self_ms.append(1e3 * (r["dur_s"] - sum(c["dur_s"] for c in mine)))
        edge = r["t0"]
        for c in mine:
            by_child[c["name"]] = by_child.get(c["name"], 0.0) + c["dur_s"]
            key = "before:" + c["name"]
            gaps[key] = gaps.get(key, 0.0) + c["t0"] - edge
            edge = c["t0"] + c["dur_s"]
        gaps["after:last"] = gaps.get("after:last", 0.0) + (
            r["t0"] + r["dur_s"] - edge)
    n = len(roots)
    root_ms = 1e3 * fmean(r["dur_s"] for r in roots)
    children = {k: 1e3 * v / n for k, v in sorted(by_child.items())}
    in_flight = [r["attrs"]["in_flight"] for r in roots
                 if "in_flight" in r.get("attrs", {})]
    hist: dict = {}
    for v in in_flight:
        hist[str(v)] = hist.get(str(v), 0) + 1
    out = {"windows": n, "root_ms": root_ms, "children_ms": children,
           "self_ms": fmean(self_ms), "self_ms_p50": median(self_ms),
           "self_ms_max": max(self_ms),
           "gaps_ms": {k: 1e3 * v / n for k, v in gaps.items()},
           "unseen_limit_ms": max(UNSEEN_LIMIT_MS, UNSEEN_LIMIT_SHARE * (
               root_ms - children.get("ingest.wait_source", 0.0))),
           "events_per_window": events / n}
    if in_flight:
        out.update(in_flight_mean=fmean(in_flight), in_flight_hist=hist,
                   ring=max(r["attrs"].get("ring", 0) for r in roots))
    return out


def serving_block(spans: list, every: list) -> dict:
    """The result document's ``serving``: over the sweeps in ``spans``
    (``serving.answer``, those that ended inside the measured window),
    a sweep's ``reads`` (the device reads it enqueued before its first
    fetch) and ``late_reads`` (those of them, the first aside, whose
    ``serving.device_wait`` took over 1 ms: a fold slipped between two
    dispatches), as the span says them, and the mean
    ``serving.device_wait`` of a sweep's first read and of a later one,
    in ms, the waits looked up in ``every`` under the sweep or under
    its ``serving.size_lookup``. A program whose sweeps do not count
    their reads gives the waits alone."""
    from statistics import fmean

    sweeps = [e for e in spans if e["name"] == "serving.answer"]
    if not sweeps:
        return {}
    kids = _children(every)
    first, later = [], []
    for a in sweeps:
        under = kids.get(a["sid"], [])
        under = under + [g for c in under for g in kids.get(c["sid"], [])]
        waits = sorted((e for e in under
                        if e["name"] == "serving.device_wait"),
                       key=lambda e: e["t0"])
        first += [1e3 * w["dur_s"] for w in waits[:1]]
        later += [1e3 * w["dur_s"] for w in waits[1:]]
    out = {"sweeps": len(sweeps),
           "first_wait_ms": fmean(first) if first else None,
           "later_wait_ms": fmean(later) if later else None}
    for key in ("reads", "late_reads"):
        said = [a["attrs"][key] for a in sweeps if key in a.get("attrs", {})]
        if said:
            out[f"{key}_per_sweep"] = fmean(said)
    return out


def _serving(ctx: dict) -> dict:
    return serving_block(ctx["spans"], ctx["run"]["spans"])


def _window_tree(ctx: dict) -> dict:
    return window_block(ctx["spans"], ctx["run"]["spans"])


def _clock_check(ctx: dict) -> dict:
    """A span's ``t0`` placed on the profiler's clock through the traced
    slice's bracket, against the same span's own annotation there."""
    from benchmarks.lib import trace_reduce

    a = ctx["traced"]["lo"]           # perf_counter just before the mark
    mark = ctx["lo"]                  # the mark's start, profiler's clock
    out = {}
    for name in ("forest.window", "degrees.window", "serving.answer"):
        ann = sorted(
            s for p in ctx["planes"]
            if not trace_reduce.DEVICE_PLANE_RE.match(p["name"])
            for ln in p["lines"] for n, s, _d in ln["events"] if n == name)
        mapped = sorted(mark + (e["t0"] - a) * 1e9
                        for e in ctx["run"]["spans"] if e["name"] == name)
        mapped = [t for t in mapped if ctx["lo"] <= t <= ctx["hi"]]
        ann = [t for t in ann if ctx["lo"] <= t <= ctx["hi"]]
        # each mapped start against the nearest annotation start
        diffs = [min(abs(t - s) for s in ann) / 1e3 for t in mapped if ann]
        if diffs:
            out[name] = {"n": len(diffs),
                         "median_us": sorted(diffs)[len(diffs) // 2]}
    return out


def _dump(ctx: dict, out_dir: str, n_exec: int) -> None:
    """The stretch of the trace that holds the first whole executions of
    the cell's per-window program in the window, as plain lists: the device's modules and
    ops (names uncut, scopes in them), and the host annotations of the
    program's spans and the window's mark. What a test recording is cut
    from, and what to look at by hand."""
    from benchmarks.lib import scope_reduce, trace_reduce

    os.makedirs(out_dir, exist_ok=True)
    scoped = scope_reduce._scoped(ctx)
    runs = scope_reduce.executions(scoped, STEP_PROGRAM[ctx["cell"].name],
                                   ctx["lo"], ctx["hi"])[:n_exec]
    lo, hi = runs[0][0] - 2e5, runs[-1][1] + 2e5
    span_names = {e["name"] for e in ctx["run"]["spans"]}
    planes = []
    for plane in scoped:
        planes.append({"name": plane["name"], "lines": [
            {"name": ln["name"],
             "events": [e for e in ln["events"]
                        if e[1] >= lo and e[1] + e[2] <= hi]}
            for ln in plane["lines"]]})
    for plane in ctx["planes"]:
        if trace_reduce.DEVICE_PLANE_RE.match(plane["name"]):
            continue
        lines = []
        for ln in plane["lines"]:
            events = [e for e in ln["events"]
                      if e[0] == trace_reduce.WINDOW_MARK
                      or (e[0] in span_names and e[1] + e[2] >= lo
                          and e[1] <= hi)]
            if events:
                lines.append({"name": ln["name"], "events": events})
        if lines:
            planes.append({"name": plane["name"], "lines": lines})
    with open(os.path.join(out_dir, "scoped.json"), "w") as f:
        json.dump({"lo": lo, "hi": hi, "window": [ctx["lo"], ctx["hi"]],
                   "planes": planes}, f)
    # the program's span events inside the measured window, as they
    # were emitted, and the collector's pauses beside them: where a
    # window's host time went, stall by stall
    with open(os.path.join(out_dir, "spans.json"), "w") as f:
        json.dump({"spans": ctx["spans"],
                   "gc_pauses": ctx["run"]["stats"]["gc_pauses"]}, f)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--dump", default=None,
                    help="directory for the first executions' events")
    ap.add_argument("--dump-executions", type=int, default=3)
    args = ap.parse_args(argv)

    result_out = os.fdopen(os.dup(1), "w")
    os.dup2(2, 1)
    sys.stdout = sys.stderr

    from benchmarks.lib import cellrun, lastline, scope_reduce, spec
    from benchmarks.lib.cellrun import log

    cell = spec.load_cell(args.workload)
    backend = cellrun.start_backend()
    from gelly_streaming_tpu.utils.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    added = add_proposed(cell)
    extras: dict = {}

    def tolerant(reader):
        def read(spec, ctx):   # a scope that is not there costs the
            try:               # metric, not the run's other numbers
                return reader(spec, ctx)
            except cellrun.trace_reduce.TraceError as e:
                log(f"{spec}: {e}")
                return None
        return read

    cellrun.READERS.update(
        {k: tolerant(fn) for k, fn in scope_reduce.READERS.items()})

    def read_extras(_spec: dict, ctx: dict):
        """Rides the harness's own pass over the readers for its
        context (the loaded trace, the spans); reports no metric."""
        readers = [("events", _span_counts), ("clock", _clock_check),
                   ("window", _window_tree), ("serving", _serving)]
        if cell.name in V4:
            readers.append(("exchanges", _exchanges))
        if cell.name in DYN + SIZE:
            readers.append(("table_ops", _table_ops))
        if cell.name in DYN:
            readers.append(("hist_fallback", _hist_fallback))
        for key, fn in readers:
            try:
                extras[key] = fn(ctx)
            except Exception as e:   # the run's numbers matter more
                log(f"{key}: {e!r}")
        if args.dump:
            try:
                _dump(ctx, args.dump, args.dump_executions)
            except Exception as e:
                log(f"dump: {e!r}")
        return None

    cellrun.READERS["tool_extras"] = read_extras
    cell.per_layer["_tool_extras"] = {"name": "_tool_extras", "unit": "-"}
    cell.readers["_tool_extras"] = {"reader": {"kind": "tool_extras"}}
    try:
        doc = cellrun.run_cell(cell, args.seed, args.seconds, True,
                               t_process=_T_PROCESS, backend=backend,
                               work_root=ROOT)
    except Exception:
        import traceback

        traceback.print_exc()
        log("no result")
        return 2
    del cell.per_layer["_tool_extras"]
    phases = phases_block(doc["metrics"])
    if phases:
        phases.update(extras.pop("exchanges", {}))
        phases.update(extras.pop("hist_fallback", {}))
        extras["phases"] = phases
    doc.update(extras)
    doc["proposed"] = added
    line = json.dumps(doc)
    # a proposed metric whose scope did not run (the fallback no window
    # took, a scope the parent of the PR that adds it lacks) is left out
    problems = lastline.validate(
        line, required={n: u for n, u in cell.units("per_layer").items()
                        if n not in added},
        allowed={**cell.units("end_to_end"), **cell.units("per_layer")},
        trace=True, chips=cell.chips)
    for p in problems:
        log(f"contract: {p}")
    result_out.write(line + "\n")
    result_out.flush()
    return 3 if problems else 0


if __name__ == "__main__":
    sys.exit(main())
