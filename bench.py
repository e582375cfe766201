"""Benchmarks: the five BASELINE.json configs, measured END-TO-END.

Default run prints ONE JSON line: the headline streaming-CC metric
{"metric", "value", "unit", "vs_baseline", "device"}. ``python bench.py
--all`` additionally measures the other configs and writes the detail
table to ``chiprun_out/bench_all.json`` (stderr log only — stdout stays
one line).

Headline: the timed path is the whole system — corpus FILE -> native
chunk parser -> Windower -> vertex mapping -> device blocks -> CC
fold/combine summary — not a pre-staged device kernel loop. The
kernel-only number is still reported in the detail table for the
device-side story.

``vs_baseline``: ratio against a COMPILED C++ implementation of the
reference's own architecture on the same file — parse + per-partition
window folds into hash-map union-find + sequential per-window merges
(``native/ingest.cpp:cc_baseline_run``; the shapes of
``SummaryBulkAggregation.java:68-90`` and ``summaries/DisjointSet.java``).
That baseline is strictly FASTER than the actual reference (JVM Flink with
serialization + network shuffles), so the printed ratio is a conservative
lower bound on the true advantage; the interpreted-Python tier of the same
model (the execution model the reference actually runs per record) is
reported in the detail table as `python_unionfind_eps`.

ONE PROCESS FOR EACH CHIP. A chip belongs to one process at a time: a
parent that has initialised a JAX backend holds it, and a child that
needs it then fails or hangs. So a chip measurement takes one of two
shapes, never a mix:

- everything in THIS process: the default run, ``--all``, ``--northstar``
  and ``--serving`` call :func:`require_tpu` first (no TPU -> non-zero
  exit, nothing printed) and run every config in-process, sharing one
  persistent compilation cache (``utils/compile_cache.py``);
- a parent that never initialises a backend: ``--latency-curve`` stays
  off JAX and runs each point as a SEQUENTIAL child that calls
  :func:`require_tpu` itself.

The CPU scenario drivers (``--cpu``, ``--chaos``, ``--storm``,
``--transport``, ``--serving --rpc|--sharded``, ``--ingest``,
``--eventtime``, ``--autotune``) pin themselves and every child to the
CPU; they are correctness harnesses that report counts, never need the
chip, and a chip-holding parent may start them.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def require_tpu() -> dict:
    """The device every chip measurement names, as JAX reports it. Exits
    non-zero, saying why, when ``jax.devices()[0]`` is not a TPU: JAX
    falls back to the CPU without a word when libtpu finds no chip, and a
    rate measured there must never print under a device metric's name."""
    from gelly_streaming_tpu.utils.compile_cache import enable_compile_cache
    from gelly_streaming_tpu.utils.profiling import describe_device

    enable_compile_cache()
    device = describe_device()
    if device["platform"] != "tpu":
        log(f"bench: no TPU — jax.devices()[0] is {device} "
            f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}); chip "
            "measurements do not fall back to another backend")
        sys.exit(1)
    log(f"bench: device {device}")
    return device


def run_configs(configs, detail: dict, flush) -> None:
    """Run ``(key, fn)`` configs IN THIS PROCESS, recording each result
    under ``detail[key]`` and flushing after every one. A config that
    raises is logged with its traceback and the rest still run — one chip
    call should say everything it can — but the run then exits non-zero
    naming every failed config: a hole is never a finished artifact."""
    import traceback

    failed = []
    for key, fn in configs:
        log(f"bench: {key}...")
        try:
            detail[key] = fn()
        except Exception:
            log(traceback.format_exc())
            detail[key] = None
            failed.append(key)
        flush()
    if failed:
        sys.exit(f"bench: {len(failed)} config(s) failed: {', '.join(failed)}")


STEADY_REPS = 3  # median-of-N steady passes per e2e config (verdict #1c)


def median_steady(one_pass, n: int = STEADY_REPS):
    """Warm once (pays jit compiles), then ``n`` steady passes; returns
    (median_pass_result, all_eps) keyed by the 'eps'/first element."""
    one_pass()
    passes = [one_pass() for _ in range(n)]
    key = (lambda p: p["eps"]) if isinstance(passes[0], dict) else (lambda p: p)
    passes.sort(key=key)
    return passes[n // 2], [round(key(p), 1) for p in passes]


def make_stream(n_vertices: int, n_edges: int, seed: int = 7):
    """Power-law-ish random edge stream (Zipf endpoints, like social graphs)."""
    rng = np.random.default_rng(seed)
    u = rng.random(n_edges)
    v = rng.random(n_edges)
    a = 0.75  # skew
    src = np.minimum((n_vertices * u**a * rng.random(n_edges)).astype(np.int64), n_vertices - 1)
    dst = np.minimum((n_vertices * v**a * rng.random(n_edges)).astype(np.int64), n_vertices - 1)
    return src.astype(np.int32), dst.astype(np.int32)


# --------------------------------------------------------------------- #
# Headline: END-TO-END streaming Connected Components on the corpus file
# --------------------------------------------------------------------- #
CORPUS = "livejournal"
WINDOW = 1 << 20
ID_BOUND = 1 << 21  # surrogate R-MAT scale 21; the real corpus needs 1<<23


def _corpus_path():
    from gelly_streaming_tpu import datasets

    path, is_real = datasets.ensure_corpus(CORPUS)
    return path, is_real


def _id_bound(path: str, is_real: bool) -> int:
    if not is_real:
        return ID_BOUND
    # real LiveJournal: ids < 4,847,571
    return 1 << 23


def bench_cc_e2e(path: str, vdict_factory, n_edges: int,
                 window: int = WINDOW, carry: str = "auto") -> dict:
    """file -> parse -> window -> vertex map -> device CC, warm + steady.

    ``carry`` pins the CC carry strategy (auto/forest/host/dense — see
    ``library/connected_components.py``); the result records which one
    actually ran so artifacts are self-describing."""
    from gelly_streaming_tpu import datasets
    from gelly_streaming_tpu.core.window import CountWindow
    from gelly_streaming_tpu.library import ConnectedComponents

    def one_pass():
        stream = datasets.stream_file(
            path, window=CountWindow(window), vertex_dict=vdict_factory(),
            prefetch_depth=2,
        )
        agg = ConnectedComponents(carry=carry)
        lat = []
        t0 = time.perf_counter()
        last_t = t0
        last = None
        for last in stream.aggregate(agg):
            now = time.perf_counter()
            lat.append(now - last_t)
            last_t = now
        # sync INSIDE dt: the aggregate loop only DISPATCHES async device
        # work, so without this the measured rate is an enqueue rate, not
        # throughput (on the CPU backend the gap measured >100x; on TPU
        # it is the in-flight pipeline drain). Component materialization
        # stays lazy and outside the rate.
        agg.sync()
        dt = time.perf_counter() - t0
        lat_ms = np.asarray(lat) * 1e3
        return {
            "eps": n_edges / dt,
            "p50_ms": float(np.percentile(lat_ms, 50)),
            "p95_ms": float(np.percentile(lat_ms, 95)),
            "components": len(last.component_sets()),
            "carry": agg._cc_mode,
        }

    out, eps_all = median_steady(one_pass)
    out["eps_all"] = eps_all
    return out


BASELINE_REPS = 3  # median-of-N: one noisy C++ run must not set the ratio


def bench_cc_baseline(path: str) -> tuple:
    """Compiled reference-architecture CC on the same file (parse included).

    The CC fold runs ``BASELINE_REPS`` times and the MEDIAN is used — the
    round-2 verdict flagged the ratio moving ~2x between runs on a single
    baseline execution. Returns (stats, src, dst) — the parsed columns
    ride along so --all does not re-parse the corpus."""
    from gelly_streaming_tpu import native

    t0 = time.perf_counter()
    s, d, _ = native.parse_edge_file(path)
    t_parse = time.perf_counter() - t0
    runs = [native.cc_baseline(s, d, window=WINDOW) for _ in range(BASELINE_REPS)]
    secs = float(np.median([r[0] for r in runs]))
    comps = runs[0][1]
    return {
        "eps": len(s) / (t_parse + secs),
        "parse_s": t_parse,
        "cc_s": secs,
        "cc_s_all": [round(r[0], 3) for r in runs],
        "components": comps,
        "n_edges": len(s),
    }, s, d


def bench_cc_baseline_binary(bin_path: str) -> dict:
    """Compiled reference-architecture CC fed the binary corpus — the
    apples-to-apples comparator for the binary device path (both sides
    relieved of text parsing; the baseline's load+convert is counted).
    Median-of-``BASELINE_REPS`` CC folds, like the text baseline."""
    import numpy as np

    from gelly_streaming_tpu import datasets, native

    t0 = time.perf_counter()
    chunks = list(datasets.iter_binary_chunks(bin_path, 1 << 22))
    s = np.concatenate([c[0] for c in chunks]).astype(np.int64)
    d = np.concatenate([c[1] for c in chunks]).astype(np.int64)
    t_load = time.perf_counter() - t0
    runs = [native.cc_baseline(s, d, window=WINDOW) for _ in range(BASELINE_REPS)]
    secs = float(np.median([r[0] for r in runs]))
    comps = runs[0][1]
    return {
        "eps": len(s) / (t_load + secs),
        "load_s": t_load,
        "cc_s": secs,
        "cc_s_all": [round(r[0], 3) for r in runs],
        "components": comps,
        "n_edges": len(s),
    }


def bench_cc_e2e_device(
    bin_path: str, bound: int, n_edges: int, window: int = WINDOW
) -> dict:
    """Binary corpus -> memmap -> device put -> DEVICE vertex compaction ->
    CC summary (stream_file(device_encode=True)), warm + steady."""
    from gelly_streaming_tpu import datasets
    from gelly_streaming_tpu.core.window import CountWindow
    from gelly_streaming_tpu.library import ConnectedComponents

    def one_pass():
        stream = datasets.stream_file(
            bin_path, window=CountWindow(window), device_encode=True,
            min_vertex_capacity=bound, prefetch_depth=2,
        )
        agg = ConnectedComponents()
        lat = []
        t0 = time.perf_counter()
        last_t = t0
        last = None
        for last in stream.aggregate(agg):
            now = time.perf_counter()
            lat.append(now - last_t)
            last_t = now
        agg.sync()  # throughput, not enqueue rate
        dt = time.perf_counter() - t0
        lat_ms = np.asarray(lat) * 1e3
        return {
            "eps": n_edges / dt,
            "p50_ms": float(np.percentile(lat_ms, 50)),
            "p95_ms": float(np.percentile(lat_ms, 95)),
            "components": len(last.component_sets()),
            "carry": agg._cc_mode,
        }

    out, eps_all = median_steady(one_pass)
    out["eps_all"] = eps_all
    return out


def bench_cc_e2e_device_text(path: str, cap_hint: int, n_edges: int) -> dict:
    """GENERAL text ingest, end-to-end: text file -> AVX-512 chunk parse
    (arbitrary non-negative int32 ids, no dense-id declaration) -> device
    put -> DEVICE dictionary compaction (growth mode, host novelty
    tracking) -> CC summary. This is the framework's answer to the
    reference's native habitat (``env.readTextFile`` +
    per-line mappers, ``ConnectedComponentsExample.java:106-118``)."""
    from gelly_streaming_tpu import datasets
    from gelly_streaming_tpu.core.window import CountWindow
    from gelly_streaming_tpu.library import ConnectedComponents

    def one_pass():
        stream = datasets.stream_file(
            path, window=CountWindow(WINDOW), device_encode=True,
            dense_ids=False, min_vertex_capacity=cap_hint,
            prefetch_depth=2,
        )
        agg = ConnectedComponents()
        lat = []
        t0 = time.perf_counter()
        last_t = t0
        last = None
        for last in stream.aggregate(agg):
            now = time.perf_counter()
            lat.append(now - last_t)
            last_t = now
        agg.sync()  # throughput, not enqueue rate
        dt = time.perf_counter() - t0
        lat_ms = np.asarray(lat) * 1e3
        return {
            "eps": n_edges / dt,
            "p50_ms": float(np.percentile(lat_ms, 50)),
            "p95_ms": float(np.percentile(lat_ms, 95)),
            "components": len(last.component_sets()),
            "carry": agg._cc_mode,
        }

    out, eps_all = median_steady(one_pass)
    out["eps_all"] = eps_all
    return out


def auto_superbatch_k(window: int, target: int = 1 << 18) -> int:
    """Default superbatch K for a window size: enough windows per group
    to put ~256k edges in one fused dispatch (where the measured
    per-window fixed costs amortize to noise), capped at 256."""
    return max(1, min(256, target // max(1, window)))


def bench_latency_window(binp: str, bound: int, window: int,
                         n_edges: int = 1 << 22,
                         superbatch: int = 1,
                         algo: str = "cc",
                         id_fold: int = 0) -> dict:
    """One point of the latency/throughput curve (round-3 verdict missing
    #1: the low-latency micro-batch configuration was never measured):
    one streaming algorithm over a corpus prefix at the given
    CountWindow, recording per-window p50/p95 latency alongside
    throughput. Small windows buy latency with dispatch overhead; the
    curve quantifies the trade.

    ``superbatch=K > 1`` measures the fused K-window path: one dispatch
    per K windows, per-window emission values unchanged (ISSUE 2 for
    CC; ISSUE 14 generalized the group-fold contract so ``algo=``
    selects any carry that declares one — ``cc``, ``pagerank``,
    ``bipartiteness``). The stream flows through the SAME shared
    packing helper as production ingest (``Windower.pack_window_cols``
    via the count-window column fast path), so curve numbers measure
    the real path. Note the p50/p95 under superbatch measure EMISSION
    INTER-ARRIVAL — a group's K records surface together, so p50
    collapses and p95 reflects the group period (the latency grain the
    superbatch trades away).

    ``id_fold=M > 0`` folds the prefix's vertex ids into ``[0, M)``
    (``id % M``). The PageRank cell uses it: at the corpus's full 2M-id
    space its per-window cost is the vcap-sized fixpoint (~300 ms a
    window — compute, which no dispatch fusion removes and nobody
    claims to), so the CLIFF configuration — the one the superbatch
    targets — is high-frequency windows over a modest graph, the
    incremental-rank serving shape. The artifact records the fold."""
    from gelly_streaming_tpu import datasets
    from gelly_streaming_tpu.core.stream import SimpleEdgeStream
    from gelly_streaming_tpu.core.window import CountWindow

    src, dst = _corpus_cols(binp, n_edges)
    if id_fold:
        src = src % id_fold
        dst = dst % id_fold
        bound = id_fold

    def make_agg():
        if algo == "cc":
            from gelly_streaming_tpu.library import ConnectedComponents

            return ConnectedComponents(superbatch=superbatch)
        if algo == "pagerank":
            from gelly_streaming_tpu.library import IncrementalPageRank

            return IncrementalPageRank(superbatch=superbatch)
        if algo == "bipartiteness":
            from gelly_streaming_tpu.library import BipartitenessCheck

            return BipartitenessCheck(superbatch=superbatch)
        raise ValueError(f"unknown algo {algo!r}")

    def one_pass():
        stream = SimpleEdgeStream(
            (src, dst), window=CountWindow(window),
            vertex_dict=datasets.IdentityDict(bound),
        )
        lat = []
        t0 = time.perf_counter()
        last_t = t0
        agg = make_agg()
        for _ in agg.run(stream):
            now = time.perf_counter()
            lat.append(now - last_t)
            last_t = now
        agg.sync()  # throughput, not enqueue rate
        dt = time.perf_counter() - t0
        lat_ms = np.asarray(lat) * 1e3
        out = {
            "window": window,
            "eps": len(src) / dt,
            "p50_ms": float(np.percentile(lat_ms, 50)),
            "p95_ms": float(np.percentile(lat_ms, 95)),
            "carry": getattr(agg, "_cc_mode", None)
            or getattr(agg, "_bp_mode", None),
        }
        if algo != "cc":
            out["algo"] = algo
        if id_fold:
            out["id_fold"] = id_fold
        if superbatch > 1:
            out["superbatch"] = superbatch
        return out

    out, eps_all = median_steady(one_pass)
    out["eps_all"] = eps_all
    return out


LATENCY_SWEEP_WEXP = (10, 12, 13, 14, 16, 18, 20, 22, 24)

#: per-algorithm latency-curve cells (ISSUE 14): every carry that
#: declares a group fold gets a keyed per-window vs superbatch cell at
#: the cliff window (1024 edges). Edge budgets differ by cost shape:
#: PageRank re-converges over the ACCUMULATED graph per window, so its
#: prefix stays small; the cover carry pays O(window) per window like
#: CC and takes a 1M-edge prefix.
#: (algo, n_edges, id_fold, superbatch_k): the per-algorithm cliff
#: cells. Bipartiteness rides auto-K like CC (its host cover union-find
#: has the CC cost shape — fixed per-window overhead the fusion
#: amortizes). PageRank folds ids into a 16k-vertex space (the
#: incremental-rank serving shape: high-frequency windows over a modest
#: graph — at the full 2M-id bound its per-window cost is the
#: vcap-sized fixpoint) and uses K=16: its per-window cost is DOMINATED
#: by the warm-start fixpoint (iterations x accumulated edge lanes),
#: which fusion cannot remove — the fused cell records the honest
#: ~parity on CPU (the dispatch share it amortizes is ~5% here; a win
#: needs a backend where dispatch latency is the cost) while larger K
#: would pay the group's edge-capacity quantization against pure
#: compute.
LATENCY_ALGO_CELLS = (
    ("pagerank", 1 << 15, 1 << 14, 16),
    ("bipartiteness", 1 << 20, 0, 0),  # 0 -> auto_superbatch_k
)
LATENCY_ALGO_WINDOW = 1024


def run_latency_curve(artifact: str, cpu: bool = False,
                      algos_only: bool = False) -> dict:
    """The full window-size sweep 1k -> 16M as a KEYED artifact (ISSUE 2
    satellite: the cliff was tracked only by a one-off BENCH_CPU entry).
    Per window size: the per-window path and, where the superbatch can
    bite (window <= 256k), the fused path at :func:`auto_superbatch_k`.
    Each point runs in a fresh SEQUENTIAL subprocess and this parent
    never initialises a JAX backend (the module docstring's second
    shape), so on the chip each child can take it; the artifact flushes
    incrementally and is marked ``incomplete`` until every point landed.

    Per-algorithm cells (ISSUE 14): every carry that declares a group
    fold (``summaries/groupfold.py``) gets a keyed per-window vs
    superbatch cell at the 1024-edge cliff window under ``algos`` —
    PageRank and bipartiteness beside the CC ``points`` — guarded by
    ``tools/benchguard`` ``min:`` watches. ``algos_only=True``
    (``--latency-curve --algos``) refreshes ONLY those cells, merging
    into the existing artifact's CC sweep (the full sweep re-measures
    everything).

    Obs evidence (ISSUE 3 satellite): the sweep DRIVER records one span
    per point (``bench.latency_point``: window size, variant, K,
    subprocess rc, measured eps) to an event log keyed next to the
    artifact. Driver spans time the whole subprocess, so the log
    documents the sweep's shape and wall cost, flushed incrementally
    like the artifact itself."""
    import subprocess

    from gelly_streaming_tpu import datasets, obs

    path, is_real = _corpus_path()
    bound = _id_bound(path, is_real)
    binp = datasets.binary_cache(path)
    corpus_edges = int(np.sum(
        [len(c[0]) for c in datasets.iter_binary_chunks(binp, 1 << 24)]
    ))
    doc = {
        "note": (
            "streaming latency/throughput vs window size, per-window "
            "vs superbatch (fused K-window dispatch). points = the CC "
            "sweep (same 4M-edge prefix + identity mapping as "
            "BENCH_CPU.json's historical latency_curve for "
            "comparability); algos = per-algorithm cells at the "
            "1024-edge cliff window for every carry declaring a group "
            "fold (pagerank over a 32k-edge prefix folded into a "
            "16k-vertex space — its per-window fixpoint re-converges "
            "the ACCUMULATED graph — bipartiteness over 1M). "
            "Superbatch p50/p95 measure "
            "emission inter-arrival (a group's records surface "
            "together)."
        ),
        "platform": "cpu-xla" if cpu else "tpu",
        "corpus": path,
        "corpus_edges": corpus_edges,
        "points": {},
        "algos": {},
        "incomplete": True,
    }
    prev_incomplete = False
    if algos_only:
        # keep the committed CC sweep; refresh only the algo cells
        try:
            with open(artifact) as f:
                prev = json.load(f)
            doc["points"] = prev.get("points", {})
            prev_incomplete = "incomplete" in prev
        except (OSError, ValueError):
            prev_incomplete = True  # no committed CC sweep to carry
    obs_path = (
        artifact[: -len(".json")] if artifact.endswith(".json") else artifact
    ) + "_OBS.jsonl"
    doc["obs_log"] = os.path.basename(obs_path)
    obs_sink = obs.JsonlSink(obs_path)
    obs_sink.emit({"kind": "meta", "bench": "latency_curve",
                   "artifact": os.path.basename(artifact)})
    obs.enable()
    obs.attach_sink(obs_sink)
    # each child places itself: pinned to the CPU, or refusing to run
    # without the chip (this parent holds no backend, so the child can
    # take it)
    pin = (
        "import jax; jax.config.update('jax_platforms','cpu'); "
        "import bench, json; "
        if cpu else "import bench, json; bench.require_tpu(); "
    )

    def flush():
        with open(artifact, "w") as f:
            json.dump(doc, f, indent=2)
        obs_sink.write()

    def run_point(window, n_e, name, kk, algo="cc", id_fold=0):
        """One subprocess point; returns (result|None, failed)."""
        with obs.span(
            "bench.latency_point",
            {"window": window, "variant": name, "k": kk, "algo": algo},
        ) as sp:
            try:
                out = subprocess.run(
                    [sys.executable, "-c",
                     f"{pin}"
                     "print(json.dumps(bench.bench_latency_window("
                     f"{binp!r}, {bound}, {window}, n_edges={n_e}, "
                     f"superbatch={kk}, algo={algo!r}, "
                     f"id_fold={id_fold})))"],
                    capture_output=True, text=True, timeout=1800,
                )
            except subprocess.TimeoutExpired:
                # one hung point is a per-point failure, not a crashed
                # sweep: the remaining points still run and the artifact
                # keeps its incomplete marker + nonzero exit
                sp.set(outcome="timeout")
                log(f"latency-curve: {algo} {name} @{window} hung >1800s")
                return None, True
            if out.returncode == 0:
                res = _parse_sub(out.stdout)
                sp.set(rc=0, eps=(res or {}).get("eps"))
                return res, False
            sp.set(rc=out.returncode)
            log(out.stderr[-500:])
            return None, True

    try:
        flush()
        failures = 0
        for wexp in (() if algos_only else LATENCY_SWEEP_WEXP):
            window = 1 << wexp
            if window > corpus_edges:
                break
            n_e = min(corpus_edges, max(1 << 22, window))
            point = {}
            variants = [("per_window", 1)]
            k = auto_superbatch_k(window)
            if k > 1:
                variants.append(("superbatch", k))
            for name, kk in variants:
                log(f"latency-curve: window=2^{wexp} {name} (k={kk})...")
                point[name], failed = run_point(window, n_e, name, kk)
                failures += failed
            if point.get("per_window") and point.get("superbatch"):
                point["superbatch_speedup"] = round(
                    point["superbatch"]["eps"] / point["per_window"]["eps"],
                    2,
                )
            doc["points"][str(window)] = point
            flush()
        # per-algorithm cells at the cliff window (ISSUE 14): one
        # per-window + one fused cell per group-fold-declaring carry
        window = LATENCY_ALGO_WINDOW
        for algo, n_e, id_fold, cell_k in LATENCY_ALGO_CELLS:
            n_e = min(corpus_edges, n_e)
            point = {}
            k = cell_k or auto_superbatch_k(window)
            for name, kk in (("per_window", 1), ("superbatch", k)):
                log(f"latency-curve: algo={algo} @{window} {name} "
                    f"(k={kk})...")
                point[name], failed = run_point(
                    window, n_e, name, kk, algo=algo, id_fold=id_fold
                )
                failures += failed
            if point.get("per_window") and point.get("superbatch"):
                point["superbatch_speedup"] = round(
                    point["superbatch"]["eps"] / point["per_window"]["eps"],
                    2,
                )
            doc["algos"].setdefault(algo, {})[str(window)] = point
            flush()
        if not failures and not prev_incomplete:
            doc.pop("incomplete", None)
        flush()
    finally:
        obs.detach_sink(obs_sink)
        obs.disable()
    log(f"latency-curve: {json.dumps(doc)}")
    if failures:
        sys.exit(1)
    return doc


# --------------------------------------------------------------------- #
# Self-tuning control plane (ISSUE 15): superbatch="auto" vs hand-tuned
# --------------------------------------------------------------------- #
#: the autotune proof cells run at the committed latency-curve CLIFF
#: window (1024-edge count windows, identity mapping — the
#: configuration behind the hand-tuned 5.99M-eps cell in
#: BENCH_LATENCY_CPU.json) over an 8M-edge prefix: twice the latency
#: cell's, so the controller's ONE-TIME cold-start ramp (K=1 up the
#: ladder, ~50-90ms of absolute cost whatever the stream length) is
#: measured against a stream long enough to show the steady state it
#: actually holds — production streams are unbounded, and a 4M prefix
#: ends ~0.45s after the ramp by construction. The ramp stays INSIDE
#: the measured window either way (auto eps includes it).
AUTOTUNE_WINDOW = 1024
AUTOTUNE_EDGES = 1 << 23


def _corpus_cols(binp: str, n_edges: int):
    """First ``n_edges`` corpus edges as int64 columns (the shared
    prefix loader of the latency-curve and autotune cells)."""
    from gelly_streaming_tpu import datasets

    cols = []
    have = 0
    for c in datasets.iter_binary_chunks(binp, 1 << 22):
        cols.append(c)
        have += len(c[0])
        if have >= n_edges:
            break
    src = np.concatenate([c[0] for c in cols])[:n_edges]
    dst = np.concatenate([c[1] for c in cols])[:n_edges]
    return src, dst


def bench_autotune_pair(binp: str, bound: int,
                        window: int = AUTOTUNE_WINDOW,
                        n_edges: int = AUTOTUNE_EDGES,
                        reps: int = 3) -> dict:
    """The autotune proof cell: streaming CC over the corpus prefix at
    the cliff window, hand-tuned superbatch (:func:`auto_superbatch_k`,
    the committed latency-curve recipe) vs ``superbatch="auto"`` (the
    controller starts at K=1 with NO hand-picked K and climbs from
    measured group throughput; eps INCLUDES the convergence ramp — the
    controller must not lose to the constant even while it is still
    learning it). The two variants run ALTERNATING in one process
    (warm pass each, then ``reps`` hand/auto pairs, medians compared)
    — the PR 3 ``obs_overhead`` discipline: this box's throughput
    drifts ~10% over minutes, so two variants measured in separate
    back-to-back subprocesses would compare different machines."""
    from gelly_streaming_tpu import datasets
    from gelly_streaming_tpu.core.stream import SimpleEdgeStream
    from gelly_streaming_tpu.core.window import CountWindow
    from gelly_streaming_tpu.library import ConnectedComponents

    src, dst = _corpus_cols(binp, n_edges)
    hand_k = auto_superbatch_k(window)

    def one_pass(mode):
        stream = SimpleEdgeStream(
            (src, dst), window=CountWindow(window),
            vertex_dict=datasets.IdentityDict(bound),
        )
        agg = ConnectedComponents(
            superbatch=hand_k if mode == "hand" else "auto"
        )
        t0 = time.perf_counter()
        for _ in agg.run(stream):
            pass
        agg.sync()  # throughput, not enqueue rate
        return len(src) / (time.perf_counter() - t0), agg

    one_pass("hand")
    one_pass("auto")  # warm both shapes
    hand_eps, auto_eps = [], []
    last_auto = None
    for _ in range(reps):
        hand_eps.append(one_pass("hand")[0])
        eps, last_auto = one_pass("auto")
        auto_eps.append(eps)
    hand_med = sorted(hand_eps)[reps // 2]
    auto_med = sorted(auto_eps)[reps // 2]
    ak = last_auto.control.autok
    return {
        "window": window,
        "n_edges": int(len(src)),
        "carry": last_auto._cc_mode,
        "hand": {"eps": hand_med, "superbatch": hand_k,
                 "eps_all": [round(e, 1) for e in hand_eps]},
        "auto": {"eps": auto_med, "k_final": int(ak.k),
                 "retunes": len(ak.history),
                 "k_path": [[o, n, s] for o, n, s in ak.history],
                 "eps_all": [round(e, 1) for e in auto_eps]},
        "ratio_vs_hand": round(auto_med / hand_med, 3),
    }


def _cc_digest(c) -> tuple:
    """Cheap complete value digest of a CC emission: CRC of the fully
    RESOLVED label table + the touched watermark (together they
    determine the Components view) — materializing the component map
    itself would dominate the shift cell's wall time."""
    import zlib

    from gelly_streaming_tpu.summaries.forest import resolve_flat_host

    if getattr(c, "_lazy_replay", None) is not None:
        replay, win, log, count, _vd = c._lazy_replay
        lab = resolve_flat_host(replay.canon_np(win))
        return zlib.crc32(lab.tobytes()), int(count)
    if getattr(c, "_lazy_forest", None) is not None:
        canon, _log, count, _vd = c._lazy_forest
        lab = resolve_flat_host(np.asarray(canon))
        return zlib.crc32(lab.tobytes()), int(count)
    return zlib.crc32(str(c).encode()), None


def bench_autotune_shift(binp: str, n_edges: int = 1 << 22,
                         id_fold: int = 1 << 16) -> dict:
    """The mid-stream window-size-shift cell: a
    :class:`~gelly_streaming_tpu.core.window.ScheduledCountWindow`
    stream runs 512 windows at 1024 edges, then shifts to 8192-edge
    windows for the rest of the prefix. The ``superbatch="auto"`` run
    must (a) re-tune K across the shift (a ``window-shift`` decision in
    its history) and (b) stay emission-identical to the pinned-K=1
    oracle — the SAME dynamic machinery with the knob pinned through
    the ``AutoK(k0=1, k_max=1)`` seam, so the only variable is the
    controller's tiling. ``k_max=64`` bounds the cell's ladder so
    post-shift groups (64 x 8192 edges) stay small enough to decide on
    within the prefix; the headline cc_1024 cells run the default
    ladder. Runs IN-PROCESS so the controller's ``control.retune``
    events land in the committed OBS log."""
    from gelly_streaming_tpu import datasets
    from gelly_streaming_tpu.control import AutoK, ControlPlane, PrefetchTuner
    from gelly_streaming_tpu.core.stream import SimpleEdgeStream
    from gelly_streaming_tpu.core.window import ScheduledCountWindow
    from gelly_streaming_tpu.library import ConnectedComponents

    src, dst = _corpus_cols(binp, n_edges)
    src = src % id_fold
    dst = dst % id_fold
    schedule = ((0, 1024), (512, 8192))

    def run(plane):
        stream = SimpleEdgeStream(
            (src, dst), window=ScheduledCountWindow(schedule),
            vertex_dict=datasets.IdentityDict(id_fold),
        )
        agg = ConnectedComponents(superbatch="auto")
        agg.control = plane
        digests = []
        t0 = time.perf_counter()
        for c in agg.run(stream):
            digests.append(_cc_digest(c))
        agg.sync()
        return agg, digests, time.perf_counter() - t0

    _oracle, base, _dt = run(ControlPlane(autok=AutoK(k0=1, k_max=1)))
    agg, got, dt = run(ControlPlane(
        autok=AutoK(k_max=64, decide_groups=2), prefetch=PrefetchTuner(),
    ))
    mismatches = sum(1 for a, b in zip(base, got) if a != b) \
        + abs(len(base) - len(got))
    ak = agg.control.autok
    return {
        "schedule": [list(s) for s in schedule],
        "windows": len(got),
        "edges": int(len(src)),
        "id_fold": id_fold,
        "eps": len(src) / dt,
        "oracle_mismatches": int(mismatches),
        "k_final": int(ak.k),
        "k_path": [[o, n, s] for o, n, s in ak.history],
        "shift_retuned": bool(any(
            s == "window-shift" for _o, _n, s in ak.history
        )),
    }


def bench_autotune_pagerank_hold(binp: str, n_edges: int = 1 << 15,
                                 id_fold: int = 1 << 14,
                                 window: int = 1024,
                                 reps: int = 3) -> dict:
    """The NEGATIVE-control cell (ROADMAP 5b): PageRank at the
    latency-curve cell's exact configuration (32k corpus edges folded
    into a 16k-vertex space, 1024-edge windows) is documented honest
    ~parity on CPU — its per-window cost is the warm-start fixpoint,
    which fusion cannot remove. ``superbatch="auto"`` here must
    therefore learn to HOLD K=1: probe up, measure no win, revert, and
    end the stream at K=1 with throughput at parity with the pinned
    K=1 run (alternating pinned/auto passes, medians — the same
    drift discipline as the cc_1024 cell). A controller that ends
    anywhere else has started paying group quantization for fusion
    that buys nothing, which is exactly the regression the benchguard
    watch on ``auto.k_final`` exists to catch."""
    from gelly_streaming_tpu import datasets
    from gelly_streaming_tpu.core.stream import SimpleEdgeStream
    from gelly_streaming_tpu.core.window import CountWindow
    from gelly_streaming_tpu.library import IncrementalPageRank

    src, dst = _corpus_cols(binp, n_edges)
    src = src % id_fold
    dst = dst % id_fold

    def one_pass(mode):
        stream = SimpleEdgeStream(
            (src, dst), window=CountWindow(window),
            vertex_dict=datasets.IdentityDict(id_fold),
        )
        agg = IncrementalPageRank(
            superbatch=1 if mode == "pinned" else "auto"
        )
        t0 = time.perf_counter()
        for _ in agg.run(stream):
            pass
        agg.sync()
        return len(src) / (time.perf_counter() - t0), agg

    one_pass("pinned")
    one_pass("auto")  # warm both shapes
    pinned_eps, auto_eps = [], []
    last_auto = None
    for _ in range(reps):
        pinned_eps.append(one_pass("pinned")[0])
        eps, last_auto = one_pass("auto")
        auto_eps.append(eps)
    pinned_med = sorted(pinned_eps)[reps // 2]
    auto_med = sorted(auto_eps)[reps // 2]
    ak = last_auto.control.autok
    return {
        "window": window,
        "n_edges": int(len(src)),
        "id_fold": id_fold,
        "pinned": {"eps": pinned_med,
                   "eps_all": [round(e, 1) for e in pinned_eps]},
        "auto": {"eps": auto_med, "k_final": int(ak.k),
                 "held": int(ak.k) == 1,
                 "k_path": [[o, n, s] for o, n, s in ak.history],
                 "eps_all": [round(e, 1) for e in auto_eps]},
        "ratio_vs_pinned": round(auto_med / pinned_med, 3),
    }


#: acceptance floor: auto-K (incl. its convergence ramp) must reach at
#: least this fraction of the hand-tuned cell's throughput
AUTOTUNE_MIN_RATIO = 0.9


def run_autotune(artifact: str, pagerank_only: bool = False) -> dict:
    """The self-tuning proof harness (ISSUE 15 acceptance): commit
    ``BENCH_AUTOTUNE_CPU.json`` + ``_OBS.jsonl`` with (a) the cliff-cell
    auto-vs-hand eps ratio (>= :data:`AUTOTUNE_MIN_RATIO` required — the
    controller must never lose to the hand-picked constant) and (b) the
    mid-stream window-size-shift cell (K re-tunes across the shift,
    zero oracle mismatches required). The eps cell runs in ONE fresh
    subprocess with hand/auto passes ALTERNATING (box throughput
    drifts ~10% over minutes — separate subprocesses would compare
    different machines; the obs_overhead discipline); the shift cell
    runs in-process under the driver's obs sink so its RETUNE events
    are committed evidence.

    The ``pagerank_hold`` cell is the NEGATIVE control (ROADMAP 5b,
    ISSUE 16 satellite): auto-K on the fixpoint-bound PageRank parity
    workload must end the stream holding K=1 at throughput parity with
    pinned K=1 (see :func:`bench_autotune_pagerank_hold`).
    ``pagerank_only=True`` (``--autotune --pagerank``) refreshes ONLY
    that cell, merging into the committed artifact — the
    ``--latency-curve --algos`` idiom."""
    import subprocess

    from gelly_streaming_tpu import datasets, obs

    path, _is_real = _corpus_path()
    bound = _id_bound(path, _is_real)
    binp = datasets.binary_cache(path)

    def run_pr_cell():
        out = subprocess.run(
            [sys.executable, "-c",
             "import jax; "
             "jax.config.update('jax_platforms','cpu'); "
             "import bench, json; "
             "print(json.dumps(bench.bench_autotune_pagerank_hold("
             f"{binp!r})))"],
            capture_output=True, text=True, timeout=1800,
        )
        if out.returncode != 0:
            log(out.stderr[-500:])
            return None
        return _parse_sub(out.stdout)

    if pagerank_only:
        with open(artifact) as f:
            doc = json.load(f)
        log("autotune: pagerank negative-control cell (hold at K=1)...")
        cell = run_pr_cell()
        doc["cells"]["pagerank_hold"] = cell or {}
        head = doc.setdefault("headline", {})
        held = bool(cell and cell["auto"]["held"])
        head["pagerank_held"] = held
        head["pagerank_ratio_vs_pinned"] = (cell or {}).get(
            "ratio_vs_pinned")
        head["ok"] = bool(head.get("ok")) and held
        with open(artifact, "w") as f:
            json.dump(doc, f, indent=2)
        log(f"autotune: {json.dumps(head)}")
        return doc
    doc = {
        "note": (
            "self-tuning control plane (ISSUE 15): superbatch='auto' "
            "(controller starts at K=1, no hand-picked K; eps includes "
            "the convergence ramp) vs the hand-tuned "
            "auto_superbatch_k cell at the committed latency-curve "
            "cliff window (1024-edge count windows; 8M-edge prefix — "
            "2x the latency cell's, so the one-time cold-start ramp "
            "is measured against a stream long enough to reach steady "
            "state; the ramp itself stays inside the measured window; "
            "hand/auto passes alternate in one process and medians "
            "compare, because box throughput drifts ~10% over "
            "minutes), plus a mid-stream window-size-shift cell "
            "(ScheduledCountWindow 1024->8192 at window 512; "
            "k_max=64 ladder so post-shift groups decide within the "
            "prefix) checked emission-identical against the "
            "pinned-K=1 oracle. The OBS log carries the shift cell's "
            "live control.retune events."
        ),
        "platform": "cpu-xla",
        "corpus": path,
        "cells": {},
        "incomplete": True,
    }
    obs_path = (
        artifact[: -len(".json")] if artifact.endswith(".json") else artifact
    ) + "_OBS.jsonl"
    doc["obs_log"] = os.path.basename(obs_path)
    obs_sink = obs.JsonlSink(obs_path)
    obs_sink.emit({"kind": "meta", "bench": "autotune",
                   "artifact": os.path.basename(artifact)})
    obs.enable()
    obs.attach_sink(obs_sink)

    def flush():
        with open(artifact, "w") as f:
            json.dump(doc, f, indent=2)
        obs_sink.write()

    def run_cell():
        with obs.span("bench.autotune_cell") as sp:
            try:
                out = subprocess.run(
                    [sys.executable, "-c",
                     "import jax; "
                     "jax.config.update('jax_platforms','cpu'); "
                     "import bench, json; "
                     "print(json.dumps(bench.bench_autotune_pair("
                     f"{binp!r}, {bound})))"],
                    capture_output=True, text=True, timeout=1800,
                )
            except subprocess.TimeoutExpired:
                # one hung cell is a per-cell failure (the run_point
                # discipline): the other cells still run and the
                # artifact keeps its incomplete marker + nonzero exit
                sp.set(outcome="timeout")
                log("autotune: cc_1024 cell hung >1800s")
                return None
            if out.returncode != 0:
                sp.set(rc=out.returncode)
                log(out.stderr[-500:])
                return None
            res = _parse_sub(out.stdout)
            sp.set(rc=0, ratio=(res or {}).get("ratio_vs_hand"))
            return res

    failures = 0
    try:
        flush()
        log("autotune: cc_1024 hand-vs-auto (alternating passes)...")
        cell = run_cell()
        failures += cell is None
        cell = cell or {}
        doc["cells"]["cc_1024"] = cell
        flush()
        log("autotune: window-size shift cell (in-process)...")
        with obs.span("bench.autotune_shift"):
            doc["cells"]["shift"] = bench_autotune_shift(binp)
        flush()
        log("autotune: pagerank negative-control cell (hold at K=1)...")
        with obs.span("bench.autotune_pagerank_hold"):
            pr = run_pr_cell()
        failures += pr is None
        doc["cells"]["pagerank_hold"] = pr or {}
        flush()
        ratio = (doc["cells"]["cc_1024"] or {}).get("ratio_vs_hand")
        shift = doc["cells"]["shift"]
        held = bool(pr and pr["auto"]["held"])
        doc["headline"] = {
            "auto_eps": (cell.get("auto") or {}).get("eps"),
            "hand_eps": (cell.get("hand") or {}).get("eps"),
            "ratio_vs_hand": ratio,
            "min_ratio": AUTOTUNE_MIN_RATIO,
            "shift_retuned": shift["shift_retuned"],
            "shift_oracle_mismatches": shift["oracle_mismatches"],
            "pagerank_held": held,
            "pagerank_ratio_vs_pinned": (pr or {}).get(
                "ratio_vs_pinned"),
            "ok": bool(
                not failures
                and ratio is not None
                and ratio >= AUTOTUNE_MIN_RATIO
                and shift["shift_retuned"]
                and shift["oracle_mismatches"] == 0
                and held
            ),
        }
        if not failures:
            doc.pop("incomplete", None)
        flush()
    finally:
        obs.detach_sink(obs_sink)
        obs.disable()
    log(f"autotune: {json.dumps(doc.get('headline'))}")
    return doc


def bench_cc_flink_proxy(src, dst) -> dict:
    """Flink-representative CPU baseline (round-3 verdict #4): the
    reference's CC job graph with per-record serialized shuffles + a
    serialized partial-merge hop, compiled (``native.flink_proxy``).
    No JVM is available in this image, so the real reference cannot run
    here; this proxy deliberately over-estimates Flink (C++, in-process
    queues, no GC/netty), making ``vs_flink`` a conservative lower bound.
    Median-of-``BASELINE_REPS``; the caller cross-checks the bracket
    python_unionfind <= proxy <= compiled_baseline."""
    from gelly_streaming_tpu import native

    runs = [native.flink_proxy(src, dst, window=WINDOW)
            for _ in range(BASELINE_REPS)]
    secs = float(np.median([r[0] for r in runs]))
    return {
        "eps": round(len(src) / secs, 1),
        "cc_s_all": [round(r[0], 3) for r in runs],
        "components": runs[0][1],
        "model": "compiled reference job graph + per-record serialized "
                 "shuffle + serialized partial merge; upper-bounds real "
                 "single-host Flink (no JVM/GC/netty modeled)",
    }


def bench_cc_python_tier(src, dst, sample: int) -> float:
    """Per-edge union-find in interpreted Python — the reference's actual
    per-record execution model, minus the JVM. Reference shape:
    ``summaries/DisjointSet.java:97-123``."""
    parent = {}
    rank = {}

    def find(x):
        root = x
        while parent.get(root, root) != root:
            root = parent[root]
        while parent.get(x, x) != root:
            parent[x], x = root, parent[x]
        return root

    t0 = time.perf_counter()
    for s, d in zip(src[:sample].tolist(), dst[:sample].tolist()):
        rs, rd = find(s), find(d)
        if rs != rd:
            if rank.get(rs, 0) < rank.get(rd, 0):
                rs, rd = rd, rs
            parent[rd] = rs
            if rank.get(rs, 0) == rank.get(rd, 0):
                rank[rs] = rank.get(rs, 0) + 1
    dt = time.perf_counter() - t0
    return sample / dt


# --------------------------------------------------------------------- #
# Kernel-only CC (round-1 headline, kept as the device-side number)
# --------------------------------------------------------------------- #
def bench_cc_kernel(src, dst, n_vertices: int, window: int) -> dict:
    """Median-of-N kernel rate. Every timed dispatch carries a DISTINCT
    (summary, block) pair, so no layer can answer a repeated dispatch
    from a cache (re-timing the same block chain, warm block included,
    once inflated the rate). Each rep streams its own disjoint window
    span; the warm window is outside every timed span."""
    import jax
    import jax.numpy as jnp

    from gelly_streaming_tpu.summaries.labels import cc_fold, init_labels, label_combine

    n_edges = src.shape[0]

    @jax.jit
    def step(summary, s, d, m):
        part = cc_fold(init_labels(n_vertices), s, d, m)
        return label_combine(summary, part)

    n_total = n_edges // window
    assert n_total >= 2, (
        "need >=2 windows: one warms the jit, the rest are timed"
    )
    reps = min(STEADY_REPS, n_total - 1)
    n_win = (n_total - 1) // reps
    blocks = [
        (
            jnp.asarray(src[i * window : (i + 1) * window]),
            jnp.asarray(dst[i * window : (i + 1) * window]),
            jnp.ones(window, bool),
        )
        for i in range(1 + reps * n_win)
    ]
    summary = init_labels(n_vertices)
    warm = step(summary, *blocks[0])
    jax.block_until_ready(warm)

    rates = []
    summary = warm
    for r in range(reps):
        span = blocks[1 + r * n_win : 1 + (r + 1) * n_win]
        t0 = time.perf_counter()
        for s, d, m in span:
            summary = step(summary, s, d, m)
        jax.block_until_ready(summary)
        rates.append(n_win * window / (time.perf_counter() - t0))
    lab = np.asarray(summary["labels"])
    assert (lab[lab] == lab).all()
    rates.sort()
    return {"eps": round(rates[len(rates) // 2], 1),
            "eps_all": [round(x, 1) for x in rates]}


def bench_degrees_e2e(bin_path: str, bound: int, n_edges: int) -> dict:
    """BASELINE config #1 end-to-end: binary corpus -> stream ->
    continuous degree emission (batched view consumed per window)."""
    from gelly_streaming_tpu import datasets
    from gelly_streaming_tpu.core.window import CountWindow

    def one_pass():
        stream = datasets.stream_file(
            bin_path, window=CountWindow(WINDOW),
            vertex_dict=datasets.IdentityDict(bound), prefetch_depth=2,
        )
        t0 = time.perf_counter()
        for _ in stream.get_degrees().batches():
            pass
        return n_edges / (time.perf_counter() - t0)

    med, eps_all = median_steady(one_pass)
    return {"eps": round(med, 1), "eps_all": eps_all}


# --------------------------------------------------------------------- #
# Config #1: continuous degree aggregate
# --------------------------------------------------------------------- #
def bench_segmented_fold(window: int = 1 << 16,
                         n_vertices: int = 1 << 12) -> dict:
    """Tier-3 arrival-order fold rate (round-4 verdict weak #5: the
    sequential-scan tier had no bench entry). The fold is a genuine
    arrival-order UDF (running value sum — what ``EdgesFold`` runs), so
    the measured rate IS the per-edge scan-step rate the tier's
    documented cost model warns about; distinct inputs per timed
    dispatch, every output synced."""
    import jax
    import jax.numpy as jnp

    from gelly_streaming_tpu.ops.segment import segmented_fold

    reps = 3
    src, dst = make_stream(n_vertices, window * (reps + 1), seed=13)
    vals = np.random.default_rng(5).random(window * (reps + 1)).astype(np.float32)
    mask = jnp.ones(window, bool)

    @jax.jit
    def run(s, d, v):
        out, nonempty = segmented_fold(
            jnp.float32(0.0), lambda acc, vid, nbr, val: acc + val,
            s, d, v, mask, n_vertices,
        )
        return out

    def block(i):
        sl = slice(i * window, (i + 1) * window)
        return (jnp.asarray(src[sl]), jnp.asarray(dst[sl]),
                jnp.asarray(vals[sl]))

    run(*block(0)).block_until_ready()  # warm
    t0 = time.perf_counter()
    outs = [run(*block(i)) for i in range(1, reps + 1)]
    jax.block_until_ready(outs)
    dt = time.perf_counter() - t0
    return {
        "eps": reps * window / dt,
        "window": window,
        "model": "sequential lax.scan over the window (tier 3); use "
                 "reduce_on_edges tiers 1-2 for associative folds",
    }


def bench_weighted_e2e(binp: str, bound: int, n_edges: int) -> dict:
    """Value-CONSUMING device-encode e2e vs the same pipeline with
    ``drop_values`` (round-4 verdict missing #6): a weighted-degree
    summary (scatter-add of edge values — the weighted-matching feed
    shape) over a ratings-valued copy of the corpus. The packed value
    columns (u8 codes + LUT, ``datasets._ValuePacker``) must hold the
    value-consuming rate within ~15% of the value-ignoring one."""
    import jax.numpy as jnp

    from gelly_streaming_tpu import datasets
    from gelly_streaming_tpu.aggregate.summary import SummaryBulkAggregation
    from gelly_streaming_tpu.core.window import CountWindow

    # ratings-valued twin of the corpus (MovieLens value shape: 10
    # distinct half-star levels), cached beside the original. Written
    # chunk-by-chunk with seeks into the columnar layout — materializing
    # the full int64 columns would peak at GBs on the northstar corpus.
    wpath = binp.replace(".gbin", ".weighted.gbin")
    if not os.path.exists(wpath):
        rng = np.random.default_rng(17)
        from gelly_streaming_tpu.datasets import _BIN_MAGIC as magic
        base = len(magic) + 8 + 1
        with open(wpath + ".tmp", "wb") as f:
            f.write(magic)
            f.write(np.int64(n_edges).tobytes())
            f.write(np.uint8(1).tobytes())
            off = 0
            for s, d, _v in datasets.iter_binary_chunks(binp, 1 << 22):
                n = len(s)
                f.seek(base + 4 * off)
                f.write(np.ascontiguousarray(s, np.int32).tobytes())
                f.seek(base + 4 * n_edges + 4 * off)
                f.write(np.ascontiguousarray(d, np.int32).tobytes())
                f.seek(base + 8 * n_edges + 4 * off)
                vv = (rng.integers(1, 11, n) * 0.5).astype(np.float32)
                f.write(vv.tobytes())
                off += n
        assert off == n_edges, (off, n_edges)
        os.replace(wpath + ".tmp", wpath)

    class _WeightedDegrees(SummaryBulkAggregation):
        def initial_state(self, vcap):
            return jnp.zeros(vcap, jnp.float32)

        def grow_state(self, state, old, new):
            return jnp.concatenate([state, jnp.zeros(new - old, jnp.float32)])

        def update(self, state, src, dst, val, mask):
            w = jnp.where(mask, val, 0.0)
            return state.at[src].add(w).at[dst].add(w)

        def combine(self, a, b):
            return a + b

    def one_pass(drop):
        stream = datasets.stream_file(
            wpath, window=CountWindow(WINDOW), device_encode=True,
            min_vertex_capacity=bound, prefetch_depth=2, drop_values=drop,
        )
        agg = _WeightedDegrees()
        t0 = time.perf_counter()
        for _ in agg.run(stream):
            pass
        agg.sync()
        return n_edges / (time.perf_counter() - t0)

    packed, packed_all = median_steady(lambda: one_pass(False))
    dropped, dropped_all = median_steady(lambda: one_pass(True))
    return {
        "eps_packed_values": packed,
        "eps_drop_values": dropped,
        "ratio": round(packed / dropped, 3),
        "eps_packed_all": packed_all,
        "eps_drop_all": dropped_all,
    }


def bench_bipartiteness_e2e(binp: str, bound: int, n_edges: int,
                            carry: str = "auto") -> dict:
    """Streaming bipartiteness over the corpus (round-5 cover-forest
    carry vs the dense cover engine — pass carry= to pin). Binary corpus
    + identity mapping; syncs the carried cover state inside dt."""
    import jax

    from gelly_streaming_tpu import datasets
    from gelly_streaming_tpu.core.window import CountWindow
    from gelly_streaming_tpu.library import BipartitenessCheck

    def one_pass():
        stream = datasets.stream_file(
            binp, window=CountWindow(WINDOW),
            vertex_dict=datasets.IdentityDict(bound), prefetch_depth=2,
        )
        agg = BipartitenessCheck(carry=carry)
        t0 = time.perf_counter()
        last = None
        for last in agg.run(stream):
            pass
        jax.block_until_ready(agg._sync_ref)
        dt = time.perf_counter() - t0
        return {
            "eps": n_edges / dt,
            "bipartite": bool(last.success),
            "carry": agg._bp_mode,
        }

    out, eps_all = median_steady(one_pass)
    out["eps_all"] = eps_all
    return out


def bench_degrees(src, dst, n_vertices: int, window: int) -> dict:
    """Median-of-N; the carried ``deg`` makes every dispatch distinct
    (no memoization hazard), but each rep still times a disjoint span."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def step(deg, s, d):
        ones = jnp.ones(s.shape[0], jnp.int32)
        return deg.at[s].add(ones).at[d].add(ones)

    n_total = src.shape[0] // window
    assert n_total >= 2, (
        "need >=2 windows: one warms the jit, the rest are timed"
    )
    reps = min(STEADY_REPS, n_total - 1)
    n_win = (n_total - 1) // reps
    deg = jnp.zeros(n_vertices, jnp.int32)
    blocks = [
        (jnp.asarray(src[i * window : (i + 1) * window]),
         jnp.asarray(dst[i * window : (i + 1) * window]))
        for i in range(1 + reps * n_win)
    ]
    deg = step(deg, *blocks[0])
    jax.block_until_ready(deg)
    rates = []
    for r in range(reps):
        span = blocks[1 + r * n_win : 1 + (r + 1) * n_win]
        t0 = time.perf_counter()
        for s, d in span:
            deg = step(deg, s, d)
        jax.block_until_ready(deg)
        rates.append(n_win * window / (time.perf_counter() - t0))
    rates.sort()
    return {"eps": round(rates[len(rates) // 2], 1),
            "eps_all": [round(x, 1) for x in rates]}


# --------------------------------------------------------------------- #
# Config #3: window triangle count (1M-edge windows)
# --------------------------------------------------------------------- #
def bench_window_triangles(n_vertices: int = 1 << 17, window: int = 1 << 20) -> dict:
    """Median-of-N over DISTINCT window blocks: timing the warm block
    again inside the loop is an identical dispatch a cache may answer,
    which once inflated the recorded rate."""
    import jax
    import jax.numpy as jnp

    from gelly_streaming_tpu.library.triangles import (
        _oriented_degree_bucket,
        _window_step,
    )

    n_blocks = 1 + STEADY_REPS * 2  # warm + STEADY_REPS groups of 2
    # Zipf-skewed stream: the degree-oriented kernel bounds row width by
    # the max out-degree (~sqrt(2E)), so hubs no longer size the rows.
    src, dst = make_stream(n_vertices, window * n_blocks, seed=9)
    spans = [
        (src[i * window : (i + 1) * window], dst[i * window : (i + 1) * window])
        for i in range(n_blocks)
    ]
    max_deg = max(
        _oriented_degree_bucket(s, d, n_vertices) for s, d in spans
    )
    blocks = [
        (jnp.asarray(s), jnp.asarray(d), jnp.ones(window, bool))
        for s, d in spans
    ]
    out = _window_step(*blocks[0], n_vertices, max_deg)
    jax.block_until_ready(out)
    rates = []
    group = 2
    for r in range(STEADY_REPS):
        span = blocks[1 + r * group : 1 + (r + 1) * group]
        t0 = time.perf_counter()
        outs = [_window_step(*b, n_vertices, max_deg) for b in span]
        # sync every output (the runtime completes dispatches out of order)
        jax.block_until_ready(outs)
        rates.append(group * window / (time.perf_counter() - t0))
    rates.sort()
    return {"eps": round(rates[len(rates) // 2], 1),
            "eps_all": [round(x, 1) for x in rates]}


def bench_window_triangles_e2e(
    n_vertices: int = 1 << 17, window: int = 1 << 20, n_win: int = 2
) -> dict:
    """Config #3 as a SYSTEM bench: array stream -> stream.slice(1M-edge
    CountWindow) -> per-slice device triangle count (BASELINE.md:31
    'via slice(1M edges)'). Counts stay on device; one sync at the end."""
    import jax

    from gelly_streaming_tpu.core.stream import SimpleEdgeStream
    from gelly_streaming_tpu.core.window import CountWindow
    from gelly_streaming_tpu.datasets import IdentityDict
    from gelly_streaming_tpu.library.triangles import WindowTriangles

    src, dst = make_stream(n_vertices, window * n_win, seed=9)

    def one_pass():
        stream = SimpleEdgeStream(
            (src, dst), window=CountWindow(window),
            vertex_dict=IdentityDict(n_vertices),
        )
        wt = WindowTriangles(CountWindow(window))
        t0 = time.perf_counter()
        last = None
        for last, _ in wt.run_stream(stream):
            pass
        jax.block_until_ready(last)
        return n_win * window / (time.perf_counter() - t0)

    med, eps_all = median_steady(one_pass)
    return {"eps": round(med, 1), "eps_all": eps_all}


def bench_exact_triangles(
    n_vertices: int = 1 << 17, window: int = 1 << 18, n_win: int = 4
) -> dict:
    """Streaming EXACT triangles end-to-end: stream -> per-window packed
    adjacency carry + rank-closed counting (``ExactTriangleCount``).
    Emission batches stay lazy (unread); one sync at the end."""
    import jax

    from gelly_streaming_tpu.core.stream import SimpleEdgeStream
    from gelly_streaming_tpu.core.window import CountWindow
    from gelly_streaming_tpu.datasets import IdentityDict
    from gelly_streaming_tpu.library.triangles import ExactTriangleCount

    src, dst = make_stream(n_vertices, window * n_win, seed=15)

    def one_pass():
        stream = SimpleEdgeStream(
            (src, dst), window=CountWindow(window),
            vertex_dict=IdentityDict(n_vertices),
        )
        etc = ExactTriangleCount()
        t0 = time.perf_counter()
        for _ in etc.run(stream):
            pass
        jax.block_until_ready((etc._counts, etc._total))
        return n_win * window / (time.perf_counter() - t0)

    med, eps_all = median_steady(one_pass)
    return {"eps": round(med, 1), "eps_all": eps_all}


def bench_graphsage_e2e(
    n_vertices: int = 1 << 16, window: int = 1 << 18, feat: int = 128,
    n_win: int = 2,
) -> dict:
    """Config #5 as a SYSTEM bench: StreamingGraphSAGE over the stream
    with a carried DEVICE feature table (TableFeatureSource — no host
    dict loop), one forward over the accumulated graph per window."""
    import jax
    import jax.numpy as jnp

    from gelly_streaming_tpu.core.stream import SimpleEdgeStream
    from gelly_streaming_tpu.core.window import CountWindow
    from gelly_streaming_tpu.datasets import IdentityDict
    from gelly_streaming_tpu.models.graphsage import (
        StreamingGraphSAGE,
        TableFeatureSource,
        init_graphsage,
    )

    src, dst = make_stream(n_vertices, window * n_win, seed=13)
    params = init_graphsage(
        jax.random.PRNGKey(0), [feat, 256, 128], dtype=jnp.bfloat16
    )
    table = TableFeatureSource(
        jax.random.normal(
            jax.random.PRNGKey(1), (n_vertices, feat), jnp.bfloat16
        )
    )

    def one_pass():
        stream = SimpleEdgeStream(
            (src, dst), window=CountWindow(window),
            vertex_dict=IdentityDict(n_vertices),
        )
        sage = StreamingGraphSAGE(params, feature_dim=feat)
        t0 = time.perf_counter()
        out = None
        for out in sage.run(stream, table):
            pass
        jax.block_until_ready(out)
        return n_win * window / (time.perf_counter() - t0)

    med, eps_all = median_steady(one_pass)
    return {"eps": round(med, 1), "eps_all": eps_all}


# --------------------------------------------------------------------- #
# Config #4: incremental PageRank (end-to-end through the stream)
# --------------------------------------------------------------------- #
def bench_pagerank(n_vertices: int = 1 << 18, window: int = 1 << 18, n_win: int = 4) -> dict:
    from gelly_streaming_tpu.core.stream import SimpleEdgeStream
    from gelly_streaming_tpu.core.window import CountWindow
    from gelly_streaming_tpu.library.pagerank import IncrementalPageRank

    from gelly_streaming_tpu.datasets import IdentityDict

    src, dst = make_stream(n_vertices, window * n_win, seed=11)

    def one_pass():
        # synthetic ids are already dense ints: identity mapping, like the
        # CC configs (the host compaction would otherwise dominate)
        stream = SimpleEdgeStream(
            (src, dst), window=CountWindow(window),
            vertex_dict=IdentityDict(n_vertices),
        )
        pr = IncrementalPageRank(tol=1e-6, max_iter=50)
        t0 = time.perf_counter()
        for _ in pr.run(stream):
            pass
        pr.sync()  # throughput, not enqueue rate
        return n_win * window / (time.perf_counter() - t0)

    # warm pass inside median_steady pays the per-capacity-bucket compiles
    med, eps_all = median_steady(one_pass)
    return {"eps": round(med, 1), "eps_all": eps_all}


# --------------------------------------------------------------------- #
# Config #5: streaming GraphSAGE layer
# --------------------------------------------------------------------- #
def bench_graphsage(n_vertices: int = 1 << 16, window: int = 1 << 18, feat: int = 128) -> dict:
    """Median-of-N over DISTINCT (h, block) dispatches, grouped with one
    trailing sync per group (re-dispatching the warm block with identical
    inputs once inflated the recorded rate)."""
    import jax
    import jax.numpy as jnp

    from gelly_streaming_tpu.models.graphsage import init_graphsage, sage_forward

    group = 2
    n_blocks = 1 + STEADY_REPS * group
    src, dst = make_stream(n_vertices, window * n_blocks, seed=13)
    params = init_graphsage(jax.random.PRNGKey(0), [feat, 256, 128], dtype=jnp.bfloat16)
    fwd = jax.jit(sage_forward)
    blocks = [
        (jax.random.normal(jax.random.PRNGKey(100 + i), (n_vertices, feat),
                           jnp.bfloat16),
         jnp.asarray(src[i * window : (i + 1) * window]),
         jnp.asarray(dst[i * window : (i + 1) * window]),
         jnp.ones(window, bool))
        for i in range(n_blocks)
    ]
    out = fwd(params, blocks[0][0], *blocks[0][1:])
    jax.block_until_ready(out)
    rates = []
    for r in range(STEADY_REPS):
        span = blocks[1 + r * group : 1 + (r + 1) * group]
        t0 = time.perf_counter()
        outs = [fwd(params, h, s, d, m) for h, s, d, m in span]
        jax.block_until_ready(outs)
        rates.append(group * window / (time.perf_counter() - t0))
    rates.sort()
    return {"eps": round(rates[len(rates) // 2], 1),
            "eps_all": [round(x, 1) for x in rates]}


def bench_serving(
    n_vertices: int = 1 << 17, window: int = 1 << 18, n_win: int = 8,
    burst: int = 256, pace_s: float = 0.01,
    obs_log: str = None,
) -> dict:
    """The serving scenario: streaming CC with a StreamServer publishing
    per-window snapshots while a client thread drives batched
    ConnectedQuery bursts for the whole ingest. Reports query p50/p99
    latency + staleness (from the server's own stats stream) and the
    ingest rate vs the no-server path on the same stream — the read path
    must cost ingest <= ~10%.

    The client is PACED (``burst`` queries every ``pace_s``): the
    acceptance bound is about the read path's cost at a bounded query
    rate, not about an unthrottled closed loop saturating the same
    cores ingest parses on (which on the shared-host CPU backend would
    measure core contention, not serving overhead).

    ``obs_log`` (ISSUE 3 satellite): path for the obs JSONL event log of
    the MEDIAN served pass. Every ServingStats mutation is mirrored to a
    sink during each served pass (the sink rides inside the measured
    region — it is part of the serving cost being reported), and before
    the log is written the run REPLAYS it through a fresh registry and
    asserts the reconstructed ``ServingStats.snapshot()`` equals the
    live one — the reported p50/p99 ship with a log that proves them.
    Global span tracing stays OFF here on purpose: enabling it for the
    served passes but not the plain passes would bias the
    ingest-overhead comparison this bench exists to make."""
    import threading

    from gelly_streaming_tpu.core.stream import SimpleEdgeStream
    from gelly_streaming_tpu.core.window import CountWindow
    from gelly_streaming_tpu.datasets import IdentityDict
    from gelly_streaming_tpu.library import ConnectedComponents
    from gelly_streaming_tpu.serving import (
        ConnectedQuery,
        Overloaded,
        StreamServer,
    )

    n_edges = window * n_win
    src, dst = make_stream(n_vertices, n_edges, seed=23)

    def plain_pass():
        stream = SimpleEdgeStream(
            (src, dst), window=CountWindow(window),
            vertex_dict=IdentityDict(n_vertices),
        )
        agg = ConnectedComponents()
        t0 = time.perf_counter()
        for _ in stream.aggregate(agg):
            pass
        agg.sync()
        return {"eps": n_edges / (time.perf_counter() - t0)}

    def served_pass():
        from gelly_streaming_tpu.obs.export import JsonlSink

        stream = SimpleEdgeStream(
            (src, dst), window=CountWindow(window),
            vertex_dict=IdentityDict(n_vertices),
        )
        agg = ConnectedComponents()
        server = StreamServer(agg.servable(), stream, max_pending=1 << 15)
        sink = JsonlSink()
        if obs_log:
            server.stats.attach_sink(sink)
        rng = np.random.default_rng(29)
        answered = [0]
        rejected = [0]
        client_errs = []

        def client():
            # sustained query load for the WHOLE ingest: rolling bursts,
            # results collected before the next burst (closed loop). Any
            # answer-path error is RECORDED, not swallowed — a silently
            # dead client would report stats from a fraction of the
            # intended load as if the full run succeeded
            try:
                while not server.ingest_finished():
                    futs = []
                    qu = rng.integers(0, n_vertices, burst)
                    qv = rng.integers(0, n_vertices, burst)
                    for a, b in zip(qu.tolist(), qv.tolist()):
                        try:
                            futs.append(
                                server.submit(ConnectedQuery(a, b))
                            )
                        except Overloaded:
                            rejected[0] += 1
                    for f in futs:
                        f.result(120)
                    answered[0] += len(futs)
                    if pace_s:
                        time.sleep(pace_s)
            except BaseException as e:
                client_errs.append(e)

        t0 = time.perf_counter()
        server.start()
        # daemon: if the measured pass raises before the join below,
        # the load thread must die with the process, not outlive the
        # leaked reference submitting forever (GL010)
        ct = threading.Thread(target=client, daemon=True)
        ct.start()
        server.join(3600)
        agg.sync()
        dt = time.perf_counter() - t0
        ct.join(120)
        # snapshot AFTER close: close() may answer straggler queries,
        # and the replay check below needs snapshot == f(event log)
        server.close()
        stats = server.stats.snapshot()
        if client_errs:
            raise RuntimeError(
                f"serving bench client failed after {answered[0]} queries"
            ) from client_errs[0]
        q = stats["queries"].get("ConnectedQuery", {})
        obs_runs.append((sink.events if obs_log else None, stats))
        return {
            "eps": n_edges / dt,
            "queries_answered": answered[0],
            "queries_rejected": rejected[0],
            "query_p50_ms": round(q.get("p50_ms", 0.0), 3),
            "query_p99_ms": round(q.get("p99_ms", 0.0), 3),
            "staleness_mean": round(q.get("staleness_mean", 0.0), 3),
            "staleness_max": q.get("staleness_max", 0),
            "batches": stats["batches"],
        }

    # warm BOTH paths first, then interleave steady passes: the two
    # sides share jit/OS caches in-process, so back-to-back blocks of
    # passes would hand whichever runs second an unearned warm-cache
    # advantage (measured swinging the "overhead" by tens of percent)
    obs_runs = []
    plain_pass()
    served_pass()
    obs_runs.clear()  # keep only the steady passes' logs
    plain_runs, served_runs = [], []
    for _ in range(STEADY_REPS):
        plain_runs.append(plain_pass())
        served_runs.append(served_pass())
    plain_runs.sort(key=lambda p: p["eps"])
    # sort indices, not dicts: the median pass's event log must stay
    # paired with its stats for the replay check
    order = sorted(range(STEADY_REPS), key=lambda i: served_runs[i]["eps"])
    mid = order[STEADY_REPS // 2]
    plain = plain_runs[STEADY_REPS // 2]
    served = served_runs[mid]
    overhead = (
        100.0 * (plain["eps"] - served["eps"]) / plain["eps"]
        if plain["eps"] else 0.0
    )
    out = {
        "eps_no_server": round(plain["eps"], 1),
        "eps_serving": round(served["eps"], 1),
        "ingest_overhead_pct": round(overhead, 2),
        "eps_no_server_all": [round(p["eps"], 1) for p in plain_runs],
        "eps_serving_all": [
            round(served_runs[i]["eps"], 1) for i in order
        ],
        "serving": served,
    }
    if obs_log:
        from gelly_streaming_tpu.obs.export import write_jsonl
        from gelly_streaming_tpu.serving.stats import ServingStats

        events, live_snap = obs_runs[mid]
        replayed = ServingStats.from_events(events).snapshot()
        if replayed != live_snap:
            # the log failing to reproduce its own run's stats means the
            # evidence is broken — fail loudly, never ship the artifact
            raise RuntimeError(
                "serving obs event log did not replay to the live "
                f"stats snapshot:\nlive     {live_snap}\nreplayed "
                f"{replayed}"
            )
        write_jsonl(
            [{"kind": "meta", "bench": "serving", "pass": "median",
              "queries_answered": served["queries_answered"]}] + events,
            obs_log,
        )
        out["serving"] = dict(served, stats=live_snap)
        out["obs"] = {
            "log": obs_log,
            "events": len(events),
            "replay_ok": True,
        }
    return out


def bench_ingest(smoke: bool = False) -> dict:
    """Sharded parallel ingest (ISSUE 11): eps per (connections, format)
    cell against a serve-from-memory peer subprocess, so the
    single-reader text baseline and the sharded binary result sit in one
    keyed artifact.

    Every cell consumes the SAME R-MAT stream to the same endpoint —
    superbatch groups assembled and encoded, ready for engine dispatch
    (the PR 2 ingest unit) — through its cell's wire path:

    - ``c1_text``: one ``SocketEdgeSource`` reader (the pre-ISSUE-11
      path, upgraded to the native chunk line parse) feeding the
      per-record windower, blocks packed generically.
    - ``cN_binary`` / ``cN_text``: ``ShardedEdgeSource`` with N
      connections partitioned by edge-endpoint hash, per-shard
      windowers, closed windows group-encoded with zero per-window
      device work (``Windower.pack_window_cols``).

    The peer (``python -m gelly_streaming_tpu.core.ingest --serve``)
    pre-encodes each shard's frames/lines in memory before advertising
    its ports, so the wire side is never the generator's Python. Each
    cell runs ``reps`` passes (fresh connections; the peer re-serves)
    and reports the median.

    Acceptance (committed artifact): sharded binary >= 3x the
    single-connection text baseline, and eps monotone in the connection
    count on the TEXT column up to ``min(4, host cores)``. Two honesty
    notes baked into the criterion:

    - The monotone criterion lives on the TEXT column: connections are
      the scaling lever exactly where per-record decode costs something
      (text parse runs in the reader threads as GIL-released native
      calls — the realistic shape for any nontrivial wire decode).
      Binary decode is a memcpy, so one or two connections already
      saturate the single merge consumer at/above the engine plateau
      (BENCH_LATENCY_CPU.json) and further readers only add contention;
      the artifact keeps the whole binary column so that saturation
      shape stays visible.
    - The monotone reach is CORE-BOUNDED: on a 2-core host, 4 reader
      threads + 4 peer senders + the merge thread cannot outrun the 2-
      connection cell, and pretending otherwise would gate CI on the
      hosting plan. ``config.host_cores`` and
      ``monotone_text_counts`` record exactly what was claimed.
    """
    import subprocess

    from gelly_streaming_tpu.core.ingest import ShardedEdgeSource
    from gelly_streaming_tpu.core.sources import SocketEdgeSource
    from gelly_streaming_tpu.core.stream import SimpleEdgeStream
    from gelly_streaming_tpu.core.window import CountWindow

    if smoke:
        n_edges, scale, window, superbatch, reps = 1 << 17, 16, 1 << 12, 8, 1
        cells = [(1, "text"), (2, "binary")]
    else:
        n_edges, scale, window, superbatch, reps = 1 << 22, 20, 1 << 14, 8, 3
        cells = [
            (1, "text"), (2, "text"), (4, "text"),
            (1, "binary"), (2, "binary"), (4, "binary"),
        ]
    frame_edges = 8192

    def group_edges(g) -> int:
        if g.cols is not None:
            return sum(len(c[0]) for c in g.cols)
        return sum(len(b._host_cache[0]) for b in g._blocks)

    def one_pass(conns: int, fmt: str, ports) -> dict:
        addrs = [("127.0.0.1", p) for p in ports]
        if conns == 1 and fmt == "text":
            # THE baseline: the single socket reader every edge used to
            # enter through (per-record tuples into the windower)
            src = SocketEdgeSource("127.0.0.1", ports[0], tick_s=0.05)
            stream = SimpleEdgeStream(src, window=CountWindow(window))
        else:
            stream = ShardedEdgeSource(
                addrs, window=window, fmt=fmt, queue_windows=8,
            ).stream()
        t0 = time.perf_counter()
        consumed = 0
        for g in stream.superbatches(superbatch):
            consumed += group_edges(g)
        dt = time.perf_counter() - t0
        if consumed != n_edges:
            raise RuntimeError(
                f"ingest cell c{conns}_{fmt} consumed {consumed} of "
                f"{n_edges} edges"
            )
        return {"seconds": dt, "eps": n_edges / dt}

    out_cells = {}
    for conns, fmt in cells:
        peer = subprocess.Popen(
            [
                sys.executable, "-m", "gelly_streaming_tpu.core.ingest",
                "--serve", "--shards", str(conns),
                "--edges", str(n_edges), "--scale", str(scale),
                "--seed", "7", "--format", fmt,
                "--frame-edges", str(frame_edges),
                "--accepts", str(reps),
            ],
            stdout=subprocess.PIPE,
            env=dict(os.environ, JAX_PLATFORMS="cpu"),
        )
        try:
            ready = json.loads(peer.stdout.readline())
            runs = [one_pass(conns, fmt, ready["ports"])
                    for _ in range(reps)]
        finally:
            peer.stdout.close()
            try:
                peer.wait(timeout=30)
            except subprocess.TimeoutExpired:
                peer.kill()
                peer.wait()
        runs.sort(key=lambda r: r["eps"])
        mid = runs[len(runs) // 2]
        key = f"c{conns}_{fmt}"
        out_cells[key] = {
            "connections": conns,
            "format": fmt,
            "eps": round(mid["eps"], 1),
            "seconds": round(mid["seconds"], 3),
            "eps_all": [round(r["eps"], 1) for r in runs],
        }
        log(f"ingest[{key}]: {out_cells[key]['eps']:.0f} eps "
            f"({mid['seconds']:.2f}s)")

    doc = {
        "config": {
            "n_edges": n_edges, "scale": scale, "window": window,
            "superbatch": superbatch, "frame_edges": frame_edges,
            "reps": reps,
            "endpoint": "superbatch groups assembled + encoded "
                        "(engine dispatch excluded; see "
                        "BENCH_LATENCY_CPU.json for the dispatch side)",
        },
        "cells": out_cells,
    }
    base = out_cells.get("c1_text", {}).get("eps")
    best = out_cells.get("c4_binary", out_cells.get("c2_binary", {}))
    if base and best.get("eps"):
        doc["ratio_sharded_binary_vs_text_baseline"] = round(
            best["eps"] / base, 2
        )
    cores = os.cpu_count() or 1
    doc["config"]["host_cores"] = cores
    mono_counts = [c for c in (1, 2, 4)
                   if f"c{c}_text" in out_cells and c <= max(2, cores)]
    text_eps = [out_cells[f"c{c}_text"]["eps"] for c in mono_counts]
    doc["monotone_text_counts"] = mono_counts
    doc["monotone_text_scaling"] = bool(
        len(text_eps) >= 2
        and all(a <= b for a, b in zip(text_eps, text_eps[1:]))
    )
    if smoke:
        doc["ok"] = True  # smoke = liveness; ratios need the full run
    else:
        doc["ok"] = bool(
            doc.get("ratio_sharded_binary_vs_text_baseline", 0) >= 3.0
            and doc["monotone_text_scaling"]
        )
    return doc


def _drifting_ts_stream(panes: int, per_pane: int, vspan: int,
                        seed: int = 7, wrap: int = 12):
    """A drifting-keyspace event-time stream: pane ``p``'s edges live
    on vertices ``[b, b + vspan)`` with ``b = (p % wrap) * vspan/2`` —
    consecutive panes share half their vertex range, and the base
    WRAPS so retired keys recur once they have aged out of every live
    window (the recurring-entity shape real event streams have; it
    also bounds the label tables, the way any system that "forgets"
    must). This is the workload event-time retraction exists for, and
    it is the honest middle ground for the repair-vs-rebuild cell: the
    expired pane SHARES components with the oldest survivors (repair
    must re-fold real edges, unlike fully disjoint panes) but not with
    the whole graph (unlike one R-MAT giant component, where bounded
    repair degenerates into a full rebuild — that regime is covered by
    the per-cycle rebuild timing this cell compares against)."""
    rng = np.random.default_rng(seed)
    srcs, dsts, tss = [], [], []
    for p in range(panes):
        base = (p % wrap) * (vspan // 2)
        srcs.append(base + rng.integers(0, vspan, per_pane))
        dsts.append(base + rng.integers(0, vspan, per_pane))
        tss.append(np.full(per_pane, p, np.int64))
    return (
        np.concatenate(srcs).astype(np.int64),
        np.concatenate(dsts).astype(np.int64),
        np.concatenate(tss),
    )


def bench_eventtime(smoke: bool = False) -> dict:
    """Event-time sliding windows + retraction (ISSUE 18): two cells.

    ``cells.sliding`` — end-to-end events/s of the sliding aggregator
    (watermarks, pane assembly, retraction, all three summaries) over a
    drifting-keyspace stream; throughput, guarded ``min:``.

    ``cells.retract`` — the tentpole's economic claim: at every expiry
    boundary, time the INCREMENTAL path (degree subtract + forest
    repair + cover repair/latch re-resolution + new-pane fold) against
    a FROM-SCRATCH rebuild of the same three summaries on the surviving
    multiset, and assert the answers are byte-identical (the
    zero-mismatch contract). ``ratio_vs_rebuild`` > 1 means repair
    wins; guarded ``min:``.
    """
    from gelly_streaming_tpu.eventtime import (
        SlidingGraphAggregator,
        oracle_bipartite,
        oracle_degrees,
        oracle_labels,
    )

    panes = 24 if smoke else 96
    per_pane = (1 << 11) if smoke else (1 << 13)
    # vspan keeps each pane's subgraph BELOW percolation (avg degree
    # 2*per_pane/vspan = 0.5): components stay small and local, which
    # is the regime where bounded repair has something to be bounded
    # BY — at giant-component density, repairing the one component IS
    # a rebuild, and the ratio honestly says so
    vspan = (1 << 13) if smoke else (1 << 15)
    window_panes = 8
    chunk = 1 << 13
    src, dst, ts = _drifting_ts_stream(panes, per_pane, vspan)
    n_edges = len(src)

    # -- cell 1: sliding throughput ------------------------------------ #
    def one_pass():
        agg = SlidingGraphAggregator(window_panes, 1)
        t0 = time.perf_counter()
        for a in range(0, n_edges, chunk):
            agg.push(src[a:a + chunk], dst[a:a + chunk], ts[a:a + chunk])
        agg.finish()
        dt = time.perf_counter() - t0
        return {"eps": n_edges / dt, "seconds": round(dt, 3)}

    sliding, eps_all = median_steady(one_pass)
    sliding["eps"] = round(sliding["eps"], 1)
    sliding["eps_all"] = eps_all
    log(f"eventtime[sliding]: {sliding['eps']:.0f} eps "
        f"({n_edges} edges, {panes} panes, window {window_panes})")

    # -- cell 2: retraction repair vs from-scratch rebuild -------------- #
    agg = SlidingGraphAggregator(window_panes, 1)
    t_inc = 0.0
    t_rebuild = 0.0
    cycles = 0
    refolded = []
    mismatches = 0
    for a in range(0, n_edges, chunk):
        t0 = time.perf_counter()
        results = agg.push(src[a:a + chunk], dst[a:a + chunk],
                           ts[a:a + chunk])
        t_inc += time.perf_counter() - t0
        for res in results:
            if res.repair is None:
                continue  # no expiry yet: the window is still filling
            cycles += 1
            refolded.append(res.repair["refolded"])
            m = (ts >= res.start) & (ts < res.end)
            s, d = src[m], dst[m]
            vcap = len(res.labels)
            t0 = time.perf_counter()
            want_lab = oracle_labels(vcap, s, d)
            want_deg = oracle_degrees(vcap, s, d)
            want_bip = oracle_bipartite(vcap, s, d)
            t_rebuild += time.perf_counter() - t0
            if (not np.array_equal(res.labels, want_lab)
                    or not np.array_equal(res.degrees, want_deg)
                    or res.bipartite != want_bip):
                mismatches += 1
    retract = {
        "expiry_cycles": cycles,
        "incremental_s": round(t_inc, 3),
        "rebuild_s": round(t_rebuild, 3),
        # repair wins when > 1: rebuild seconds per incremental second.
        # t_inc includes pane assembly + watermark bookkeeping the
        # rebuild side skips, so the ratio UNDER-counts the repair win.
        "ratio_vs_rebuild": round(t_rebuild / t_inc, 2) if t_inc else None,
        "refolded_median": int(np.median(refolded)) if refolded else 0,
        "surviving_per_cycle": per_pane * window_panes,
        "mismatches": mismatches,
    }
    log(f"eventtime[retract]: repair {t_inc:.2f}s vs rebuild "
        f"{t_rebuild:.2f}s over {cycles} cycles "
        f"(ratio {retract['ratio_vs_rebuild']}, "
        f"mismatches {mismatches})")

    doc = {
        "config": {
            "n_edges": n_edges,
            "panes": panes,
            "per_pane": per_pane,
            "vspan_drift": vspan,
            "window_panes": window_panes,
            "chunk": chunk,
            "reps": STEADY_REPS,
            "workload": "drifting keyspace (consecutive panes share "
                        "half their vertex range; see "
                        "_drifting_ts_stream)",
            "host_cores": os.cpu_count() or 1,
        },
        "cells": {"sliding": sliding, "retract": retract},
        "ok": bool(
            mismatches == 0
            and (smoke or (retract["ratio_vs_rebuild"] or 0) > 1.0)
        ),
    }
    return doc


def bench_obs_overhead(
    n_vertices: int = 1 << 17, window: int = 1 << 20, n_win: int = 4,
    reps: int = 7,
) -> dict:
    """Observability cost on the hot path (ISSUE 3 acceptance): the
    1M-edge-window streaming-CC identity pipeline with instrumentation
    OFF vs ON (spans + registry mirroring + a JSONL sink attached — the
    full enabled configuration, not a cheaper one).

    Measurement: passes interleave with ALTERNATING order per rep (the
    shared host drifts several percent over a run, so a fixed A-then-B
    order biases whichever side runs second), and the headline ratio
    compares BEST passes — best-of-N approximates the unhindered
    runtime of each mode, which is the right estimator when the noise
    (scheduler preemption, frequency drift) is strictly additive. All
    passes are recorded so the artifact shows the spread. The
    acceptance bound is enabled < 2% overhead; disabled is the measured
    baseline itself (the off-path guard is one flag check per
    instrumentation site)."""
    from gelly_streaming_tpu import obs
    from gelly_streaming_tpu.core.stream import SimpleEdgeStream
    from gelly_streaming_tpu.core.window import CountWindow
    from gelly_streaming_tpu.datasets import IdentityDict
    from gelly_streaming_tpu.library import ConnectedComponents

    n_edges = window * n_win
    src, dst = make_stream(n_vertices, n_edges, seed=31)

    def one_pass():
        stream = SimpleEdgeStream(
            (src, dst), window=CountWindow(window),
            vertex_dict=IdentityDict(n_vertices),
        )
        agg = ConnectedComponents()
        t0 = time.perf_counter()
        for _ in stream.aggregate(agg):
            pass
        agg.sync()
        return n_edges / (time.perf_counter() - t0)

    events = [0]

    def enabled_pass():
        obs.enable()
        sink = obs.JsonlSink()
        obs.attach_sink(sink)
        try:
            eps = one_pass()
        finally:
            obs.detach_sink(sink)
            obs.disable()
        events[0] = max(events[0], len(sink))
        return eps

    one_pass()
    enabled_pass()
    dis, en = [], []
    for i in range(reps):
        if i % 2 == 0:
            dis.append(one_pass())
            en.append(enabled_pass())
        else:
            en.append(enabled_pass())
            dis.append(one_pass())
    dis.sort()
    en.sort()
    d, e = dis[-1], en[-1]  # best pass per mode (see docstring)
    return {
        "eps_disabled": round(d, 1),
        "eps_enabled": round(e, 1),
        "overhead_pct": round(100.0 * (d - e) / d, 3) if d else 0.0,
        "overhead_pct_median": round(
            100.0 * (dis[reps // 2] - en[reps // 2]) / dis[reps // 2], 3
        ) if dis[reps // 2] else 0.0,
        "events_per_run": events[0],
        "eps_disabled_all": [round(x, 1) for x in dis],
        "eps_enabled_all": [round(x, 1) for x in en],
        "model": "streaming-CC identity path, 1M-edge windows; enabled "
                 "= spans + registry mirroring + JSONL sink attached; "
                 "headline = best-of-reps per mode, alternating order",
    }


ROOFLINE_REPS = 8  # number of DISTINCT input variants per roofline kernel


def bench_spanner(
    n_vertices: int = 1 << 18, window: int = 1 << 18, n_win: int = 4,
    k: int = 2,
) -> dict:
    """Streaming k-spanner end-to-end. k=2: per-window class-bounded
    common-neighbor rejection on the packed device adjacency; k>=3: the
    bitplane-packed frontier BFS path."""
    from gelly_streaming_tpu.core.stream import SimpleEdgeStream
    from gelly_streaming_tpu.core.window import CountWindow
    from gelly_streaming_tpu.datasets import IdentityDict
    from gelly_streaming_tpu.library.spanner import DeviceSpanner

    src, dst = make_stream(n_vertices, window * n_win, seed=17)

    def one_pass():
        stream = SimpleEdgeStream(
            (src, dst), window=CountWindow(window),
            vertex_dict=IdentityDict(n_vertices),
        )
        sp = DeviceSpanner(k=k, expected_edges=window * n_win)
        t0 = time.perf_counter()
        for _ in sp.run(stream):
            pass
        sp.sync()  # throughput, not enqueue rate
        return n_win * window / (time.perf_counter() - t0)

    med, eps_all = median_steady(one_pass)
    return {"eps": round(med, 1), "eps_all": eps_all}


def bench_roofline(part: str = "all") -> dict:
    """Anchor the kernel rates against the chip roofline (round-2 verdict
    #4): MFU for the MXU-dense paths, fraction of HBM bandwidth for the
    scatter/gather kernels. Each entry's ``model`` string states exactly
    what FLOPs/bytes were counted — the byte models are LOWER bounds
    (mandatory traffic only), so the printed percentages are conservative.

    Timing amortizes the device sync over ``reps`` back-to-back dispatches
    with one trailing sync. This is wall clock around dispatch, not kernel
    time from a trace (ROADMAP S2 replaces it).
    """
    import jax
    import jax.numpy as jnp

    from gelly_streaming_tpu.utils.profiling import chip_spec, roofline_entry

    out = {"chip": chip_spec()}

    def timed(fn, variants):
        """THROUGHPUT timing: one dispatch per DISTINCT input variant,
        one trailing sync, wall/len(variants). Every rep must be a unique
        (executable, inputs) pair, so no layer can answer a repeated
        dispatch from a cache — cycling 4 variants over 16 reps once
        inflated rates exactly 4x (a fabricated 250% "MFU" flagged it).
        Independent dispatches may overlap on the device — the measured
        quantity is sustained kernel throughput (the per-window steady
        state of a pipelined stream), not single-dispatch latency."""
        warm = fn(*variants[0])
        jax.block_until_ready(warm)  # compile
        t0 = time.perf_counter()
        outs = [fn(*v) for v in variants[1:]]
        # block on EVERY output: independent dispatches may complete out
        # of order, so syncing only the last one can under-count
        jax.block_until_ready(outs)
        return (time.perf_counter() - t0) / (len(variants) - 1)

    if part in ("all", "sage_forward"):
        out.update(_roofline_sage(timed, roofline_entry))
    if part in ("all", "cc_fold"):
        out.update(_roofline_cc(timed, roofline_entry))
    if part in ("all", "degree_segment_count"):
        out.update(_roofline_degrees(timed, roofline_entry))
    if part in ("all", "window_triangles"):
        out.update(_roofline_triangles(timed, roofline_entry))
    return out


def _roofline_sage(timed, roofline_entry) -> dict:
    import jax
    import jax.numpy as jnp

    out = {}
    # 1. GraphSAGE forward — the MXU path (bf16 matmuls, f32 accum)
    from gelly_streaming_tpu.models.graphsage import init_graphsage, sage_forward

    V, E, dims = 1 << 16, 1 << 18, [128, 256, 128]
    params = init_graphsage(jax.random.PRNGKey(0), dims, dtype=jnp.bfloat16)
    s = jax.random.randint(jax.random.PRNGKey(2), (E,), 0, V, jnp.int32)
    d = jax.random.randint(jax.random.PRNGKey(3), (E,), 0, V, jnp.int32)
    m = jnp.ones(E, bool)
    fwd = jax.jit(sage_forward)
    variants = [
        (params,
         jax.random.normal(jax.random.PRNGKey(10 + i), (V, dims[0]),
                           jnp.bfloat16),
         s, d, m)
        for i in range(1 + ROOFLINE_REPS)
    ]
    t = timed(fwd, variants)
    flops = sum(4.0 * V * fi * fo for fi, fo in zip(dims[:-1], dims[1:]))
    out["sage_forward"] = roofline_entry(
        t, flops=flops,
        model=f"2 matmuls x 2VFiFo per layer, V={V}, dims={dims}; "
        "aggregation gathers uncounted",
    )
    return out


def _roofline_cc(timed, roofline_entry) -> dict:
    import jax
    import jax.numpy as jnp

    out = {}
    # 2. CC fold+combine — scatter/gather bound
    from gelly_streaming_tpu.summaries.labels import cc_fold, init_labels, label_combine

    V2, E2 = 1 << 18, 1 << 20

    @jax.jit
    def cc_step(summary, s, d, m):
        return label_combine(summary, cc_fold(init_labels(V2), s, d, m))

    m2 = jnp.ones(E2, bool)
    variants = []
    for i in range(1 + ROOFLINE_REPS):
        sv, dv = make_stream(V2, E2, seed=5 + i)
        variants.append(
            (init_labels(V2), jnp.asarray(sv), jnp.asarray(dv), m2)
        )
    t = timed(cc_step, variants)
    bytes_moved = E2 * 24.0 + V2 * 8.0
    out["cc_fold"] = roofline_entry(
        t, bytes_moved=bytes_moved,
        model=f"E*(8B ids + 8B label gathers + 8B scatter) + V*8B, "
        f"E={E2}, V={V2}; fixpoint re-passes uncounted (lower bound)",
    )
    return out


def _roofline_degrees(timed, roofline_entry) -> dict:
    import jax
    import jax.numpy as jnp

    out = {}
    V2, E2 = 1 << 18, 1 << 20
    m2 = jnp.ones(E2, bool)
    # 3. degree segment_count — the canonical scatter-add
    from gelly_streaming_tpu.ops.segment import segment_count

    @jax.jit
    def deg_step(acc, s, d, m):
        return acc + segment_count(s, m, V2) + segment_count(d, m, V2)

    variants = []
    for i in range(1 + ROOFLINE_REPS):
        sv, dv = make_stream(V2, E2, seed=5 + i)
        variants.append(
            (jnp.zeros(V2, jnp.int32), jnp.asarray(sv), jnp.asarray(dv), m2)
        )
    t = timed(deg_step, variants)
    out["degree_segment_count"] = roofline_entry(
        t, bytes_moved=E2 * 16.0 + V2 * 8.0,
        model=f"E*(8B ids + 8B scatter-add) + V*8B, E={E2}, V={V2}",
    )
    return out


def _roofline_triangles(timed, roofline_entry) -> dict:
    import jax
    import jax.numpy as jnp

    out = {}
    # 4. window-triangle membership — row gather + ranged binary search
    from gelly_streaming_tpu.library.triangles import (
        _oriented_degree_bucket,
        _window_step,
    )

    V3, E3 = 1 << 17, 1 << 20
    m3 = jnp.ones(E3, bool)
    cols = [make_stream(V3, E3, seed=9 + i) for i in range(1 + ROOFLINE_REPS)]
    W = max(_oriented_degree_bucket(s, d, V3) for s, d in cols)

    @jax.jit
    def tri(s, d, m):
        total, _ = _window_step(s, d, m, V3, W)
        return total

    variants = [
        (jnp.asarray(s), jnp.asarray(d), m3) for s, d in cols
    ]
    t = timed(tri, variants)
    out["window_triangles"] = roofline_entry(
        t, bytes_moved=E3 * (W * 4.0),
        model=f"E * row-width*4B LOGICAL membership row reads, E={E3}, "
        f"width={W}; row reuse in VMEM means achieved can exceed the HBM "
        "roofline — read as effective logical bandwidth",
    )
    return out


def _headline(e2e_fn=None) -> dict:
    """Headline = binary corpus, device-side vertex compaction, vs the
    compiled reference-architecture CC fed the same binary data — both
    sides relieved of text parsing, same file, same workload. The text
    path (parse included on both sides) is measured in the detail table.
    ``e2e_fn(binp, bound, n_edges) -> dict`` overrides the measured e2e
    pipeline (the --cpu path substitutes the identity mapping) while
    keeping every baseline, bracket, and correctness check shared.
    """
    from gelly_streaming_tpu import datasets

    path, is_real = _corpus_path()
    bound = _id_bound(path, is_real)
    base, s64, d64 = bench_cc_baseline(path)
    n_edges = base["n_edges"]
    binp = datasets.binary_cache(path, arrays=(s64, d64, None))
    base_bin = bench_cc_baseline_binary(binp)
    # numerator and denominator must be the same corpus, byte for byte
    assert base_bin["n_edges"] == n_edges, (binp, path)
    log(f"bench: e2e CC on {binp} ({'real' if is_real else 'surrogate'}, "
        f"{n_edges} edges)...")
    e2e = (e2e_fn or bench_cc_e2e_device)(binp, bound, n_edges)
    assert e2e["components"] == base_bin["components"], (
        f"correctness cross-check failed: device {e2e['components']} vs "
        f"baseline {base_bin['components']} components"
    )
    # vs_flink on the headline (round-3 verdict #4): the Flink-proxy
    # comparator is CPU-only, so it rides every headline run
    flink = bench_cc_flink_proxy(s64, d64)
    assert flink["components"] == base_bin["components"]
    # enforce the documented bracket on EVERY run (BASELINE.md). Hard
    # bounds use 1.5x slack: proxy and compiled baseline legitimately sit
    # within each other's run-to-run noise (serialization adds only
    # ~5-10%), so the tight comparison is a warning while a gross
    # violation (proxy slower than interpreted Python, or markedly faster
    # than the zero-overhead baseline) fails the run as a measurement bug.
    py_eps = bench_cc_python_tier(s64, d64, sample=min(n_edges, 400_000))
    assert py_eps <= flink["eps"], (
        f"flink proxy {flink['eps']:.0f} eps below the interpreted tier "
        f"{py_eps:.0f} — proxy measurement broken"
    )
    assert flink["eps"] <= base_bin["eps"] * 1.5, (
        f"flink proxy {flink['eps']:.0f} eps far above the compiled "
        f"baseline {base_bin['eps']:.0f} — proxy measurement broken"
    )
    if flink["eps"] > base_bin["eps"] * 1.05:
        log(f"bench: WARNING flink proxy {flink['eps']:.0f} eps above the "
            f"compiled baseline {base_bin['eps']:.0f} (within noise; the "
            "proxy remains an upper bound on Flink either way)")
    flink["python_unionfind_eps"] = round(py_eps, 1)
    headline = {
        "metric": "streaming_cc_e2e_edges_per_sec",
        "value": round(e2e["eps"], 1),
        "unit": "edges/sec",
        "vs_baseline": round(e2e["eps"] / base_bin["eps"], 2),
        "vs_flink": round(e2e["eps"] / flink["eps"], 2),
    }
    # ONE dict shared by --cpu and main(): adding a field here
    # automatically reaches every consumer (they read by key)
    return {
        "headline": headline, "e2e": e2e, "base": base,
        "base_bin": base_bin, "flink": flink, "path": path, "binp": binp,
        "bound": bound, "n_edges": n_edges,
    }


def run_northstar(artifact: str = "BENCH_NORTHSTAR.json") -> dict:
    """The BASELINE.md north-star shape (round-3 verdict #5): streaming CC
    at >=100M streamed edges — a scale-23 R-MAT surrogate ~2x the real
    LiveJournal (the real corpus is used instead when $GELLY_DATA provides
    it) — at both the headline 1M-edge windows (with p50/p95 window
    latency) and ONE 100M-edge window (BASELINE.md: "100M-edge windows").
    Writes BENCH_NORTHSTAR.json."""
    from gelly_streaming_tpu import datasets

    real = datasets.locate("livejournal")
    if real is not None:
        path, bound = real, 1 << 23
    else:
        path, _ = datasets.ensure_corpus("livejournal-xl")
        bound = 1 << 23
    log(f"northstar: corpus {path}")
    binp = datasets.binary_cache(path)
    base = bench_cc_baseline_binary(binp)
    n_edges = base["n_edges"]
    chunks = list(datasets.iter_binary_chunks(binp, 1 << 24))
    s64 = np.concatenate([c[0] for c in chunks]).astype(np.int64)
    d64 = np.concatenate([c[1] for c in chunks]).astype(np.int64)
    del chunks
    flink = bench_cc_flink_proxy(s64, d64)
    del s64, d64
    def run_e2e(w):
        return bench_cc_e2e_device(binp, bound, n_edges, window=w)

    from gelly_streaming_tpu import obs

    obs_path = (
        artifact[: -len(".json")] if artifact.endswith(".json") else artifact
    ) + "_OBS.jsonl"
    doc = {
        "corpus": path,
        "n_edges": n_edges,
        "baseline_compiled_binary": base,
        "flink_proxy": flink,
        "obs_log": os.path.basename(obs_path),
    }
    # obs evidence rides the measurement (ISSUE 3 satellite): the e2e
    # phases run in-process, so the log holds the REAL pipeline spans
    # (window.pack, engine.dispatch, prefetch coupling) behind each
    # committed eps. Enabled instrumentation is part of the measured
    # path — bounded < 2% by the overhead guard (tests/test_obs.py, the
    # obs_overhead config) — and the log says so.
    obs_sink = obs.JsonlSink(obs_path)
    obs_sink.emit({"kind": "meta", "bench": "northstar",
                   "artifact": os.path.basename(artifact)})
    obs.enable()
    obs.attach_sink(obs_sink)

    def _flush():
        # partial artifact after every expensive phase: a timeout
        # mid-northstar must still leave evidence — marked BOTH partial
        # and incomplete so no reader can mistake the hole for a
        # finished measurement
        with open(artifact, "w") as f:
            json.dump(dict(doc, partial=True, incomplete=True), f, indent=2)
        obs_sink.write()

    try:
        log(f"northstar: {n_edges} edges; 1M-edge windows...")
        with obs.span("bench.northstar_phase", {"phase": "window_1m"}):
            e2e = run_e2e(WINDOW)
        assert e2e["components"] == base["components"], (
            e2e["components"], base["components"]
        )
        doc["window_1m"] = e2e
        doc["vs_baseline"] = round(e2e["eps"] / base["eps"], 2)
        doc["vs_flink"] = round(e2e["eps"] / flink["eps"], 2)
        _flush()
        # the identity-mapping variant keeps compact columns
        # host-visible, which unlocks the window-local carries
        # (forest/host) — at scale 23 a 1M-edge window touches ~1.7M
        # of 8M vertices, exactly the T << V regime the forest carry
        # exists for. Recorded alongside the device-encode number so
        # the artifact shows both ingest contracts.
        log("northstar: 1M-edge windows, identity mapping "
            "(windowed carry)...")
        with obs.span(
            "bench.northstar_phase", {"phase": "window_1m_identity"}
        ):
            e2e_ident = bench_cc_e2e(
                binp, lambda: datasets.IdentityDict(bound), n_edges,
                window=WINDOW,
            )
        assert e2e_ident["components"] == base["components"], (
            e2e_ident["components"], base["components"]
        )
        doc["window_1m_identity"] = e2e_ident
        _flush()
        log("northstar: one 100M-edge window...")
        with obs.span("bench.northstar_phase", {"phase": "window_100m"}):
            mega = run_e2e(max(n_edges, 100_000_000))
        assert mega["components"] == base["components"], (
            mega["components"], base["components"]
        )
        doc["window_100m"] = mega
        # BASELINE.md's north-star config IS the 100M-edge window; the
        # 1M-window series is the latency-oriented configuration
        doc["vs_baseline_100m"] = round(mega["eps"] / base["eps"], 2)
        doc["vs_flink_100m"] = round(mega["eps"] / flink["eps"], 2)
        holes = [
            key for key in ("window_1m", "window_1m_identity", "window_100m")
            if doc.get(key) is None
        ]
        if holes:
            # a hole can never be silently committed as a finished
            # artifact again: mark it and FAIL the run so the driver
            # sees it
            doc["incomplete"] = True
            with open(artifact, "w") as f:
                json.dump(doc, f, indent=2)
            obs_sink.write()
            log(f"northstar: INCOMPLETE (holes: {holes}) — failing the run")
            sys.exit(1)
        with open(artifact, "w") as f:
            json.dump(doc, f, indent=2)
        obs_sink.write()
    finally:
        obs.detach_sink(obs_sink)
        obs.disable()
    log(f"northstar: {json.dumps(doc)}")
    return doc


def _parse_sub(out_text: str):
    """Subprocess configs print ONE JSON line last; accept bare floats."""
    last = out_text.strip().splitlines()[-1]
    try:
        return json.loads(last)
    except json.JSONDecodeError:
        return round(float(last), 1)


def run_transport_bench(artifact: str, obs_log: str,
                        smoke: bool = False) -> dict:
    """ISSUE 16: per-backend exchange latency + recovery numbers for the
    locally-runnable cluster-fabric backends (shared-dir, socket).

    Four legs per backend, all through the ONE ``Transport`` interface:
    (1) tag-store round trips (put+get of a 4 KiB payload — the
    rendezvous-record shape); (2) 2-rank allgathers (the dict-exchange
    primitive, measured on rank 0 including the wait for the peer's
    publication); (3) elections (the cadence-agreement primitive,
    CRC-framed winner read-back); (4) the serving lease (CRC-framed
    heartbeat write + read). Then the 2-process sharded-ingest +
    coordinated-barrier kill/recovery scenario (a reduced
    ``run_mp_sweep``: every kill point must replay oracle-identical)
    rides the same backend for its dict exchange.

    Honest annotation: CPU-core-bound, loopback/localfs only — these
    numbers bound the HARNESS (frame codec, store round trip, polling
    cadence), not a datacenter fabric. The obs artifact carries the
    driver's labeled fabric.exchange/fabric.elect counters plus every
    sweep worker's shard-labeled event stream."""
    import tempfile
    import threading

    from gelly_streaming_tpu import obs
    from gelly_streaming_tpu.fabric import (
        ExchangeDaemon,
        SharedDirTransport,
        SocketTransport,
    )
    from gelly_streaming_tpu.obs.cluster import ShardSink
    from gelly_streaming_tpu.obs.registry import nearest_rank
    from gelly_streaming_tpu.resilience import chaos
    from gelly_streaming_tpu.serving.rpc import HeartbeatLease

    def pcts(ms):
        xs = sorted(ms)
        return {
            "p50_ms": round(nearest_rank(xs, 50), 4),
            "p99_ms": round(nearest_rank(xs, 99), 4),
        }

    payload = b"x" * 4096
    rounds = 50 if smoke else 200
    ag_rounds = 10 if smoke else 30
    elections = 10 if smoke else 40
    backends = {}
    sweep_obs = []
    with tempfile.TemporaryDirectory(prefix="bench_transport_") as root:
        sink_path = os.path.join(root, "events.driver.jsonl")
        sink = ShardSink(sink_path)  # driver stream (shard-less)
        obs.get_registry().add_sink(sink)
        obs.enable()
        try:
            for backend in ("shared_dir", "socket"):
                daemon = None
                if backend == "socket":
                    daemon = ExchangeDaemon().start()

                    def make(pid=0, n=1, _d=daemon):
                        return SocketTransport(
                            _d.address, pid, n, timeout_s=60)
                else:
                    bdir = os.path.join(root, "shared_store")

                    def make(pid=0, n=1, _d=None):
                        return SharedDirTransport(
                            bdir, pid, n, timeout_s=60)

                log(f"transport[{backend}]: store round trips...")
                tr = make()
                lat = []
                t_all = time.perf_counter()
                for i in range(rounds):
                    t0 = time.perf_counter()
                    tr.put(f"pg.{i}", payload, overwrite=True)
                    got = tr.get(f"pg.{i}")
                    lat.append((time.perf_counter() - t0) * 1e3)
                    assert got == payload
                wall = time.perf_counter() - t_all
                store = {
                    "ops_per_s": round(2 * rounds / wall, 1),
                    "payload_bytes": len(payload),
                    "bytes_per_s": round(
                        2 * rounds * len(payload) / wall, 1),
                    **pcts(lat),
                }

                log(f"transport[{backend}]: 2-rank allgathers...")
                a, b = make(0, 2), make(1, 2)
                arr = np.arange(1024, dtype=np.int64)
                ag = []

                def peer():
                    for r in range(ag_rounds):
                        b.allgather(f"ag.{r}", arr * 10)

                t = threading.Thread(target=peer)
                t.start()
                try:
                    for r in range(ag_rounds):
                        t0 = time.perf_counter()
                        out = a.allgather(f"ag.{r}", arr)
                        ag.append((time.perf_counter() - t0) * 1e3)
                        assert len(out) == 2
                finally:
                    t.join(120)
                exchange = {"ranks": 2, "array_int64": 1024, **pcts(ag)}

                log(f"transport[{backend}]: elections + lease...")
                el = []
                for r in range(elections):
                    t0 = time.perf_counter()
                    won = make(0, 2).elect(f"lead.{r}", r)
                    el.append((time.perf_counter() - t0) * 1e3)
                    assert won == r
                lease_tr = make()
                lease = HeartbeatLease(lease_tr, lease_s=0.5)
                ls = []
                for r in range(rounds // 2):
                    t0 = time.perf_counter()
                    lease.write()
                    doc = HeartbeatLease.read(lease_tr)
                    ls.append((time.perf_counter() - t0) * 1e3)
                    assert doc is not None

                log(f"transport[{backend}]: kill/recovery scenario...")
                obs_tmp = os.path.join(root, f"mp_obs.{backend}.jsonl")
                sweep = chaos.run_mp_sweep(
                    processes=2, windows=3, window_edges=8,
                    superbatch=2, every=2, seed=11,
                    transport=backend, corrupt=False, failover=False,
                    rpc=False,
                    workdir=os.path.join(root, f"mp_{backend}"),
                    obs_log=obs_tmp, log=log,
                )
                sweep_obs.append(obs_tmp)
                if daemon is not None:
                    daemon.stop()
                backends[backend] = {
                    "store": store,
                    "exchange": exchange,
                    "elect": pcts(el),
                    "lease": pcts(ls),
                    "recovery": {
                        "ok": sweep["ok"],
                        "kill_points": sweep["kill_points"],
                        "recovery_s_p50": sweep["recovery_s"]["p50"],
                        "recovery_s_max": sweep["recovery_s"]["max"],
                        "cluster_restarts": sweep[
                            "cluster_restarts_total"],
                    },
                }
        finally:
            obs.disable()
            obs.get_registry().remove_sink(sink)
            sink.close()
        with open(obs_log, "w") as out:
            for p in [sink_path] + sweep_obs:
                if os.path.exists(p):
                    with open(p) as f:
                        out.writelines(f)
    doc = {
        "platform": "cpu-xla",
        "ok": all(b["recovery"]["ok"] for b in backends.values()),
        "backends": backends,
        "obs_log": os.path.basename(obs_log),
        "note": (
            "core-bound harness numbers: loopback sockets + local "
            "filesystem, CPU workers — they bound the transport "
            "machinery (frame codec, store round trip, CRC framing, "
            "polling cadence), not a datacenter fabric. allgather "
            "latency is rank 0's full exchange including the wait for "
            "the peer's publication; recovery is the reduced 2-process "
            "kill sweep (every point oracle-identical) with the dict "
            "exchange on THIS backend (epoch barriers stay shared-dir "
            "in both modes — the daemon store is in-memory)"
        ),
    }
    with open(artifact, "w") as f:
        json.dump(doc, f, indent=2)
    return doc


def main():
    if "--chaos" in sys.argv:
        # ISSUE 4 acceptance: kill-at-every-window sweep over the CC
        # superbatch pipeline. Every kill point must recover to
        # oracle-identical emissions (full window coverage,
        # value-identical replays); two points additionally corrupt the
        # committed barrier head (flip-byte / truncate) and must fall
        # back to the previous valid barrier with the rejection visible
        # as resilience.ckpt_rejected in the worker's obs event log.
        # CPU-pinned by construction (every worker subprocess pins
        # jax_platforms=cpu): the harness measures recovery
        # correctness + restore cost, not device throughput.
        #
        # --multiprocess (ISSUE 5 acceptance): the DISTRIBUTED sweep —
        # an N-process cluster on coordinated epoch barriers, one
        # worker of N killed at every window ordinal plus one
        # torn-epoch corruption point, the whole cluster restarted from
        # the agreed epoch; asserts oracle-identical emissions,
        # byte-identical VertexDicts, no mixed-epoch restore at any
        # point, and the serving-replica failover scenario's events in
        # the obs log. Artifact: BENCH_CHAOS_MP_CPU.json.
        # Both variants now commit *_OBS.jsonl evidence next to their
        # artifacts (like --serving/--northstar already do): the merged
        # shard-labeled event stream of every worker across every kill
        # point (the workers ship events via streaming ShardSinks, so
        # pre-kill telemetry is included) plus flight-dump markers; the
        # MP variant also folds the driver's coordination events in.
        from gelly_streaming_tpu.resilience import chaos

        if "--multiprocess" in sys.argv:
            # --transport socket reruns the same sweep with the workers'
            # dict exchange riding GSRP frames against the driver's
            # per-point ExchangeDaemon instead of the shared directory
            # (epoch barriers stay shared-dir in both modes); artifacts
            # get a _SOCKET suffix so both backends' evidence can sit
            # side by side.
            transport = "shared_dir"
            if "--transport" in sys.argv:
                transport = sys.argv[sys.argv.index("--transport") + 1]
            suffix = "" if transport == "shared_dir" else (
                "_" + transport.upper())
            artifact = f"BENCH_CHAOS_MP{suffix}_CPU.json"
            obs_log = f"BENCH_CHAOS_MP{suffix}_CPU_OBS.jsonl"
            # the rpc failover scenario exercises the SERVING sockets,
            # which are identical under every exchange transport — the
            # shared-dir artifact carries it once; reruns on other
            # transports measure kill/recovery + failover through the
            # transport under test without repeating it
            doc = chaos.run_mp_sweep(log=log, obs_log=obs_log,
                                     transport=transport,
                                     rpc=(transport == "shared_dir"))
            doc["platform"] = "cpu-xla"
            with open(artifact, "w") as f:
                json.dump(doc, f, indent=2)
            log(f"chaos-mp: ok={doc['ok']} "
                f"kill_points={doc['kill_points']} "
                f"cluster_restarts={doc['cluster_restarts_total']} "
                f"torn_events={doc['epoch_torn_events_total']} "
                f"flight_dumps={doc['flight_dumps_total']} "
                f"recovery_p50={doc['recovery_s']['p50']}s")
            print(json.dumps({
                "metric": "chaos_mp_kill_sweep_recovery_p50_s",
                "value": doc["recovery_s"]["p50"],
                "unit": "seconds",
                "kill_points": doc["kill_points"],
                "cluster_restarts_total": doc["cluster_restarts_total"],
                "flight_dumps_total": doc["flight_dumps_total"],
                "failover_ok": (doc.get("failover") or {}).get("ok"),
                "ok": doc["ok"],
                "artifact": artifact,
                "obs_log": obs_log,
            }))
            if not doc["ok"]:
                sys.exit(1)
            return

        artifact = "BENCH_CHAOS_CPU.json"
        obs_log = "BENCH_CHAOS_CPU_OBS.jsonl"
        doc = chaos.run_sweep(log=log, obs_log=obs_log)
        doc["platform"] = "cpu-xla"
        with open(artifact, "w") as f:
            json.dump(doc, f, indent=2)
        log(f"chaos: ok={doc['ok']} kill_points={doc['kill_points']} "
            f"rejected={doc['ckpt_rejected_total']} "
            f"flight_dumps={doc['flight_dumps_total']} "
            f"recovery_p50={doc['recovery_s']['p50']}s")
        print(json.dumps({
            "metric": "chaos_kill_sweep_recovery_p50_s",
            "value": doc["recovery_s"]["p50"],
            "unit": "seconds",
            "kill_points": doc["kill_points"],
            "restarts_total": doc["restarts_total"],
            "flight_dumps_total": doc["flight_dumps_total"],
            "ok": doc["ok"],
            "artifact": artifact,
            "obs_log": obs_log,
        }))
        if not doc["ok"]:
            sys.exit(1)
        return

    if "--transport" in sys.argv:
        # ISSUE 16 acceptance: per-backend exchange latency + recovery
        # evidence for the cluster-fabric backends. CPU-pinned by
        # construction (loopback sockets / local fs; sweep workers pin
        # their own JAX_PLATFORMS=cpu) — harness numbers, not fabric
        # numbers; the artifact says so.
        import jax

        jax.config.update("jax_platforms", "cpu")
        artifact = "BENCH_TRANSPORT_CPU.json"
        obs_log = "BENCH_TRANSPORT_CPU_OBS.jsonl"
        doc = run_transport_bench(
            artifact, obs_log, smoke="--smoke" in sys.argv)
        b = doc["backends"]
        print(json.dumps({
            "metric": "transport_put_get_ops_per_s",
            "value": {k: v["store"]["ops_per_s"] for k, v in b.items()},
            "unit": "ops/sec",
            "exchange_p50_ms": {
                k: v["exchange"]["p50_ms"] for k, v in b.items()},
            "elect_p50_ms": {
                k: v["elect"]["p50_ms"] for k, v in b.items()},
            "recovery_ok": {
                k: v["recovery"]["ok"] for k, v in b.items()},
            "ok": doc["ok"],
            "artifact": artifact,
            "obs_log": obs_log,
        }))
        if not doc["ok"]:
            sys.exit(1)
        return

    if "--latency-curve" in sys.argv:
        # window-size sweep 1k -> 16M, per-window vs superbatch, to a
        # keyed artifact (ISSUE 2 satellite: track the cliff per round)
        # this parent never initialises a JAX backend: every point is a
        # sequential child (CPU-pinned under --cpu; on the chip each
        # child calls require_tpu itself and a child without one fails
        # its point, which fails the sweep)
        cpu = "--cpu" in sys.argv
        artifact = "BENCH_LATENCY_CPU.json" if cpu else "BENCH_LATENCY.json"
        # --algos refreshes ONLY the per-algorithm group-fold cells
        # (ISSUE 14), merging into the committed CC sweep — the CI
        # benchguard step's fresh-run mode
        doc = run_latency_curve(
            artifact, cpu=cpu, algos_only="--algos" in sys.argv
        )
        small = doc["points"].get("1024", {})
        print(json.dumps({
            "metric": "latency_curve_superbatch_eps_at_1024",
            "value": (small.get("superbatch") or {}).get("eps"),
            "unit": "edges/sec",
            "points": len(doc["points"]),
            "algos": {
                a: (cells.get(str(LATENCY_ALGO_WINDOW)) or {}).get(
                    "superbatch_speedup"
                )
                for a, cells in doc.get("algos", {}).items()
            },
            "artifact": artifact,
        }))
        return

    if "--autotune" in sys.argv:
        # self-tuning control plane (ISSUE 15): superbatch="auto" must
        # reach >= 0.9x the hand-tuned cliff cell with NO hand-picked K
        # (convergence ramp included), and the window-size-shift cell
        # must show K re-tuning with zero oracle mismatches. CPU-pinned
        # (the committed artifact is the CPU trajectory, like the
        # latency curve's _CPU artifact).
        import jax

        jax.config.update("jax_platforms", "cpu")
        artifact = "BENCH_AUTOTUNE_CPU.json"
        # --pagerank refreshes ONLY the negative-control cell (ROADMAP
        # 5b: auto-K must HOLD K=1 on the fixpoint-bound parity
        # workload), merging into the committed artifact
        doc = run_autotune(artifact,
                           pagerank_only="--pagerank" in sys.argv)
        head = doc.get("headline") or {}
        print(json.dumps({
            "metric": "autotune_cc_1024_eps",
            "value": head.get("auto_eps"),
            "unit": "edges/sec",
            "ratio_vs_hand": head.get("ratio_vs_hand"),
            "shift_retuned": head.get("shift_retuned"),
            "shift_oracle_mismatches": head.get(
                "shift_oracle_mismatches"
            ),
            "pagerank_held": head.get("pagerank_held"),
            "ok": head.get("ok"),
            "artifact": artifact,
            "obs_log": doc.get("obs_log"),
        }))
        if not head.get("ok"):
            sys.exit(1)
        return

    if "--ingest" in sys.argv:
        # sharded parallel ingest (ISSUE 11): the million-writes path.
        # eps per (connections, format) cell against a serve-from-memory
        # peer subprocess; acceptance is sharded-binary >= 3x the
        # single-reader text baseline with monotone binary scaling to 4
        # connections. --smoke is the CI liveness variant (small stream,
        # two cells, no committed artifact).
        import jax

        jax.config.update("jax_platforms", "cpu")
        smoke = "--smoke" in sys.argv
        doc = bench_ingest(smoke=smoke)
        doc["platform"] = "cpu-xla"
        best = doc["cells"].get(
            "c4_binary", doc["cells"].get("c2_binary", {})
        )
        if not smoke:
            artifact = "BENCH_INGEST_CPU.json"
            with open(artifact, "w") as f:
                json.dump(doc, f, indent=2)
            doc["artifact"] = artifact
        print(json.dumps({
            "metric": "ingest_sharded_binary_eps",
            "value": best.get("eps"),
            "unit": "edges/sec",
            "baseline_c1_text_eps": doc["cells"].get(
                "c1_text", {}
            ).get("eps"),
            "ratio_vs_text_baseline": doc.get(
                "ratio_sharded_binary_vs_text_baseline"
            ),
            "monotone_text_scaling": doc["monotone_text_scaling"],
            "ok": doc["ok"],
            "artifact": doc.get("artifact"),
        }))
        if not doc["ok"]:
            sys.exit(1)
        return

    if "--eventtime" in sys.argv:
        # ISSUE 18 acceptance: event-time sliding windows + retraction.
        # Two cells — sliding eps (the whole watermark/pane/retract
        # drive) and the repair-vs-rebuild ratio at every expiry
        # boundary, with byte-identity against the from-scratch oracles
        # asserted inline (zero-mismatch). CPU-pinned: the decremental
        # kernels are host kernels by design. --smoke is the CI
        # liveness variant (small stream, no committed artifact, no
        # ratio gate — 2-core CI boxes make the ratio noisy).
        import jax

        jax.config.update("jax_platforms", "cpu")
        smoke = "--smoke" in sys.argv
        doc = bench_eventtime(smoke=smoke)
        doc["platform"] = "cpu-xla"
        if not smoke:
            artifact = "BENCH_EVENTTIME_CPU.json"
            with open(artifact, "w") as f:
                json.dump(doc, f, indent=2)
            doc["artifact"] = artifact
        print(json.dumps({
            "metric": "eventtime_sliding_eps",
            "value": doc["cells"]["sliding"]["eps"],
            "unit": "edges/sec",
            "ratio_vs_rebuild": doc["cells"]["retract"][
                "ratio_vs_rebuild"],
            "expiry_cycles": doc["cells"]["retract"]["expiry_cycles"],
            "mismatches": doc["cells"]["retract"]["mismatches"],
            "ok": doc["ok"],
            "artifact": doc.get("artifact"),
        }))
        if not doc["ok"]:
            sys.exit(1)
        return

    if "--storm" in sys.argv:
        # the failover storm (ISSUE 19): one sustained Zipfian run
        # through a 2-router fleet over 2 shard replicas, surviving a
        # router SIGKILL, a shard-primary SIGKILL (lease-lapse standby
        # promotion) and a LIVE split of the hot shard — autotune on
        # both tiers throughout. Gates: zero client-visible failures in
        # every phase, zero post-split oracle mismatches, a trace
        # joining client -> surviving router -> both post-split shards,
        # and no admission knob reverting more than once per phase.
        # ISSUE 20 adds the transactional lane: snapshot-pinned
        # multi-read txns spanning KILL/PROMOTE/SPLIT with zero
        # consistency violations (honest typed expiries only).
        import tempfile

        from gelly_streaming_tpu.resilience.chaos import (
            run_storm_scenario,
        )

        root = tempfile.mkdtemp(prefix="bench_storm_")
        # --smoke (the CI liveness step): shrunken geometry + shorter
        # phases, nothing committed — the non-blocking tier-1 probe
        smoke = "--smoke" in sys.argv
        if smoke:
            artifact = None
            obs_log = os.path.join(root, "obs_smoke.jsonl")
            kw = dict(
                n_vertices=1 << 11, n_edges=1 << 12, phase_s=1.2,
                clients=2, oracle_checks=64,
            )
        else:
            artifact = "BENCH_STORM_CPU.json"
            obs_log = "BENCH_STORM_CPU_OBS.jsonl"
            kw = {}
        obs_f = open(obs_log, "w")
        scenario_ok = False
        try:
            doc = run_storm_scenario(root, log=log, obs_f=obs_f, **kw)
            scenario_ok = bool(doc.get("ok"))
        finally:
            obs_f.close()
            import shutil

            # keep the run directory (replica/router logs, portfiles)
            # as the post-mortem for a failed full run
            if (scenario_ok or smoke) and os.path.isdir(root):
                shutil.rmtree(root, ignore_errors=True)
            elif not scenario_ok:
                log(f"storm: scenario artifacts kept at {root} "
                    f"for post-mortem")
        doc["platform"] = "cpu-xla"
        if artifact is not None:
            doc["obs_log"] = obs_log
            with open(artifact, "w") as f:
                json.dump(doc, f, indent=2)
        log(f"storm: ok={doc['ok']} "
            f"failures={doc['load_total']['failures']} "
            f"promoted={doc['storm']['promoted']} "
            f"adopted={doc['storm']['split_adopted']} "
            f"oracle_mismatches={doc['oracle']['mismatches']} "
            f"retune_moves={doc['retune']['total_moves']} "
            f"worst_reverts={doc['retune']['worst_reverts_per_phase']} "
            f"txn_committed={doc['txn']['committed']} "
            f"txn_violations={doc['txn']['violations']}")
        print(json.dumps({
            "metric": "storm_client_failures",
            "value": doc["load_total"]["failures"],
            "unit": "count",
            "batches": doc["load_total"]["batches"],
            "steady_p50_ms": doc["load"]["steady"]["p50_ms"],
            "kill_router_p99_ms": doc["load"]["kill_router"]["p99_ms"],
            "split_p99_ms": doc["load"]["split"]["p99_ms"],
            "promoted": doc["storm"]["promoted"],
            "split_adopted": doc["storm"]["split_adopted"],
            "oracle_mismatches": doc["oracle"]["mismatches"],
            "joined_trace": doc["trace"]["joined_trace"],
            "retune_moves": doc["retune"]["total_moves"],
            "worst_reverts": doc["retune"]["worst_reverts_per_phase"],
            "txn_committed": doc["txn"]["committed"],
            "txn_expired": doc["txn"]["expired"],
            "txn_violations": doc["txn"]["violations"],
            "txn_spanning": doc["txn"]["spanning"],
            "txn_zero_violations": doc["txn"]["zero_violations"],
            "ok": doc["ok"],
            "artifact": artifact,
            "obs_log": obs_log if artifact else None,
        }))
        if not doc["ok"]:
            sys.exit(1)
        return

    if "--serving" in sys.argv and "--sharded" in sys.argv:
        # sharded serving (ISSUE 12): shard replicas + the routing tier
        # as real processes — aggregate QPS scaling across 1/2/4
        # shards, Zipfian latency with the hot-key cache off vs on
        # (the headline compares the 2-shard cached tier against a
        # single replica on the same box), cross-shard CC answers
        # checked oracle-identical, a traced batch joining client ->
        # router -> both shards, and a kill-one-shard point where only
        # that shard's keyspace sees the outage (its standby promotes;
        # the other shard's keys see zero failures). ISSUE 17 adds the
        # churn cell: pull-protocol-v2 (since_version delta) router vs
        # a full-re-pull baseline over the same live-ingest stream;
        # per-refresh pulled bytes and merge time must both sit >= 5x
        # below the baseline with post-churn oracle identity.
        import tempfile

        from gelly_streaming_tpu.resilience.chaos import (
            run_sharded_scenario,
        )

        root = tempfile.mkdtemp(prefix="bench_sharded_")
        # --smoke (the CI liveness step): shrunken geometry + shorter
        # measure windows, nothing committed. The ok verdict still
        # computes, but a smoke run is a liveness probe, not the
        # committed perf claim — its CI step is non-blocking for the
        # same hosting-noise reason as the ingest smoke.
        smoke = "--smoke" in sys.argv
        if smoke:
            artifact = None
            obs_log = os.path.join(root, "obs_smoke.jsonl")
            kw = dict(
                n_edges=1 << 13, measure_s=1.0, oracle_checks=128,
                post_kill_batches=10, churn_bumps=12,
            )
        else:
            artifact = "BENCH_SERVING_SHARDED_CPU.json"
            obs_log = "BENCH_SERVING_SHARDED_CPU_OBS.jsonl"
            kw = {}
        obs_f = open(obs_log, "w")
        scenario_ok = False
        try:
            doc = run_sharded_scenario(root, log=log, obs_f=obs_f, **kw)
            scenario_ok = bool(doc.get("ok"))
        finally:
            obs_f.close()
            import shutil

            # the run directory (replica/router logs, portfiles,
            # un-shipped event streams) IS the post-mortem for a failed
            # scenario — keep it unless the run passed (or is a smoke
            # probe, whose geometry makes its numbers uncommittable)
            if (scenario_ok or smoke) and os.path.isdir(root):
                shutil.rmtree(root, ignore_errors=True)
            elif not scenario_ok:
                log(f"serving-sharded: scenario artifacts kept at "
                    f"{root} for post-mortem")
        doc["platform"] = "cpu-xla"
        if artifact is not None:
            doc["obs_log"] = obs_log
            with open(artifact, "w") as f:
                json.dump(doc, f, indent=2)
        churn = doc.get("churn", {})
        log(f"serving-sharded: ok={doc['ok']} "
            f"scaling={ {k: v['qps'] for k, v in doc['scaling'].items()} } "
            f"headline={doc['headline']} "
            f"kill={doc.get('shard_kill', {}).get('promoted')} "
            f"churn bytes_x={churn.get('bytes_x')} "
            f"merge_x={churn.get('merge_x')}")
        print(json.dumps({
            "metric": "serving_sharded_headline_qps",
            "value": doc["headline"]["qps"],
            "unit": "queries_per_second",
            "vs_single_x": doc["headline"]["vs_single_x"],
            "scaling": {k: v["qps"] for k, v in doc["scaling"].items()},
            "zipf_cache_on_p50_ms": doc["zipf"]["cache_on"]["p50_ms"],
            "zipf_cache_off_p50_ms": doc["zipf"]["cache_off"]["p50_ms"],
            "oracle_mismatches": doc["oracle"]["mismatches"],
            "joined_trace": doc["trace"]["joined_trace"],
            "churn_bytes_x": churn.get("bytes_x"),
            "churn_merge_x": churn.get("merge_x"),
            "churn_oracle_mismatches": churn.get("oracle_mismatches"),
            "ok": doc["ok"],
            "artifact": artifact,
            "obs_log": obs_log if artifact else None,
        }))
        if not doc["ok"]:
            sys.exit(1)
        return

    if "--serving" in sys.argv and "--rpc" in sys.argv:
        # wire-level serving resilience (ISSUE 8): a primary + standby
        # serving BINARY pair on a shared snapshot directory, a
        # multi-connection RPC load generator sustaining batched query
        # traffic, and a FaultPlan kill of the primary mid-run. The
        # acceptance bar is availability, client-measured: ZERO
        # client-visible query failures across the kill (every query
        # answered or cleanly DeadlineExceeded per its own budget),
        # p50/p99 reported separately for steady state and for the
        # promotion window, serving.promotion_seconds recorded from the
        # standby's event stream, and the dead primary's
        # flight-recorder black box present. CPU-pinned by construction
        # (both replica subprocesses pin jax_platforms=cpu).
        import tempfile

        from gelly_streaming_tpu.resilience.chaos import run_rpc_scenario

        artifact = "BENCH_SERVING_RPC_CPU.json"
        obs_log = "BENCH_SERVING_RPC_CPU_OBS.jsonl"
        root = tempfile.mkdtemp(prefix="bench_rpc_")
        obs_f = open(obs_log, "w")
        try:
            doc = run_rpc_scenario(
                root,
                clients=4, batch=16, pace_s=0.005,
                kill_at_sweep=1500, post_kill_batches=150,
                autotune=True,
                log=log, obs_f=obs_f,
            )
        finally:
            obs_f.close()
            import shutil

            shutil.rmtree(root, ignore_errors=True)
        doc["platform"] = "cpu-xla"
        doc["obs_log"] = obs_log
        with open(artifact, "w") as f:
            json.dump(doc, f, indent=2)
        log(f"serving-rpc: ok={doc['ok']} batches={doc['batches']} "
            f"failures={doc['failures']} outage={doc.get('outage_s')}s "
            f"steady_p99={doc['steady']['p99_ms']}ms "
            f"promo_p99={doc['promotion_window']['p99_ms']}ms")
        tuner = (doc.get("autotune") or {}).get("standby") or {}
        log(f"serving-rpc autotune: moves={len(tuner.get('history', []))} "
            f"max_pending={tuner.get('max_pending')}"
            f"/{tuner.get('ceiling')} "
            f"shed_watermark={tuner.get('shed_watermark')}")
        # the per-stage attribution table (ISSUE 9): where an answered
        # batch's milliseconds went, steady vs promotion window, from
        # the merged trace spans in the OBS log
        attr = doc.get("attribution") or {}
        for bucket in ("steady", "promotion_window"):
            b = attr.get(bucket) or {}
            log(f"serving-rpc attribution[{bucket}]: "
                f"traces={b.get('traces')} "
                f"e2e_p50={((b.get('e2e_ms') or {}).get('p50'))}ms "
                f"stages_ms={b.get('stages_ms')} "
                f"client_wait={b.get('client_wait_ms')}ms "
                f"coverage_p50={b.get('coverage_p50')}")
        log(f"serving-rpc traces: completed="
            f"{attr.get('traces_completed')} kill_crossing="
            f"{attr.get('kill_crossing_traces')} example="
            f"{attr.get('example_kill_crossing_trace')} "
            f"p99_exemplar={doc.get('wire_p99_exemplar_trace')}")
        print(json.dumps({
            "metric": "serving_rpc_steady_p99_ms",
            "value": doc["steady"]["p99_ms"],
            "unit": "milliseconds",
            "promotion_window_p99_ms": doc["promotion_window"]["p99_ms"],
            "outage_s": doc.get("outage_s"),
            "promotion_seconds": doc.get("serving_promotion_seconds"),
            "queries": doc["queries"],
            "failures": doc["failures"],
            "kill_crossing_traces": attr.get("kill_crossing_traces"),
            "attribution_coverage_p50": (
                (attr.get("steady") or {}).get("coverage_p50")
            ),
            "autotune_moves": len(tuner.get("history", [])),
            "shed_watermark": tuner.get("shed_watermark"),
            "ok": doc["ok"],
            "artifact": artifact,
            "obs_log": obs_log,
        }))
        if not doc["ok"]:
            sys.exit(1)
        return

    if "--serving" in sys.argv:
        # query serving under concurrent ingest (ISSUE 1): p50/p99 query
        # latency + staleness + ingest overhead vs the no-server path.
        # Writes a keyed JSON artifact with the obs JSONL event log next
        # to it; the log provably replays to the reported stats snapshot
        # (ISSUE 3 — bench_serving raises on replay mismatch, so a
        # committed artifact ALWAYS matches its log).
        cpu = "--cpu" in sys.argv
        if cpu:
            import jax

            jax.config.update("jax_platforms", "cpu")
        else:
            device = require_tpu()
        artifact = "BENCH_SERVING_CPU.json" if cpu else "BENCH_SERVING.json"
        obs_log = artifact[: -len(".json")] + "_OBS.jsonl"
        out = bench_serving(obs_log=obs_log)
        out["platform"] = "cpu-xla" if cpu else device
        with open(artifact, "w") as f:
            json.dump(out, f, indent=2)
        log(f"serving: {json.dumps(out)}")
        print(json.dumps(out))
        return

    if "--cpu" in sys.argv:
        # Same-host CPU-backend measurement: the framework's XLA-CPU path
        # vs the compiled reference baselines on IDENTICAL hardware, no
        # accelerator in the loop. HONEST FRAMING (round 4, after fixing
        # the dispatch-vs-throughput harness bug): on a single CPU core
        # the windowed dense-label design LOSES to the compiled hash-map
        # baseline — its per-window V-sized fixpoint passes are
        # bandwidth-hungry by construction, which is precisely the work
        # an accelerator's HBM absorbs. This artifact exists to keep the
        # comparison honest, not to claim a CPU win; the identity mapping
        # is used (the device-dict probe kernel is TPU-oriented and
        # pathological on XLA CPU).
        import jax

        jax.config.update("jax_platforms", "cpu")
        from gelly_streaming_tpu import datasets

        def identity_e2e(binp, bound, n_edges):
            return bench_cc_e2e(
                binp, lambda: datasets.IdentityDict(bound), n_edges
            )

        info = _headline(e2e_fn=identity_e2e)
        e2e, base, base_bin, flink = (
            info["e2e"], info["base"], info["base_bin"], info["flink"],
        )
        path, n_edges = info["path"], info["n_edges"]
        headline = dict(info["headline"], platform="cpu-xla")
        doc = {
            "note": "framework on the XLA CPU backend vs the compiled "
                    "reference-architecture baselines on the same host "
                    "CPU (single core); identity vertex mapping; every "
                    "rate syncs the carried summary inside the timed "
                    "region (throughput, not enqueue rate). The auto "
                    "carry picks the native host union-find with a "
                    "device pointer-forest mirror on CPU backends "
                    "(round 5); each entry records which carry ran.",
            "headline": headline,
            "e2e_binary_identity": e2e,
            "baseline_compiled_text": base,
            "baseline_compiled_binary": base_bin,
            "flink_proxy": flink,
            "corpus": path,
            "n_edges": n_edges,
        }
        # the TEXT-ingest e2e paths on the same CPU, judged against
        # baseline_compiled_text in this doc — each in a CPU-pinned
        # subprocess
        import subprocess

        bound = info["bound"]
        binp = info["binp"]
        for key, expr in [
            ("e2e_text_identity",
             f"bench.bench_cc_e2e({path!r}, "
             f"lambda: datasets.IdentityDict({bound}), {n_edges})"),
            ("e2e_dict_host",
             "bench.bench_cc_e2e("
             f"{path!r}, lambda: VertexDict(min_capacity={bound}), {n_edges})"),
            # the carry trio on the CPU backend: the committed record of
            # why auto picks the host union-find here (forest keeps the
            # merge on the XLA-CPU "device"; dense is the r4 baseline)
            ("e2e_carry_forest",
             f"bench.bench_cc_e2e({binp!r}, "
             f"lambda: datasets.IdentityDict({bound}), {n_edges}, carry='forest')"),
            ("e2e_carry_dense",
             f"bench.bench_cc_e2e({binp!r}, "
             f"lambda: datasets.IdentityDict({bound}), {n_edges}, carry='dense')"),
            # the ISSUE 3 acceptance bound lives on THIS backend: obs
            # instrumentation enabled vs disabled on the 1M-edge-window
            # CPU identity path
            ("obs_overhead", "bench.bench_obs_overhead()"),
        ]:
            log(f"cpu run: {key}...")
            code = (
                "import jax; jax.config.update('jax_platforms','cpu'); "
                "import bench, json; "
                "from gelly_streaming_tpu import datasets; "
                "from gelly_streaming_tpu.core.vertexdict import VertexDict; "
                f"print(json.dumps({expr}))"
            )
            out = subprocess.run(
                [sys.executable, "-c", code], capture_output=True,
                text=True, timeout=1800,
            )
            doc[key] = (
                _parse_sub(out.stdout) if out.returncode == 0 else None
            )
            if out.returncode != 0:
                log(out.stderr[-500:])
        # latency/throughput window-size curve on the CPU backend (the
        # windowed carries made small windows viable here too; the curve
        # records which carry each point ran)
        curve = []
        for wexp in (10, 12, 14, 16, 18, 20):
            log(f"cpu run: latency_curve window=2^{wexp}...")
            out = subprocess.run(
                [sys.executable, "-c",
                 "import jax; jax.config.update('jax_platforms','cpu'); "
                 "import bench, json; "
                 f"print(json.dumps(bench.bench_latency_window({binp!r}, "
                 f"{bound}, {1 << wexp})))"],
                capture_output=True, text=True, timeout=1800,
            )
            if out.returncode == 0:
                curve.append(_parse_sub(out.stdout))
            else:
                log(out.stderr[-500:])
        doc["latency_curve"] = curve
        with open("BENCH_CPU.json", "w") as f:
            json.dump(doc, f, indent=2)
        log(f"cpu run: {json.dumps(doc)}")
        print(json.dumps(headline))
        return

    device = require_tpu()

    if "--northstar" in sys.argv:
        out = run_northstar()
        print(json.dumps({
            # the north-star config per BASELINE.md: 100M-edge window
            "metric": "northstar_cc_100m_window_edges_per_sec",
            "value": round(out["window_100m"]["eps"], 1),
            "unit": "edges/sec",
            "vs_baseline": out["vs_baseline_100m"],
            "vs_flink": out["vs_flink_100m"],
            "device": device,
        }))
        return

    info = _headline()
    headline = dict(info["headline"], device=device)
    e2e, base, base_bin, flink = (
        info["e2e"], info["base"], info["base_bin"], info["flink"],
    )
    path, binp, bound, n_edges = (
        info["path"], info["binp"], info["bound"], info["n_edges"],
    )

    if "--all" in sys.argv:
        from gelly_streaming_tpu import datasets
        from gelly_streaming_tpu.core.vertexdict import VertexDict

        artifact = os.path.join("chiprun_out", "bench_all.json")
        os.makedirs("chiprun_out", exist_ok=True)
        detail = {
            "headline": headline,
            "device": device,
            "e2e_device_encode": e2e,
            "baseline_compiled_text": base,
            "baseline_compiled_binary": base_bin,
            # measured inside _headline alongside the bracket check
            "python_unionfind_eps": flink["python_unionfind_eps"],
            "flink_proxy": flink,
            "corpus": path,
            # until run_configs returns, a reader must not mistake the
            # file for a finished table
            "partial": True,
        }

        def _flush():
            with open(artifact, "w") as f:
                json.dump(detail, f, indent=2)

        _flush()
        n_vertices = 1 << 18
        window = 1 << 18
        n_e = window * 8

        def identity():
            return datasets.IdentityDict(bound)

        def synthetic(fn):
            s, d = make_stream(n_vertices, n_e)
            return fn(s, d, n_vertices, window)

        def latency_curve():
            # window size sweep; quantifies the micro-batch trade
            return [
                bench_latency_window(binp, bound, 1 << wexp)
                for wexp in (12, 14, 16, 18, 20)
            ]

        def roofline():
            roof = {}
            for part in ("sage_forward", "cc_fold", "degree_segment_count",
                         "window_triangles"):
                roof.update(bench_roofline(part=part))
            return roof

        run_configs([
            ("e2e_text_identity_eps",
             lambda: bench_cc_e2e(path, identity, n_edges)),
            ("e2e_dict_eps",
             lambda: bench_cc_e2e_device_text(path, bound, n_edges)),
            ("e2e_dict_host_eps",
             lambda: bench_cc_e2e(
                 path, lambda: VertexDict(min_capacity=bound), n_edges)),
            ("e2e_binary_identity_eps",
             lambda: bench_cc_e2e(binp, identity, n_edges)),
            # the CC carry comparison: same corpus + identity mapping,
            # each carry strategy pinned
            ("e2e_carry_forest",
             lambda: bench_cc_e2e(binp, identity, n_edges, carry="forest")),
            ("e2e_carry_host",
             lambda: bench_cc_e2e(binp, identity, n_edges, carry="host")),
            ("e2e_carry_dense",
             lambda: bench_cc_e2e(binp, identity, n_edges, carry="dense")),
            ("exact_triangles_eps", bench_exact_triangles),
            ("spanner_eps", bench_spanner),
            ("spanner_k3_eps", lambda: bench_spanner(k=3)),
            ("kernel_cc_eps", lambda: synthetic(bench_cc_kernel)),
            ("weighted_e2e",
             lambda: bench_weighted_e2e(binp, bound, n_edges)),
            ("bipartiteness_forest",
             lambda: bench_bipartiteness_e2e(
                 binp, bound, n_edges, carry="forest")),
            ("bipartiteness_dense",
             lambda: bench_bipartiteness_e2e(
                 binp, bound, n_edges, carry="dense")),
            ("segmented_fold_eps", bench_segmented_fold),
            ("degrees_eps", lambda: synthetic(bench_degrees)),
            ("degrees_e2e_eps",
             lambda: bench_degrees_e2e(binp, bound, n_edges)),
            ("window_triangles_eps", bench_window_triangles),
            ("window_triangles_e2e_eps", bench_window_triangles_e2e),
            ("serving_e2e", bench_serving),
            ("obs_overhead", bench_obs_overhead),
            ("pagerank_eps", bench_pagerank),
            ("graphsage_eps", bench_graphsage),
            ("graphsage_e2e_eps", bench_graphsage_e2e),
            ("latency_curve", latency_curve),
            ("roofline", roofline),
        ], detail, _flush)
        detail.pop("partial")
        _flush()
        log(f"detail: {json.dumps(detail)}")

    print(json.dumps(headline))


if __name__ == "__main__":
    main()
