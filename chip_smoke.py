"""The quickest proof that the system still starts on the chip.

One process drives the README's flagship path once, through the entry
points a user calls, at the repository's north-star width: a seeded
scale-23 R-MAT corpus written as the packed binary file ->
``datasets.stream_file`` (2^20-edge count windows, ``IdentityDict`` over
a 2^23 id space, prefetch thread) -> ``ConnectedComponents()`` with the
carry left at ``"auto"`` -> ``StreamServer(agg.servable(), stream)`` ->
``ConnectedQuery`` / ``ComponentSizeQuery`` batches answered by the
server's default ``QueryEngine`` while ingest runs, and again after
``server.join()``. Only depth is cut: 16 windows (2^24 edges).

It refuses to run without a TPU, asserts that the chip's code paths ran
(forest carry, device query path, native window prep), and checks values,
not liveness, against a host oracle over the same columns. Any failed
check or exception is a non-zero exit; stdout stays empty then.

Log lines go to stderr. stdout carries two JSON lines: the run's record
``{"chip_smoke": {...}}`` (device, versions, carry, engine path, windows,
set-up and steady seconds, queries, mismatches, compile cache, peak
memory), then the verdict, last and with exactly these keys:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
The times in the record are log values for the reader of a run, not
benchmark records.

    python chip_smoke.py          # on the machine that holds the chip
"""

from __future__ import annotations

import importlib.metadata
import json
import os
import sys
import tempfile
import threading
import time

import numpy as np

SCALE = 23            # R-MAT scale: the 2^23 id space, not cut
WINDOW = 1 << 20      # edges per count window, not cut
N_WINDOWS = 16        # depth: the only thing cut
SEED = 2026
N_PAIRS = 1000        # final connected(u, v) checks
N_SIZES = 250         # final component-size checks
LIVE_PAIRS = 64       # per live batch
LIVE_SIZES = 16

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def log(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr, flush=True)


class _CompileLog:
    """Counts XLA compilations per thread through ``jax.monitoring`` (a
    loaded persistent-cache entry counts too: the event wraps the
    compile-or-load call), so a window that paid set-up can be told from
    a steady one without guessing from its duration. A context manager:
    the listeners are process-wide and are removed on exit."""

    def __init__(self):
        self.by_thread: dict = {}
        self.seconds = 0.0
        self.cache_hits = 0

    def __enter__(self) -> "_CompileLog":
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        return self

    def __exit__(self, *exc) -> None:
        import jax

        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)

    def _duration(self, event: str, seconds: float, **_kw) -> None:
        if event == _COMPILE_EVENT:
            tid = threading.get_ident()
            self.by_thread[tid] = self.by_thread.get(tid, 0) + 1
            self.seconds += seconds

    def _event(self, event: str, **_kw) -> None:
        if event == _CACHE_HIT_EVENT:
            self.cache_hits += 1

    def here(self) -> int:
        """Compilations the calling thread has paid so far."""
        return self.by_thread.get(threading.get_ident(), 0)

    @property
    def programs(self) -> int:
        return sum(self.by_thread.values())


def write_corpus(path: str, scale: int, n_edges: int, seed: int):
    """The seeded R-MAT stream as ONE packed binary corpus file; returns
    the file's ``(src, dst)`` columns as read back from it (memmap views),
    so queries and oracle see exactly what the stream will."""
    from gelly_streaming_tpu import datasets

    src, dst = datasets.rmat_edges(n_edges, scale, seed=seed)
    datasets.write_binary(path, src, dst)
    src, dst, _val = next(datasets.iter_binary_chunks(path, n_edges))
    return src, dst


def make_queries(src, dst, id_bound: int, n: int, rng):
    """``n`` seeded (u, v) pairs with both answers likely: a third are
    actual edges of the corpus (connected once their window folds, not
    before), a third pair two unrelated endpoints, a third are uniform
    over the id space (mostly vertices the stream never touches)."""
    k = n // 3
    i, j, l = (rng.integers(0, len(src), k) for _ in range(3))
    us = np.concatenate([src[i], src[j], rng.integers(0, id_bound, n - 2 * k)])
    vs = np.concatenate([dst[i], dst[l], rng.integers(0, id_bound, n - 2 * k)])
    return us.astype(np.int64), vs.astype(np.int64)


def run_smoke(
    workdir: str,
    *,
    scale: int = SCALE,
    window: int = WINDOW,
    n_windows: int = N_WINDOWS,
    seed: int = SEED,
    n_pairs: int = N_PAIRS,
    n_sizes: int = N_SIZES,
) -> dict:
    """The smoke's body: corpus -> stream -> CC -> server -> live and
    final queries -> oracle. Returns the result fields; raises on any
    failed check. Which code paths ran is reported, not asserted, so the
    same body rehearses on the CPU at a tiny size (``assert_chip_paths``
    is the chip's part)."""
    import jax

    from gelly_streaming_tpu import datasets, native
    from gelly_streaming_tpu.core.window import CountWindow
    from gelly_streaming_tpu.library import ConnectedComponents
    from gelly_streaming_tpu.serving import (
        ComponentSizeQuery,
        ConnectedQuery,
        StreamServer,
    )
    from gelly_streaming_tpu.summaries.forest import (
        fold_into_forest_host,
        resolve_flat_host,
    )

    compiles = _CompileLog()
    id_bound = 1 << scale
    n_edges = window * n_windows
    rng = np.random.default_rng(seed)

    t0 = time.perf_counter()
    binp = os.path.join(workdir, f"rmat{scale}_{n_edges}_{seed}.gbin")
    src, dst = write_corpus(binp, scale, n_edges, seed)
    log(f"corpus {binp}: {n_edges} edges over 2^{scale} ids "
        f"in {time.perf_counter() - t0:.1f}s")

    stream = datasets.stream_file(
        binp, window=CountWindow(window),
        vertex_dict=datasets.IdentityDict(id_bound), prefetch_depth=2,
    )
    agg = ConnectedComponents()
    server = StreamServer(agg.servable(), stream)

    # one record per published window, taken on the ingest thread:
    # (window, clock, compilations that thread has paid so far)
    published: list = []
    server.store.add_listener(
        lambda snap: published.append(
            (snap.window, time.perf_counter(), compiles.here())
        )
    )

    def ask(queries):
        """One batch through the server's front door, answers in order."""
        return [f.result(900) for f in server.submit_many(queries)]

    def pair_queries(us, vs):
        return [ConnectedQuery(int(u), int(v)) for u, v in zip(us, vs)]

    def size_queries(vs):
        return [ComponentSizeQuery(int(v)) for v in vs]

    live_pairs: list = []  # rows (window, u, v, connected)
    live_sizes: list = []  # rows (window, v, size)
    t_start = time.perf_counter()
    with compiles, server:
        while not server.ingest_finished():
            us, vs = make_queries(src, dst, id_bound, LIVE_PAIRS, rng)
            sv = np.concatenate([us[:LIVE_SIZES // 2], vs[:LIVE_SIZES // 2]])
            answers = ask(pair_queries(us, vs) + size_queries(sv))
            conn, size = answers[:len(us)], answers[len(us):]
            for a in answers:
                # the stamp every live answer carries: the window it was
                # answered at, how far behind the head, the edges folded
                if not (0 <= a.window < n_windows and a.staleness >= 0
                        and a.watermark == (a.window + 1) * window):
                    raise AssertionError(f"live answer stamp: {a}")
            live_pairs += [
                (a.window, u, v, a.value) for a, u, v in zip(conn, us, vs)
            ]
            live_sizes += [(a.window, v, a.value) for a, v in zip(size, sv)]
            time.sleep(0.01)
        server.join(900)
        final_snap = server.snapshot()
        jax.block_until_ready(final_snap.payload["labels"])
        t_ingested = time.perf_counter()

        fu, fv = make_queries(src, dst, id_bound, n_pairs, rng)
        fs = np.concatenate([fu[:n_sizes // 2], fv[:n_sizes - n_sizes // 2]])
        # two batches: together they would pass the admission limit
        final_conn = ask(pair_queries(fu, fv))
        final_size = ask(size_queries(fs))
        stats = server.stats.snapshot()
    live_pairs = np.asarray(live_pairs, np.int64).reshape(-1, 4)
    live_sizes = np.asarray(live_sizes, np.int64).reshape(-1, 3)
    log(f"ingest {t_ingested - t_start:.1f}s, {len(published)} windows "
        f"published, {len(live_pairs) + len(live_sizes)} live answers")

    # ---- what ran ---------------------------------------------------- #
    if [p[0] for p in published] != list(range(n_windows)):
        raise AssertionError(f"published windows {[p[0] for p in published]}")
    for a in final_conn + final_size:
        if (a.window, a.staleness, a.watermark) != (n_windows - 1, 0, n_edges):
            raise AssertionError(f"final answer not at the head: {a}")
    if not len(live_pairs):
        raise AssertionError("no query was answered while ingest ran")

    # ---- the host oracle: numpy group folds over the same columns, each
    # live answer judged against the prefix of the window it names ------ #
    t_or = time.perf_counter()
    vcap = int(final_snap.payload["labels"].shape[0])
    lab = np.arange(vcap, dtype=np.int64)
    live_mismatches = 0
    for w in range(n_windows):
        lab = fold_into_forest_host(
            lab, src[w * window:(w + 1) * window],
            dst[w * window:(w + 1) * window],
        )
        lp = live_pairs[live_pairs[:, 0] == w]
        live_mismatches += int(np.sum(
            (lab[lp[:, 1]] == lab[lp[:, 2]]) != lp[:, 3].astype(bool)
        ))
        ls = live_sizes[live_sizes[:, 0] == w]
        if len(ls):
            sizes = np.bincount(lab, minlength=vcap)
            live_mismatches += int(np.sum(sizes[lab[ls[:, 1]]] != ls[:, 2]))
    touched = np.zeros(vcap, bool)
    touched[src] = True
    touched[dst] = True
    oracle_components = int(np.unique(lab[touched]).size)
    _secs, baseline_components = native.cc_baseline(
        src.astype(np.int64), dst.astype(np.int64), window=window,
    )
    if oracle_components != baseline_components:
        raise AssertionError(
            f"the two host references disagree: numpy oracle "
            f"{oracle_components} components, compiled baseline "
            f"{baseline_components}"
        )
    device_lab = resolve_flat_host(np.asarray(final_snap.payload["labels"]))
    table_mismatches = int(np.sum(device_lab != lab))
    sizes = np.bincount(lab, minlength=vcap)
    conn_mismatches = int(np.sum(
        np.asarray([a.value for a in final_conn]) != (lab[fu] == lab[fv])
    ))
    size_mismatches = int(np.sum(
        np.asarray([a.value for a in final_size]) != sizes[lab[fs]]
    ))
    n_live = len(live_pairs) + len(live_sizes)
    log(f"oracle {time.perf_counter() - t_or:.1f}s: {oracle_components} "
        f"components; mismatches live={live_mismatches}/{n_live} "
        f"connected={conn_mismatches}/{len(fu)} size={size_mismatches}/"
        f"{len(fs)} table={table_mismatches}/{vcap}")
    mismatches = (
        live_mismatches + conn_mismatches + size_mismatches + table_mismatches
    )
    if mismatches:
        raise AssertionError(f"{mismatches} oracle mismatches")

    # ---- set-up apart from steady: a window is set-up when the ingest
    # thread compiled (or loaded) a program while folding it
    edges_t = [t_start] + [p[1] for p in published]
    paid = [0] + [p[2] for p in published]
    setup_s = steady_s = 0.0
    setup_windows = 0
    for k in range(n_windows):
        dt = edges_t[k + 1] - edges_t[k]
        if paid[k + 1] > paid[k]:
            setup_s += dt
            setup_windows += 1
        else:
            steady_s += dt
    steady_s += t_ingested - edges_t[-1]  # the in-flight tail draining
    mem = jax.devices()[0].memory_stats()
    return {
        "carry": agg._cc_mode,
        "engine_path": "host" if server.engine.prefer_host else "device",
        "native_loaded": native.native_available(),
        "native_window_prep": (
            agg._prep is not None and agg._prep._native is not None
        ),
        "windows": n_windows,
        "window_edges": window,
        "edges": n_edges,
        "id_space": id_bound,
        "setup_s": round(setup_s, 3),
        "setup_windows": setup_windows,
        "steady_s": round(steady_s, 3),
        "steady_windows": n_windows - setup_windows,
        "programs_compiled_or_loaded": compiles.programs,
        "compile_or_load_s": round(compiles.seconds, 3),
        "persistent_cache_hits": compiles.cache_hits,
        "queries_live": n_live,
        "live_windows_seen": sorted(set(live_pairs[:, 0].tolist())),
        "queries_final": len(final_conn) + len(final_size),
        "query_batches": stats["batches"],
        "components": oracle_components,
        "mismatches": mismatches,
        "peak_bytes_in_use": mem["peak_bytes_in_use"] if mem else None,
    }


def assert_chip_paths(result: dict) -> None:
    """The chip takes different code from every CPU test; a run that
    passed on the other paths proves nothing about the chip's."""
    problems = []
    if result["carry"] != "forest":
        problems.append(f"CC carry is {result['carry']!r}, not 'forest'")
    if result["engine_path"] != "device":
        problems.append("QueryEngine took the host path (prefer_host=True)")
    if not (result["native_loaded"] and result["native_window_prep"]):
        problems.append("the forest carry ran on the numpy window prep")
    if not result["peak_bytes_in_use"]:
        problems.append("the device reported no peak memory")
    if problems:
        raise AssertionError("; ".join(problems))


def main() -> int:
    from gelly_streaming_tpu import native
    from gelly_streaming_tpu.utils.compile_cache import (
        cache_entry_count,
        enable_compile_cache,
    )
    from gelly_streaming_tpu.utils.profiling import describe_device

    cache_dir = enable_compile_cache()
    device = describe_device()
    versions = {
        p: importlib.metadata.version(p) for p in ("jax", "jaxlib", "libtpu")
    }
    log(f"device {json.dumps(device)} versions {json.dumps(versions)}")
    if device["platform"] != "tpu":
        log(f"no TPU: jax.devices()[0].platform is {device['platform']!r} "
            f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}); refusing "
            "to run the chip smoke on it")
        return 1
    if not native.native_available():
        log("the native library did not build; the forest carry would run "
            "on the numpy window prep:\n" + str(native.build_error()))
        return 1
    entries_before = cache_entry_count(cache_dir)
    # the corpus lives and dies inside the checkout (a .gitignore'd name)
    here = os.path.dirname(os.path.abspath(__file__))
    with tempfile.TemporaryDirectory(prefix=".chip_smoke_", dir=here) as workdir:
        result = run_smoke(workdir)
    assert_chip_paths(result)
    detail = {
        "device": device,
        "versions": versions,
        **result,
        "compile_cache_dir": cache_dir,
        "compile_cache_entries_before": entries_before,
        "compile_cache_entries": cache_entry_count(cache_dir),
    }
    print(json.dumps({"chip_smoke": detail}), flush=True)
    # the verdict, last and alone: exactly these keys, nothing added
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
