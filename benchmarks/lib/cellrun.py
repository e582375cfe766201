"""One run of one cell: set-up, the measured window, the comparison with
the reference, and the numbers.

The system under test is driven through the entry points a user calls:
a stream over the benchmark's window source (``default_stream``, or the
algorithm module's own) -> the algorithm's aggregation ->
``StreamServer(agg.servable(), stream)`` -> ``server.submit_many``.
From the program the benchmark takes that, its spans (through a sink)
and the names of its jitted programs in the trace; generator, clocks,
reduction and reference are the benchmark's own. What the stream holds
is the generator module's to say and what the algorithm computes the
algorithm module's: this file knows windows, queries, stamps and clocks.
"""

from __future__ import annotations

import gc
import math
import os
import shutil
import sys
import tempfile
import threading
import time
from concurrent.futures import TimeoutError as FutureTimeout

import numpy as np

from . import bytes_model, trace_reduce
from .peaks import peaks_for
from .traffic import QueryLoad, WindowSource, query_schedule

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
TRACE_DELAY_S = 1.0      # the traced slice starts this long after t0
TRACE_SLICE_S = 6.0      # and lasts this long (or to a second before the end)
ANSWER_WAIT_S = 60.0     # how long a late answer is waited for
STREAM_WARN_SHARE = 0.6  # of the stream handed out: time to lengthen it


class RunError(RuntimeError):
    """The run cannot give a result (no chip, stream exhausted, ...)."""


_T_IMPORT = time.perf_counter()


def log(msg: str) -> None:
    """To stderr, stamped with the seconds since the harness was loaded
    (so that a slow set-up shows which phase took it)."""
    print(f"bench: [{time.perf_counter() - _T_IMPORT:6.1f}s] {msg}",
          file=sys.stderr, flush=True)


def p95(values) -> float:
    """95th percentile of ALL samples (nearest rank)."""
    v = np.sort(np.asarray(values, float))
    if not len(v):
        raise RunError("no sample to take a 95th percentile of")
    return float(v[min(len(v) - 1, math.ceil(0.95 * len(v)) - 1)])


class CompileLog:
    """Times of XLA compilations (``jax.monitoring``; a loaded
    persistent-cache entry fires too: the event wraps compile-or-load).
    The way ``chip_smoke._CompileLog`` counts them."""

    def __init__(self):
        self.times: list = []

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on)

    def _on(self, event: str, seconds: float, **_kw) -> None:
        if event == _COMPILE_EVENT:
            self.times.append(time.perf_counter())

    def between(self, lo: float, hi: float) -> int:
        return sum(1 for t in self.times if lo <= t <= hi)


class GcLog:
    """Pauses of the interpreter's garbage collector (the harness keeps
    every query, future and answer of a run for the comparison, so a
    full collection walks millions of objects)."""

    def __init__(self):
        self.pauses: list = []   # (start, seconds, generation)
        self._t = None

    def __enter__(self):
        gc.callbacks.append(self._on)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._on)

    def _on(self, phase: str, info: dict) -> None:
        now = time.perf_counter()
        if phase == "start":
            self._t = now
        elif self._t is not None:
            self.pauses.append((self._t, now - self._t, info["generation"]))


class SpanSink:
    """Collects the program's finished spans (``obs.trace`` sink)."""

    def __init__(self):
        self.events: list = []

    def emit(self, event: dict) -> None:
        if event.get("kind") == "span":
            self.events.append(event)


class TimedServable:
    """The program's servable, with the host time of every ``next()`` on
    its payload iterator taken by the benchmark: pack, touched set,
    renumbering and dispatch of one window. The wait at the source's
    gate (the benchmark's own) is taken out."""

    def __init__(self, inner, source: WindowSource):
        self._inner, self._source = inner, source
        self.query_classes = inner.query_classes
        self.host_s: list = []   # (end time, host seconds) per window

    def payloads(self, stream):
        it = self._inner.payloads(stream)
        while True:
            t = time.perf_counter()
            g = self._source.gate_wait_s
            try:
                item = next(it)
            except StopIteration:
                return
            now = time.perf_counter()
            self.host_s.append(
                (now, now - t - (self._source.gate_wait_s - g)))
            yield item

    def boot_payload(self):
        return self._inner.boot_payload()


class ReadyWatcher(threading.Thread):
    """Waits, off the ingest thread, until each published snapshot's
    table is ready on the device, stamps that time and opens the
    source's gate."""

    def __init__(self, source: WindowSource, payload_key: str):
        super().__init__(name="bench-ready-watcher", daemon=True)
        self._source, self._key = source, payload_key
        self._q: list = []
        self._cond = threading.Condition()
        self._closed = False
        self.published_t: list = []
        self.ready_t: list = []
        self.error = None

    def on_publish(self, snap) -> None:   # the store's listener
        now = time.perf_counter()
        with self._cond:
            self.published_t.append(now)
            self._q.append(snap.payload[self._key])
            self._cond.notify()

    def head(self) -> int:
        """Index of the newest published window (-1 before the first)."""
        return len(self.published_t) - 1

    def close(self) -> None:
        with self._cond:
            self._closed = True
            self._cond.notify()

    def run(self) -> None:
        import jax

        try:
            while True:
                with self._cond:
                    while not self._q and not self._closed:
                        self._cond.wait()
                    if not self._q:
                        return
                    table = self._q.pop(0)
                jax.block_until_ready(table)
                del table
                self.ready_t.append(time.perf_counter())
                self._source.mark_ready(len(self.ready_t))
        except Exception as e:   # surfaced by the run
            self.error = e
            self._source.stop()


# --------------------------------------------------------------------- #
def start_backend() -> tuple:
    """``(device, seconds)``: the first touch of the accelerator
    runtime, made by the harness itself BEFORE any module of the
    program is imported, and timed. Nothing of the program or of the
    benchmark runs inside it, so nothing a later PR changes can move
    into it or out of it; ``setup_s`` leaves it out (PERF.md, section
    2) and the result line carries it as ``runtime_init_s``."""
    if any(m == "gelly_streaming_tpu" or m.startswith("gelly_streaming_tpu.")
           for m in sys.modules):
        raise RunError("the program was imported before the backend started")
    t = time.perf_counter()
    device = describe_device()
    return device, time.perf_counter() - t


def describe_device() -> dict:
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def memory_peak_bytes() -> int:
    import jax

    peak = 0
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def stream_length(cell, seconds: float) -> int:
    """Edges of the pre-generated stream: what ``stream_edges_per_s``
    of the traffic file would fold in the window, the warm-up's windows
    and four to spare. No run reads a rate above it (traffic.py)."""
    t = cell.traffic
    w = int(cell.config["window_edges"])
    warm = int(t.get("warm_windows", 8))
    rate = float(t["stream_edges_per_s"])
    return w * (warm + 4 + math.ceil(rate * seconds / w))


def stream_headroom_warning(handed: int, stream: int, traffic_name: str):
    """The line to log when a run has handed out more than
    ``STREAM_WARN_SHARE`` of its stream's windows (None otherwise): the
    program is within reach of the end of the stream, past which a run
    gives no result, and a ``benchmark`` PR has to raise
    ``stream_edges_per_s`` before a faster program gets there."""
    if handed <= STREAM_WARN_SHARE * stream:
        return None
    return (f"WARNING: {handed} of the stream's {stream} windows were handed "
            f"out ({100.0 * handed / stream:.0f}%, over "
            f"{100.0 * STREAM_WARN_SHARE:.0f}%): raise stream_edges_per_s in "
            f"benchmarks/traffic/{traffic_name}.json before a faster program "
            "reaches the end of the stream")


def default_stream(config: dict, source: WindowSource, context=None):
    """Count windows of ``window_edges`` over the source's columns, ids
    through ``IdentityDict`` over the configuration's id space."""
    from gelly_streaming_tpu.core.stream import SimpleEdgeStream
    from gelly_streaming_tpu.core.window import CountWindow
    from gelly_streaming_tpu.datasets import IdentityDict

    return SimpleEdgeStream(
        source, window=CountWindow(int(config["window_edges"])),
        vertex_dict=IdentityDict(int(config["id_space"])), context=context)


def run_cell(cell, seed: int, seconds: float, trace: bool, *,
             t_process: float, backend: tuple, require_tpu: bool = True,
             control: str | None = None, work_root: str | None = None):
    """Returns the result document (without validating it).
    ``backend`` is what :func:`start_backend` gave."""
    import jax

    from gelly_streaming_tpu import native
    from gelly_streaming_tpu.obs import trace as obs_trace
    from gelly_streaming_tpu.serving import StreamServer

    device, runtime_init_s = backend
    log(f"device {device}; the runtime took {runtime_init_s:.2f}s to start")
    if require_tpu:
        if device["platform"] != "tpu":
            raise RunError(f"no TPU: platform is {device['platform']!r}")
        if device["count"] < cell.chips:
            raise RunError(f"{device['count']} chips, the cell asks for "
                           f"{cell.chips}")
        if not native.native_available():
            raise RunError("the native library did not build: "
                           f"{native.build_error()}")
        peaks_for(device["kind"])   # an unknown chip is an error up front
    cfg, traffic = cell.config, cell.traffic
    algo = cell.algorithm()
    w_edges = int(cfg["window_edges"])
    qspec = traffic["queries"]
    batch = int(qspec["batch"])
    period_s = float(qspec["period_ms"]) / 1e3
    warm_windows = int(traffic.get("warm_windows", 8))
    lookback = int(cfg["guarantees"]["max_staleness_windows"])

    # ---- the stream, from the seed: the benchmark's own work, as the
    # runtime's start-up is, so its seconds are taken apart (stream_s)
    # and are no part of setup_s, however long the stream is
    n_edges = stream_length(cell, seconds)
    t = time.perf_counter()
    gen = cell.generator()
    src, dst = gen.edges(cfg, n_edges, seed, warm_windows * w_edges)
    closing = (gen.closing_edges(cfg, seed)
               if hasattr(gen, "closing_edges") else None)
    stream_s = time.perf_counter() - t
    log(f"stream: {n_edges} edges in {stream_s:.2f}s")

    source = WindowSource(src, dst, w_edges, traffic["ingest"],
                          closing=closing)
    stream = (algo.make_stream(cfg, source)
              if hasattr(algo, "make_stream")
              else default_stream(cfg, source))
    agg = algo.build(cfg)
    servable = TimedServable(agg.servable(), source)
    server = StreamServer(servable, stream)
    watcher = ReadyWatcher(source, algo.PAYLOAD_KEY)
    server.store.add_listener(watcher.on_publish)
    sink = SpanSink()
    if trace:
        obs_trace.enable(jax_annotations=True, registry_spans=False)
        obs_trace.add_sink(sink)
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, 1])
    recent_n = int(qspec.get("recent_windows", 8))

    def draw():
        rs, rd = source.recent(recent_n)
        return algo.draw_queries(rng, batch, rs, rd, cfg)

    compiles = CompileLog()
    gc_log = GcLog()
    trace_dir = None
    breakdown = None
    watcher.start()
    try:
        with compiles, gc_log:
            log("server starting")
            server.start()
            # ---- warm-up: the first windows of the stream compile (or
            # load) the fold; then every query bucket the admission
            # limit allows a sweep to reach
            if not source.wait_ready(warm_windows, 1100):
                raise RunError(
                    f"warm-up: {source.ready} of {warm_windows} windows "
                    f"ready (ingest error: {server._ingest_error!r}, "
                    f"watcher error: {watcher.error!r})")
            for k in qspec.get("warm_sweeps", []):
                # one sweep of k coalesced batches: the query kernels
                # are jitted per power-of-two bucket of a sweep's ids
                queries = []
                while len(queries) < k * batch:
                    queries += draw()[0]
                for f in server.submit_many(queries[:k * batch]):
                    f.result(600)
            warm_programs = len(compiles.times)
            log(f"warm: {source.ready} windows ready, {warm_programs} "
                "programs compiled or loaded")
            if source.mode == "open":
                # an open loop starts on an idle system: the closed-loop
                # warm-up's windows in flight drain first
                source.quiesce(600)

            # ---- the measured window --------------------------------- #
            t0 = time.perf_counter()
            wall0 = time.time()
            # process start to here, less the accelerator runtime's
            # own start-up (start_backend) and the generation of the
            # stream (above): both logged and given beside it
            setup_s = t0 - t_process - runtime_init_s - stream_s
            t_end = t0 + seconds
            source.start_measuring(t0)
            load = QueryLoad(server.submit_many, draw,
                             query_schedule(t0, seconds, period_s),
                             watcher.head)
            load.start()
            traced = None
            if trace:
                traced = _trace_slice(t0, t_end, work_root)
                trace_dir = traced["dir"]
            left = t_end - time.perf_counter()
            if left > 0:
                time.sleep(left)
            source.finish()
            load.halt()
            load.join(30)
            t_closed = time.perf_counter()
            load.wait_answers(ANSWER_WAIT_S)
            # ---- closed: the generator's closing windows follow the
            # stream's last, then the closing batches ask about them;
            # both are compared and neither is timed
            server.join(ANSWER_WAIT_S + 60)
            n_handed = source.handed
            if not source.wait_ready(n_handed, ANSWER_WAIT_S):
                log(f"only {source.ready} of {n_handed} windows ready")
            closing_batches = [
                _ask(server, draw, watcher.head())
                for _ in range(int(qspec.get("closing_batches", 0)))]
            in_window = compiles.between(t0, t_closed)
        if watcher.error is not None:
            raise RunError(f"ready watcher failed: {watcher.error!r}")
        if source.exhausted:
            raise RunError(
                f"the stream of {n_edges} edges ended inside the window: "
                "raise stream_edges_per_s in the traffic file")
        final = server.snapshot()
        table_dev = final.payload[algo.PAYLOAD_KEY]
        jax.block_until_ready(table_dev)
        peak = memory_peak_bytes()
        off_chip = (algo.chip_paths_problem(agg, server)
                    if hasattr(algo, "chip_paths_problem") else None)
        table = np.asarray(table_dev)
        final_window = int(final.window)
        stats = {"t0": t0, "t_end": t_end, "wall0": wall0,
                 "seconds": seconds, "setup_s": setup_s,
                 "runtime_init_s": runtime_init_s,
                 "compiles_in_window": in_window,
                 "warm_programs": warm_programs,
                 "gc_pauses": [p for p in gc_log.pauses
                               if t0 <= p[0] <= t_closed]}
    finally:
        # free the program's state before the reference runs
        try:
            source.stop()
            server.close(30)
        except Exception as e:
            log(f"server.close: {e!r}")
        watcher.close()
        if trace:
            obs_trace.remove_sink(sink)
            obs_trace.disable()
    host_s = list(servable.host_s)
    del table_dev, final, server, agg, servable, stream
    n_main = source.n_main or 0
    log(f"window closed: handed {n_main} of {source.n_windows} windows of "
        f"the stream and {n_handed - n_main} closing, {in_window} "
        f"compilations inside, peak {peak / 2**30:.2f} GiB, "
        f"set-up {setup_s:.1f}s")
    # an open loop hands out what its own file paces, whatever the
    # program does: only a closed loop can run into the end of the stream
    warning = (source.mode == "closed" and stream_headroom_warning(
        n_main, source.n_windows, cell.traffic_name))
    if warning:
        log(warning)
    if require_tpu and off_chip:
        raise RunError(f"the chip's paths did not run: {off_chip}")

    run = {
        "cell": cell, "source": source, "watcher": watcher, "load": load,
        "host_s": host_s, "stats": stats, "spans": sink.events,
        "n_handed": n_handed, "final_window": final_window,
        "lookback": lookback, "closing_batches": closing_batches,
    }
    values = measure(run)
    device = dict(device, memory_peak_bytes=peak)
    metrics = {n: {"value": values[n], "unit": m["unit"]}
               for n, m in cell.end_to_end.items()}
    if trace:
        try:
            layer, dev_extra, breakdown = read_trace(
                run, values, traced, device)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        device.update(dev_extra)
        for n, m in cell.per_layer.items():
            if layer.get(n) is not None:
                metrics[n] = {"value": layer[n], "unit": m["unit"]}
    t = time.perf_counter()
    compared = check(run, table, algo, control=control)
    log(f"reference and comparison: {time.perf_counter() - t:.1f}s")
    correct = all(c["value"] <= c["limit"] for c in compared.values())
    out = {"correct": bool(correct), "attempted": values["_attempted"],
           "failed": values["_failed"], "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["windows"] = {"handed": n_handed, "stream": source.n_windows,
                      "ready_in_window": values["_windows_in_window"],
                      "closing": n_handed - n_main,
                      "max_outstanding": source.max_outstanding,
                      "compiles_in_window": in_window}
    out["runtime_init_s"] = runtime_init_s
    out["stream_s"] = stream_s
    out["compared"] = compared    # last: each number beside its limit
    return out


def _ask(server, draw, head: int):
    """One batch, sent and waited for: ``(rows, answers, head)`` as the
    comparison takes a batch of the window's load."""
    queries, rows = draw()
    got = []
    for f in server.submit_many(queries):
        try:
            got.append(f.result(ANSWER_WAIT_S))
        except FutureTimeout:    # never came: counted as unanswered
            got.append(None)
        except Exception as e:   # refused or failed: says nothing wrong
            got.append(e)
    return rows, got, head


def _trace_slice(t0: float, t_end: float, work_root) -> dict:
    """Trace a few seconds in the middle of the window, bracketed by a
    host annotation on the profiler's own clock."""
    import jax

    root = os.path.join(work_root or os.getcwd(), ".bench_work")
    os.makedirs(root, exist_ok=True)
    log_dir = tempfile.mkdtemp(prefix="trace-", dir=root)
    lo = t0 + TRACE_DELAY_S
    hi = min(lo + TRACE_SLICE_S, t_end - 1.0)
    if hi - lo < 0.25:
        lo, hi = t0, max(t0 + 0.25, t_end)
    wait = lo - time.perf_counter()
    if wait > 0:
        time.sleep(wait)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0     # no per-call Python events
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        a = time.perf_counter()
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_MARK):
            wait = hi - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
        b = time.perf_counter()
    finally:
        jax.profiler.stop_trace()
    log(f"traced {b - a:.2f}s; stop_trace took "
        f"{time.perf_counter() - b:.2f}s")
    return {"dir": log_dir, "lo": a, "hi": b}


# --------------------------------------------------------------------- #
def windows_done(ready, t0: float, t_end: float) -> float:
    """Windows completed inside ``[t0, t_end]``, the two that straddle
    its ends counted by their share: window ``k`` was in service from
    when window ``k - 1`` was ready until it was ready itself (the
    device folds one window at a time), and is credited the part of
    that interval that lies inside. A count of whole windows would move
    by a whole window (a quarter of a percent of a run) with where the
    ends happen to fall."""
    ready = np.asarray(ready, float)
    if not len(ready):
        return 0.0
    start = np.concatenate([[min(t0, ready[0])], ready[:-1]])
    inside = np.clip(np.minimum(ready, t_end) - np.maximum(start, t0),
                     0.0, None)
    span = ready - start
    whole = (ready > t0) & (ready <= t_end)   # for intervals of no length
    return float(np.sum(np.where(span > 0, inside / np.where(
        span > 0, span, 1.0), whole)))


def measure(run: dict) -> dict:
    """Every end-to-end quantity and the harness's own per-layer
    statistics, from the generator's and the watcher's records. Rates
    are all work over all of the window; tails are of all samples."""
    cell, source, watcher, load = (run[k] for k in
                                   ("cell", "source", "watcher", "load"))
    st = run["stats"]
    t0, t_end, seconds = st["t0"], st["t_end"], st["seconds"]
    w_edges = int(cell.config["window_edges"])
    ready = np.asarray(watcher.ready_t)
    n_in = int(np.sum((ready > t0) & (ready <= t_end)))
    out = {"setup_s": st["setup_s"],
           "edges_per_s": windows_done(ready, t0, t_end) * w_edges / seconds,
           "_windows_in_window": n_in,
           "compiles_in_window": st["compiles_in_window"]}
    # windows whose last edge was due inside the window, each waited for
    due = np.asarray(source.due_t)
    k_in = np.flatnonzero((due > t0) & (due <= t_end))
    k_in = k_in[k_in < len(ready)]
    if len(k_in):
        out["window_p95_ms"] = 1e3 * p95(ready[k_in] - due[k_in])
    lat, age, late = [], [], []
    attempted = failed = 0
    for i in range(len(load.due)):
        rows = load.records[i]
        if rows is None:            # never reached: the load was halted
            continue
        attempted += len(rows)
        late.append(load.sent[i] - load.due[i])
        done, got = load.done_t[i], load.answers[i]
        if done is None:            # the whole batch was rejected
            failed += len(rows)
            continue
        for j in range(len(rows)):
            a = got[j]
            if a is None or isinstance(a, BaseException) or np.isnan(done[j]):
                failed += 1
                continue
            lat.append(done[j] - load.due[i])
            if 0 <= a.window < len(due):
                age.append(done[j] - due[a.window])
    out["_attempted"], out["_failed"] = attempted, failed
    if lat:
        out["query_p95_ms"] = 1e3 * p95(lat)
        out["query_mean_ms"] = 1e3 * float(np.mean(lat))
    if age:
        out["answer_age_p95_ms"] = 1e3 * p95(age)
    if late:
        out["generator_late_p95_ms"] = 1e3 * p95(late)
    host = [h for t, h in run["host_s"] if t0 < t <= t_end]
    if host:
        out["ingest_host_ms"] = 1e3 * float(np.mean(host))
    if lat:
        # a queue that grows shows as latency rising through the run
        thirds = np.array_split(np.asarray(lat), 3)
        log("query latency by third of the window, median ms: "
            + ", ".join(f"{1e3 * np.median(x):.1f}" for x in thirds if len(x))
            + f"; longest {1e3 * max(lat):.1f}; {attempted} attempted, "
            f"{failed} failed")
    if load.rejected:
        log(f"{len(load.rejected)} batches rejected; first: "
            f"{load.rejected[0][1]}")
    _log_stalls(st, source, load, ready, k_in, late)
    return out


def _log_stalls(st, source, load, ready, k_in, late) -> None:
    """Where a run stalled, if it did: the host (the generator's own
    threads ran late, the collector paused) or the system behind it."""
    t0 = st["t0"]
    pauses = st["gc_pauses"]
    if pauses:
        worst = max(pauses, key=lambda p: p[1])
        log(f"gc: {len(pauses)} collections inside the window, "
            f"{sum(p[1] for p in pauses):.3f}s in all, "
            f"{sum(1 for p in pauses if p[2] == 2)} full; longest "
            f"{worst[1]:.3f}s (generation {worst[2]}) at "
            f"{worst[0] - t0:.1f}s")
    if late:
        i = int(np.nanargmax(load.sent - load.due))
        log(f"query generator: latest batch {1e3 * max(late):.1f} ms behind "
            f"its schedule, at {load.due[i] - t0:.1f}s")
    if len(k_in):
        due = np.asarray(source.due_t)[k_in]
        handed = np.asarray(source.handed_t)[k_in]
        behind = ready[k_in] - due
        j = int(np.argmax(behind))
        log(f"windows: handed out at most {1e3 * np.max(handed - due):.1f} ms "
            f"after they were due; ready at most {1e3 * behind[j]:.1f} ms "
            f"after (window {k_in[j]}, due at {due[j] - t0:.1f}s); "
            f"{int(np.sum(behind > 0.5))} over 500 ms")


# --------------------------------------------------------------------- #
def read_trace(run: dict, values: dict, traced: dict, device: dict):
    """Per-layer metrics, ``busy_s``/``window_s`` and the breakdown from
    the traced slice and the program's spans. Each per-layer metric has
    a reader file of its own; a reader that finds nothing returns None."""
    cell = run["cell"]
    st = run["stats"]
    span_names = {e["name"] for e in run["spans"]}

    def keep(plane, line):
        return (trace_reduce.DEVICE_PLANE_RE.match(plane) is None
                or line in (trace_reduce.OPS_LINE, trace_reduce.MODULES_LINE))

    t = time.perf_counter()
    planes = trace_reduce.load(trace_reduce.find_xplane(traced["dir"]),
                               keep_line=keep)
    lo, hi = trace_reduce.window_bounds(planes)
    log(f"trace read in {time.perf_counter() - t:.1f}s: "
        + ", ".join(f"{p['name']}[{len(p['lines'])}]" for p in planes))
    busy = trace_reduce.busy_seconds(planes, lo, hi)
    dev_extra = {"window_s": (hi - lo) / 1e9, "busy_s": busy}
    breakdown = {
        "device_ops": trace_reduce.top_programs(planes, lo, hi),
        "idle_gaps": trace_reduce.idle_gaps(planes, lo, hi, span_names),
    }
    # spans that ended inside the measured window, with self times
    wall_lo = st["wall0"]
    wall_hi = wall_lo + st["seconds"]
    spans = [e for e in run["spans"] if wall_lo < e["ts"] <= wall_hi]
    child_s: dict = {}
    for e in run["spans"]:
        if "parent" in e:
            child_s[e["parent"]] = child_s.get(e["parent"], 0.0) + e["dur_s"]
    ctx = {"planes": planes, "lo": lo, "hi": hi, "spans": spans,
           "child_s": child_s, "values": values, "cell": cell,
           "run": run, "traced": traced,
           "peaks": peaks_for(device["kind"]) if device["platform"] == "tpu"
           else None}
    layer = {}
    for name, reader in cell.readers.items():
        kind = reader["reader"]["kind"]
        if kind not in READERS:
            raise RunError(f"per-layer metric {name}: unknown reader kind "
                           f"{kind!r} (known: {sorted(READERS)})")
        layer[name] = READERS[kind](reader["reader"], ctx)
    return layer, dev_extra, breakdown


def _read_harness_stat(spec: dict, ctx: dict):
    return ctx["values"].get(spec["key"])


def _read_span_mean_ms(spec: dict, ctx: dict):
    """Mean duration (self time where asked) of one of the program's
    spans, over its occurrences inside the measured window."""
    durs = []
    for e in ctx["spans"]:
        if e["name"] != spec["span"]:
            continue
        d = e["dur_s"]
        if spec.get("self_time"):
            d -= ctx["child_s"].get(e["sid"], 0.0)
        durs.append(d)
    return 1e3 * float(np.mean(durs)) if durs else None


def _read_program_mean_ms(spec: dict, ctx: dict):
    """Mean device time of one execution of a jitted program, from the
    device's own line of the trace."""
    durs = trace_reduce.program_durations(
        ctx["planes"], spec["program"], ctx["lo"], ctx["hi"])
    return 1e3 * float(np.mean(durs))


def _read_program_bytes_share(spec: dict, ctx: dict):
    """Least bytes the program has to move, over the chip's peak
    bandwidth, over its measured device time, in percent."""
    if ctx["peaks"] is None:
        return None
    durs = trace_reduce.program_durations(
        ctx["planes"], spec["program"], ctx["lo"], ctx["hi"])
    cell, source = ctx["cell"], ctx["run"]["source"]
    # the shapes of the windows handed out during the traced slice (a
    # handful; their touched sets differ by a part in 1000)
    handed = np.asarray(source.handed_t)
    ks = np.flatnonzero((handed >= ctx["traced"]["lo"])
                        & (handed <= ctx["traced"]["hi"]))[:8]
    if not len(ks):
        ks = np.asarray([max(0, source.handed - 1)])
    algo = cell.algorithm()
    model = bytes_model.MODELS[spec["bytes_model"]]
    nbytes = float(np.mean([
        model(**algo.fold_shape(cell.config, *source.window(k)))
        for k in ks]))
    least_s = nbytes / ctx["peaks"]["hbm_bytes_s"]
    return 100.0 * least_s / float(np.mean(durs))


READERS = {
    "harness_stat": _read_harness_stat,
    "span_mean_ms": _read_span_mean_ms,
    "program_mean_ms": _read_program_mean_ms,
    "program_bytes_share": _read_program_bytes_share,
}


# --------------------------------------------------------------------- #
def check(run: dict, table: np.ndarray, algo, control: str | None = None):
    """The comparison that decides ``correct``: every answer given in
    the window (and to the closing batches after it) against the
    reference's prefix of the window it is stamped with, every stamp
    against the guarantees, and the final device table of the timed
    run, the closing windows folded, against the reference's. The
    reference (``algo.Reference``) folds the same windows in order and
    says what each query has to answer and how a table is held to it;
    stamps, staleness and what never came are counted here.

    ``control="stale_prefix"`` puts the reference in the program's
    place with one guarantee broken: its answers and the table it
    publishes are those of the prefix ONE window older than their
    stamp. They pass through the same comparison, which has to come out
    not correct then."""
    cell, source, load = run["cell"], run["source"], run["load"]
    n_handed, final_window = run["n_handed"], run["final_window"]
    # answers by the window they are stamped with
    recs, vals, wins, heads = [], [], [], []
    stamp_errors = unanswered = 0
    batches = [
        (load.records[i], [None if np.isnan(t) else a for a, t in
                           zip(load.answers[i], load.done_t[i])],
         load.head_at_submit[i])
        for i in range(len(load.due))
        if load.records[i] is not None and load.answers[i] is not None]
    for rows, got, head in batches + run["closing_batches"]:
        for j, a in enumerate(got):
            if a is None:
                unanswered += 1
                continue
            if isinstance(a, BaseException):
                continue            # counted in 'failed', says nothing wrong
            if not (0 <= a.window < n_handed):
                stamp_errors += 1
                continue
            recs.append(rows[j])
            vals.append(algo.answer_value(a))
            wins.append(a.window)
            heads.append(head)
    recs = np.asarray(recs, np.int64).reshape(len(recs), -1)
    vals = np.asarray(vals, np.int64)
    wins = np.asarray(wins, np.int64)
    heads = np.asarray(heads, np.int64)
    # no answer is staler than the store's lookback: the snapshot it was
    # answered from is at most `lookback` windows behind the newest one
    # published when the query was SENT (the benchmark's own record)
    stale = int(np.sum(wins < heads - run["lookback"]))

    ref = algo.Reference(cell.config)
    order = np.argsort(wins, kind="stable")
    bounds = np.searchsorted(wins[order], np.arange(n_handed + 1))
    mismatches = 0
    for k in range(final_window + 1):
        idx = order[bounds[k]:bounds[k + 1]]
        if control == "stale_prefix":
            if len(idx):
                stale_vals = ref.expected(recs[idx])
            if k == final_window:
                table = ref.table()
        ref.fold(*source.window(k))
        if len(idx):
            have = stale_vals if control == "stale_prefix" else vals[idx]
            mismatches += int(np.sum(have != ref.expected(recs[idx])))
    compared = {
        "answers_compared": {"value": 0 if len(vals) else 1, "limit": 0},
        "answer_mismatches": {"value": mismatches, "limit": 0},
        "stale_answers": {"value": stale, "limit": 0},
        "stamp_errors": {"value": stamp_errors, "limit": 0},
        "unanswered": {"value": unanswered, "limit": 0},
        "windows_unpublished": {"value": n_handed - (final_window + 1),
                                "limit": 0},
    }
    for name, value in ref.compare_final(table).items():
        compared[name] = {"value": int(value), "limit": 0}
    log(f"compared {len(vals)} answers and the final table over "
        f"{final_window + 1} windows")
    return compared
