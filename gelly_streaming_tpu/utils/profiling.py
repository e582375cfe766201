"""Per-window step timing and roofline accounting (SURVEY.md §5).

The reference has no profiling beyond ``getNetRuntime()`` printed by one
example (``CentralizedWeightedMatching.java:62-64``); its pom references
measurement jars whose classes don't exist. SURVEY.md §5 directs: plan for
``jax.profiler`` traces + per-window step timing from day one, and keep the
reference's design stance that metrics are ordinary output streams
(``README.md:26-32``).

- :func:`profiled` wraps any per-window emission iterator and yields
  ``(result, WindowStats)`` pairs — the metrics ARE a stream.
- :class:`StreamProfiler` aggregates those stats (edges/sec, p50/p95
  window latency). Since ISSUE 3 it is also a VIEW over the obs metric
  registry: with observability enabled (or a registry passed), every
  recorded window mirrors into ``profiler.window_seconds`` /
  ``profiler.window_edges`` so the same numbers surface through the
  Prometheus/JSONL exporters; percentiles use the repo-wide
  :func:`~gelly_streaming_tpu.obs.registry.nearest_rank` rule.

Device traces are the tracing system's: ``obs.device_trace(log_dir)``.
"""

from __future__ import annotations

import time
from typing import Any, Iterator, List, NamedTuple, Optional, Tuple

from ..obs import trace as _trace
from ..obs.registry import get_registry, nearest_rank


class WindowStats(NamedTuple):
    """One window's measurements."""

    index: int
    wall_seconds: float
    edges: Optional[int]  # None when the source doesn't expose block sizes


class StreamProfiler:
    """Aggregate window stats; exposes throughput and latency percentiles.

    ``registry`` (optional) pins where mirrored metrics go; by default
    they go to the global obs registry ONLY while observability is
    enabled, so a bare profiler stays a private list like it always was.
    ``name`` prefixes the mirrored instrument names (one profiler per
    pipeline stage stays distinguishable).
    """

    def __init__(self, registry=None, name: str = "profiler"):
        self.stats: List[WindowStats] = []
        self._registry = registry
        self._name = name

    def record(self, s: WindowStats) -> None:
        self.stats.append(s)
        reg = self._registry
        if reg is None and _trace.on():
            reg = get_registry()
        if reg is not None:
            reg.histogram(self._name + ".window_seconds").observe(
                s.wall_seconds
            )
            if s.edges:
                reg.counter(self._name + ".window_edges").inc(s.edges)

    # ------------------------------------------------------------------ #
    def total_edges(self) -> int:
        return sum(s.edges or 0 for s in self.stats)

    def total_seconds(self) -> float:
        return sum(s.wall_seconds for s in self.stats)

    def edges_per_sec(self) -> float:
        t = self.total_seconds()
        return self.total_edges() / t if t > 0 else 0.0

    def latency_percentile(self, q: float) -> float:
        """q in [0, 100]: percentile of per-window wall time (seconds).
        Nearest-rank, via the shared obs helper (previously duplicated
        here and in ``serving/stats._pct``)."""
        return nearest_rank(sorted(s.wall_seconds for s in self.stats), q)

    def summary(self) -> dict:
        return {
            "windows": len(self.stats),
            "edges": self.total_edges(),
            "edges_per_sec": self.edges_per_sec(),
            "p50_window_s": self.latency_percentile(50),
            "p95_window_s": self.latency_percentile(95),
        }


def profiled(
    iterator: Iterator[Any],
    profiler: Optional[StreamProfiler] = None,
    edges_per_window: Optional[Iterator[int]] = None,
) -> Iterator[Tuple[Any, WindowStats]]:
    """Yield ``(result, WindowStats)`` per window of any emission stream.

    Timing covers the work to produce each emission (next() call), i.e. the
    host windowing + device step + host emission — the end-to-end per-window
    latency BASELINE.md's p50 metric asks for.
    """
    prof = profiler if profiler is not None else StreamProfiler()
    idx = 0
    it = iter(iterator)
    sizes = iter(edges_per_window) if edges_per_window is not None else None
    while True:
        t0 = time.perf_counter()
        try:
            result = next(it)
        except StopIteration:
            return
        dt = time.perf_counter() - t0
        n = next(sizes, None) if sizes is not None else None
        stats = WindowStats(idx, dt, n)
        prof.record(stats)
        yield result, stats
        idx += 1


# --------------------------------------------------------------------- #
# Roofline accounting (round-2 verdict #4): every perf claim anchored as
# a fraction of the chip's peak — MFU for MXU-dense paths, fraction of
# HBM bandwidth for memory-bound scatter/gather kernels.
# --------------------------------------------------------------------- #

#: published peaks, keyed by the EXACT ``device_kind`` string JAX reports
#: for the chip: (bf16 FLOP/s, HBM bytes/s). A row is added when a run on
#: that chip has printed its ``device_kind`` (``chip_smoke.py`` does) —
#: never from a guess at the string.
_CHIP_PEAKS = {
    # one TPU v5e chip: 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s (Google
    # Cloud documentation, "TPU v5e"); kind string from chip_smoke.py's
    # first pass on the chip (CHANGES.md, PR 21)
    "TPU v5 lite": (197e12, 819e9),
}


def describe_device() -> dict:
    """The default device as JAX reports it — the stamp every chip result
    carries and every chip entry point checks: ``{"platform", "kind",
    "count"}`` from ``jax.devices()[0].platform``, ``.device_kind`` and
    ``len(jax.devices())``."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def chip_spec() -> dict:
    """Published peaks of the attached device, by its exact
    ``device_kind``. A device that is not in the table is an error, not a
    default: a roofline share against an assumed peak is not a
    measurement. ``jax.devices()`` raising propagates."""
    kind = describe_device()["kind"]
    if kind not in _CHIP_PEAKS:
        raise ValueError(
            f"no published peaks for device_kind {kind!r}; add a row with "
            f"its source to _CHIP_PEAKS (known: {sorted(_CHIP_PEAKS)})"
        )
    flops, bw = _CHIP_PEAKS[kind]
    return {"kind": kind, "peak_bf16_flops": flops, "hbm_bytes_s": bw}


def roofline_entry(
    seconds: float, *, flops: float = 0.0, bytes_moved: float = 0.0,
    model: str = "",
) -> dict:
    """One kernel's achieved rate vs the chip roofline.

    ``flops``/``bytes_moved`` are the caller's ANALYTIC model of the
    kernel's work (the model string documents what was counted); the
    returned percentages are achieved/peak for whichever resources were
    modeled.
    """
    spec = chip_spec()
    out = {"time_ms": seconds * 1e3, "model": model}
    if flops:
        out["gflops_s"] = flops / seconds / 1e9
        out["mfu_pct"] = 100.0 * flops / seconds / spec["peak_bf16_flops"]
    if bytes_moved:
        out["gbytes_s"] = bytes_moved / seconds / 1e9
        out["hbm_pct"] = 100.0 * bytes_moved / seconds / spec["hbm_bytes_s"]
    return out
