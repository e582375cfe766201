"""Unified observability: metric registry + pipeline spans + exporters.

The reference has no metrics layer at all (one example prints
``getNetRuntime()``); SURVEY.md §5 directs building per-window timing
from day one while keeping the reference's design stance that metrics
are ordinary OUTPUT STREAMS, never a side server. After the serving
(PR 1) and superbatch (PR 2) layers, telemetry lived in two
disconnected ad-hoc modules; this package is the one coherent layer the
ROADMAP follow-ons (auto-K from measured window cost, multi-host
fan-out) read from:

- :mod:`registry` — process-wide thread-safe counters/gauges/bounded
  histograms; :func:`~gelly_streaming_tpu.obs.registry.nearest_rank`
  is THE shared percentile rule.
- :mod:`trace` — ``span("pack")`` structured spans, nested per thread,
  near-zero when disabled, optional ``jax.profiler`` annotation.
- :mod:`export` — JSONL event log (replayable:
  :func:`~gelly_streaming_tpu.obs.export.replay` reconstructs an
  identical registry), Prometheus text renderer, periodic snapshots
  composable with any emission stream.
- :mod:`cluster` — the multi-process plane (ISSUE 7): per-shard
  streaming :class:`~gelly_streaming_tpu.obs.cluster.ShardSink` event
  shipping merged by
  :class:`~gelly_streaming_tpu.obs.cluster.ClusterAggregator` into one
  shard-labeled registry (snapshot == union of per-worker replays).
- :mod:`endpoint` — stdlib HTTP scrape surface (``/metrics`` /
  ``/healthz`` / ``/events``) over any registry or aggregator.
- :mod:`flight` — crash flight recorder: a bounded ring of the last N
  events, atomically dumped on worker death / fault kills / supervisor
  restarts and collected into failure reports.
- :mod:`timeline` — ``python -m gelly_streaming_tpu.obs.timeline
  <dir>`` merges a run's shard logs + flight dumps into one ordered
  story.

Usage::

    from gelly_streaming_tpu import obs

    obs.enable()                      # spans + hot-path gauges on
    sink = obs.JsonlSink("run.jsonl")
    obs.attach_sink(sink)             # event log: spans + metric events
    ... run the pipeline ...
    obs.get_registry().snapshot()     # plain-dict metrics
    sink.write()                      # span/metric evidence to disk
    obs.detach_sink(sink); obs.disable()

Instrumented stages (all gated on ``obs.enable()`` except the serving
stats, which are part of the serving API and always on):
``window.pack`` / ``window.superbatch_pack`` / ``window.stack`` host
packing, ``engine.dispatch`` / ``engine.superbatch_dispatch`` device
dispatch (+ ``engine.donated_dispatches`` counter),
``pipeline.queue_depth`` / ``producer_blocked_s`` / ``consumer_idle_s``
prefetch coupling, ``checkpoint.barrier`` / ``barrier_wait`` /
``serialize``, and the ``serving.*`` admission/batch/drain surface.

A served window's host life is ONE span tree on the ingest thread
(``serving/server.py``): the root ``serving.window`` (attributes
``window``, ``in_flight``, ``ring``; never a profiler annotation, so a
device trace's idle gaps still go to its children) over
``ingest.wait_source``, ``window.pack``, the fold's span
(``forest.window`` / ``degrees.window`` and their children),
``window.emit`` (the touch log and the emission; ``fresh``) and
``serving.publish`` (``evicted``, ``evicted_addr``). The root's SELF
time is the host time of a window that no span names. The gauge
``serving.windows_in_flight`` is the operator's reading of who sets
the pace, set at every publish while tracing is on: the published
tables still being computed, the one just published among them. At the
ingest loop's depth (2 under a closed loop of 2) the device does; at 1
the device was waiting for the window just dispatched (the host or the
source sets the pace); at 0 the fold was over before it was published.

Resilience events (PR 4) are ALWAYS on — a restart or a rejected
checkpoint is operational truth, not optional telemetry:
``resilience.restarts{kind}`` / ``recovery_seconds`` /
``deduped_windows`` / ``backoff_s`` / ``poison_windows`` /
``ckpt_rejected`` / ``fault_injected{site}``,
``pipeline.producer_leaked`` / ``pipeline.stalls``,
``source.reconnects`` / ``source.malformed_lines``, and
``serving.shed{cls}`` / ``retries`` / ``deadline_expired`` /
``worker_stalls`` (see ``gelly_streaming_tpu/resilience/__init__.py``).
"""

from .registry import (
    Counter,
    Gauge,
    Histogram,
    MetricRegistry,
    format_key,
    get_registry,
    nearest_rank,
    set_registry,
)
from .trace import (
    NOOP_SPAN,
    Span,
    TraceContext,
    activate,
    current_context,
    current_span,
    device_trace,
    disable,
    enable,
    enabled,
    new_trace_id,
    next_sid,
    on,
    record_span,
    span,
)
from . import trace as _trace
from .export import (
    JsonlSink,
    prometheus_text,
    read_jsonl,
    replay,
    snapshot_stream,
    write_jsonl,
)
from .cluster import (
    ClusterAggregator,
    ShardSink,
    iter_shard_events,
    iter_trace_events,
    shard_events_path,
)
from .flight import FlightRecorder, read_dump
from . import flight as _flight


def __getattr__(name: str):
    # MetricsEndpoint is lazy on purpose: hot-path modules import this
    # package for get_registry/trace, and the endpoint's http.server /
    # socketserver chain is startup cost no obs-disabled run should pay
    # for a scrape surface it never starts (cluster/flight stay eager —
    # they ARE the always-on sink path).
    if name == "MetricsEndpoint":
        from .endpoint import MetricsEndpoint

        return MetricsEndpoint
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def attach_sink(sink) -> None:
    """Attach one sink to BOTH event sources: finished spans (tracer)
    and metric mutations (the global registry). One call gives one
    unified chronological event log."""
    _trace.add_sink(sink)
    get_registry().add_sink(sink)


def detach_sink(sink) -> None:
    _trace.remove_sink(sink)
    get_registry().remove_sink(sink)


def reset() -> None:
    """Test/bench hygiene: disable tracing, drop all tracer sinks,
    uninstall any flight recorder, and install a fresh global
    registry."""
    disable()
    _flight.uninstall()
    for s in _trace.sinks():
        _trace.remove_sink(s)
    set_registry(None)


__all__ = [
    "ClusterAggregator",
    "Counter",
    "FlightRecorder",
    "Gauge",
    "Histogram",
    "JsonlSink",
    "MetricRegistry",
    "MetricsEndpoint",
    "ShardSink",
    "NOOP_SPAN",
    "Span",
    "TraceContext",
    "activate",
    "attach_sink",
    "current_context",
    "current_span",
    "detach_sink",
    "device_trace",
    "disable",
    "enable",
    "enabled",
    "format_key",
    "get_registry",
    "iter_shard_events",
    "iter_trace_events",
    "nearest_rank",
    "new_trace_id",
    "next_sid",
    "on",
    "prometheus_text",
    "record_span",
    "read_dump",
    "read_jsonl",
    "replay",
    "reset",
    "set_registry",
    "shard_events_path",
    "snapshot_stream",
    "span",
    "write_jsonl",
]
