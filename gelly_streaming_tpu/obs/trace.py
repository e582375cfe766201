"""Lightweight structured spans over the pipeline's host-side stages.

``span("pack")`` wraps a stage; nested spans form a tree via a
thread-local stack (each pipeline thread — windower, prefetch producer,
serving worker — gets its own lineage). A finished span becomes ONE
event dict pushed to the attached sinks and one observation in the
global registry's ``trace.span_seconds{span=...}`` histogram, so span
timing shows up in the same snapshot/Prometheus surface as every other
metric. Optionally (``enable(jax_annotations=True)``) each span also
opens a ``jax.profiler.TraceAnnotation`` so host stages line up against
device ops in TensorBoard traces; :func:`device_trace` is the operator's
form of that (profiler on, annotations on, for one block).

ONE CLOCK: every span event carries ``t0``, the span's START on
``time.perf_counter()``, beside ``dur_s`` (same clock) and ``ts`` (wall
clock at exit; the cluster timeline and the JSONL replay read that one).
Cross-thread ``record_span`` events are never profiler annotations, so
``t0`` is what places them on a device trace: bracket any annotation the
profiler did record with two ``perf_counter`` reads and the offset
between the two clocks is known to within that bracket.

CROSS-PROCESS TRACES (ISSUE 9): a :class:`TraceContext` carries a trace
id + a parent span id across threads, futures, and the RPC wire. The
query client mints one per batch (``TraceContext()``), injects it into
the frame body (:meth:`TraceContext.to_wire`), and the serving path
extracts it (:meth:`TraceContext.from_wire`) and stamps every stage
span with the trace id — so one query's causal path across client,
primary, and promoted standby joins on ``trace`` in the merged shard
event stream. Propagation is EXPLICIT where threads change hands: the
context is thread-local only for same-thread nesting
(:func:`activate`); code that hops threads (future callbacks, the
serving worker's drained entries) carries the context object itself and
emits via :func:`record_span`, which synthesizes a finished-span event
without touching any thread's span stack. Span ids are process-local
(the merged stream disambiguates by ``shard``); the trace id is the one
globally meaningful join key.

DISABLED COST IS THE DESIGN CONSTRAINT: instrumentation is threaded
through per-window hot paths (``core/window.py`` pack,
``aggregate/summary.py`` dispatch, ``core/pipeline.py`` prefetch), so
``span()`` with tracing off must be near-free. The disabled path is one
attribute check and returns a SHARED no-op singleton — no object, no
dict, no clock read is allocated or taken (the zero-allocation property
``tests/test_obs.py`` pins). Hot sites that would pay even for building
an attrs dict guard on :func:`on` first.

Timing semantics: spans measure HOST wall time between ``__enter__`` and
``__exit__``. Around an async device dispatch that is enqueue time, not
compute time — the same contract as ``SummaryAggregation.sync()``
documents for throughput measurement.
"""

from __future__ import annotations

import contextlib
import itertools
import os
import threading
import time
from typing import Optional


class _Config:
    __slots__ = ("enabled", "annotate_jax", "registry_spans",
                 "key_metadata_was")

    def __init__(self):
        self.enabled = False
        self.annotate_jax = False
        self.registry_spans = True
        self.key_metadata_was = None


_CFG = _Config()
_SINKS: list = []
_LOCAL = threading.local()
_IDS = itertools.count(1)


def on() -> bool:
    """True when tracing is enabled (the hot-path guard)."""
    return _CFG.enabled


enabled = on  # alias; both read naturally at call sites


def enable(*, jax_annotations: bool = False,
           registry_spans: bool = True) -> None:
    """Turn span recording on.

    ``jax_annotations`` additionally opens a
    ``jax.profiler.TraceAnnotation`` per span (device-trace alignment;
    requires jax, imported lazily). ``registry_spans`` mirrors span
    durations into the global registry's ``trace.span_seconds``
    histogram (on by default — it is what makes span timing visible to
    the Prometheus/snapshot exporters).
    """
    _set_annotate(bool(jax_annotations))
    _CFG.registry_spans = bool(registry_spans)
    _CFG.enabled = True


def disable() -> None:
    _CFG.enabled = False
    _set_annotate(False)


_KEY_METADATA = "jax_compilation_cache_include_metadata_in_key"


def _set_annotate(on: bool) -> None:
    """``annotate_jax``, and with it whether JAX's persistent compilation
    cache keys a program by its metadata too. A device trace names every
    op by the metadata of the EXECUTABLE that ran (the ``forest.*``
    scopes, the source lines). The cache's key leaves metadata out by
    default, so a hit hands back whatever was first compiled from the
    same HLO — on a machine whose cache an older checkout filled, that
    checkout's names. Someone who turns annotations on is about to read
    a device trace: from then on a program is compiled or loaded under a
    key that holds its names, and the setting goes back with the
    annotations. Programs already in memory keep the names they came
    with."""
    if on == _CFG.annotate_jax:
        return
    _CFG.annotate_jax = on
    try:
        import jax

        if on:
            _CFG.key_metadata_was = getattr(jax.config, _KEY_METADATA)
            jax.config.update(_KEY_METADATA, True)
        elif _CFG.key_metadata_was is not None:
            jax.config.update(_KEY_METADATA, _CFG.key_metadata_was)
            _CFG.key_metadata_was = None
    except (ImportError, AttributeError):
        pass   # no jax, or one without the option: spans still record


@contextlib.contextmanager
def device_trace(log_dir: str):
    """Profile the block into ``log_dir`` (``jax.profiler``; TensorBoard
    and ``ProfileData.from_file`` read the ``.xplane.pb`` it leaves) with
    every span opened inside it written into the same trace as a
    ``TraceAnnotation``, next to the device's ``forest.*`` /
    ``query.chase`` scopes. Turns span recording and ``annotate_jax`` on
    for the block and puts both back as they were: a server that runs
    with tracing off pays for spans only while it is being traced."""
    import jax

    was = (_CFG.enabled, _CFG.annotate_jax)
    _set_annotate(True)
    _CFG.enabled = True
    try:
        with jax.profiler.trace(log_dir):
            yield
    finally:
        _CFG.enabled = was[0]
        _set_annotate(was[1])


def add_sink(sink) -> None:
    """Attach a span-event sink (``sink.emit(event_dict)``)."""
    if sink not in _SINKS:
        _SINKS.append(sink)


def remove_sink(sink) -> None:
    if sink in _SINKS:
        _SINKS.remove(sink)


def sinks() -> list:
    return list(_SINKS)


# --------------------------------------------------------------------- #
# Trace context (cross-thread / cross-process propagation)
# --------------------------------------------------------------------- #
def new_trace_id() -> str:
    """A fresh 16-hex-char trace id (random, so ids minted by many
    client processes never collide — the property the merged cluster
    stream depends on; span SIDS stay per-process counters)."""
    return os.urandom(8).hex()


def next_sid() -> int:
    """Reserve one span id from the process counter — for call sites
    that must name a span's id BEFORE the span's event is emitted (the
    RPC client advertises its batch-root sid on the wire so server-side
    spans can parent to it)."""
    return next(_IDS)


class TraceContext:
    """One query batch's identity across threads and processes.

    ``trace_id`` is the global join key (minted once, client-side);
    ``parent_sid`` is the span id server/child spans parent to —
    typically the minting side's root span, whose id is reserved with
    :func:`next_sid` so it can travel before the root span finishes.

    The context is a plain carryable object: store it on a batch, a
    future, or a pending-queue entry and every hop keeps the trace —
    that explicit handoff is the design (thread-locals silently drop
    context at thread boundaries; queues and executors cross them
    constantly in the serving tier).
    """

    __slots__ = ("trace_id", "parent_sid")

    def __init__(self, trace_id: Optional[str] = None,
                 parent_sid: Optional[int] = None):
        self.trace_id = trace_id if trace_id else new_trace_id()
        self.parent_sid = parent_sid

    def __repr__(self) -> str:
        return f"TraceContext({self.trace_id!r}, {self.parent_sid!r})"

    # -- wire codec ---------------------------------------------------- #
    def to_wire(self) -> dict:
        """The compact frame-body form (``{"t": ..., "s": ...}``)."""
        doc = {"t": self.trace_id}
        if self.parent_sid is not None:
            doc["s"] = int(self.parent_sid)
        return doc

    @classmethod
    def from_wire(cls, doc) -> Optional["TraceContext"]:
        """Rebuild a context from a frame body. TOLERANT by contract:
        a missing/garbage ``tc`` field is an untraced batch, never a
        request error — tracing must not change the wire's accept set."""
        if not isinstance(doc, dict):
            return None
        tid = doc.get("t")
        if not isinstance(tid, str) or not tid:
            return None
        sid = doc.get("s")
        return cls(tid, int(sid) if isinstance(sid, int) else None)


def current_context() -> Optional[TraceContext]:
    """The context active on THIS thread (None outside any activation)."""
    return getattr(_LOCAL, "ctx", None)


class _Activation:
    """``with activate(ctx):`` — scoped thread-local context install."""

    __slots__ = ("ctx", "prev")

    def __init__(self, ctx: Optional[TraceContext]):
        self.ctx = ctx
        self.prev = None

    def __enter__(self) -> Optional[TraceContext]:
        self.prev = getattr(_LOCAL, "ctx", None)
        _LOCAL.ctx = self.ctx
        return self.ctx

    def __exit__(self, *exc) -> bool:
        _LOCAL.ctx = self.prev
        return False


def activate(ctx: Optional[TraceContext]) -> _Activation:
    """Install ``ctx`` as this thread's current context for the block:
    spans opened inside are stamped with its trace id (and root spans
    parent to its ``parent_sid``). This is the explicit cross-thread
    handoff — a worker thread activates the context it was HANDED, it
    never inherits one implicitly."""
    return _Activation(ctx)


def record_span(
    name: str,
    dur_s: float,
    *,
    trace_id: Optional[str] = None,
    parent: Optional[int] = None,
    sid: Optional[int] = None,
    attrs: Optional[dict] = None,
    ts: Optional[float] = None,
    t0: Optional[float] = None,
) -> Optional[int]:
    """Emit one already-finished span event without entering the
    thread's span stack — the async/cross-thread form of ``span()``
    (future callbacks and drained-queue settles know their duration
    only after the fact, on a thread that never opened the span).
    ``t0`` is the span's start on ``time.perf_counter()`` where the
    call site knows it; left out, the span is taken to end now.

    Returns the span's sid (pass ``sid=`` to emit under a pre-reserved
    id from :func:`next_sid`), or None when tracing is disabled — the
    disabled path is one flag check, nothing allocated."""
    if not _CFG.enabled:
        return None
    span_id = next(_IDS) if sid is None else int(sid)
    event = {
        "kind": "span",
        "name": name,
        "ts": time.time() if ts is None else ts,
        "t0": time.perf_counter() - dur_s if t0 is None else float(t0),
        "dur_s": float(dur_s),
        "sid": span_id,
        "depth": 0,
    }
    if trace_id:
        event["trace"] = trace_id
    if parent is not None:
        event["parent"] = parent
    if attrs:
        event["attrs"] = attrs
    for s in _SINKS:
        s.emit(event)
    if _CFG.registry_spans:
        from .registry import get_registry

        get_registry().histogram(
            "trace.span_seconds", span=name
        ).observe(float(dur_s))
    return span_id


class _NoopSpan:
    """The disabled-mode singleton: every method is a no-op, entering
    returns the singleton itself. ``recording`` lets call sites skip
    building expensive attributes."""

    __slots__ = ()
    recording = False

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs):
        return self

    def cancel(self):
        return self


NOOP_SPAN = _NoopSpan()


class Span:
    """One recorded stage. Use via ``with span("pack", {...}):``."""

    __slots__ = ("name", "attrs", "sid", "parent", "depth", "t0",
                 "dur_s", "_ann", "ctx", "annotate", "cancelled")
    recording = True

    def __init__(self, name: str, attrs: Optional[dict] = None,
                 annotate: bool = True):
        self.name = name
        self.attrs = attrs
        self.annotate = annotate
        self.cancelled = False
        self.sid = 0
        self.parent = None
        self.depth = 0
        self.t0 = 0.0
        self.dur_s = 0.0
        self._ann = None
        self.ctx = None

    def set(self, **attrs) -> "Span":
        """Attach attributes after entry (lets call sites add values
        computed inside the span without paying for them when tracing
        is off — guard on ``.recording``)."""
        if self.attrs is None:
            self.attrs = {}
        self.attrs.update(attrs)
        return self

    def cancel(self) -> "Span":
        """This span turned out to time nothing (the pull that found the
        stream at its end): it still leaves the thread's stack on exit,
        but emits no event and observes nothing."""
        self.cancelled = True
        return self

    def __enter__(self) -> "Span":
        stack = getattr(_LOCAL, "stack", None)
        if stack is None:
            stack = _LOCAL.stack = []
        self.sid = next(_IDS)
        self.depth = len(stack)
        self.ctx = getattr(_LOCAL, "ctx", None)
        if stack:
            self.parent = stack[-1].sid
        elif self.ctx is not None:
            # a root span under an activated context parents to the
            # context's (possibly remote) span id — the cross-process
            # link the timeline joins on
            self.parent = self.ctx.parent_sid
        else:
            self.parent = None
        stack.append(self)
        if _CFG.annotate_jax and self.annotate:
            try:
                import jax

                self._ann = jax.profiler.TraceAnnotation(self.name)
                self._ann.__enter__()
            except Exception:
                self._ann = None
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        self.dur_s = time.perf_counter() - self.t0
        if self._ann is not None:
            try:
                self._ann.__exit__(*exc)
            # graftlint: disable=GL003 (span teardown must never raise, and the obs layer cannot count into the registry it feeds — a sink mirroring events back through a span would recurse)
            except Exception:
                pass
        stack = getattr(_LOCAL, "stack", None)
        if stack and stack[-1] is self:
            stack.pop()
        elif stack and self in stack:  # mis-nested exit: drop through it
            del stack[stack.index(self):]
        if self.cancelled:
            return False
        event = {
            "kind": "span",
            "name": self.name,
            "ts": time.time(),
            "t0": self.t0,
            "dur_s": self.dur_s,
            "sid": self.sid,
            "depth": self.depth,
        }
        if self.parent is not None:
            event["parent"] = self.parent
        if self.ctx is not None:
            event["trace"] = self.ctx.trace_id
        if self.attrs:
            event["attrs"] = self.attrs
        for s in _SINKS:
            s.emit(event)
        if _CFG.registry_spans:
            from .registry import get_registry

            get_registry().histogram(
                "trace.span_seconds", span=self.name
            ).observe(self.dur_s)
        return False


def span(name: str, attrs: Optional[dict] = None, annotate: bool = True):
    """A context manager timing one named stage (no-op when disabled).

    ``attrs`` is an optional plain dict of span attributes (window
    index, superbatch K, block edges, ...). Truly hot call sites guard
    with :func:`on` before building the dict; everywhere else the dict
    literal's cost is negligible next to the stage it measures.

    ``annotate=False`` keeps the span out of the profiler even under
    ``enable(jax_annotations=True)``: an event for the sinks only. For
    a span that ENCLOSES a whole unit of work (``serving.window``, one
    per window around everything the ingest thread does for it): a
    reader that gives each idle gap of the device to the host
    annotation covering most of it would give every gap to the
    enclosing one and never name a child. ``t0`` still places the
    event on a device trace (ONE CLOCK, above).
    """
    if not _CFG.enabled:
        return NOOP_SPAN
    return Span(name, attrs, annotate)


def current_span() -> Optional[Span]:
    """The innermost open span on this thread (None outside any span)."""
    stack = getattr(_LOCAL, "stack", None)
    return stack[-1] if stack else None
