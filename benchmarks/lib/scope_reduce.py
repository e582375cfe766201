"""Inside one program of a profiler trace: device time and loop trips
by ``jax.named_scope``.

The program wraps its phases in named scopes (``forest.chase``,
``forest.group``, ``forest.fixpoint``, ``forest.commit``,
``forest.latch``, ``query.chase``). XLA keeps a scope as a segment of
every instruction's ``op_name``. Where it shows in a TPU trace (looked
at by hand on a v5e, PR 26): NOT in the event's name, which is the HLO
instruction's text without its metadata, and not among the event's own
stats (``device_offset_ps``, ``device_duration_ps``), which is all
``jax.profiler.ProfileData`` hands out. It is the ``tf_op`` stat of the
event's METADATA record in the ``.xplane.pb``
(``jit(step)/forest.chase/while/body/gather:``), beside ``source``,
``hlo_category`` and ``bytes_accessed``. ``scoped_planes`` therefore
reads the file's wire format itself (a few fields of ``XSpace``; no
dependency) and returns the device planes in the shape
``trace_reduce.load`` gives, each ops event's name ending in
``metadata={op_name="<tf_op>"}``. The functions below read only those
plain lists, so a trimmed recording is a JSON fixture, and they key on
the scope's SEGMENT, never on an instruction's number, so they survive
a refactor of the step.

A ``while`` instruction is an event of the same line as the
instructions of its body and spans them (it carries no ``tf_op`` of its
own), so a scope's time is the UNION of its events' intervals, never
their sum. One program runs on a device at a time: the ops of an
execution are those that start inside its event on the ``XLA Modules``
line. Instructions the compiler made itself (the table's copy, the
asynchronous copies of operands) carry no scope and belong to no phase.

The names are the EXECUTABLE's: JAX's persistent compilation cache
leaves metadata out of its key, so a program loaded from a cache that an
older checkout filled shows that checkout's scopes and source lines.
``obs.enable(jax_annotations=True)`` puts the metadata into the key
(``obs/trace.py``), which is how a traced run of the benchmark gets
programs that carry the scopes of the code it runs.

``READERS`` holds the two reader kinds for ``cellrun.READERS``
(``READERS.update(scope_reduce.READERS)`` registers them).
"""

from __future__ import annotations

import re
from collections import Counter
from statistics import fmean

from .trace_reduce import (
    DEVICE_PLANE_RE,
    MODULES_LINE,
    OPS_LINE,
    TraceError,
    device_planes,
    find_xplane,
    line_of,
    union_seconds,
)

OP_NAME_RE = re.compile(r'op_name="([^"]*)"')
SCOPE_STAT = "tf_op"


# --------------------------------------------------------------------- #
# the file: the few fields of XSpace that hold an op's scope
# --------------------------------------------------------------------- #
def _varint(buf, i: int):
    shift = out = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one protobuf message: an int for a
    varint, a ``memoryview`` for a length-delimited field; fixed-width
    fields are skipped (none of the ones read here is one)."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
            yield field, value
        elif wire == 2:
            size, i = _varint(buf, i)
            yield field, buf[i:i + size]
            i += size
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
        else:
            raise TraceError(f"wire type {wire} in the .xplane.pb")


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _map_values(entries):
    """The values of a protobuf map field's entries (key 1, value 2)."""
    for entry in entries:
        for field, value in _fields(entry):
            if field == 2:
                yield value


def _plane(buf) -> dict:
    """One ``XPlane``: its name, and its ``XLA Modules`` / ``XLA Ops``
    lines with each event named by its metadata's name and ``tf_op``."""
    name, lines, event_md, stat_md = "", [], [], []
    for field, value in _fields(buf):
        if field == 2:
            name = _text(value)
        elif field == 3:
            lines.append(value)
        elif field == 4:
            event_md.append(value)
        elif field == 5:
            stat_md.append(value)
    if not DEVICE_PLANE_RE.match(name):
        return {"name": name, "lines": []}
    stat_names = {}         # XStatMetadata: id 1, name 2
    for md in _map_values(stat_md):
        doc = dict(_fields(md))
        stat_names[doc.get(1)] = _text(doc.get(2, b""))
    scope_stat = next(
        (k for k, v in stat_names.items() if v == SCOPE_STAT), None)
    names = {}              # XEventMetadata: id 1, name 2, stats 5
    for md in _map_values(event_md):
        md_id, md_name, scope = None, "", None
        for field, value in _fields(md):
            if field == 1:
                md_id = value
            elif field == 2:
                md_name = _text(value)
            elif field == 5:   # XStat: metadata_id 1, str_value 5, or
                stat = dict(_fields(value))   # ref_value 7 (interned)
                if stat.get(1) != scope_stat:
                    continue
                if 5 in stat:
                    scope = _text(stat[5])
                elif 7 in stat:
                    scope = stat_names.get(stat[7])
        names[md_id] = (md_name if scope is None else
                        f'{md_name}, metadata={{op_name="{scope}"}}')
    out = []
    for line in lines:      # XLine: name 2, timestamp_ns 3, events 4
        line_name, t0_ns, events = "", 0, []
        for field, value in _fields(line):
            if field == 2:
                line_name = _text(value)
            elif field == 3:
                t0_ns = value
            elif field == 4:
                events.append(value)
        if line_name not in (MODULES_LINE, OPS_LINE):
            continue
        rows = []
        for event in events:  # XEvent: metadata_id 1, offset_ps 2, dur 3
            doc = dict(_fields(event))
            rows.append([names.get(doc.get(1), ""),
                         t0_ns + doc.get(2, 0) / 1e3, doc.get(3, 0) / 1e3])
        out.append({"name": line_name, "events": rows})
    return {"name": name, "lines": out}


def scoped_planes(path: str) -> list:
    """The device planes of an ``.xplane.pb`` as ``trace_reduce.load``
    shapes them (same clock, nanoseconds), every ops event's name
    carrying its scope path."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    planes = [_plane(value) for field, value in _fields(buf) if field == 1]
    return [p for p in planes if p["lines"]]


def op_path(name: str) -> list:
    """The segments of the ``op_name`` an ops event's name carries
    (``[]`` where it carries none: copies and other instructions the
    compiler made itself)."""
    m = OP_NAME_RE.search(name)
    return m.group(1).split("/") if m else []


def op_id(name: str) -> str:
    """The instruction's own name (``%fusion.12``): what repeats when a
    loop's body runs again."""
    return name.split(" = ", 1)[0].strip()


def executions(planes: list, program: str, lo: float, hi: float) -> list:
    """``[(start_ns, end_ns), ...]`` of the executions of one program
    that lie wholly inside ``[lo, hi]``, on the first device plane; the
    name is matched as ``trace_reduce.program_durations`` matches it."""
    modules = line_of(device_planes(planes)[0], MODULES_LINE)["events"]
    pat = re.compile(r"^" + re.escape(program) + r"(\(|$)")
    out = [(s, s + d) for n, s, d in modules
           if pat.match(n) and s >= lo and s + d <= hi]
    if not out:
        seen = sorted({n.split("(")[0] for n, _s, _d in modules})
        raise TraceError(f"no execution of program {program!r} in the "
                         f"traced window (programs seen: {seen})")
    return out


def scope_events(planes: list, program: str, scope: str,
                 lo: float, hi: float) -> list:
    """``[((start_ns, end_ns), events), ...]``: per execution of
    ``program``, the ops events under ``scope`` (``[name, start_ns,
    dur_ns]``). Raises when no op of any execution carries the scope: a
    renamed or removed scope is an error, never a phase of 0 ms."""
    ops = line_of(device_planes(planes)[0], OPS_LINE)["events"]
    scoped = [e for e in ops if scope in op_path(e[0])]
    out = [((a, b), [e for e in scoped if a <= e[1] < b])
           for a, b in executions(planes, program, lo, hi)]
    if not any(events for _run, events in out):
        seen = sorted({seg for e in ops for seg in op_path(e[0])
                       if "." in seg and "(" not in seg})
        raise TraceError(
            f"no op of program {program!r} carries the scope {scope!r} "
            f"(scopes seen on the ops line: {seen})")
    return out


def scope_seconds(planes: list, program: str, scope: str,
                  lo: float, hi: float) -> list:
    """Per execution, the seconds in which an op under the scope ran:
    the union of the events' intervals, clipped to the execution."""
    return [union_seconds(events, a, b) for (a, b), events in
            scope_events(planes, program, scope, lo, hi)]


def scope_rounds(planes: list, program: str, scope: str,
                 lo: float, hi: float) -> list:
    """Per execution, the trips of the scope's loop: how often the most
    frequent instruction of a loop BODY under the scope ran (an op whose
    path has ``body`` after the scope's segment; a loop's condition runs
    once more than its body). An execution with ops under the scope and
    none of them in a body counts 0."""
    out = []
    for _run, events in scope_events(planes, program, scope, lo, hi):
        counts = Counter()
        for name, _s, _d in events:
            path = op_path(name)
            if "body" in path[path.index(scope) + 1:]:
                counts[op_id(name)] += 1
        out.append(max(counts.values(), default=0))
    return out


def _scoped(ctx: dict) -> list:
    """The traced run's device planes with their scopes: a second read
    of the trace file, made once per run."""
    if "scoped_planes" not in ctx:
        ctx["scoped_planes"] = scoped_planes(
            find_xplane(ctx["traced"]["dir"]))
    return ctx["scoped_planes"]


def _read_scope_mean_ms(spec: dict, ctx: dict):
    """Mean over the program's executions of the device time under one
    named scope, in ms."""
    return 1e3 * fmean(scope_seconds(
        _scoped(ctx), spec["program"], spec["scope"], ctx["lo"], ctx["hi"]))


def _read_scope_rounds_mean(spec: dict, ctx: dict):
    """Mean over the program's executions of the trips of the loop
    under one named scope."""
    return fmean(scope_rounds(
        _scoped(ctx), spec["program"], spec["scope"], ctx["lo"], ctx["hi"]))


READERS = {
    "scope_mean_ms": _read_scope_mean_ms,
    "scope_rounds_mean": _read_scope_rounds_mean,
}
