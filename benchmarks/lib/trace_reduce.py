"""From a profiler trace to device numbers: the one reduction every PR
is measured by.

The JAX profiler writes an ``.xplane.pb``: planes (one per device, one
for the host's threads), each with lines, each with events that have a
name, a start and a duration in nanoseconds on one clock. ``load`` turns
the file into plain lists (so a trimmed recording can be a JSON fixture),
and the functions below read only those lists.

On a TPU plane the line ``XLA Modules`` holds one event per executed
program (named ``jit_<function>(<fingerprint>)``) and ``XLA Ops`` one
per operation inside it. Lines overlap each other (a module spans its
ops; a step spans its modules), so busy time is the union of the
intervals of ONE line, clipped to the window: summed over lines it
would pass the window's length.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE_RE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_MARK = "bench.window"


class TraceError(RuntimeError):
    """The trace does not hold what a metric needs: an error, never a 0."""


def find_xplane(log_dir: str) -> str:
    files = sorted(glob.glob(
        os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise TraceError(f"the profiler wrote no .xplane.pb under {log_dir}")
    return files[-1]


def load(path: str, *, keep_line=None) -> list:
    """``[{"name", "lines": [{"name", "events": [[name, start_ns,
    dur_ns], ...]}]}]``. ``keep_line(plane, line) -> bool`` skips lines
    nobody reads (host thread pools are most of a trace's events)."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        lines = []
        for line in plane.lines:
            if keep_line is not None and not keep_line(plane.name, line.name):
                continue
            lines.append({
                "name": line.name,
                "events": [[e.name, float(e.start_ns), float(e.duration_ns)]
                           for e in line.events],
            })
        planes.append({"name": plane.name, "lines": lines})
    return planes


def device_planes(planes: list) -> list:
    out = [p for p in planes if DEVICE_PLANE_RE.match(p["name"])]
    if not out:
        raise TraceError(
            "no device plane in the trace (planes: "
            f"{[p['name'] for p in planes]}); nothing ran on a TPU, or "
            "the plane's name changed")
    return sorted(out, key=lambda p: int(
        DEVICE_PLANE_RE.match(p["name"]).group(1)))


def line_of(plane: dict, name: str) -> dict:
    for line in plane["lines"]:
        if line["name"] == name:
            return line
    raise TraceError(f"plane {plane['name']} has no line {name!r} (lines: "
                     f"{[ln['name'] for ln in plane['lines']]})")


def window_bounds(planes: list, mark: str = WINDOW_MARK):
    """``(start_ns, end_ns)`` of the host annotation that brackets the
    traced window: the same clock as the device events."""
    for plane in planes:
        if DEVICE_PLANE_RE.match(plane["name"]):
            continue
        for line in plane["lines"]:
            for name, start, dur in line["events"]:
                if name == mark:
                    return start, start + dur
    raise TraceError(f"no {mark!r} annotation in the trace")


def union_seconds(events, lo: float, hi: float) -> float:
    """Length of the union of ``[start, start + dur)`` clipped to
    ``[lo, hi)``, in seconds."""
    spans = sorted(
        (max(s, lo), min(s + d, hi)) for _n, s, d in events
        if s + d > lo and s < hi
    )
    total = 0.0
    cur_lo = cur_hi = None
    for a, b in spans:
        if b <= a:
            continue
        if cur_hi is None or a > cur_hi:
            if cur_hi is not None:
                total += cur_hi - cur_lo
            cur_lo, cur_hi = a, b
        elif b > cur_hi:
            cur_hi = b
    if cur_hi is not None:
        total += cur_hi - cur_lo
    return total / 1e9


def busy_seconds(planes: list, lo: float, hi: float) -> float:
    """Seconds in which an operation ran on the device, averaged over
    the device planes."""
    devs = device_planes(planes)
    return sum(
        union_seconds(line_of(p, OPS_LINE)["events"], lo, hi) for p in devs
    ) / len(devs)


def program_durations(planes: list, program: str, lo: float, hi: float):
    """Durations (seconds) of the executions of one program that lie
    wholly inside the window, on the first device plane. ``program`` is
    the name the trace prints without its fingerprint: ``jit_step``
    matches ``jit_step(1234...)``. A name that matches nothing raises."""
    modules = line_of(device_planes(planes)[0], MODULES_LINE)["events"]
    pat = re.compile(r"^" + re.escape(program) + r"(\(|$)")
    out = [d / 1e9 for n, s, d in modules
           if pat.match(n) and s >= lo and s + d <= hi]
    if not out:
        seen = sorted({n.split("(")[0] for n, _s, _d in modules})
        raise TraceError(f"no execution of program {program!r} in the "
                         f"traced window (programs seen: {seen})")
    return out


def top_programs(planes: list, lo: float, hi: float, k: int = 10) -> list:
    """``[[name, seconds], ...]``: programs by device time in the window."""
    modules = line_of(device_planes(planes)[0], MODULES_LINE)["events"]
    total: dict = {}
    for n, s, d in modules:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            key = n.split("(")[0]
            total[key] = total.get(key, 0.0) + (b - a) / 1e9
    return [[n, t] for n, t in sorted(
        total.items(), key=lambda kv: -kv[1])[:k]]


def idle_gaps(planes: list, lo: float, hi: float, host_names,
              k: int = 10) -> list:
    """``[[what the host was doing, seconds], ...]``: idle time of the
    first device between operations, attributed to the host span (of
    ``host_names``) that covers most of each gap, summed by span name."""
    ops = sorted(
        (max(s, lo), min(s + d, hi))
        for _n, s, d in line_of(device_planes(planes)[0], OPS_LINE)["events"]
        if s + d > lo and s < hi
    )
    gaps = []
    edge = lo
    for a, b in ops:
        if a > edge:
            gaps.append((edge, a))
        edge = max(edge, b)
    if hi > edge:
        gaps.append((edge, hi))
    host = []
    wanted = set(host_names)
    for plane in planes:
        if DEVICE_PLANE_RE.match(plane["name"]):
            continue
        for line in plane["lines"]:
            host += [(s, s + d, n) for n, s, d in line["events"]
                     if n in wanted]
    host.sort()
    # only the longest gaps are attributed one by one; the rest is "short gaps"
    gaps.sort(key=lambda g: g[0] - g[1])
    total: dict = {}
    for a, b in gaps[:2000]:
        best, best_cover = "no host span", 0.0
        for s, e, n in host:
            if s >= b:
                break
            cover = min(e, b) - max(s, a)
            if cover > best_cover:
                best, best_cover = n, cover
        total[best] = total.get(best, 0.0) + (b - a) / 1e9
    rest = sum(b - a for a, b in gaps[2000:]) / 1e9
    if rest:
        total["short gaps"] = total.get("short gaps", 0.0) + rest
    return [[n, t] for n, t in sorted(
        total.items(), key=lambda kv: -kv[1])[:k]]

