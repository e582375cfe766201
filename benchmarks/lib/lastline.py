"""The contract of the result line, checked before it is printed.

``validate`` returns the list of ways a line breaks the contract (empty
when it holds). The harness prints the line only when the list is
empty, and otherwise exits non-zero with the list on stderr: a
malformed line is then found in the first short chip call and not by
the driver's check.

Which metrics a line has to hold, by mode: ``--trace 0`` every
end-to-end metric of the cell; ``--trace 1`` every per-layer metric
whose ``workloads`` names the cell (the harness adds the end-to-end
ones too; they are allowed and not required there).
"""

from __future__ import annotations

import json
import math
import re

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
TOP_KEYS = ("correct", "attempted", "failed", "metrics", "device")


def _finite_number(x) -> bool:
    return (isinstance(x, (int, float)) and not isinstance(x, bool)
            and math.isfinite(x))


def validate(line: str, *, required: dict, trace: bool, chips: int,
             allowed: dict | None = None) -> list:
    """``required``/``allowed`` map metric name -> unit. Every required
    metric has to be there; anything else has to be in ``allowed``."""
    problems = []
    if "\n" in line.strip("\n"):
        problems.append("the result is not one line")
    try:
        doc = json.loads(line)
    except ValueError as e:
        return problems + [f"not JSON: {e}"]
    if not isinstance(doc, dict):
        return ["not a JSON object"]
    for k in TOP_KEYS:
        if k not in doc:
            problems.append(f"missing key {k!r}")
    if problems:
        return problems
    if not isinstance(doc["correct"], bool):
        problems.append("'correct' is not a boolean")
    for k in ("attempted", "failed"):
        v = doc[k]
        if not isinstance(v, int) or isinstance(v, bool) or v < 0:
            problems.append(f"{k!r} is not a non-negative integer")
    if (isinstance(doc["attempted"], int) and isinstance(doc["failed"], int)
            and doc["failed"] > doc["attempted"]):
        problems.append("'failed' exceeds 'attempted'")
    metrics = doc["metrics"]
    if not isinstance(metrics, dict):
        problems.append("'metrics' is not an object")
        metrics = {}
    known = dict(allowed or {})
    known.update(required)
    for name, unit in required.items():
        if name not in metrics:
            problems.append(f"metric {name!r} of this workload is missing")
    for name, m in metrics.items():
        if not NAME_RE.match(name):
            problems.append(f"metric name {name!r} outside the allowed form")
        if name not in known:
            problems.append(f"metric {name!r} is not one of this workload's")
            continue
        if not isinstance(m, dict) or "value" not in m or "unit" not in m:
            problems.append(f"metric {name!r} lacks value or unit")
            continue
        if not _finite_number(m["value"]):
            problems.append(f"metric {name!r} value {m['value']!r} is not "
                            "a finite number")
        if not isinstance(m["unit"], str) or not UNIT_RE.match(m["unit"]):
            problems.append(f"metric {name!r} unit {m['unit']!r} outside "
                            "the allowed form")
        elif m["unit"] != known[name]:
            problems.append(f"metric {name!r} unit {m['unit']!r} is not "
                            f"{known[name]!r}")
    dev = doc["device"]
    if not isinstance(dev, dict):
        return problems + ["'device' is not an object"]
    for k in ("platform", "kind", "count", "memory_peak_bytes"):
        if k not in dev:
            problems.append(f"device lacks {k!r}")
    if not isinstance(dev.get("platform"), str) or not dev.get("platform"):
        problems.append("device.platform is not a string")
    if not isinstance(dev.get("kind"), str) or not dev.get("kind"):
        problems.append("device.kind is not a string")
    if dev.get("count") != chips:
        problems.append(f"device.count {dev.get('count')!r} is not the "
                        f"cell's {chips}")
    peak = dev.get("memory_peak_bytes")
    if not isinstance(peak, int) or isinstance(peak, bool) or peak <= 0:
        problems.append("device.memory_peak_bytes is not a positive integer")
    if trace:
        w, b = dev.get("window_s"), dev.get("busy_s")
        if not _finite_number(w) or w <= 0:
            problems.append(f"device.window_s {w!r} is not above 0")
        if not _finite_number(b) or b <= 0:
            problems.append(f"device.busy_s {b!r} is not above 0")
        if _finite_number(w) and _finite_number(b) and b > w:
            problems.append(f"device.busy_s {b} exceeds window_s {w}")
        bd = doc.get("breakdown")
        if bd is not None:
            if not isinstance(bd, dict):
                problems.append("'breakdown' is not an object")
            else:
                for k in ("device_ops", "idle_gaps"):
                    rows = bd.get(k)
                    if not isinstance(rows, list) or len(rows) > 10:
                        problems.append(f"breakdown.{k} is not a list of "
                                        "at most 10")
                        continue
                    for row in rows:
                        if (not isinstance(row, list) or len(row) != 2
                                or not isinstance(row[0], str)
                                or not _finite_number(row[1])):
                            problems.append(
                                f"breakdown.{k} entry {row!r} is not "
                                "[name, seconds]")
    return problems
