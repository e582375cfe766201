"""The reduction from profiler trace to device numbers, on a trimmed
recording of the first chip run of ``cc-g500-s28.ingest-saturated``
(TPU v5e, PR 24): planes ``/device:TPU:0`` (lines ``XLA Modules`` and
``XLA Ops``) and ``/host:CPU``. The numbers asserted here are
properties of the reduction, not measurements."""

from __future__ import annotations

import json
import math
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks.lib import cellrun, spec, trace_reduce as tr  # noqa: E402

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "trace_cc_saturated_v5e.json")


@pytest.fixture(scope="module")
def planes():
    with open(FIXTURE) as f:
        return json.load(f)


def test_the_window_is_the_host_annotation_on_the_trace_clock(planes):
    lo, hi = tr.window_bounds(planes)
    assert 5.9e9 < hi - lo < 6.0e9
    with pytest.raises(tr.TraceError, match="no 'other.mark'"):
        tr.window_bounds(planes, mark="other.mark")


def test_busy_is_above_zero_and_at_most_the_window(planes):
    lo, hi = tr.window_bounds(planes)
    busy = tr.busy_seconds(planes, lo, hi)
    assert 0 < busy <= (hi - lo) / 1e9
    # clipped: a window that ends inside the recording cuts the busy time
    half = lo + (hi - lo) / 64
    assert 0 < tr.busy_seconds(planes, lo, half) <= (half - lo) / 1e9 + 1e-12


def test_busy_is_the_union_of_one_line_not_a_sum_over_lines(planes):
    """Modules span their ops: summing both lines would count every
    busy second twice and pass the window's length."""
    lo, hi = tr.window_bounds(planes)
    dev = tr.device_planes(planes)[0]
    ops = tr.line_of(dev, tr.OPS_LINE)["events"]
    mods = tr.line_of(dev, tr.MODULES_LINE)["events"]
    last_op_end = max(s + d for _n, s, d in ops)
    u_ops = tr.union_seconds(ops, lo, last_op_end)
    u_mods = tr.union_seconds(mods, lo, last_op_end)
    u_both = tr.union_seconds(ops + mods, lo, last_op_end)
    assert u_both <= (last_op_end - lo) / 1e9
    assert u_both < u_ops + u_mods          # the naive sum double-counts
    assert abs(u_both - max(u_ops, u_mods)) < 0.01


@pytest.mark.parametrize("events,lo,hi,want", [
    ([["a", 0, 10], ["b", 5, 10]], 0, 100, 15e-9),       # overlap
    ([["a", 0, 10], ["b", 20, 10]], 0, 100, 20e-9),      # disjoint
    ([["a", 0, 100]], 40, 60, 20e-9),                    # clipped both ends
    ([["a", 0, 10]], 50, 60, 0.0),                       # outside
    ([["a", 0, 50], ["b", 10, 5], ["c", 49, 11]], 0, 100, 60e-9),  # nested
    ([], 0, 100, 0.0),
])
def test_union_of_intervals(events, lo, hi, want):
    assert tr.union_seconds(events, lo, hi) == pytest.approx(want)


def test_a_program_is_found_by_the_name_the_trace_prints(planes):
    lo, hi = tr.window_bounds(planes)
    steps = tr.program_durations(planes, "jit_step", lo, hi)
    roots = tr.program_durations(planes, "jit__batch_roots", lo, hi)
    assert len(steps) >= 5 and all(0.05 < d < 0.5 for d in steps)
    assert len(roots) >= 5 and all(0 < d < 0.01 for d in roots)


def test_a_program_name_that_matches_nothing_is_an_error_not_a_zero(planes):
    lo, hi = tr.window_bounds(planes)
    with pytest.raises(tr.TraceError, match="no execution of program"):
        tr.program_durations(planes, "jit_step_renamed", lo, hi)
    with pytest.raises(tr.TraceError):   # a prefix is not a match either
        tr.program_durations(planes, "jit_ste", lo, hi)


def test_no_device_plane_is_an_error_not_a_zero(planes):
    host_only = [p for p in planes if not p["name"].startswith("/device:")]
    with pytest.raises(tr.TraceError, match="no device plane"):
        tr.busy_seconds(host_only, 0, 1e9)
    renamed = [dict(p, name=p["name"].replace("TPU", "XPU")) for p in planes]
    with pytest.raises(tr.TraceError, match="no device plane"):
        tr.device_planes(renamed)
    dev = tr.device_planes(planes)[0]
    with pytest.raises(tr.TraceError, match="has no line"):
        tr.line_of(dev, "XLA Ops v2")


def test_breakdown_lists_programs_and_attributed_gaps(planes):
    lo, hi = tr.window_bounds(planes)
    top = tr.top_programs(planes, lo, hi)
    assert top[0][0] == "jit_step" and top[0][1] > top[-1][1] > 0
    assert len(top) <= 10
    gaps = tr.idle_gaps(planes, lo, hi, {"window.pack", "serving.answer"})
    assert 1 <= len(gaps) <= 10
    assert all(isinstance(n, str) and s > 0 for n, s in gaps)
    busy = tr.busy_seconds(planes, lo, hi)
    # every idle second is attributed to something: gaps + busy = window
    assert sum(s for _n, s in gaps) + busy == pytest.approx((hi - lo) / 1e9)


@pytest.mark.parametrize(
    "cell_name", [w["name"] for w in spec.load_benchmark()["workloads"]])
def test_every_trace_metric_of_a_cell_resolves_to_a_finite_number(
        planes, cell_name):
    """Each per-layer metric that reads the trace, through its own
    reader file, against the recording."""
    cell = spec.load_cell(cell_name)
    lo, hi = tr.window_bounds(planes)
    ctx = {"planes": planes, "lo": lo, "hi": hi}
    seen = 0
    for name, reader in cell.readers.items():
        r = reader["reader"]
        assert r["kind"] in cellrun.READERS, name
        if r["kind"] != "program_mean_ms":
            continue
        value = cellrun.READERS[r["kind"]](r, ctx)
        assert math.isfinite(value) and value > 0, name
        seen += 1
    assert seen >= 1
