"""The validator of the result line: a good line passes, and each way
of being malformed that refused PR 22 (or could have) is named."""

from __future__ import annotations

import copy
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks.lib import lastline  # noqa: E402

E2E = {"edges_per_s": "edges/s", "query_p95_ms": "ms", "setup_s": "s"}
LAYER = {"forest_step_ms.sat": "ms", "forest_step_roofline.sat": "%",
         "compiles_in_window": "count"}


def good(trace: bool) -> dict:
    doc = {
        "correct": True, "attempted": 400, "failed": 0,
        "metrics": {
            "edges_per_s": {"value": 1.5e6, "unit": "edges/s"},
            "query_p95_ms": {"value": 212.4, "unit": "ms"},
            "setup_s": {"value": 35.2, "unit": "s"},
        },
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                   "memory_peak_bytes": 6 << 30},
    }
    if trace:
        doc["metrics"].update({
            "forest_step_ms.sat": {"value": 80.1, "unit": "ms"},
            "forest_step_roofline.sat": {"value": 3.3, "unit": "%"},
            "compiles_in_window": {"value": 0, "unit": "count"},
        })
        doc["device"].update(window_s=6.0, busy_s=4.2)
        doc["breakdown"] = {"device_ops": [["jit_step", 4.0]],
                            "idle_gaps": [["window.pack", 1.1]]}
    doc["compared"] = {"answer_mismatches": {"value": 0, "limit": 0}}
    return doc


def problems(doc, trace):
    line = doc if isinstance(doc, str) else json.dumps(doc)
    return lastline.validate(
        line, required=LAYER if trace else E2E, allowed={**E2E, **LAYER},
        trace=trace, chips=1)


@pytest.mark.parametrize("trace", [False, True])
def test_a_good_line_passes_in_both_modes(trace):
    assert problems(good(trace), trace) == []


def _mut(path, value=None, delete=False):
    def apply(doc):
        node = doc
        for k in path[:-1]:
            node = node[k]
        if delete:
            del node[path[-1]]
        else:
            node[path[-1]] = value
    return apply


MALFORMED = {
    # the ways named in ISSUE 24's motivation
    "busy_summed_over_lines_exceeds_window":
        (True, _mut(["device", "busy_s"], 9.7), "exceeds window_s"),
    "busy_zero_no_plane_matched":
        (True, _mut(["device", "busy_s"], 0.0), "busy_s"),
    "per_layer_metric_missing":
        (True, _mut(["metrics", "forest_step_ms.sat"], delete=True),
         "is missing"),
    "per_layer_metric_null":
        (True, _mut(["metrics", "forest_step_ms.sat", "value"], None),
         "not a finite number"),
    "per_layer_metric_nan":
        (True, _mut(["metrics", "forest_step_roofline.sat", "value"],
                    float("nan")), "not a finite number"),
    "traced_run_leaves_out_a_metric":
        (True, _mut(["metrics", "compiles_in_window"], delete=True),
         "is missing"),
    "traced_run_without_window_s":
        (True, _mut(["device", "window_s"], delete=True), "window_s"),
    # and the rest of the contract
    "end_to_end_metric_missing":
        (False, _mut(["metrics", "setup_s"], delete=True), "is missing"),
    "missing_top_level_key":
        (False, _mut(["failed"], delete=True), "missing key 'failed'"),
    "correct_not_boolean":
        (False, _mut(["correct"], "yes"), "boolean"),
    "failed_exceeds_attempted":
        (False, _mut(["failed"], 401), "exceeds"),
    "metric_without_unit":
        (False, _mut(["metrics", "setup_s"], {"value": 3.0}),
         "lacks value or unit"),
    "metric_with_wrong_unit":
        (False, _mut(["metrics", "setup_s", "unit"], "ms"), "is not 's'"),
    "unit_with_a_space":
        (False, _mut(["metrics", "edges_per_s", "unit"], "edges per s"),
         "outside the allowed form"),
    "metric_not_of_this_workload":
        (False, _mut(["metrics", "window_p95_ms"],
                     {"value": 1.0, "unit": "ms"}), "not one of"),
    "infinite_value":
        (False, _mut(["metrics", "edges_per_s", "value"], float("inf")),
         "not a finite number"),
    "wrong_device_count":
        (False, _mut(["device", "count"], 4), "is not the cell's 1"),
    "no_memory_peak":
        (False, _mut(["device", "memory_peak_bytes"], 0), "memory_peak_bytes"),
    "no_device_kind":
        (False, _mut(["device", "kind"], delete=True), "kind"),
    "breakdown_too_long":
        (True, _mut(["breakdown", "device_ops"], [["a", 1.0]] * 11),
         "at most 10"),
    "breakdown_entry_malformed":
        (True, _mut(["breakdown", "idle_gaps"], [["a", None]]),
         "[name, seconds]"),
}


@pytest.mark.parametrize("case", sorted(MALFORMED))
def test_each_malformed_line_is_named(case):
    trace, mutate, needle = MALFORMED[case]
    doc = copy.deepcopy(good(trace))
    mutate(doc)
    found = problems(doc, trace)
    assert found, case
    assert any(needle in p for p in found), (case, found)


@pytest.mark.parametrize("text,needle", [
    ("profiler: session closed", "not JSON"),
    ("[1, 2]", "not a JSON object"),
    (json.dumps(good(False)) + "\nI0000 something printed after it",
     "not one line"),
])
def test_text_that_is_not_the_one_object(text, needle):
    found = problems(text, False)
    assert any(needle in p for p in found), found
