"""Two tests of this directory describe the benchmark as it stood when
they were written, and no cell added after them can make them hold:

- ``test_perf_vsharded.py`` asserts that PR 28's cell, configuration
  and seven metrics are the LAST entries of ``BENCHMARK.json``. The
  rule every PR adds to the benchmark under is "put new entries at the
  end of their lists: one put first or in the middle reads as a change
  to what was there", so a later PR's entries follow PR 28's and no
  ordering it may choose keeps that assertion.
- ``test_perf_trace.py`` resolves the ``program_mean_ms`` readers of
  EVERY cell of ``BENCHMARK.json`` against one recording of the CC cell
  (programs ``jit_step`` and ``jit__batch_roots``); a cell whose
  programs are others' finds none of them there.

A file of this directory may be edited by a ``benchmark`` PR alone, so
the PR that adds a cell marks these two cases, as expected failures and
strictly (a repaired test that passes again fails the mark, which then
has to go), and holds EVERYTHING ELSE they held in the new cell's own
test file, ``test_perf_degdist.py``, with entries found by name:
``test_pr_28s_entries_stand_as_they_stood`` (four chips, the seven
``.v4`` metrics and their order, ``workloads`` that cell alone, reader
kinds, one four-chip cell in at most a quarter) and the degree cell's
trace readers against a recording of the degree cell. The repair, for a
``benchmark`` PR: the first test should find its entries by name and
not by position, the second should skip a reader whose program the
recording does not hold, or name the recording a cell is read against.
"""

from __future__ import annotations

import pytest

OUTGROWN = {
    "test_perf_vsharded.py::"
    "test_the_new_entries_resolve_and_keep_to_the_contract":
        "asserts PR 28's entries are the last of BENCHMARK.json; PR 34 "
        "added its own after them",
    "test_perf_trace.py::"
    "test_every_trace_metric_of_a_cell_resolves_to_a_finite_number"
    "[dd-g500-s28.ingest-saturated-dyn]":
        "reads the degree cell's programs in a recording of the CC cell",
}


def pytest_collection_modifyitems(items):
    for item in items:
        for case, why in OUTGROWN.items():
            if item.nodeid.endswith(case):
                item.add_marker(pytest.mark.xfail(reason=why, strict=True))
