"""Runs the tiny cell of a benchmark tree made by ``_tiny.make_tiny_root``
FROM that tree (its ``benchmarks`` package, not the repository's), on
the CPU, and prints the result document: what a later PR's added files
meet. ``python _run_tiny.py <root> <seed> [<control>]``."""

from __future__ import annotations

import json
import sys
import time

root, seed = sys.argv[1], int(sys.argv[2])
control = sys.argv[3] if len(sys.argv) > 3 else None
sys.path.insert(0, root)

from benchmarks.lib import cellrun, spec  # noqa: E402

assert spec.ROOT == root, (spec.ROOT, root)
assert spec.check_names_resolve() == []
cell = spec.load_cell("tiny.tiny-mix")
t_process = time.perf_counter()
backend = cellrun.start_backend()   # before anything of the program
doc = cellrun.run_cell(cell, seed, 1.2, False, t_process=t_process,
                       backend=backend, require_tpu=False, control=control,
                       work_root=root)
print(json.dumps(doc))
