"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the machine it is started on and
prints, as the LAST line of standard output, one JSON object with the
keys ``correct``, ``attempted``, ``failed``, ``metrics`` and ``device``
(and ``breakdown`` with ``--trace 1``). Everything else goes to
standard error: file descriptor 1 is pointed at stderr for the whole
run, so that nothing a library prints can follow the result. The line
is checked against the contract (``lib/lastline.py``) BEFORE it is
printed; a line that breaks it is not printed, the reasons go to
stderr and the exit code is 3.

Exit codes: 0 a result was printed; 2 no TPU, too few chips, or the
run could not give a result; 3 the result line broke the contract.

``--control stale_prefix`` is for the builder of a benchmark PR: the
reference, answering one window staler than its stamp, takes the
program's place in the comparison, which has to come out not correct.
The driver never passes it.
"""

from __future__ import annotations

import time

_T_PROCESS = time.perf_counter()

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def _parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", choices=("stale_prefix",), default=None)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = _parse(argv)
    # nothing but the result may reach standard output
    result_out = os.fdopen(os.dup(1), "w")
    os.dup2(2, 1)
    sys.stdout = sys.stderr

    from benchmarks.lib import lastline, spec
    from benchmarks.lib.cellrun import RunError, log, run_cell, start_backend

    try:
        cell = spec.load_cell(args.workload)
        # the runtime starts before anything of the program is imported
        backend = start_backend()
        # the program's own placement of the persistent compile cache:
        # JAX_COMPILATION_CACHE_DIR where set, else .jax_cache/ in the
        # checkout (a fixed path; the path is part of the cache's key)
        from gelly_streaming_tpu.utils.compile_cache import (
            enable_compile_cache,
        )

        log(f"compile cache: {enable_compile_cache()}")
        import jax

        # every program is kept, however fast it compiled (JAX's default
        # skips those under a second, which is most of the serving
        # kernels and the generator), so a second run compiles nothing
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        doc = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                       t_process=_T_PROCESS, backend=backend,
                       control=args.control,
                       work_root=ROOT)
    except (RunError, KeyError, OSError, ImportError) as e:
        log(f"no result: {type(e).__name__}: {e}")
        return 2
    except Exception:  # a fault of the harness: still leave, with no result
        import traceback

        traceback.print_exc()
        log("no result: the harness failed (traceback above)")
        return 2
    line = json.dumps(doc)
    required = cell.units("per_layer") if args.trace else cell.units(
        "end_to_end")
    allowed = {**cell.units("end_to_end"), **cell.units("per_layer")}
    problems = lastline.validate(line, required=required, allowed=allowed,
                                 trace=bool(args.trace), chips=cell.chips)
    for name, c in doc["compared"].items():
        log(f"compared {name}: {c['value']} (limit {c['limit']})")
    if problems:
        log("the result line breaks the contract and was not printed:")
        for p in problems:
            log(f"  - {p}")
        log(line[:6000])
        return 3
    result_out.write(line + "\n")
    result_out.flush()
    return 0


if __name__ == "__main__":
    rc = main()
    sys.stderr.flush()
    # leave without waiting on library teardown: every thread the run
    # started has been joined or is a daemon of this process
    os._exit(rc)
