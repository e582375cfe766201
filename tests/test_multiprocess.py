"""2-process ``jax.distributed`` smoke test (round-1 review item #5).

The reference validates distributed behavior on Flink's in-process
mini-cluster; the closest JAX analog with real process boundaries is two
coordinated CPU processes, each with 4 virtual devices, running one
sharded CC window step over a global 8-device mesh. This is the only test
that actually executes ``jax.process_count() == 2``.

CAPABILITY PROBE (ISSUE 5 satellite): most CPU-only environments cannot
run this at all — jaxlib's CPU backend raises "Multiprocess computations
aren't implemented on the CPU backend" at the first cross-process
collective. That is an ENVIRONMENT limit, not a repo regression, so the
test probes the capability once (two tiny coordinated processes running
one ``process_allgather``) and ``pytest.skip``s with the probe's reason
when the environment cannot do it — tier-1 reports green instead of
carrying a permanent known failure. CI still runs this file in its own
non-blocking step so a hosting environment that CAN run it exercises it
visibly.
"""

import os
import socket
import subprocess
import sys

WORKER = os.path.join(os.path.dirname(__file__), "_mp_worker.py")

#: cached (supported, reason) of the one-shot environment probe
_CAPABILITY = None

#: the probe worker: join the 2-process runtime and run ONE collective —
#: exactly the operation the CPU backend may not implement. Cheap (no
#: mesh, no CC step), but a real cross-process allgather.
_PROBE = (
    "import sys, numpy as np, jax; "
    "jax.distributed.initialize('localhost:%d', num_processes=2, "
    "process_id=%d); "
    "from jax.experimental import multihost_utils; "
    "out = multihost_utils.process_allgather(np.ones(1, np.float32)); "
    "assert np.asarray(out).size == 2, out; "
    "print('PROBE_OK')"
)


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def _clean_env() -> dict:
    # the workers are CPU processes: JAX_PLATFORMS pins them, and any
    # TPU_* runtime variable the parent carries is not theirs to inherit
    env = {
        k: v for k, v in os.environ.items() if not k.startswith("TPU_")
    }
    env["JAX_PLATFORMS"] = "cpu"
    return env


def multiprocess_supported() -> tuple:
    """One-shot probe: can this environment run 2-process ``jax.distributed``
    with a real cross-process collective on the CPU backend? Returns
    ``(supported, reason)`` and caches the answer for the session."""
    global _CAPABILITY
    if _CAPABILITY is not None:
        return _CAPABILITY
    port = _free_port()
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", _PROBE % (port, i)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=_clean_env(),
        )
        for i in range(2)
    ]
    outs = []
    try:
        for p in procs:
            out, err = p.communicate(timeout=120)
            outs.append((p.returncode, out, err))
    except subprocess.TimeoutExpired:
        for q in procs:
            q.kill()
            q.communicate()
        _CAPABILITY = (False, "probe timed out after 120s")
        return _CAPABILITY
    for rc, out, err in outs:
        if rc != 0 or "PROBE_OK" not in out:
            tail = err.strip().splitlines()[-1] if err.strip() else f"rc={rc}"
            _CAPABILITY = (False, tail)
            return _CAPABILITY
    _CAPABILITY = (True, "")
    return _CAPABILITY


def test_two_process_distributed_cc():
    import pytest

    supported, reason = multiprocess_supported()
    if not supported:
        pytest.skip(
            f"environment cannot run multi-process JAX on the CPU "
            f"backend: {reason}"
        )
    port = _free_port()
    env = _clean_env()
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    procs = [
        subprocess.Popen(
            [sys.executable, WORKER, str(i), str(port)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env,
        )
        for i in range(2)
    ]
    outs = []
    for p in procs:
        try:
            out, err = p.communicate(timeout=240)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        outs.append((p.returncode, out, err))
    for rc, out, err in outs:
        assert rc == 0, f"worker failed rc={rc}\nstdout={out}\nstderr={err[-2000:]}"
        assert "MP_OK" in out, out
    # both processes computed the same replicated global summary
    lines = [o.splitlines()[-1] for _, o, _ in outs]
    assert lines[0] == lines[1], lines
