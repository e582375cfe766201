"""Chip measurements fail loudly: ``bench.py`` has no path that prints a
number when there is no TPU (it used to replay a committed artifact —
once a retracted one — with exit 0), and a config that fails inside
``--all`` fails the run by name instead of leaving a ``null`` in a table
that looks finished."""

import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import bench  # noqa: E402


def _run_bench(tmp_path, *flags):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench.py"), *flags],
        capture_output=True, text=True, timeout=300, cwd=tmp_path, env=env,
    )


def test_default_run_exits_nonzero_without_tpu(tmp_path):
    out = _run_bench(tmp_path)
    assert out.returncode != 0
    assert out.stdout.strip() == ""  # no headline, stale or otherwise
    assert "no TPU" in out.stderr and "'cpu'" in out.stderr


def test_all_exits_nonzero_without_tpu_and_writes_nothing(tmp_path):
    out = _run_bench(tmp_path, "--all")
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert not os.path.exists(tmp_path / "chiprun_out")


def test_require_tpu_refuses_the_cpu_in_process():
    with pytest.raises(SystemExit) as ei:
        bench.require_tpu()
    assert ei.value.code == 1


def test_failed_config_fails_the_run_and_is_named():
    detail, flushes = {}, []

    def boom():
        raise RuntimeError("the compiler said no")

    with pytest.raises(SystemExit) as ei:
        bench.run_configs(
            [("first", lambda: {"eps": 1.0}), ("boom", boom),
             ("after", lambda: 2.0), ("boom2", boom)],
            detail, lambda: flushes.append(dict(detail)),
        )
    # non-zero, naming every failed config
    assert ei.value.code not in (0, None)
    assert "boom" in str(ei.value.code) and "boom2" in str(ei.value.code)
    assert "first" not in str(ei.value.code)
    # the rest still ran and every step was flushed
    assert detail == {
        "first": {"eps": 1.0}, "boom": None, "after": 2.0, "boom2": None,
    }
    assert len(flushes) == 4


def test_all_configs_passing_returns_normally():
    detail = {}
    bench.run_configs(
        [("a", lambda: 1), ("b", lambda: {"eps": 2})], detail, lambda: None
    )
    assert detail == {"a": 1, "b": {"eps": 2}}


def test_no_probe_or_stale_path_remains():
    for name in ("probe_backend", "stale_headline", "_artifact_honest",
                 "_headline_guarded"):
        assert not hasattr(bench, name), name
    with open(os.path.join(ROOT, "bench.py")) as f:
        src = f.read()
    assert "--no-probe" not in src and "--headline-worker" not in src
