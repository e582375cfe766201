"""``chip_smoke.py`` on the CPU: the body's control flow, oracle check and
result fields at a tiny size, the refusal to run without a TPU, and where
the compile cache goes. What only the chip can show — that the chip's
code paths ran — is ``assert_chip_paths``, exercised here on its inputs."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402


@pytest.fixture(autouse=True)
def _cold_programs():
    """The smoke's body counts on a process that has compiled nothing of
    the fold yet: its first window then compiles ("set-up"), and while
    it does a live query is answered. A test file that folded the same
    shapes on this xdist worker before this one (``tests/test_control.py``
    does) would leave every program warm: no set-up window, and four
    tiny windows folded before the first answer."""
    import jax

    jax.clear_caches()


def test_body_runs_and_matches_the_oracle_at_a_tiny_size(tmp_path):
    result = chip_smoke.run_smoke(
        str(tmp_path), scale=10, window=256, n_windows=8,
        n_pairs=90, n_sizes=30,
    )
    assert result["mismatches"] == 0
    assert result["windows"] == 8 and result["edges"] == 8 * 256
    assert result["id_space"] == 1 << 10
    assert result["queries_final"] == 120
    assert result["queries_live"] > 0
    assert set(result["live_windows_seen"]) <= set(range(8))
    assert result["setup_windows"] + result["steady_windows"] == 8
    assert result["setup_windows"] >= 1  # the first window compiles
    assert result["programs_compiled_or_loaded"] >= 1
    # whichever paths this backend picked, the result names them
    assert result["carry"] in ("host", "forest")
    assert result["engine_path"] in ("host", "device")
    assert result["native_loaded"] is True
    # the result is one JSON line's worth
    assert json.loads(json.dumps(result)) == result


def test_a_wrong_answer_is_a_failure_not_a_field(tmp_path, monkeypatch):
    from gelly_streaming_tpu.serving.query import QueryEngine

    real = QueryEngine.connected

    def lying(self, snap, us, vs):
        return ~real(self, snap, us, vs)

    monkeypatch.setattr(QueryEngine, "connected", lying)
    with pytest.raises(AssertionError, match="oracle mismatches"):
        chip_smoke.run_smoke(
            str(tmp_path), scale=8, window=64, n_windows=4,
            n_pairs=30, n_sizes=10,
        )


def test_main_refuses_to_run_without_a_tpu(capsys):
    assert chip_smoke.main() == 1
    out, err = capsys.readouterr()
    assert out == ""  # no result line
    assert "no TPU" in err and "'cpu'" in err
    # the device and the versions are the first thing it says
    first = err.splitlines()[0]
    assert '"platform": "cpu"' in first and '"libtpu"' in first


def test_last_stdout_line_is_the_verdict_and_nothing_else(
    capsys, monkeypatch, tmp_path
):
    """``main()`` past the device gate (the device faked, the body at a
    tiny size, the chip-path assertion off): stdout is the record, then
    the verdict with exactly the keys the driver reads."""
    from gelly_streaming_tpu.utils import profiling

    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    real = chip_smoke.run_smoke
    monkeypatch.setattr(profiling, "describe_device", lambda: dict(device))
    monkeypatch.setattr(
        chip_smoke, "run_smoke",
        lambda workdir: real(
            workdir, scale=8, window=64, n_windows=4, n_pairs=30, n_sizes=10
        ),
    )
    monkeypatch.setattr(chip_smoke, "assert_chip_paths", lambda result: None)
    assert chip_smoke.main() == 0
    out, _err = capsys.readouterr()
    lines = out.splitlines()
    assert len(lines) == 2
    record = json.loads(lines[0])["chip_smoke"]
    assert record["device"] == device and record["mismatches"] == 0
    assert set(record["versions"]) == {"jax", "jaxlib", "libtpu"}
    verdict = json.loads(lines[-1])
    assert verdict == {"ok": True, "device": device}
    assert list(verdict) == ["ok", "device"]
    assert list(verdict["device"]) == ["platform", "kind", "count"]
    # the corpus directory inside the checkout is gone again
    assert not [n for n in os.listdir(ROOT) if n.startswith(".chip_smoke_")]


def test_assert_chip_paths_names_every_cpu_path():
    on_chip = {
        "carry": "forest", "engine_path": "device", "native_loaded": True,
        "native_window_prep": True, "peak_bytes_in_use": 1 << 20,
    }
    chip_smoke.assert_chip_paths(on_chip)
    for field, value, says in (
        ("carry", "host", "carry"),
        ("engine_path", "host", "host path"),
        ("native_window_prep", False, "numpy window prep"),
        ("peak_bytes_in_use", None, "peak memory"),
    ):
        with pytest.raises(AssertionError, match=says):
            chip_smoke.assert_chip_paths(dict(on_chip, **{field: value}))


def _cache_dir_seen_from(cwd, **env_overrides):
    """What a fresh process resolves, without initialising a backend."""
    env = {
        k: v for k, v in os.environ.items()
        if k not in ("JAX_PLATFORMS", "JAX_COMPILATION_CACHE_DIR")
    }
    env.update(env_overrides, PYTHONPATH=ROOT)
    out = subprocess.run(
        [sys.executable, "-c",
         "import jax\n"
         "from gelly_streaming_tpu.utils.compile_cache import "
         "enable_compile_cache\n"
         "import json\n"
         "print(json.dumps([enable_compile_cache(), "
         "jax.config.jax_compilation_cache_dir, "
         "jax.config.jax_persistent_cache_min_compile_time_secs]))"],
        capture_output=True, text=True, timeout=120, cwd=cwd, env=env,
    )
    assert out.returncode == 0, out.stderr
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_cache_is_one_fixed_in_checkout_directory(tmp_path):
    from gelly_streaming_tpu.utils.compile_cache import DEFAULT_CACHE_DIR

    assert DEFAULT_CACHE_DIR == os.path.join(ROOT, ".jax_cache")
    other = tmp_path / "elsewhere"
    other.mkdir()
    a = _cache_dir_seen_from(str(tmp_path))
    b = _cache_dir_seen_from(str(other))
    assert a == b == [DEFAULT_CACHE_DIR, DEFAULT_CACHE_DIR, 0.0]
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_cache_helper_sets_nothing_where_the_environment_places_it(tmp_path):
    placed = str(tmp_path / "placed")
    ret, cfg_dir, min_secs = _cache_dir_seen_from(
        str(tmp_path), JAX_COMPILATION_CACHE_DIR=placed
    )
    # JAX itself reads the variable; the helper touched neither setting
    assert ret == placed and cfg_dir == placed
    assert min_secs == 1.0


def test_cpu_pinned_process_keeps_no_cache(tmp_path):
    ret, cfg_dir, _ = _cache_dir_seen_from(str(tmp_path), JAX_PLATFORMS="cpu")
    assert ret is None and cfg_dir is None
