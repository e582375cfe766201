"""The pre-generated stream: long enough that no program reaches its
end (a run that does gives no result), generated outside ``setup_s``,
and the same windows however long it is. On the CPU at a tiny scale;
the floors read the real traffic files through ``BENCHMARK.json``."""

from __future__ import annotations

import os
import sys
import time
import types

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
HERE = os.path.dirname(os.path.abspath(__file__))
for p in (REPO, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)

from _tiny import make_tiny_root  # noqa: E402

from benchmarks.generators import graph500  # noqa: E402
from benchmarks.lib import cellrun, spec  # noqa: E402
from benchmarks.lib.traffic import WindowSource  # noqa: E402

WINDOW = 1 << 16
#: what PR 30 multiplied ``ingest-saturated``'s stream_edges_per_s by
MULTIPLE = 2.5


def _tiny_cell(tmp_path, stream_windows_per_s: float):
    """The tiny cell (open loop, 40 windows of 256 edges a second for
    1.2 s: 48 due in the window) over a stream sized for another rate."""
    root = make_tiny_root(str(tmp_path))
    cell = spec.load_cell("tiny.tiny-mix", root)
    cell.traffic["stream_edges_per_s"] = 256 * stream_windows_per_s
    return cell


def _run(cell, tmp_path, runtime_init_s=0.0):
    """``(t_process, document)`` of one run of 1.2 s."""
    t_process = time.perf_counter()
    return t_process, cellrun.run_cell(
        cell, 21, 1.2, False, t_process=t_process,
        backend=(cellrun.describe_device(), runtime_init_s),
        require_tpu=False, work_root=str(tmp_path))


# ---- (a) the end of the stream is the end of the run ------------------ #
def test_a_stream_the_run_outruns_gives_no_result(tmp_path):
    """Sized for 20 windows a second the stream holds 31 windows; the
    open loop hands out 40 a second, so it ends 0.7 s into the window."""
    cell = _tiny_cell(tmp_path, 20)
    assert cellrun.stream_length(cell, 1.2) == 256 * 31
    with pytest.raises(cellrun.RunError, match="ended inside the window"):
        _run(cell, tmp_path)


def test_the_same_run_over_the_longer_stream_gives_a_result(
        tmp_path, capsys):
    """The same traffic over the stream lengthened by the multiple the
    saturated mix got (67 windows): a result, the stream's windows
    counted in it and in the log. Some 50 of the 67 are handed out and
    no warning comes: an open loop takes what its own file paces,
    whatever the program does (the paced cell hands out 228 of 287)."""
    cell = _tiny_cell(tmp_path, 20 * MULTIPLE)
    assert cellrun.stream_length(cell, 1.2) == 256 * 67
    _t, doc = _run(cell, tmp_path)
    assert doc["correct"] is True, doc["compared"]
    w = doc["windows"]
    assert w["stream"] == 67 and w["closing"] == 2
    main = w["handed"] - w["closing"]
    assert 41 <= main < 67
    err = capsys.readouterr().err
    assert f"window closed: handed {main} of 67 windows" in err
    assert "WARNING" not in err


def test_a_closed_loop_near_the_end_of_its_stream_warns(
        tmp_path, capsys, monkeypatch):
    """A closed loop takes what the program folds. The tiny cell folds
    some thousand windows in its 1.2 s, of a stream of 7,207; with the
    warning's share lowered to a hundredth (72 windows) the run logs
    the one line that names the traffic file."""
    monkeypatch.setattr(cellrun, "STREAM_WARN_SHARE", 0.01)
    cell = _tiny_cell(tmp_path, 6000)
    cell.traffic["ingest"] = {"mode": "closed", "outstanding": 2}
    _t, doc = _run(cell, tmp_path)
    assert doc["correct"] is True, doc["compared"]
    w = doc["windows"]
    assert w["stream"] == 7207 and 72 < w["handed"] - w["closing"] < 7207
    err = capsys.readouterr().err
    assert err.count("WARNING") == 1
    assert "benchmarks/traffic/tiny-mix.json" in err


# ---- (b) setup_s leaves the stream's generation out ------------------- #
@pytest.mark.parametrize("sleep_s", [0.0, 0.3])
def test_setup_s_leaves_the_generation_of_the_stream_out(
        tmp_path, monkeypatch, sleep_s):
    """A generator that sleeps moves ``stream_s`` by its sleep and
    ``setup_s`` by nothing: ``setup_s`` is the time from the start of
    the process to the start of the window less the runtime's start-up
    and less ``stream_s``, to the clock's last digit."""
    cell = _tiny_cell(tmp_path, 60)
    n_edges = cellrun.stream_length(cell, 1.2)
    assert n_edges == 256 * (3 + 4 + 72)
    made = (graph500.edges(cell.config, n_edges, 21, 0),
            graph500.closing_edges(cell.config, 21))   # outside the run

    def edges(config, n, seed, warm_edges):
        assert (n, seed) == (n_edges, 21)
        time.sleep(sleep_s)
        return made[0]

    mod = types.ModuleType("benchmarks.generators.sleepy_for_test")
    mod.edges, mod.closing_edges = edges, lambda config, seed: made[1]
    monkeypatch.setitem(sys.modules, mod.__name__, mod)
    cell.config["generator"] = "sleepy_for_test"
    started = []
    real = WindowSource.start_measuring
    monkeypatch.setattr(
        WindowSource, "start_measuring",
        lambda self, t0: (started.append(t0), real(self, t0))[1])
    runtime_init_s = 0.125
    t_process, doc = _run(cell, tmp_path, runtime_init_s)
    assert doc["correct"] is True, doc["compared"]
    assert doc["runtime_init_s"] == runtime_init_s
    assert sleep_s <= doc["stream_s"] < sleep_s + 0.25
    assert doc["windows"]["stream"] == n_edges // 256
    (t0,) = started
    assert doc["metrics"]["setup_s"]["value"] == pytest.approx(
        t0 - t_process - runtime_init_s - doc["stream_s"], abs=1e-9)
    # stream_s and runtime_init_s are beside the metrics, not among them
    assert not {"stream_s", "runtime_init_s"} & set(doc["metrics"])


# ---- (c) floors: a later edit cannot shorten a stream quietly --------- #
@pytest.mark.parametrize("cell_name,windows,ceiling", [
    ("cc-g500-s28.ingest-saturated", 1700, 2.4e6),
    ("cc-g500-s30-v4.ingest-saturated", 1700, 2.4e6),
    ("bip-g500-s27.ingest-saturated-poll", 1000, 1.4e6),
])
def test_the_saturated_streams_keep_their_length(cell_name, windows, ceiling):
    """At the benchmark's 45 s. The ceiling is what a run can read at
    the most: the stream less the most windows a warm-up has taken (28)
    and the two the closed loop hands out ahead."""
    cell = spec.load_cell(cell_name)
    seconds = spec.load_benchmark()["run_seconds"]
    assert seconds == 45 and cell.config["window_edges"] == WINDOW
    n = cellrun.stream_length(cell, seconds) / WINDOW
    assert n >= windows
    assert (n - 28 - 2) * WINDOW / seconds >= ceiling


def test_the_paced_stream_outlasts_its_own_open_loop():
    cell = spec.load_cell("cc-g500-s28.paced-query-heavy")
    assert cell.traffic["ingest"]["mode"] == "open"
    assert (float(cell.traffic["stream_edges_per_s"])
            >= 1.25 * float(cell.traffic["ingest"]["edges_per_s"]))


# ---- (d) the warning -------------------------------------------------- #
@pytest.mark.parametrize("handed,warns", [(59, False), (60, False),
                                          (61, True), (100, True)])
def test_the_headroom_warning_comes_past_three_fifths(handed, warns):
    line = cellrun.stream_headroom_warning(handed, 100, "ingest-saturated")
    assert (line is not None) == warns
    if warns:
        assert "benchmarks/traffic/ingest-saturated.json" in line
        assert f"{handed} of the stream's 100 windows" in line


# ---- a longer stream is the shorter one and a tail -------------------- #
@pytest.mark.parametrize("config", ["cc-g500-s28", "bip-g500-s27"])
def test_a_longer_stream_times_the_same_windows_at_the_real_chunk(config):
    """At the generator's real chunk (2^22 edges) and the real window
    (2^16), through ``edges`` as the harness calls it: every window of
    a stream that spans three chunks is, edge for edge and in the
    seed's order, the same window of one that spans five. So the
    streams PR 30 lengthened hold the windows their shorter selves held,
    and parent and change of a PR that lengthens one time the same
    work."""
    bench = spec.load_benchmark()
    cell = next(w["name"] for w in bench["workloads"]
                if w["config"] == config)
    cfg = spec.load_cell(cell).config
    assert graph500.CHUNK == 1 << 22 and cfg["window_edges"] == WINDOW
    n_short = 2 * graph500.CHUNK + 5 * WINDOW
    n_long = 4 * graph500.CHUNK + 3 * WINDOW
    seed = 2**31 + 11
    short = graph500.edges(cfg, n_short, seed, 8 * WINDOW)
    long_ = graph500.edges(cfg, n_long, seed, 8 * WINDOW)
    for a, b in zip(short, long_):
        assert len(a) == n_short and np.array_equal(a, b[:n_short])


# ---- the generator: the same edges as ever ----------------------------- #
@pytest.mark.parametrize("config,digest", [
    ("cc-g500-s28", "73a078379274c2a8"),
    ("bip-g500-s27", "3232c1382b89f9b3"),
    ("cc-g500-s30-v4", "29d1df968b01224a"),
])
def test_the_streams_are_the_ones_every_pr_so_far_has_timed(config, digest):
    """The first two windows of the configuration's stream in one
    seed's order and the first closing window of that seed, hashed as
    the generator made them at PR 28, on the CPU and on the chip alike
    (PR 30, call g1): a change to the generator that changes an edge
    changes what every cell times. (One that changes no edge can too:
    PERF.md, section 6, PR 30, "where the table lies".)"""
    import hashlib

    bench = spec.load_benchmark()
    cfg = spec.load_cell(next(w["name"] for w in bench["workloads"]
                              if w["config"] == config)).config
    seed = 2**31 + 11
    s, d = graph500.edges(cfg, 2 * WINDOW, seed, 0)
    cs, cd = graph500.closing_edges(cfg, seed)
    assert len(cs) == 8 * WINDOW
    h = hashlib.sha256(s.tobytes() + d.tobytes() + cs[:WINDOW].tobytes()
                       + cd[:WINDOW].tobytes()).hexdigest()
    assert h[:16] == digest
