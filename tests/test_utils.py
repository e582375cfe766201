"""Tests for the auxiliary subsystems: profiling streams, config, types."""

import argparse
import time

import pytest

from gelly_streaming_tpu.core.stream import SimpleEdgeStream
from gelly_streaming_tpu.core.window import CountWindow, EventTimeWindow
from gelly_streaming_tpu.library import ConnectedComponents
from gelly_streaming_tpu.utils import (
    EngineConfig,
    SignedVertex,
    StreamProfiler,
    profiled,
)


def test_profiled_aggregation_stream(sample_edges):
    stream = SimpleEdgeStream(sample_edges, window=CountWindow(3))
    prof = StreamProfiler()
    results = [
        r for r, _ in profiled(stream.aggregate(ConnectedComponents()), prof)
    ]
    assert len(results) == 3
    s = prof.summary()
    assert s["windows"] == 3
    assert s["p50_window_s"] > 0
    assert prof.latency_percentile(95) >= prof.latency_percentile(50) >= 0


def test_profiled_counts_edges():
    def gen():
        for i in range(4):
            time.sleep(0.001)
            yield i

    prof = StreamProfiler()
    out = list(profiled(gen(), prof, edges_per_window=iter([10, 20, 30, 40])))
    assert [r for r, _ in out] == [0, 1, 2, 3]
    assert prof.total_edges() == 100
    assert prof.edges_per_sec() > 0


def test_engine_config_window_selection():
    cfg = EngineConfig(window_size=128)
    assert isinstance(cfg.window(), CountWindow)
    cfg2 = EngineConfig(window_time=300.0)
    w = cfg2.window(timestamp_fn=lambda e: e[2])
    assert isinstance(w, EventTimeWindow)
    assert w.size == 300.0


def test_engine_config_cli_roundtrip():
    parser = argparse.ArgumentParser()
    EngineConfig.add_args(parser)
    ns = parser.parse_args(["--window-size", "64", "--transient-state"])
    cfg = EngineConfig.from_args(ns)
    assert cfg.window_size == 64
    assert cfg.transient_state is True
    assert cfg.tree_degree == 2


def test_signed_vertex_reverse():
    sv = SignedVertex(5, True)
    assert sv.reverse() == SignedVertex(5, False)
    assert sv.reverse().reverse() == sv


def test_emission_stream_flat_and_batched_views():
    from gelly_streaming_tpu.core.emission import EmissionStream
    from gelly_streaming_tpu.utils.profiling import StreamProfiler

    def batches():
        yield [1, 2, 3]
        yield []
        yield [4, 5]

    es = EmissionStream(batches)
    assert list(es) == [1, 2, 3, 4, 5]
    assert [list(b) for b in es.batches()] == [[1, 2, 3], [], [4, 5]]
    # re-iterable (streams are lazily re-runnable)
    assert list(es) == [1, 2, 3, 4, 5]
    prof = StreamProfiler()
    assert list(es.with_profiler(prof)) == [1, 2, 3, 4, 5]
    assert len(prof.stats) == 3
    assert [s.edges for s in prof.stats] == [3, 0, 2]


def test_property_streams_are_emission_streams():
    import numpy as np

    from gelly_streaming_tpu import CountWindow, SimpleEdgeStream
    from gelly_streaming_tpu.core.emission import EmissionStream

    src = np.array([1, 2, 3, 1], np.int64)
    dst = np.array([2, 3, 4, 3], np.int64)
    s = SimpleEdgeStream((src, dst), window=CountWindow(2))
    degrees = s.get_degrees()
    assert isinstance(degrees, EmissionStream)
    # batched view groups per window; flat view matches reference order
    flat = list(degrees)
    grouped = [list(b) for b in degrees.batches()]
    assert flat == [x for b in grouped for x in b]
    assert len(grouped) == 2
    assert isinstance(s.get_vertices(), EmissionStream)
    assert [v.id for v in s.get_vertices()] == [1, 2, 3, 4]
    assert list(s.number_of_vertices()) == [1, 2, 3, 4]
    assert list(s.number_of_edges()) == [1, 2, 3, 4]


def test_degree_batches_are_column_backed():
    import numpy as np

    from gelly_streaming_tpu import CountWindow, SimpleEdgeStream
    from gelly_streaming_tpu.core.emission import ColumnBatch, DeviceColumnBatch

    s = SimpleEdgeStream(
        (np.array([1, 2, 3]), np.array([2, 3, 4])), window=CountWindow(3)
    )
    batches = list(s.get_degrees().batches())
    assert all(
        isinstance(b, (ColumnBatch, DeviceColumnBatch)) for b in batches
    )
    raw, deg = batches[0].columns
    assert list(zip(raw.tolist(), deg.tolist())) == list(batches[0])


def test_engine_config_ingest_knobs(tmp_path):
    import argparse

    import numpy as np

    from gelly_streaming_tpu import native
    from gelly_streaming_tpu.library import ConnectedComponents
    from gelly_streaming_tpu.utils.config import EngineConfig

    p = tmp_path / "g.txt"
    native.write_edge_file(
        str(p), np.array([0, 1, 5]), np.array([1, 2, 6])
    )
    parser = argparse.ArgumentParser()
    EngineConfig.add_args(parser)
    cfg = EngineConfig.from_args(
        parser.parse_args(
            ["--window-size", "2", "--device-encode", "--id-bound", "8"]
        )
    )
    stream = cfg.open_stream(str(p))
    last = None
    for last in stream.aggregate(ConnectedComponents()):
        pass
    assert sorted(last.component_sets()) == sorted(
        [frozenset({0, 1, 2}), frozenset({5, 6})]
    )
    # identity mode without device encoding
    cfg2 = EngineConfig(window_size=2, id_bound=8)
    stream2 = cfg2.open_stream(str(p))
    got = [c for c in stream2.aggregate(ConnectedComponents())][-1]
    assert sorted(got.component_sets()) == sorted(last.component_sets())


def test_sorted_run_set_matches_naive():
    """LSM sorted-run key set: same answers as a plain python set under a
    randomized insert/probe workload, runs stay logarithmic."""
    import numpy as np

    from gelly_streaming_tpu.utils.keyruns import SortedRunSet

    rng = np.random.default_rng(11)
    s = SortedRunSet()
    ref = set()
    for _ in range(40):
        batch = rng.integers(0, 500, rng.integers(1, 60))
        keys = np.unique(batch.astype(np.int64))
        new = s.filter_new(keys)
        expect_new = sorted(set(keys.tolist()) - ref)
        assert new.tolist() == expect_new
        s.add(new)
        ref |= set(keys.tolist())
        assert len(s) == len(ref)
        probe = rng.integers(0, 600, 32).astype(np.int64)
        got = s.contains(probe)
        assert got.tolist() == [int(p) in ref for p in probe]
    assert len(s._runs) <= 12  # geometric merging keeps runs logarithmic
    assert s.to_array().tolist() == sorted(ref)


def test_chip_spec_raises_for_a_device_not_in_the_table():
    """A roofline share against an assumed peak is not a measurement: the
    table is keyed by the exact ``device_kind``, has no ``cpu`` row and
    no default, and this suite's CPU device is not in it."""
    from gelly_streaming_tpu.utils import profiling

    assert "cpu" not in profiling._CHIP_PEAKS
    with pytest.raises(ValueError, match="no published peaks.*'cpu'"):
        profiling.chip_spec()
    with pytest.raises(ValueError, match="no published peaks"):
        profiling.roofline_entry(0.5, flops=1e9, model="test")


def test_chip_spec_reads_the_exact_kind_and_propagates_backend_errors(
    monkeypatch,
):
    import jax

    from gelly_streaming_tpu.utils import profiling

    class _Dev:
        platform = "tpu"
        device_kind = "TPU v5 lite"

    monkeypatch.setattr(jax, "devices", lambda: [_Dev()])
    spec = profiling.chip_spec()
    assert spec == {
        "kind": "TPU v5 lite", "peak_bf16_flops": 197e12,
        "hbm_bytes_s": 819e9,
    }
    # a near miss is not a match
    _Dev.device_kind = "TPU v5 lite (assumed)"
    with pytest.raises(ValueError, match="no published peaks"):
        profiling.chip_spec()

    def boom():
        raise RuntimeError("backend unavailable")

    monkeypatch.setattr(jax, "devices", boom)
    with pytest.raises(RuntimeError, match="backend unavailable"):
        profiling.chip_spec()
