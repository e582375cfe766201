"""Native ingest tests: C++ parser vs Python fallback equivalence."""

import numpy as np
import pytest

from gelly_streaming_tpu import native


@pytest.fixture
def edge_file(tmp_path):
    p = tmp_path / "edges.txt"
    p.write_text(
        "# comment line\n"
        "1 2 100\n"
        "3\t4\t2.5\n"
        "5,6,350\n"
        "\n"
        "7 8 +\n"
        "9 10 -\n"
        "11 12\n"  # no trailing newline handled below
        "13 14 -3.5\n"
    )
    return str(p)


def test_native_builds_and_parses(edge_file):
    assert native.native_available(), "g++ toolchain expected in this image"
    src, dst, val = native.parse_edge_file(edge_file)
    assert src.tolist() == [1, 3, 5, 7, 9, 11, 13]
    assert dst.tolist() == [2, 4, 6, 8, 10, 12, 14]
    assert val is not None
    assert val.tolist() == [100.0, 2.5, 350.0, 1.0, -1.0, 0.0, -3.5]


def test_native_matches_python_fallback(edge_file):
    ns, nd, nv = native.parse_edge_file(edge_file)
    ps, pd, pv = native._parse_python(edge_file)
    assert ns.tolist() == ps.tolist()
    assert nd.tolist() == pd.tolist()
    assert nv.tolist() == pv.tolist()


def test_no_trailing_newline(tmp_path):
    p = tmp_path / "e.txt"
    p.write_text("1 2\n3 4")  # unterminated last line
    src, dst, val = native.parse_edge_file(str(p))
    assert src.tolist() == [1, 3]
    assert dst.tolist() == [2, 4]
    assert val is None


def test_chunked_iteration_covers_whole_file(tmp_path):
    rng = np.random.default_rng(4)
    n = 5000
    a = rng.integers(0, 10000, n)
    b = rng.integers(0, 10000, n)
    w = rng.uniform(0, 10, n).round(3)
    p = tmp_path / "big.txt"
    p.write_text("".join(f"{x} {y} {z}\n" for x, y, z in zip(a, b, w)))
    # chunk boundaries are byte-budgeted (~chunk_edges each); the invariant
    # is complete, in-order coverage across multiple chunks
    chunks = list(native.iter_edge_chunks(str(p), chunk_edges=700))
    assert len(chunks) >= 2
    src = np.concatenate([c[0] for c in chunks])
    dst = np.concatenate([c[1] for c in chunks])
    val = np.concatenate([c[2] for c in chunks])
    assert src.tolist() == a.tolist()
    assert dst.tolist() == b.tolist()
    np.testing.assert_allclose(val, w)


def test_chunked_into_windower_stream(tmp_path):
    """End to end: file -> native chunks -> Windower array path -> CC."""
    from gelly_streaming_tpu.core.stream import SimpleEdgeStream
    from gelly_streaming_tpu.core.window import CountWindow
    from gelly_streaming_tpu.library import ConnectedComponents

    p = tmp_path / "cc.txt"
    p.write_text("1 2\n2 3\n6 7\n8 9\n5 6\n")
    src, dst, _ = native.parse_edge_file(str(p))
    stream = SimpleEdgeStream((src, dst), window=CountWindow(2))
    last = None
    for last in stream.aggregate(ConnectedComponents()):
        pass
    assert sorted(last.component_sets()) == sorted(
        [frozenset({1, 2, 3}), frozenset({5, 6, 7}), frozenset({8, 9})]
    )


def test_missing_file_raises():
    with pytest.raises(IOError):
        native.parse_edge_file("/nonexistent/file.txt")


def test_native_encoder_matches_numpy_fallback():
    from gelly_streaming_tpu.core.vertexdict import VertexDict

    rng = np.random.default_rng(13)
    batches = [rng.integers(0, 500, rng.integers(1, 400)) for _ in range(8)]
    a = VertexDict()
    assert a._native is not None, "native encoder must load in this image"
    b = VertexDict()
    b._native = None  # force the numpy path
    for batch in batches:
        np.testing.assert_array_equal(a.encode(batch), b.encode(batch))
    assert a.raw_ids().tolist() == b.raw_ids().tolist()
    assert len(a) == len(b)
    probe = int(batches[0][0])
    assert a.lookup(probe) == b.lookup(probe)
    assert a.lookup(10**12) is None
    # the C++ map's empty-slot sentinel value is a legal raw id
    minv = np.iinfo(np.int64).min
    batch = np.array([minv, 7, minv], np.int64)
    np.testing.assert_array_equal(a.encode(batch), b.encode(batch))
    assert a.lookup(minv) == b.lookup(minv)
    assert a.raw_ids().tolist() == b.raw_ids().tolist()


def test_chunked_iteration_skips_comment_runs(tmp_path):
    """ADVICE: a chunk span containing no parseable edges is not EOF."""
    p = tmp_path / "c.txt"
    with open(p, "w") as f:
        f.write("# head\n")
        for i in range(50):
            f.write(f"{i} {i + 1}\n")
        # a comment run far larger than the over-read for chunk_edges=4
        # ( 4*64 + 4096 bytes ) so at least one whole span is commentary
        for _ in range(200):
            f.write("%" + "x" * 60 + "\n")
        for i in range(50, 100):
            f.write(f"{i} {i + 1}\n")
    chunks = list(native.iter_edge_chunks(str(p), chunk_edges=4))
    src = np.concatenate([c[0] for c in chunks])
    assert src.tolist() == list(range(100))


def test_chunked_iteration_rejects_oversized_line(tmp_path):
    """A single line larger than the read buffer errors instead of
    silently dropping the rest of the file."""
    p = tmp_path / "long.txt"
    with open(p, "w") as f:
        f.write("1 2\n")
        f.write("# " + "y" * 20000 + "\n")
        f.write("3 4\n")
    with pytest.raises(IOError):
        list(native.iter_edge_chunks(str(p), chunk_edges=2))


def test_i32_chunks_match_and_bound_check(tmp_path):
    p = tmp_path / "g.txt"
    p.write_text("# c\n1 2\n3 4 0.5\n70000 5\n")
    a = list(native.iter_edge_chunks(str(p)))
    b = list(native.iter_edge_chunks_i32(str(p)))
    assert a[0][0].tolist() == b[0][0].tolist()
    assert b[0][0].dtype == np.int32
    np.testing.assert_allclose(a[0][2], b[0][2])
    with pytest.raises(ValueError, match="dense-id"):
        list(native.iter_edge_chunks_i32(str(p), id_bound=100))


def test_parser_fuzz_matches_python_fallback(tmp_path):
    """Random byte soup + structured noise: the C parser must never crash,
    must terminate, and must extract the same edges as the Python
    fallback (grammar oracle)."""
    rng = np.random.default_rng(123)
    tokens = [
        "12 34", "5\t6", "7,8", "#x", "%y", "", " ", "9 10 1.5", "11 12 +",
        "13 14 -", "-1 -2", "99999999999 1", "3 4 abc", "a b", "5", "6 7 8 9",
        "0 0", "  15  16  ", "\t", "17 18 -0.25",
        # >= 20-digit runs: both parsers must saturate to INT64_MAX, not
        # wrap (round-2 advisor finding: 18446744073709551621 parsed as 5)
        "18446744073709551621 1", "2 99999999999999999999999",
        "9223372036854775807 9223372036854775808",
    ]
    for trial in range(8):
        n = int(rng.integers(5, 120))
        lines = [tokens[i] for i in rng.integers(0, len(tokens), n)]
        body = "\n".join(lines)
        if rng.random() < 0.5:
            body += "\n"
        if rng.random() < 0.3:
            body += tokens[int(rng.integers(0, len(tokens)))]  # ragged tail
        p = tmp_path / f"fuzz{trial}.txt"
        p.write_text(body)
        ns, nd, nv = native.parse_edge_file(str(p))
        ps, pd, pv = native._parse_python(str(p))
        assert ns.tolist() == ps.tolist(), body
        assert nd.tolist() == pd.tolist(), body
        if pv is None:
            assert nv is None or not len(nv)
        else:
            np.testing.assert_allclose(nv, pv)
        # chunked i32 (with its fast path) agrees wherever ids are dense
        if len(ps) and ps.min() >= 0 and pd.min() >= 0 and max(
            ps.max(), pd.max()
        ) < 2**31:
            cs = np.concatenate(
                [c[0] for c in native.iter_edge_chunks_i32(str(p), 16)]
            ) if len(ps) else np.zeros(0, np.int32)
            assert cs.tolist() == ps.tolist(), body


def test_parser_survives_binary_garbage(tmp_path):
    """Arbitrary bytes (nulls, high bytes, no newlines, huge runs): the C
    parser must terminate without crashing and never emit ids it did not
    parse from digit runs."""
    rng = np.random.default_rng(77)
    for trial in range(6):
        n = int(rng.integers(10, 30000))
        blob = bytes(rng.integers(0, 256, n, dtype=np.uint8))
        p = tmp_path / f"bin{trial}"
        p.write_bytes(blob)
        try:
            s, d, v = native.parse_edge_file(str(p))
            assert len(s) == len(d)
        except IOError:
            pass  # an oversized "line" rejection is acceptable
    # digits-only megarun (one enormous number, no separators)
    p = tmp_path / "digits"
    p.write_bytes(b"9" * 100000)
    try:
        s, d, _ = native.parse_edge_file(str(p))
        assert len(s) == 0  # a single number is not an edge
    except IOError:
        pass


def test_novelty_bitmap_native_matches_fallback():
    rng = np.random.default_rng(9)
    nat = native.NoveltyBitmap()
    fb = native.NoveltyBitmap()
    fb._lib = None  # force the numpy bit-packed fallback
    assert nat._lib is not None, "native bitmap must load in this image"
    for _ in range(6):
        n = int(rng.integers(1, 400))
        s = rng.integers(0, 2**30, n).astype(np.int32)
        d = rng.integers(0, 2**30, n).astype(np.int32)
        assert nat.novel2(s, d) == fb.novel2(s, d)
    # ids sharing a byte cell in one batch, duplicates, and id 0
    s = np.array([0, 1, 2, 3, 0, 1], np.int32)
    d = np.array([4, 5, 6, 7, 4, 5], np.int32)
    assert nat.novel2(s, d) == fb.novel2(s, d)


def test_native_window_prep_matches_numpy_fallback():
    """NativeWindowPrep (single-pass epoch-stamped touched set) must
    produce the same touched SET and a consistent local renumbering as
    the numpy bitmap+LUT fallback; order may differ (arrival vs sorted),
    which the forest kernels are insensitive to."""
    import numpy as np
    import pytest

    from gelly_streaming_tpu import native

    try:
        prep = native.NativeWindowPrep()
    except Exception:
        pytest.skip("native toolchain unavailable")
    rng = np.random.default_rng(3)
    for trial in range(3):
        V = int(rng.integers(16, 500))
        n = int(rng.integers(1, 400))
        src = rng.integers(0, V, n).astype(np.int32)
        dst = rng.integers(0, V, n).astype(np.int32)
        tids, lu, lv = prep.run(src, dst, V)
        # renumbering consistency: tids[local] round-trips the columns
        assert np.array_equal(tids[lu], src)
        assert np.array_equal(tids[lv], dst)
        # touched set equality with the bitmap truth
        bm = np.zeros(V, bool)
        bm[src] = True
        bm[dst] = True
        assert np.array_equal(np.sort(tids), np.nonzero(bm)[0])
        # ids out of range raise
        with pytest.raises(ValueError):
            prep.run(np.array([V], np.int32), np.array([0], np.int32), V)


def test_failed_native_build_keeps_the_compiler_message(
    monkeypatch, tmp_path
):
    """The numpy twins keep the package working without a toolchain, but
    the reason is no longer thrown away: a caller that must not run on
    them (the chip smoke, a benchmark) can refuse and say why."""
    from gelly_streaming_tpu.summaries.forest import WindowPrep

    # point the lazy build at a private source + output and forget the
    # loaded library, so _load runs its one build attempt again
    src = tmp_path / "ingest.cpp"
    src.write_text("this is not C++ @@@ ;\n#error refused\n")
    monkeypatch.setattr(native, "_SRC", str(src))
    monkeypatch.setattr(native, "_SO", str(tmp_path / "_ingest.so"))
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_lib_error", None)
    assert native.native_available() is False
    msg = native.build_error()
    assert "g++" in msg and "exited" in msg
    assert "error" in msg and "refused" in msg  # the compiler's own words
    # one attempt, remembered: no rebuild per call
    assert native.native_available() is False
    assert native.build_error() == msg
    # no half-written output is left behind
    assert [p.name for p in tmp_path.iterdir()] == ["ingest.cpp"]
    # the twins still serve; the forest prep says which one it is
    prep = WindowPrep()
    assert prep._native is None
    tids, lu, lv = prep.prep(
        np.array([3, 1], np.int32), np.array([1, 2], np.int32), 8
    )
    assert sorted(tids.tolist()) == [1, 2, 3]
    with pytest.raises(RuntimeError, match="unavailable"):
        native.cc_baseline(np.zeros(1, np.int64), np.zeros(1, np.int64), 1)


def test_two_processes_building_together_do_not_race(tmp_path):
    """Fresh checkouts start processes in bunches (a replica fleet, two
    smokes): each builds to its own temporary name and renames it into
    place, so none loads — or fails on — another's half-written file."""
    import os
    import shutil
    import subprocess
    import sys

    if not native.native_available():
        pytest.skip("no native toolchain")
    pkg = os.path.dirname(os.path.abspath(native.__file__))
    shutil.copy(os.path.join(pkg, "ingest.cpp"), tmp_path / "ingest.cpp")
    code = (
        "import sys, os\n"
        "import gelly_streaming_tpu.native as n\n"
        f"n._SRC = {str(tmp_path / 'ingest.cpp')!r}\n"
        f"n._SO = {str(tmp_path / '_ingest.so')!r}\n"
        "ok = n.native_available()\n"
        "print('BUILD', ok, n.build_error())\n"
        "sys.exit(0 if ok else 1)\n"
    )
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=root)
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", code], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        )
        for _ in range(3)
    ]
    outs = [p.communicate(timeout=300)[0] for p in procs]
    assert [p.returncode for p in procs] == [0, 0, 0], outs
    left = sorted(p.name for p in tmp_path.iterdir())
    assert left == ["_ingest.so", "_ingest.so.hash", "ingest.cpp"], left
