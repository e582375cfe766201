"""Sharded parallel ingest (ISSUE 11): the GSEW wire format, the
N-connection sharded source, and its merge into the block/superbatch
execution path.

The load-bearing contracts pinned here:

- the frame layer REJECTS every malformed byte stream — garbage magic,
  wrong version, oversized declarations, payload/geometry disagreement,
  torn frames — as a counted ``source.malformed_frames{kind}`` plus a
  clean reconnect, never a dead reader thread (the stream completes);
- closed shard windows are VALUE-IDENTICAL to the hash-partitioned
  unsharded oracle (``partition_edges`` + per-shard count windows),
  including across a mid-ingest shard disconnect (``FaultPlan``) with
  at-least-once peer replay — frame sequence dedup makes delivery
  exactly-once at frame granularity;
- a deliberately slow consumer bounds queue depth and memory (the
  per-shard queue is the backpressure boundary), the stall/resume
  episode is counted evidence, and ingest resumes with windows intact;
- the superbatch path (``pack_window_cols`` group encode) produces the
  same compact-id columns as the per-window block path, and a full CC
  aggregation over the sharded stream equals the unsharded run.
"""

import socket
import threading
import time

import numpy as np
import pytest

from gelly_streaming_tpu import native, obs
from gelly_streaming_tpu.core import ingest as ing
from gelly_streaming_tpu.core.ingest import (
    HEADER,
    MAGIC,
    MAX_FRAME_EDGES,
    VERSION,
    MalformedFrame,
    ShardedEdgeSource,
    encode_shard_frames,
    encode_shard_text,
    pack_edge_frame,
    partition_edges,
    serve_blobs,
    shard_of,
)
from gelly_streaming_tpu.core.stream import SimpleEdgeStream
from gelly_streaming_tpu.core.window import CountWindow
from gelly_streaming_tpu.obs import timeline
from gelly_streaming_tpu.obs.registry import get_registry
from gelly_streaming_tpu.resilience import faults
from gelly_streaming_tpu.resilience.errors import TransientSourceError
from gelly_streaming_tpu.resilience.faults import FaultPlan


@pytest.fixture(autouse=True)
def _hygiene():
    obs.reset()
    faults.clear()
    yield
    obs.reset()
    faults.clear()


def counter_value(name, **labels):
    for lab, inst in get_registry().find(name):
        if all(lab.get(k) == v for k, v in labels.items()):
            return inst.value
    return 0.0


def make_edges(n=500, vmax=60, seed=11):
    rng = np.random.default_rng(seed)
    return (
        rng.integers(0, vmax, n).astype(np.int64),
        rng.integers(0, vmax, n).astype(np.int64),
    )


def oracle_windows(src, dst, nshards, window):
    """Per-shard count windows of the hash-partitioned stream — what a
    correct sharded ingest must deliver, shard by shard."""
    out = {}
    for i, (s, d, _v) in enumerate(
        partition_edges(src, dst, None, nshards)
    ):
        wins = [
            (s[a:a + window].tolist(), d[a:a + window].tolist())
            for a in range(0, len(s), window)
        ]
        if wins:  # an empty shard delivers no windows at all
            out[i] = wins
    return out


def collected_windows(wins):
    got = {}
    for sh, s, d, _v in wins:
        got.setdefault(sh, []).append((s.tolist(), d.tolist()))
    return got


# --------------------------------------------------------------------- #
# Wire format + codec
# --------------------------------------------------------------------- #
def test_frame_codec_round_trips_narrow_wide_and_val():
    src = np.array([3, 1, 4], np.int64)
    dst = np.array([1, 5, 9], np.int64)
    frame = pack_edge_frame(src, dst, seq=7)
    magic, ver, flags, n, plen, seq = HEADER.unpack(frame[:HEADER.size])
    assert (magic, ver, seq) == (MAGIC, VERSION, 7)
    assert not flags & ing.F_WIDE and not flags & ing.F_VAL
    s, d, v = ing.decode_frame_payload(frame[HEADER.size:], n, flags)
    assert s.tolist() == src.tolist() and d.tolist() == dst.tolist()
    assert v is None and s.dtype == np.int64

    big = np.array([1 << 40, -5], np.int64)
    val = np.array([0.5, -2.25])
    frame = pack_edge_frame(big, dst[:2], val, seq=8)
    _m, _v, flags, n, _p, _s = HEADER.unpack(frame[:HEADER.size])
    assert flags & ing.F_WIDE and flags & ing.F_VAL
    s, d, v = ing.decode_frame_payload(frame[HEADER.size:], n, flags)
    assert s.tolist() == big.tolist() and v.tolist() == val.tolist()


def test_decode_fallback_matches_native(monkeypatch):
    if not native.native_available():
        pytest.skip("no native toolchain: only the fallback exists")
    src = np.array([7, 1 << 34, 0], np.int64)
    dst = np.array([2, 4, 6], np.int64)
    val = np.array([1.5, 0.0, -3.0])
    frames = [
        pack_edge_frame(src % (1 << 20), dst, seq=1),          # narrow
        pack_edge_frame(src, dst, val, seq=2),                 # wide+val
    ]
    lines = b"1\t2\nbogus line\n# c\n3 4 0.25\n"

    # decode with the native library, then again with it forced away
    def decode_all():
        out = []
        for f in frames:
            _m, _ver, flags, n, _p, _s = HEADER.unpack(f[:HEADER.size])
            out.append(ing.decode_frame_payload(f[HEADER.size:], n, flags))
        return out, native.parse_edge_lines(lines)

    with_native, parsed_native = decode_all()
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_lib_error", "forced away by the test")
    without, parsed_py = decode_all()
    for (s1, d1, v1), (s2, d2, v2) in zip(with_native, without):
        assert s1.tolist() == s2.tolist()
        assert d1.tolist() == d2.tolist()
        assert (v1 is None) == (v2 is None)
        if v1 is not None:
            assert v1.tolist() == v2.tolist()
    # text chunk parse: columns AND malformed count agree byte-for-byte
    assert parsed_native[0].tolist() == parsed_py[0].tolist()
    assert parsed_native[1].tolist() == parsed_py[1].tolist()
    assert parsed_native[2].tolist() == parsed_py[2].tolist()
    assert parsed_native[3] == parsed_py[3] == 1


def test_geometry_mismatch_raises_malformed():
    src, dst = make_edges(4)
    frame = pack_edge_frame(src, dst, seq=1)
    with pytest.raises(MalformedFrame) as ei:
        ing.decode_frame_payload(frame[HEADER.size:][:-4], 4, 0)
    assert ei.value.kind == "columns"


def test_shard_of_is_deterministic_and_total():
    src, dst = make_edges(2000)
    a = shard_of(src, dst, 4)
    b = shard_of(src, dst, 4)
    assert (a == b).all() and a.min() >= 0 and a.max() < 4
    # every shard gets real work on a random stream
    assert len(np.unique(a)) == 4
    parts = partition_edges(src, dst, None, 4)
    assert sum(len(p[0]) for p in parts) == len(src)


# --------------------------------------------------------------------- #
# Fuzz: every malformed byte stream is counted + survived
# --------------------------------------------------------------------- #
def _serve_script(blobs_per_accept):
    """One port; accept N times, each sending its scripted bytes then
    closing (a reconnecting reader sees them in order)."""
    srv = socket.create_server(("127.0.0.1", 0))
    port = srv.getsockname()[1]

    def run():
        try:
            for blob in blobs_per_accept:
                conn, _ = srv.accept()
                try:
                    conn.sendall(blob)
                finally:
                    conn.close()
        finally:
            srv.close()

    t = threading.Thread(target=run, daemon=True)
    t.start()
    return port, t


GOOD_SRC, GOOD_DST = make_edges(64, vmax=40, seed=3)
GOOD_BLOB = encode_shard_frames(GOOD_SRC, GOOD_DST, frame_edges=16)
#: bytes of one complete 16-edge narrow frame in GOOD_BLOB
FRAME_BYTES = HEADER.size + 16 * 4 * 2
assert len(GOOD_BLOB) == 4 * FRAME_BYTES


@pytest.mark.parametrize("raw, kind", [
    (b"X" * 64, "magic"),
    (HEADER.pack(MAGIC, VERSION + 9, 0, 0, 0, 0), "version"),
    (HEADER.pack(MAGIC, VERSION, 0, MAX_FRAME_EDGES + 1, 0, 0),
     "oversized"),
    (HEADER.pack(MAGIC, VERSION, 0, 2, 99, 0), "columns"),
    (GOOD_BLOB[: HEADER.size + 20], "truncated"),
])
def test_malformed_streams_count_resync_and_never_kill_the_reader(
    raw, kind
):
    port, t = _serve_script([raw, GOOD_BLOB])
    src = ShardedEdgeSource(
        [("127.0.0.1", port)], window=16,
        reconnect=4, reconnect_base_s=0.01,
    )
    wins = list(src.windows())
    t.join(10)
    # the malformed prefix was classified + counted, the reconnect
    # resynced, and the FULL stream still arrived
    assert counter_value("source.malformed_frames", kind=kind) == 1
    assert counter_value("source.reconnects") >= 1
    assert collected_windows(wins) == oracle_windows(
        GOOD_SRC, GOOD_DST, 1, 16
    )


def test_reset_at_frame_boundary_reconnects_not_truncates():
    """A connection RESET between frames is a reconnectable failure —
    only the peer's orderly FIN may end a shard. Mapping resets to a
    clean close would silently truncate the stream."""
    import struct as _struct

    first = GOOD_BLOB[:FRAME_BYTES]  # exactly one COMPLETE frame
    srv = socket.create_server(("127.0.0.1", 0))
    port = srv.getsockname()[1]

    def run():
        try:
            conn, _ = srv.accept()
            conn.sendall(first)
            time.sleep(0.2)  # let the reader drain frame 1 fully
            # SO_LINGER(on, 0): close() sends RST, not FIN
            conn.setsockopt(
                socket.SOL_SOCKET, socket.SO_LINGER,
                _struct.pack("ii", 1, 0),
            )
            conn.close()
            conn2, _ = srv.accept()
            try:
                conn2.sendall(GOOD_BLOB)  # full replay (at-least-once)
            finally:
                conn2.close()
        finally:
            srv.close()

    t = threading.Thread(target=run, daemon=True)
    t.start()
    src = ShardedEdgeSource(
        [("127.0.0.1", port)], window=16,
        reconnect=4, reconnect_base_s=0.01,
    )
    wins = list(src.windows())
    t.join(10)
    assert counter_value("source.reconnects") >= 1
    # the WHOLE stream arrived: nothing was dropped as a "clean" close
    assert collected_windows(wins) == oracle_windows(
        GOOD_SRC, GOOD_DST, 1, 16
    )


def test_deterministic_corruption_gives_up_instead_of_looping():
    """Every reconnect replays intact frames (deduped, no progress)
    then the same garbage: the malformed streak must exhaust a bounded
    budget and surface TransientSourceError — never loop forever."""
    # 2 complete frames, then garbage where frame 3's header should be
    corrupt = GOOD_BLOB[:FRAME_BYTES * 2] + b"\xff" * 40
    port, t = _serve_script([corrupt] * 8)
    src = ShardedEdgeSource(
        [("127.0.0.1", port)], window=16,
        reconnect=2, reconnect_base_s=0.01,
    )
    with pytest.raises(TransientSourceError, match="malformed"):
        list(src.windows())
    assert counter_value("source.malformed_frames", kind="magic") >= 3


def test_pack_rejects_frames_every_reader_would_reject():
    """Encoder/reader bound symmetry: a frame whose payload exceeds the
    reader's byte bound must fail at PACK time, not dead-loop replays."""
    n = ing.DEFAULT_MAX_FRAME // 24 + 1  # wide + val: 24 bytes/edge
    big = np.full(n, 1 << 40, np.int64)
    with pytest.raises(ValueError, match="frame_edges"):
        pack_edge_frame(big, big, np.zeros(n), seq=1)


def test_exhausted_reconnect_budget_raises_at_the_consumer():
    srv = socket.create_server(("127.0.0.1", 0))
    port = srv.getsockname()[1]
    srv.close()  # nothing listens: bounded attempts, then transient
    src = ShardedEdgeSource(
        [("127.0.0.1", port)], window=8,
        reconnect=2, reconnect_base_s=0.01,
    )
    with pytest.raises(TransientSourceError):
        list(src.windows())
    assert counter_value("source.reader_errors") == 1


# --------------------------------------------------------------------- #
# Oracle identity + the execution path
# --------------------------------------------------------------------- #
def test_sharded_windows_match_the_partitioned_oracle():
    src, dst = make_edges(700, seed=5)
    parts = partition_edges(src, dst, None, 3)
    blobs = [encode_shard_frames(s, d, frame_edges=37) for s, d, _ in parts]
    ports, threads, _stop = serve_blobs(blobs)
    source = ShardedEdgeSource(
        [("127.0.0.1", p) for p in ports], window=16
    )
    got = collected_windows(source.windows())
    for t in threads:
        t.join(10)
    assert got == oracle_windows(src, dst, 3, 16)


def test_sharded_text_mode_matches_oracle_and_counts_malformed():
    src, dst = make_edges(300, seed=9)
    parts = partition_edges(src, dst, None, 2)
    blobs = [
        b"# header\nnot an edge\n" + encode_shard_text(s, d)
        for s, d, _ in parts
    ]
    ports, threads, _stop = serve_blobs(blobs)
    source = ShardedEdgeSource(
        [("127.0.0.1", p) for p in ports], window=32, fmt="text"
    )
    got = collected_windows(source.windows())
    for t in threads:
        t.join(10)
    assert got == oracle_windows(src, dst, 2, 32)
    assert counter_value("source.malformed_lines") == 2


def test_superbatch_groups_match_per_window_blocks():
    src, dst = make_edges(400, seed=13)
    parts = partition_edges(src, dst, None, 2)
    blobs = [encode_shard_frames(s, d) for s, d, _ in parts]

    def fresh_stream():
        ports, _threads, _stop = serve_blobs(blobs)
        return ShardedEdgeSource(
            [("127.0.0.1", p) for p in ports], window=32
        ).stream()

    blocks_stream = fresh_stream()
    block_raw = []
    for b in blocks_stream.blocks():
        s, d, _v = b._host_cache
        block_raw.append((
            blocks_stream.vertex_dict.decode(s).tolist(),
            blocks_stream.vertex_dict.decode(d).tolist(),
        ))

    groups_stream = fresh_stream()
    group_raw = []
    for g in groups_stream.superbatches(4):
        for s, d, _v in g.cols:
            group_raw.append((
                groups_stream.vertex_dict.decode(np.asarray(s)).tolist(),
                groups_stream.vertex_dict.decode(np.asarray(d)).tolist(),
            ))
    # merge order across shards is nondeterministic; window CONTENTS
    # (and their per-shard sequence) are not
    assert sorted(block_raw) == sorted(group_raw)
    assert sum(len(s) for s, _ in group_raw) == 400


def test_sharded_cc_equals_the_unsharded_run():
    from gelly_streaming_tpu.library import ConnectedComponents

    src, dst = make_edges(600, vmax=80, seed=17)
    parts = partition_edges(src, dst, None, 3)
    blobs = [encode_shard_frames(s, d) for s, d, _ in parts]
    ports, threads, _stop = serve_blobs(blobs)
    stream = ShardedEdgeSource(
        [("127.0.0.1", p) for p in ports], window=64
    ).stream()
    sharded = None
    for sharded in stream.aggregate(ConnectedComponents()):
        pass
    for t in threads:
        t.join(10)
    ref_stream = SimpleEdgeStream((src, dst), window=CountWindow(64))
    ref = None
    for ref in ref_stream.aggregate(ConnectedComponents()):
        pass
    assert str(sharded) == str(ref)


# --------------------------------------------------------------------- #
# Backpressure: bounded queues, stall/resume evidence, intact windows
# --------------------------------------------------------------------- #
def test_slow_consumer_bounds_queue_depth_and_resumes():
    src, dst = make_edges(3000, seed=23)
    parts = partition_edges(src, dst, None, 2)
    blobs = [encode_shard_frames(s, d, frame_edges=64) for s, d, _ in parts]
    ports, threads, _stop = serve_blobs(blobs)
    source = ShardedEdgeSource(
        [("127.0.0.1", p) for p in ports], window=32,
        queue_windows=2, stall_event_s=0.05,
    )
    max_depth = 0
    wins = []
    for i, w in enumerate(source.windows()):
        wins.append(w)
        max_depth = max(
            max_depth, *(sh.q.qsize() for sh in source._shards)
        )
        if i < 5:
            # deliberately slow: longer than a full put-timeout slice,
            # so the blocked reader's stall episode reliably registers
            time.sleep(0.3)
    for t in threads:
        t.join(10)
    # the queue (and so memory) stayed bounded: never more than the
    # configured depth of closed windows buffered per shard
    assert max_depth <= 2
    assert counter_value("source.backpressure_stalls") >= 1
    assert counter_value("source.backpressure_resumes") >= 1
    assert counter_value("source.backpressure_s") > 0
    # and the stall changed NOTHING about the data
    assert collected_windows(wins) == oracle_windows(src, dst, 2, 32)


def test_mid_ingest_disconnect_replays_exactly_once():
    src, dst = make_edges(800, seed=29)
    parts = partition_edges(src, dst, None, 2)
    blobs = [encode_shard_frames(s, d, frame_edges=16) for s, d, _ in parts]
    # accepts=2: a reconnecting reader gets the WHOLE stream again —
    # at-least-once delivery from the peer, deduped by frame seq
    ports, threads, _stop = serve_blobs(blobs, accepts=2)
    source = ShardedEdgeSource(
        [("127.0.0.1", p) for p in ports], window=32,
        reconnect=4, reconnect_base_s=0.01,
    )
    with faults.injected(FaultPlan(disconnect_at_record=37)):
        got = collected_windows(source.windows())
    _stop.set()
    # the disconnect fired, the reader reconnected, the peer's full
    # replay was deduped, and the windows are EXACTLY the oracle
    assert counter_value(
        "resilience.fault_injected", site="source.record") == 1
    assert counter_value("source.reconnects") >= 1
    assert counter_value("source.replayed_frames") >= 1
    assert got == oracle_windows(src, dst, 2, 32)


def test_source_is_single_use_and_close_is_idempotent():
    src, dst = make_edges(60)
    blobs = [encode_shard_frames(src, dst)]
    ports, threads, _stop = serve_blobs(blobs)
    source = ShardedEdgeSource([("127.0.0.1", p) for p in ports], window=16)
    list(source.windows())
    with pytest.raises(RuntimeError):
        next(iter(source.windows()))
    source.close()
    source.close()
    for t in threads:
        t.join(10)


# --------------------------------------------------------------------- #
# Obs + timeline story
# --------------------------------------------------------------------- #
def test_timeline_renders_ingest_stall_resume_story():
    events = [
        {"kind": "counter", "name": "source.reconnects", "ts": 1.0,
         "shard": "p0", "v": 1},
        {"kind": "counter", "name": "source.malformed_frames", "ts": 2.0,
         "shard": "p0", "labels": {"kind": "magic"}, "v": 1},
        {"kind": "counter", "name": "source.backpressure_stalls",
         "ts": 3.0, "shard": "p0", "labels": {"shard": "1"}, "v": 1},
        {"kind": "counter", "name": "source.backpressure_resumes",
         "ts": 4.0, "shard": "p0", "labels": {"shard": "1"}, "v": 1},
    ]
    lines = timeline.render(events)
    assert len(lines) == 4
    assert "RECONNECT" in lines[0]
    assert "MALFORMED" in lines[1] and "kind=magic" in lines[1]
    assert "INGEST-STALL" in lines[2]
    assert "INGEST-RESUME" in lines[3]
    # the story ORDER is the backpressure lifecycle: stall, then resume
    assert lines[2] < lines[3] or events[2]["ts"] < events[3]["ts"]


def test_shard_depth_gauge_and_decode_span_fire_when_enabled():
    obs.enable()
    try:
        src, dst = make_edges(200)
        blobs = [encode_shard_frames(src, dst, frame_edges=32)]
        ports, threads, _stop = serve_blobs(blobs)
        source = ShardedEdgeSource(
            [("127.0.0.1", p) for p in ports], window=16
        )
        list(source.windows())
        for t in threads:
            t.join(10)
        assert get_registry().find("source.shard_depth")
        spans = [
            inst for lab, inst in get_registry().find("trace.span_seconds")
            if lab.get("span") == "ingest.decode"
        ]
        assert spans and spans[0].count >= 1
    finally:
        obs.disable()
