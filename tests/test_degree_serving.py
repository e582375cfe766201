"""The degree histogram, served (ISSUE 34): ``DegreeCountQuery`` beside
``DegreeQuery`` in one heterogeneous batch, answered from ONE snapshot
(one stamp for both) and exact for the prefix that stamp names; the
payload a window publishes; the wire code."""

from __future__ import annotations

import threading

import numpy as np
import pytest

from _degree_ref import replay
from gelly_streaming_tpu.core.stream import SimpleEdgeStream
from gelly_streaming_tpu.core.window import CountWindow
from gelly_streaming_tpu.datasets import IdentityDict
from gelly_streaming_tpu.library.degrees import DegreeDistribution
from gelly_streaming_tpu.serving import (
    DegreeCountQuery,
    DegreeQuery,
    QueryEngine,
    StreamServer,
)
from gelly_streaming_tpu.serving.rpc import decode_queries, encode_queries

N_IDS, WINDOW = 40, 32


def _events(seed: int, n: int):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, N_IDS, n).astype(np.int32)
    dst = rng.integers(0, N_IDS, n).astype(np.int32)
    sign = np.where(rng.random(n) < 0.65, 1, -1).astype(np.int32)
    return src, dst, sign


def _prefix(src, dst, sign, n_windows: int):
    """Upstream's state after the first ``n_windows`` windows."""
    n = n_windows * WINDOW
    deg = replay(src[:n], dst[:n], sign[:n], N_IDS)
    return deg, np.bincount(deg[deg > 0], minlength=64)


class _Gated:
    """Column chunks of one window each, every one held back until the
    test lets it through: the stamps of a batch then name a prefix the
    test knows."""

    def __init__(self, cols):
        self._cols = cols
        self._go = threading.Semaphore(0)

    def release(self, n: int = 1) -> None:
        for _ in range(n):
            self._go.release()

    def iter_chunks(self):
        for lo in range(0, len(self._cols[0]), WINDOW):
            self._go.acquire()
            yield tuple(c[lo:lo + WINDOW] for c in self._cols)


@pytest.mark.parametrize("prefer_host", [True, False],
                         ids=["host_path", "device_path"])
@pytest.mark.parametrize("seed", [1, 2**31 + 3])
def test_both_query_kinds_in_one_batch_carry_one_stamp(seed, prefer_host):
    src, dst, sign = _events(seed, 6 * WINDOW)
    source = _Gated((src, dst, sign))
    stream = SimpleEdgeStream(source, window=CountWindow(WINDOW),
                              vertex_dict=IdentityDict(N_IDS))
    dd = DegreeDistribution(hist_capacity=64)
    queries = ([DegreeQuery(v) for v in range(0, N_IDS, 3)]
               + [DegreeCountQuery(d) for d in (0, 1, 2, 3, 5, 8, 63, 64, 999)]
               + [DegreeQuery(10_000)])
    n_deg = len(range(0, N_IDS, 3))
    with StreamServer(dd.servable(), stream,
                      engine=QueryEngine(prefer_host=prefer_host)) as server:
        for k in range(6):
            source.release()
            server.store.wait_for(k + 1, timeout=30)   # window k is out
            answers = [f.result(30) for f in server.submit_many(queries)]
            stamps = {(a.window, a.version, a.watermark) for a in answers}
            assert len(stamps) == 1, stamps
            window = answers[0].window
            assert 0 <= window <= k
            deg, hist = _prefix(src, dst, sign, window + 1)
            assert answers[0].watermark == (window + 1) * WINDOW
            got = [a.value for a in answers]
            assert got[:n_deg] == deg[::3].tolist()
            # degree 0 is not tracked; past the capacity nothing is
            assert got[n_deg:-1] == [0] + [
                int(hist[d]) for d in (1, 2, 3, 5, 8, 63)] + [0, 0]
            assert got[-1] == 0          # a vertex the stream never saw
        server.join(30)
        snap = server.snapshot()
    assert sorted(snap.payload) == ["deg", "hist", "vdict"]
    assert snap.payload["hist"].shape == (64,)
    deg, hist = _prefix(src, dst, sign, 6)
    assert np.array_equal(np.asarray(snap.payload["deg"])[:N_IDS], deg)
    assert np.array_equal(np.asarray(snap.payload["hist"]), hist)


def test_the_servable_declares_both_classes_and_refuses_others():
    from gelly_streaming_tpu.serving import ConnectedQuery

    dd = DegreeDistribution(hist_capacity=16)
    servable = dd.servable()
    assert servable.query_classes == (DegreeQuery, DegreeCountQuery)
    assert QueryEngine.PAYLOAD_KEYS[DegreeCountQuery] == "hist"
    assert QueryEngine.PAYLOAD_KEYS[DegreeQuery] == "deg"
    src, dst, sign = _events(4, WINDOW)
    stream = SimpleEdgeStream((src, dst, sign), window=CountWindow(WINDOW),
                              vertex_dict=IdentityDict(N_IDS))
    with StreamServer(servable, stream) as server:
        server.join(30)
        with pytest.raises(TypeError):
            server.ask(ConnectedQuery(1, 2), 30)
        assert server.ask(DegreeCountQuery(1), 30).staleness == 0


def test_the_record_path_publishes_the_histogram_too():
    """``StreamServer(dd.servable(), records)``: the growing histogram
    of the record path is served like the fixed one."""
    src, dst, sign = _events(7, 4 * WINDOW)
    records = [(int(s), int(d), "+" if c > 0 else "-")
               for s, d, c in zip(src, dst, sign)]
    dd = DegreeDistribution(CountWindow(WINDOW),
                            vertex_dict=IdentityDict(N_IDS))
    with StreamServer(dd.servable(), records) as server:
        server.join(30)
        deg, hist = _prefix(src, dst, sign, 4)
        for d in (1, 2, 3, 4):
            assert server.ask(DegreeCountQuery(d), 30).value == int(hist[d])
        assert server.ask(DegreeQuery(5), 30).value == int(deg[5])


def test_a_degree_count_query_crosses_the_wire():
    qs = [DegreeQuery(7), DegreeCountQuery(12), DegreeCountQuery(0)]
    wire = encode_queries(qs)
    assert wire == [["D", 7], ["H", 12], ["H", 0]]
    assert decode_queries(wire) == qs
    with pytest.raises(ValueError):
        decode_queries([["H"]])
