"""Which kernels the TPU compiler takes, beyond what ``chip_smoke.py`` drives.

One short run of each device path the benchmark will give a cell, in ONE
process with the persistent compile cache on, values checked against the
host twin or oracle the tests already use. It prints a table (check,
outcome, detail), writes it to ``chiprun_out/chip_kernel_check.json``
and exits non-zero when any check failed. A check that raises is recorded
with the exception's message (for a refused program, the compiler's) and
the rest still run: one chip call should say everything it can.

    python tools/chip_kernel_check.py              # on the chip
    JAX_PLATFORMS=cpu python tools/chip_kernel_check.py --rehearse

``--rehearse`` is the CPU dress rehearsal: small sizes, the Pallas kernel
in interpret mode, no device requirement. Its output says nothing about
the chip.
"""

from __future__ import annotations

import json
import os
import sys
import time
import traceback

import numpy as np

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

REHEARSE = "--rehearse" in sys.argv


def log(msg: str) -> None:
    print(f"kernel_check: {msg}", file=sys.stderr, flush=True)


def sized(real: int, tiny: int) -> int:
    return tiny if REHEARSE else real


# --------------------------------------------------------------------- #
# Host oracles (the tests' own, vectorized where the sizes need it)
# --------------------------------------------------------------------- #
def rmat(n_edges: int, scale: int, seed: int):
    from gelly_streaming_tpu import datasets

    s, d = datasets.rmat_edges(n_edges, scale, seed=seed)
    return s.astype(np.int32), d.astype(np.int32)


def oracle_roots(src, dst, vcap: int) -> np.ndarray:
    """root[v] for every vertex some edge touches, -1 elsewhere (the
    root is the component's min id — ``fold_edges_host``'s invariant)."""
    from gelly_streaming_tpu.summaries.forest import fold_edges_host

    lab = fold_edges_host(np.arange(vcap, dtype=np.int64), src, dst)
    touched = np.zeros(vcap, bool)
    touched[src] = True
    touched[dst] = True
    return np.where(touched, lab, -1)


def emitted_roots(components, vcap: int) -> np.ndarray:
    """The same table from a ``Components`` emission (root -> members)."""
    got = np.full(vcap, -1, np.int64)
    for root, members in components.components.items():
        got[members] = root
    return got


def adjacency(src, dst, n: int) -> np.ndarray:
    a = np.zeros((n, n), np.float64)
    a[src, dst] = 1.0
    a[dst, src] = 1.0
    np.fill_diagonal(a, 0.0)
    return a


def oracle_triangles(src, dst, n: int) -> int:
    a = adjacency(src, dst, n)
    return int(round(np.trace(a @ a @ a) / 6.0))


def oracle_pagerank(src, dst, d: float = 0.85, tol: float = 1e-12):
    """``tests/test_pagerank_sage.py:reference_pagerank`` on arrays: power
    iteration over the seen vertices, dangling mass spread uniformly,
    parallel edges counted. -> (seen ids, ranks)."""
    verts = np.unique(np.concatenate([src, dst]))
    s = np.searchsorted(verts, src)
    t = np.searchsorted(verts, dst)
    n = len(verts)
    out_deg = np.bincount(s, minlength=n).astype(np.float64)
    r = np.full(n, 1.0 / n)
    for _ in range(10000):
        new = np.bincount(t, weights=r[s] / out_deg[s], minlength=n)
        new = (1 - d) / n + d * (new + r[out_deg == 0].sum() / n)
        if np.abs(new - r).sum() < tol:
            break
        r = new
    return verts, new


# --------------------------------------------------------------------- #
# Checks. Each returns a short detail string and raises on a wrong value.
# --------------------------------------------------------------------- #
def _cc_small_windows(superbatch) -> str:
    from gelly_streaming_tpu import datasets
    from gelly_streaming_tpu.core.stream import SimpleEdgeStream
    from gelly_streaming_tpu.core.window import CountWindow
    from gelly_streaming_tpu.library import ConnectedComponents

    scale, window = sized(16, 8), 1024
    n_win = sized(256, 16)
    src, dst = rmat(window * n_win, scale, seed=61)
    stream = SimpleEdgeStream(
        (src, dst), window=CountWindow(window),
        vertex_dict=datasets.IdentityDict(1 << scale),
    )
    agg = ConnectedComponents(carry="forest", superbatch=superbatch)
    mid_at = n_win // 2 - 3  # inside a fused group, not on its boundary
    mid = last = None
    for i, last in enumerate(stream.aggregate(agg)):
        if i == mid_at:
            mid = last
    agg.sync()
    assert agg._cc_mode == "forest", agg._cc_mode
    vcap = 1 << scale
    for name, em, upto in (("mid", mid, mid_at + 1), ("last", last, n_win)):
        want = oracle_roots(src[:upto * window], dst[:upto * window], vcap)
        bad = int(np.sum(emitted_roots(em, vcap) != want))
        assert bad == 0, f"{name} emission: {bad} vertices off the oracle"
    k = getattr(getattr(agg, "_controller", None), "k", None)
    return (f"{n_win} windows of {window} edges over 2^{scale} ids, "
            f"{last.num_components()} components"
            + (f", auto-K settled at {k}" if k else ""))


def cc_forest_1k_per_window() -> str:
    return _cc_small_windows(1)


def cc_forest_1k_superbatch_auto() -> str:
    return _cc_small_windows("auto")


def cc_device_encode() -> str:
    import tempfile

    from gelly_streaming_tpu import datasets
    from gelly_streaming_tpu.core.window import CountWindow
    from gelly_streaming_tpu.library import ConnectedComponents

    scale, window, n_win = sized(18, 8), sized(1 << 16, 256), 4
    src, dst = rmat(window * n_win, scale, seed=62)
    agg = ConnectedComponents()
    last = None
    # bench.py's headline path reads the packed binary corpus
    with tempfile.TemporaryDirectory(prefix=".chip_smoke_", dir=HERE) as tmp:
        binp = datasets.write_binary(
            os.path.join(tmp, "device_encode.gbin"), src, dst
        )
        stream = datasets.stream_file(
            binp, window=CountWindow(window), device_encode=True,
            min_vertex_capacity=1 << scale, prefetch_depth=2,
        )
        for last in stream.aggregate(agg):
            pass
        agg.sync()
    assert agg._cc_mode == "dense", agg._cc_mode
    vcap = 1 << scale
    bad = int(np.sum(
        emitted_roots(last, vcap) != oracle_roots(src, dst, vcap)
    ))
    assert bad == 0, f"{bad} vertices off the oracle"
    return (f"{n_win} windows of {window} edges, device dictionary, "
            f"carry={agg._cc_mode}, {last.num_components()} components")


def bipartiteness() -> str:
    from gelly_streaming_tpu import datasets
    from gelly_streaming_tpu.core.stream import SimpleEdgeStream
    from gelly_streaming_tpu.core.window import CountWindow
    from gelly_streaming_tpu.eventtime.retract import oracle_bipartite
    from gelly_streaming_tpu.library import BipartitenessCheck

    scale, window, n_win = sized(16, 8), sized(1 << 14, 128), 4
    s, d = rmat(window * n_win, scale, seed=63)
    # even -> odd edges only: bipartite by construction...
    s, d = s & ~1, d | 1
    # ...until a triangle closes an odd cycle in the last window
    odd_s = np.concatenate([s, np.asarray([0, 1, 2], np.int32)])
    odd_d = np.concatenate([d, np.asarray([1, 2, 0], np.int32)])
    modes = []
    for src, dst in ((s, d), (odd_s, odd_d)):
        stream = SimpleEdgeStream(
            (src, dst), window=CountWindow(window),
            vertex_dict=datasets.IdentityDict(1 << scale),
        )
        agg = BipartitenessCheck()
        last = None
        for last in agg.run(stream):
            pass
        want = oracle_bipartite(1 << scale, src, dst)
        assert bool(last.success) == want, (bool(last.success), want)
        modes.append((agg._bp_mode, want))
    assert modes[0][1] and not modes[1][1], modes
    return f"carry={modes[0][0]}; bipartite stream True, odd cycle False"


def degrees_continuous() -> str:
    from gelly_streaming_tpu import datasets
    from gelly_streaming_tpu.core.stream import SimpleEdgeStream
    from gelly_streaming_tpu.core.window import CountWindow

    scale, window, n_win = sized(14, 8), sized(1 << 16, 256), 4
    src, dst = rmat(window * n_win, scale, seed=64)
    stream = SimpleEdgeStream(
        (src, dst), window=CountWindow(window),
        vertex_dict=datasets.IdentityDict(1 << scale),
    )
    got = np.zeros(1 << scale, np.int64)
    n = 0
    for v, deg in stream.get_degrees():
        got[v] = deg  # continuously improving: the last emission stands
        n += 1
    want = np.bincount(src, minlength=1 << scale) + np.bincount(
        dst, minlength=1 << scale
    )
    bad = int(np.sum(got != want))
    assert bad == 0, f"{bad} final degrees off np.bincount"
    return f"{n_win} windows of {window} edges, {n} emissions"


def window_triangles_slice() -> str:
    from gelly_streaming_tpu import datasets
    from gelly_streaming_tpu.core.stream import SimpleEdgeStream
    from gelly_streaming_tpu.core.window import CountWindow
    from gelly_streaming_tpu.library.triangles import WindowTriangles

    scale, window, n_win = sized(10, 6), sized(1 << 14, 256), 2
    src, dst = rmat(window * n_win, scale, seed=65)
    stream = SimpleEdgeStream(
        (src, dst), window=CountWindow(window),
        vertex_dict=datasets.IdentityDict(1 << scale),
    )
    counts = [
        int(c) for c, _i in
        WindowTriangles(CountWindow(window)).run_stream(stream)
    ]
    want = [
        oracle_triangles(src[i * window:(i + 1) * window],
                         dst[i * window:(i + 1) * window], 1 << scale)
        for i in range(n_win)
    ]
    assert counts == want, (counts, want)
    return f"per-slice counts {counts}"


def pagerank_incremental() -> str:
    from gelly_streaming_tpu.core.stream import SimpleEdgeStream
    from gelly_streaming_tpu.core.window import CountWindow
    from gelly_streaming_tpu.library.pagerank import IncrementalPageRank

    scale, window, n_win = sized(12, 6), sized(1 << 14, 128), 4
    src, dst = rmat(window * n_win, scale, seed=66)
    # the default VertexDict, as in the tests: IdentityDict counts every
    # id up to the largest seen as a vertex, the reference only seen ones
    stream = SimpleEdgeStream((src, dst), window=CountWindow(window))
    pr = IncrementalPageRank(tol=1e-9, max_iter=500)
    for _ in pr.run(stream):
        pass
    pr.sync()
    got = pr.ranks()
    verts, want = oracle_pagerank(src, dst)
    assert set(got) == set(verts.tolist()), (len(got), len(verts))
    err = max(abs(got[int(v)] - w) for v, w in zip(verts, want))
    assert err < 1e-5, f"max |rank - reference| = {err:.2e}"
    return (f"{len(verts)} vertices, max |rank - f64 power iteration| "
            f"= {err:.1e}, sum = {sum(got.values()):.6f}")


def exact_triangles() -> str:
    from gelly_streaming_tpu import datasets
    from gelly_streaming_tpu.core.stream import SimpleEdgeStream
    from gelly_streaming_tpu.core.window import CountWindow
    from gelly_streaming_tpu.library.triangles import (
        GLOBAL_KEY,
        ExactTriangleCount,
    )

    scale, window, n_win = sized(9, 6), sized(1 << 12, 128), 4
    src, dst = rmat(window * n_win, scale, seed=67)
    stream = SimpleEdgeStream(
        (src, dst), window=CountWindow(window),
        vertex_dict=datasets.IdentityDict(1 << scale),
    )
    final = {}
    for emissions in ExactTriangleCount().run(stream):
        final.update(dict(emissions))
    want = oracle_triangles(src, dst, 1 << scale)
    assert final.get(GLOBAL_KEY, 0) == want, (final.get(GLOBAL_KEY), want)
    a = adjacency(src, dst, 1 << scale)
    local = np.rint(np.diag(a @ a @ a) / 2.0).astype(np.int64)
    bad = [v for v in np.nonzero(local)[0] if final.get(int(v)) != local[v]]
    assert not bad, f"{len(bad)} per-vertex counts off"
    return f"{want} triangles over {n_win} windows, per-vertex counts equal"


def _sage_reference(params, h, src, dst):
    """float32 numpy ``sage_forward``: mean of in-neighbor messages, two
    matmuls, relu on all but the last layer."""
    h = np.asarray(h, np.float32)
    n = len(params)
    for i, p in enumerate(params):
        agg = np.zeros_like(h)
        np.add.at(agg, dst, h[src])
        cnt = np.bincount(dst, minlength=h.shape[0]).astype(np.float32)
        agg /= np.maximum(cnt, 1.0)[:, None]
        out = (
            h @ np.asarray(p["w_self"], np.float32)
            + agg @ np.asarray(p["w_nbr"], np.float32)
            + np.asarray(p["b"], np.float32)
        )
        h = np.maximum(out, 0.0) if i < n - 1 else out
    return h


def sage_forward_xla() -> str:
    import jax
    import jax.numpy as jnp

    from gelly_streaming_tpu.models.graphsage import (
        _forward_jit,
        init_graphsage,
    )

    # bench.py bench_graphsage_e2e's shape: 65,536 x 128 bf16 table,
    # dims [128, 256, 128], one 2^18-edge window
    v, e, feat = sized(1 << 16, 256), sized(1 << 18, 1024), 128
    params = init_graphsage(
        jax.random.PRNGKey(0), [feat, 256, 128], dtype=jnp.bfloat16
    )
    h = jax.random.normal(jax.random.PRNGKey(1), (v, feat), jnp.bfloat16)
    rng = np.random.default_rng(68)
    src = rng.integers(0, v, e).astype(np.int32)
    dst = rng.integers(0, v, e).astype(np.int32)
    out = _forward_jit(
        params, h, jnp.asarray(src), jnp.asarray(dst), jnp.ones(e, bool)
    )
    got = np.asarray(out.astype(jnp.float32))
    assert got.shape == (v, 128) and np.isfinite(got).all(), got.shape
    want = _sage_reference(params, h, src, dst)
    err = float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
    assert err < 4e-2, f"max error / max |reference| = {err:.3e}"
    return (f"[{v}, {feat}] bf16 x dims [128, 256, 128], {e} edges: max "
            f"error {err:.1e} of max |f32 reference|")


def fused_sage_matmul_pallas() -> str:
    import jax
    import jax.numpy as jnp

    from gelly_streaming_tpu.ops.pallas_kernels import fused_sage_matmul

    v, f, o = sized(65536, 512), 256, 256
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    h = jax.random.normal(ks[0], (v, f), jnp.bfloat16)
    agg = jax.random.normal(ks[1], (v, f), jnp.bfloat16)
    ws = (jax.random.normal(ks[2], (f, o)) / 16).astype(jnp.bfloat16)
    wn = (jax.random.normal(ks[3], (f, o)) / 16).astype(jnp.bfloat16)
    b = jax.random.normal(ks[4], (o,), jnp.bfloat16)

    @jax.jit
    def xla(h, agg, ws, wn, b):
        out = (
            jnp.dot(h, ws, preferred_element_type=jnp.float32)
            + jnp.dot(agg, wn, preferred_element_type=jnp.float32)
            + b.astype(jnp.float32)
        )
        return jax.nn.relu(out).astype(h.dtype)

    got = fused_sage_matmul(h, agg, ws, wn, b, interpret=REHEARSE)
    want = xla(h, agg, ws, wn, b)
    got = np.asarray(got.astype(jnp.float32))
    want = np.asarray(want.astype(jnp.float32))
    assert got.shape == (v, o) and np.isfinite(got).all(), got.shape
    # both round an f32 accumulator to bf16 (8 mantissa bits): one ulp
    err = float(np.max(np.abs(got - want) / np.maximum(np.abs(want), 1.0)))
    assert err <= 2.0 ** -7, f"max relative error {err:.3e}"
    return (f"[{v}, {f}] x [{f}, {o}] bf16, interpret={REHEARSE}: max "
            f"relative error vs the XLA dual matmul {err:.1e}")


def _serve(servable, source, queries, engine_check: bool = True):
    """Fold ``source`` behind a StreamServer, ask ``queries`` after the
    stream ends (staleness 0), return the answers' values."""
    from gelly_streaming_tpu.serving import StreamServer

    with StreamServer(servable, source) as server:
        if engine_check and not REHEARSE:
            assert server.engine.prefer_host is False, "host query path"
        server.join(600)
        return [a.value for a in (
            f.result(600) for f in server.submit_many(queries)
        )]


def query_engine_device_all_classes() -> str:
    from gelly_streaming_tpu import datasets
    from gelly_streaming_tpu.core.stream import SimpleEdgeStream
    from gelly_streaming_tpu.core.window import CountWindow
    from gelly_streaming_tpu.eventtime.retract import oracle_bipartite
    from gelly_streaming_tpu.library import (
        BipartitenessCheck,
        ConnectedComponents,
    )
    from gelly_streaming_tpu.library.degrees import DegreeDistribution
    from gelly_streaming_tpu.library.pagerank import IncrementalPageRank
    from gelly_streaming_tpu.serving import (
        BipartiteQuery,
        ComponentSizeQuery,
        ConnectedQuery,
        DegreeCountQuery,
        DegreeQuery,
        RankQuery,
        SummaryPullQuery,
    )
    from gelly_streaming_tpu.serving.query import decode_pull_doc

    scale, window, n_win = sized(14, 7), sized(1 << 12, 128), 8
    vcap = 1 << scale
    src, dst = rmat(window * n_win, scale, seed=69)
    rng = np.random.default_rng(69)
    qu = rng.integers(0, vcap, 200)
    qv = np.where(rng.random(200) < 0.5, dst[rng.integers(0, len(dst), 200)],
                  rng.integers(0, vcap, 200))

    def stream():
        return SimpleEdgeStream(
            (src, dst), window=CountWindow(window),
            vertex_dict=datasets.IdentityDict(vcap),
        )

    from gelly_streaming_tpu.summaries.forest import fold_edges_host

    lab = fold_edges_host(np.arange(vcap, dtype=np.int64), src, dst)
    sizes = np.bincount(lab, minlength=vcap)

    # CC forest payload: connected, component size, full + delta pull
    agg = ConnectedComponents(carry="forest")
    got = _serve(
        agg.servable(), stream(),
        [ConnectedQuery(int(u), int(v)) for u, v in zip(qu, qv)]
        + [ComponentSizeQuery(int(v)) for v in qv]
        + [SummaryPullQuery(), SummaryPullQuery(since_version=1)],
    )
    n = len(qu)
    assert got[:n] == (lab[qu] == lab[qv]).tolist(), "connected"
    assert got[n:2 * n] == sizes[lab[qv]].tolist(), "component size"
    full = decode_pull_doc(got[2 * n])
    assert full["kind"] == "full" and np.array_equal(
        full["r"], lab[full["u"]]
    ), "summary pull"
    delta = decode_pull_doc(got[2 * n + 1])
    assert np.array_equal(delta["r"], lab[delta["u"]]), "delta pull"

    # degree table gather
    deg = np.bincount(src, minlength=vcap) + np.bincount(dst, minlength=vcap)
    dd = DegreeDistribution(
        window=CountWindow(window), vertex_dict=datasets.IdentityDict(vcap)
    )
    events = zip(src.tolist(), dst.tolist(), ["+"] * len(src))
    counts = np.bincount(deg[deg > 0])
    qd = [1, 2, 3, 5, 8, len(counts) - 1, len(counts) + 7]
    got = _serve(dd.servable(), events,
                 [DegreeQuery(int(v)) for v in qv]
                 + [DegreeCountQuery(d) for d in qd])
    assert got[:len(qv)] == deg[qv].tolist(), "degree"
    assert got[len(qv):] == [
        int(counts[d]) if d < len(counts) else 0 for d in qd], "degree count"

    # rank table gather (the PageRank step always donates its carry)
    pr = IncrementalPageRank(tol=1e-9, max_iter=500)
    got = _serve(
        pr.servable(),
        SimpleEdgeStream((src, dst), window=CountWindow(window)),
        [RankQuery(int(v)) for v in qv],
    )
    verts, want = oracle_pagerank(src, dst)
    ref = np.zeros(vcap)
    ref[verts] = want
    err = float(np.max(np.abs(np.asarray(got) - ref[qv])))
    assert err < 1e-5, f"rank: max error {err:.2e}"

    # cover forest: typed verdict + witness
    bp = BipartitenessCheck()
    [verdict] = _serve(bp.servable(), stream(), [BipartiteQuery()])
    want_bp = oracle_bipartite(vcap, src, dst)
    assert verdict["bipartite"] == want_bp, verdict
    assert want_bp or verdict["witness"] is not None, verdict
    return (f"7 query classes, {2 * n + 2 + n + len(qd) + n + 1} answers after "
            f"{n_win} windows of {window} edges; delta pull kind="
            f"{delta['kind']}; bipartite={want_bp}")


def servable_after_donated_superbatch() -> str:
    """The dense carry's superbatch step donates its summary off-CPU; a
    published snapshot must own its buffer. Keep EVERY published payload
    and read them all after later dispatches donated the carry."""
    from gelly_streaming_tpu import datasets
    from gelly_streaming_tpu.core.stream import SimpleEdgeStream
    from gelly_streaming_tpu.core.window import CountWindow
    from gelly_streaming_tpu.library import ConnectedComponents
    from gelly_streaming_tpu.serving import ConnectedQuery, StreamServer
    from gelly_streaming_tpu.summaries.forest import (
        fold_edges_host,
        resolve_flat_host,
    )

    scale, window, n_win, k = sized(14, 7), 1024, sized(64, 16), 8
    vcap = 1 << scale
    src, dst = rmat(window * n_win, scale, seed=70)
    stream = SimpleEdgeStream(
        (src, dst), window=CountWindow(window),
        vertex_dict=datasets.IdentityDict(vcap),
    )
    agg = ConnectedComponents(carry="dense", superbatch=k)
    server = StreamServer(agg.servable(), stream)
    kept = []
    server.store.add_listener(lambda snap: kept.append(snap))
    rng = np.random.default_rng(70)
    qu, qv = rng.integers(0, vcap, 100), dst[rng.integers(0, len(dst), 100)]
    with server:
        server.join(600)
        got = [f.result(600).value for f in server.submit_many(
            [ConnectedQuery(int(u), int(v)) for u, v in zip(qu, qv)]
        )]
    assert agg._cc_mode == "dense", agg._cc_mode
    lab = fold_edges_host(np.arange(vcap, dtype=np.int64), src, dst)
    assert got == (lab[qu] == lab[qv]).tolist(), "final connected"
    assert len(kept) == n_win, len(kept)
    # a group's K publishes carry the END-of-group state (CCServable's
    # documented granularity): judge each against its group's prefix
    for snap in kept:
        end = (snap.window // k + 1) * k * window
        table = resolve_flat_host(np.asarray(snap.payload["labels"]))
        want = fold_edges_host(
            np.arange(vcap, dtype=np.int64), src[:end], dst[:end]
        )
        assert np.array_equal(table, want), f"snapshot {snap.window}"
    return (f"{len(kept)} retained snapshots read after "
            f"{n_win // k} superbatch dispatches (donated="
            f"{agg._donated_carry}), all equal to their group's oracle")


def main() -> int:
    from gelly_streaming_tpu import native
    from gelly_streaming_tpu.utils.compile_cache import (
        cache_entry_count,
        enable_compile_cache,
    )
    from gelly_streaming_tpu.utils.profiling import describe_device

    cache_dir = enable_compile_cache()
    device = describe_device()
    log(f"device {device} rehearse={REHEARSE}")
    if device["platform"] != "tpu" and not REHEARSE:
        log("no TPU; pass --rehearse for the CPU dress rehearsal")
        return 1
    if not native.native_available():
        log(f"native library unavailable:\n{native.build_error()}")
        return 1
    rows = []
    for check in (
        cc_forest_1k_per_window,
        cc_forest_1k_superbatch_auto,
        cc_device_encode,
        bipartiteness,
        degrees_continuous,
        window_triangles_slice,
        pagerank_incremental,
        exact_triangles,
        sage_forward_xla,
        fused_sage_matmul_pallas,
        query_engine_device_all_classes,
        servable_after_donated_superbatch,
    ):
        t0 = time.perf_counter()
        try:
            detail, outcome = check(), "compiled and correct"
        except AssertionError as e:
            detail, outcome = f"{e}", "wrong value"
            log(traceback.format_exc())
        except Exception as e:
            detail, outcome = f"{type(e).__name__}: {e}", "raised"
            log(traceback.format_exc())
        rows.append({
            "check": check.__name__, "outcome": outcome,
            "detail": detail[:2000],
            "seconds": round(time.perf_counter() - t0, 1),
        })
        log(f"{check.__name__}: {outcome} ({rows[-1]['seconds']}s) "
            f"{detail[:300]}")
    doc = {
        "device": device, "rehearse": REHEARSE, "rows": rows,
        "compile_cache_dir": cache_dir,
        "compile_cache_entries": cache_entry_count(cache_dir),
    }
    out_dir = os.path.join(HERE, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "chip_kernel_check.json"), "w") as f:
        json.dump(doc, f, indent=2)
    for r in rows:
        print(f"{r['check']:<36} {r['outcome']:<22} {r['detail'][:160]}")
    failed = [r["check"] for r in rows if r["outcome"] != "compiled and correct"]
    print(json.dumps({"ok": not failed, "failed": failed, "device": device}))
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
