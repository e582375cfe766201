"""Graph500 Kronecker edge stream, generated from a seed with JAX.

A configuration names this module (``"generator": "graph500"``) and
gives it a ``graph500`` block; the harness calls :func:`edges` and
:func:`closing_edges`.

The Graph500 specification's generator: each of ``scale`` bit levels
picks a quadrant with probabilities A, B, C, D = 1 - A - B - C, the
vertex labels are scrambled so that degree does not follow the id, and
the edge order is random (edges are i.i.d. here, so any order is). The
specification permutes labels through a table; at 2^28 ids a table is a
gigabyte, so the scramble is a seeded bijection on ``[0, 2^scale)``
(odd multipliers and xor-shifts, as the reference C code's
``scramble`` does).

``jax.random`` (threefry) gives the same bits on every backend, so a
seed names one stream on the CPU and on the chip. Generation runs in
chunks on the default device and lands in host int32 columns: numpy
takes a minute for 2^27 edges, the chip a second or two.
"""

from __future__ import annotations

import functools

import numpy as np

CHUNK = 1 << 22


def _key(seed: int, stream: int):
    """A key from any non-negative seed (the driver's pass 2^31)."""
    import jax

    seed = int(seed)
    key = jax.random.key(seed & 0x7FFFFFFF)
    key = jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)
    return jax.random.fold_in(key, stream)


def scramble_constants(seed: int):
    """Two odd multipliers and an offset for :func:`scramble`."""
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, 7])
    m1, m2, c = (int(x) for x in rng.integers(0, 1 << 32, 3, dtype=np.uint64))
    return (m1 | 1), (m2 | 1), c


def scramble(v, scale: int, consts, xp=np):
    """A bijection on ``[0, 2^scale)`` over uint32 arrays: every step
    (odd multiply, add, xor with a right shift) is invertible modulo
    2^scale."""
    m1, m2, c = consts
    mask = xp.uint32((1 << scale) - 1)
    half = max(1, scale // 2)
    v = (v * xp.uint32(m1) + xp.uint32(c)) & mask
    v = v ^ (v >> xp.uint32(half))
    v = (v * xp.uint32(m2)) & mask
    v = v ^ (v >> xp.uint32(half))
    return v


@functools.lru_cache(maxsize=8)
def _chunk_fn(scale: int, n: int, a: float, b: float, c: float,
              bipartite: bool, consts):
    import jax
    import jax.numpy as jnp

    t_a = np.uint32(min(int(a * 2**32), 2**32 - 1))
    t_ab = np.uint32(min(int((a + b) * 2**32), 2**32 - 1))
    t_abc = np.uint32(min(int((a + b + c) * 2**32), 2**32 - 1))

    def gen(key):
        def level(_i, carry):
            s, d, key = carry
            key, sub = jax.random.split(key)
            r = jax.random.bits(sub, (n,), jnp.uint32)
            sbit = (r >= t_ab).astype(jnp.uint32)
            dbit = (((r >= t_a) & (r < t_ab)) | (r >= t_abc)).astype(
                jnp.uint32)
            return (s << 1) | sbit, (d << 1) | dbit, key

        zero = jnp.zeros(n, jnp.uint32)
        s, d, _ = jax.lax.fori_loop(0, scale, level, (zero, zero, key))
        if consts is not None:
            s = scramble(s, scale, consts, jnp)
            d = scramble(d, scale, consts, jnp)
        if bipartite:
            # sources on even ids, targets on odd ids: every edge crosses
            s = s & jnp.uint32(0xFFFFFFFE)
            d = d | jnp.uint32(1)
        return s.astype(jnp.int32), d.astype(jnp.int32)

    return jax.jit(gen)


def kronecker_edges(seed: int, scale: int, n_edges: int, *,
                    a: float = 0.57, b: float = 0.19, c: float = 0.19,
                    scrambled: bool = True, bipartite: bool = False,
                    chunk: int = CHUNK):
    """``n_edges`` edges of the scale-``scale`` Kronecker graph as host
    int32 ``(src, dst)`` columns. Chunk ``k`` depends on ``(seed, k)``
    alone, so a longer stream extends a shorter one."""
    import jax

    if not 1 <= scale <= 31:
        raise ValueError(f"scale {scale} outside [1, 31]")
    consts = scramble_constants(seed) if scrambled else None
    chunk = min(chunk, max(8, n_edges))
    fn = _chunk_fn(scale, chunk, a, b, c, bool(bipartite), consts)
    src = np.empty(n_edges, np.int32)
    dst = np.empty(n_edges, np.int32)
    pending = None
    for k, lo in enumerate(range(0, n_edges, chunk)):
        out = fn(_key(seed, k))  # async: overlaps the previous copy-out
        if pending is not None:
            plo, (ps, pd) = pending
            m = min(chunk, n_edges - plo)
            src[plo:plo + m] = np.asarray(ps)[:m]
            dst[plo:plo + m] = np.asarray(pd)[:m]
        pending = (lo, out)
    plo, (ps, pd) = pending
    m = min(chunk, n_edges - plo)
    src[plo:plo + m] = np.asarray(jax.block_until_ready(ps))[:m]
    dst[plo:plo + m] = np.asarray(pd)[:m]
    return src, dst


def order_by_seed(src, dst, window_edges: int, seed: int):
    """The run's stream: the configuration's graph with the edges of
    every window in an order drawn from ``seed``. Every seed then folds
    the same windows (the same sizes, touched sets and component
    merges), in another order: the fold's while-loops run a number of
    rounds that depends on the graph, and a different graph for every
    seed made the same code 10% faster or slower (PERF.md, Findings).
    Whole windows only; a ragged tail keeps its order."""
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, 3])
    perm = rng.permutation(window_edges)
    n = (len(src) // window_edges) * window_edges
    out = []
    for col in (src, dst):
        head = col[:n].reshape(-1, window_edges)[:, perm].reshape(-1)
        out.append(np.concatenate([head, col[n:]]) if n < len(col) else head)
    return out[0], out[1]


def _kron_args(config: dict) -> dict:
    g = config["graph500"]
    return dict(a=g["a"], b=g["b"], c=g["c"],
                scrambled=bool(g.get("scrambled", True)),
                bipartite=bool(g.get("bipartite_even_odd", False)))


def edges(config: dict, n_edges: int, seed: int, warm_edges: int):
    """The run's stream, as the harness asks a generator for it: host
    int32 ``(src, dst)`` columns of ``n_edges`` edges, of which the
    first ``warm_edges`` are folded before the measured window opens.
    It is the configuration's graph (``graph_seed``) with the edges of
    every window ordered by the seed (:func:`order_by_seed`), so that
    every seed times the same work."""
    scale = int(config["scale"])
    src, dst = kronecker_edges(int(config["graph500"]["graph_seed"]), scale,
                               n_edges, **_kron_args(config))
    src, dst = order_by_seed(src, dst, int(config["window_edges"]), seed)
    if int(max(src.max(), dst.max())) >> scale:
        raise ValueError("generated ids pass 2^scale")
    return src, dst


def closing_edges(config: dict, seed: int):
    """``seeded_closing_windows`` windows of a Kronecker graph OF THE
    SEED'S OWN (same scale and parameters, its bits and its scramble
    drawn from ``seed``), which the harness hands out once the measured
    window has closed: every run's final table, and the answers to its
    closing batch of queries, are held to the reference on a graph no
    other seed has, and no timed window folds other work for it (a
    graph of the seed's own BEFORE the measured window moved
    ``edges_per_s`` by 7% and spread it by 4%, PERF.md section 6)."""
    n = (int(config["graph500"].get("seeded_closing_windows", 0))
         * int(config["window_edges"]))
    if not n:
        return None
    return kronecker_edges(int(seed), int(config["scale"]), n,
                           **_kron_args(config))
