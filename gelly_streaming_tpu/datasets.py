"""BASELINE corpus registry, loaders, and surrogate synthesis.

The measurement matrix in ``BASELINE.md`` names three corpora: the SNAP
LiveJournal edge list (streaming CC at scale), the SNAP twitter-ego
combined edge list, and MovieLens ratings (the weighted-matching workload —
the reference's matching example reads the same dataset,
``example/CentralizedWeightedMatching.java:41-44``). This module gives each
a loader over the native chunked parser, plus an RMAT surrogate generator
for hermetic environments (no network egress): ``ensure_corpus`` returns
the real file when present under ``$GELLY_DATA`` / ``./data`` and otherwise
synthesizes (once, cached) a surrogate with the same format and a
documented scale, so benchmarks always run file-first — the point is
timing the *system* path (file -> windower -> dict -> device), never a
pre-staged array.

Surrogates are R-MAT graphs (Graph500 parameters a=.57 b=.19 c=.19 d=.05):
power-law degrees, community structure, and raw 64-bit-id sparsity — the
properties that stress parsing, vertex compaction, and skew handling the
way the real corpora do.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Optional, Tuple

import numpy as np

from . import native
from .core.stream import SimpleEdgeStream
from .core.vertexdict import VertexDict
from .core.window import CountWindow, EventTimeWindow, WindowPolicy, Windower


@dataclasses.dataclass(frozen=True)
class CorpusSpec:
    name: str
    filename: str  # conventional filename under the data dir
    url: str  # provenance (documentation only; never fetched)
    n_edges: int  # published size of the real corpus
    n_vertices: int
    weighted: bool = False
    # surrogate scale: edges/vertices for the synthesized stand-in
    surrogate_edges: int = 1 << 24
    surrogate_vscale: int = 1 << 21


CORPORA = {
    "livejournal": CorpusSpec(
        name="livejournal",
        filename="soc-LiveJournal1.txt",
        url="https://snap.stanford.edu/data/soc-LiveJournal1.html",
        n_edges=68_993_773,
        n_vertices=4_847_571,
        surrogate_edges=1 << 24,
        surrogate_vscale=1 << 21,
    ),
    # north-star scale (BASELINE.md: ">=100M streamed edges, 100M-edge
    # windows"): a scale-23 R-MAT surrogate roughly 2x the real
    # LiveJournal's edge count — no real corpus by this name exists, so
    # this always synthesizes
    "livejournal-xl": CorpusSpec(
        name="livejournal-xl",
        filename="soc-LiveJournal1-xl.txt",
        url="https://snap.stanford.edu/data/soc-LiveJournal1.html",
        n_edges=1 << 27,
        n_vertices=1 << 23,
        surrogate_edges=1 << 27,
        surrogate_vscale=1 << 23,
    ),
    "twitter-ego": CorpusSpec(
        name="twitter-ego",
        filename="twitter_combined.txt",
        url="https://snap.stanford.edu/data/ego-Twitter.html",
        n_edges=2_420_766,
        n_vertices=81_306,
        surrogate_edges=1 << 21,
        surrogate_vscale=1 << 17,
    ),
    "movielens-100k": CorpusSpec(
        name="movielens-100k",
        filename="u.data",
        url="https://grouplens.org/datasets/movielens/100k/",
        n_edges=100_000,
        n_vertices=943 + 1682,
        weighted=True,
        surrogate_edges=100_000,
        surrogate_vscale=1 << 11,
    ),
}

# MovieLens rates (user, item) pairs whose id ranges overlap; loaders offset
# item ids into a disjoint range so the bipartite structure survives the
# shared vertex-id space (the reference's preprocessed movielens file has
# the same property).
MOVIELENS_ITEM_OFFSET = 1 << 20


def data_dirs() -> list:
    dirs = []
    env = os.environ.get("GELLY_DATA")
    if env:
        dirs.append(env)
    dirs.append(os.path.join(os.getcwd(), "data"))
    dirs.append("/tmp/gelly_data")
    return dirs


def locate(name: str) -> Optional[str]:
    """Path of the real corpus file if present under a data dir."""
    spec = CORPORA[name]
    for d in data_dirs():
        p = os.path.join(d, spec.filename)
        if os.path.exists(p):
            return p
    return None


# --------------------------------------------------------------------- #
# Surrogate synthesis (R-MAT)
# --------------------------------------------------------------------- #
def rmat_edges(
    n_edges: int,
    scale: int,
    seed: int = 0,
    a: float = 0.57,
    b: float = 0.19,
    c: float = 0.19,
) -> Tuple[np.ndarray, np.ndarray]:
    """Vectorized R-MAT: ``n_edges`` edges over ``2**scale`` vertices.

    One pass per address bit; each pass picks the quadrant for every edge
    at once (no per-edge recursion).
    """
    rng = np.random.default_rng(seed)
    src = np.zeros(n_edges, np.int64)
    dst = np.zeros(n_edges, np.int64)
    for _ in range(scale):
        r = rng.random(n_edges)
        src_bit = r >= (a + b)
        dst_bit = (r >= a) & (r < a + b) | (r >= a + b + c)
        src = (src << 1) | src_bit
        dst = (dst << 1) | dst_bit
    return src, dst


def synthesize(
    name: str, path: str, seed: int = 0, chunk: int = 1 << 22
) -> str:
    """Write the surrogate corpus for ``name`` to ``path`` (SNAP format:
    '#' header + tab-separated edges; MovieLens adds a rating column)."""
    spec = CORPORA[name]
    scale = int(spec.surrogate_vscale).bit_length() - 1
    with open(path, "w") as f:
        f.write(
            f"# surrogate for {spec.name} ({spec.url})\n"
            f"# R-MAT scale={scale} edges={spec.surrogate_edges}\n"
        )
    rng = np.random.default_rng(seed + 1)
    for start in range(0, spec.surrogate_edges, chunk):
        n = min(chunk, spec.surrogate_edges - start)
        src, dst = rmat_edges(n, scale, seed=seed + start)
        if spec.weighted:
            # ratings column: integer 1..5, appended text-side
            # raw (user, item, rating) rows like the real u.data; loaders
            # apply MOVIELENS_ITEM_OFFSET, so the file itself stays raw
            w = rng.integers(1, 6, n)
            with open(path, "a") as f:
                for s, d, r in zip(src.tolist(), dst.tolist(), w.tolist()):
                    f.write(f"{s}\t{d}\t{r}\n")
        else:
            native.write_edge_file(path, src, dst, append=True)
    return path


def ensure_corpus(name: str) -> Tuple[str, bool]:
    """(path, is_real): the real corpus if present, else the cached
    surrogate (synthesized on first use)."""
    real = locate(name)
    if real is not None:
        return real, True
    cache_dir = "/tmp/gelly_data"
    os.makedirs(cache_dir, exist_ok=True)
    spec = CORPORA[name]
    path = os.path.join(
        cache_dir, f"surrogate_{name}_{spec.surrogate_edges}.txt"
    )
    if not os.path.exists(path):
        synthesize(name, path)
    return path, False


# --------------------------------------------------------------------- #
# Identity vertex mapping (dense-integer corpora)
# --------------------------------------------------------------------- #
class IdentityDict:
    """VertexDict stand-in for corpora whose ids are already dense small
    integers (LiveJournal, most SNAP graphs): compact id == raw id, so the
    encode stage of ingest disappears.

    This mirrors the reference, which also uses raw ``Long`` ids directly
    as keys (``summaries/DisjointSet.java:30``) — no compaction exists
    there either. Emission correctness does not depend on this mapping:
    workloads track which vertices actually appeared (e.g. the label
    table's ``touched`` mask), so id-space gaps never show up as phantom
    vertices.
    """

    def __init__(self, id_bound: int):
        self.id_bound = int(id_bound)
        self._observed = 0  # max encoded id + 1

    def __len__(self) -> int:
        """Number of ids actually observed (max + 1), NOT the declared
        bound: consumers that treat ``len(vdict)`` as the seen-vertex
        count (IncrementalPageRank's teleport mass) would otherwise
        spread rank over the whole declared id space (round-2 advisor
        finding)."""
        return self._observed

    @property
    def capacity(self) -> int:
        from .core.edgeblock import bucket_capacity

        return bucket_capacity(max(1, self.id_bound))

    def observe(self, max_id: int) -> None:
        """Advance the observed-id watermark (the single implementation of
        the ``len()`` semantics — encode and the parser fast path both
        route through here)."""
        if max_id >= self._observed:
            self._observed = max_id + 1

    def encode(self, raw):
        a = np.asarray(raw)
        if a.size:
            hi = int(a.max())
            if int(a.min()) < 0 or hi >= self.id_bound:
                raise ValueError(
                    f"raw id outside [0, {self.id_bound}) — not a dense-id "
                    "corpus; use VertexDict"
                )
            self.observe(hi)
        return a if a.dtype == np.int32 else a.astype(np.int32)

    def encode_pair(self, src, dst):
        return self.encode(src), self.encode(dst)

    def decode(self, idx):
        return np.asarray(idx, np.int64)

    def decode_one(self, idx: int) -> int:
        return int(idx)

    def lookup(self, raw: int):
        return int(raw) if 0 <= int(raw) < self.id_bound else None

    def lookup_batch(self, raw) -> np.ndarray:
        """Vectorized :meth:`lookup` (the serving query path): compact
        ids, -1 for ids outside the declared bound."""
        a = np.asarray(raw, np.int64).ravel()
        return np.where(
            (a >= 0) & (a < self.id_bound), a, -1
        ).astype(np.int32)

    def raw_ids(self) -> np.ndarray:
        """Ids observed so far (the checkpoint surface): restoring these
        through ``encode`` reproduces the watermark instead of resetting
        ``len()`` to the whole declared bound."""
        return np.arange(self._observed, dtype=np.int64)

    def raw_table(self):
        import jax.numpy as jnp

        return jnp.arange(self.capacity, dtype=jnp.int32)


# --------------------------------------------------------------------- #
# Binary edge cache (the Arrow/Kafka-style ingest format)
# --------------------------------------------------------------------- #
_BIN_MAGIC = b"GELLYB1\x00"


def write_binary(bin_path: str, src, dst, val=None) -> str:
    """Write edge columns as a packed binary corpus (the layout
    :func:`binary_cache` documents and :func:`iter_binary_chunks` reads),
    atomically; returns ``bin_path``. For corpora generated from a seed
    there is no text file to convert — the columns are the source."""
    src, dst = np.asarray(src), np.asarray(dst)
    if src.size and (
        max(src.max(), dst.max()) > np.iinfo(np.int32).max or min(src.min(), dst.min()) < 0
    ):
        raise ValueError("binary cache requires non-negative int32 ids")
    with open(bin_path + ".tmp", "wb") as f:
        f.write(_BIN_MAGIC)
        np.asarray([len(src)], np.int64).tofile(f)
        np.asarray([0 if val is None else 1], np.uint8).tofile(f)
        src.astype(np.int32).tofile(f)
        dst.astype(np.int32).tofile(f)
        if val is not None:
            np.asarray(val).astype(np.float32).tofile(f)
    os.replace(bin_path + ".tmp", bin_path)
    return bin_path


def binary_cache(path: str, bin_path: Optional[str] = None, arrays=None) -> str:
    """Convert a text edge list to the packed binary format (one-time);
    returns the binary path. Layout: magic, int64 n, uint8 has_val, then
    src int32[n], dst int32[n], and val float32[n] when present — the
    shape a production ingest bus (Kafka/Arrow) would deliver, letting the
    bench separate text-parse cost from the streaming system itself.

    ``arrays=(src, dst, val|None)`` skips re-parsing when the caller
    already holds the parsed columns."""
    if bin_path is None:
        bin_path = path + ".gbin"
    # freshness by source size+mtime sidecar, not mtime ORDER: a restored
    # or copied corpus file can carry any mtime and would silently serve
    # a stale cache (round-2 advisor finding; same fix as the .so build)
    st = os.stat(path)
    stamp = f"{st.st_size}:{int(st.st_mtime_ns)}"
    sidecar = bin_path + ".src"
    if os.path.exists(bin_path):
        try:
            with open(sidecar) as f:
                if f.read().strip() == stamp:
                    return bin_path
        except OSError:
            pass
    src, dst, val = arrays if arrays is not None else native.parse_edge_file(path)
    write_binary(bin_path, src, dst, val)
    with open(sidecar, "w") as f:
        f.write(stamp)
    return bin_path


def iter_binary_chunks(bin_path: str, chunk_edges: int = 1 << 21):
    """Yield (src, dst, val|None) int32/float32 column chunks from a
    :func:`binary_cache` file via memmap views (zero-copy)."""
    with open(bin_path, "rb") as f:
        if f.read(8) != _BIN_MAGIC:
            raise IOError(f"{bin_path}: not a gelly binary edge file")
        n = int(np.fromfile(f, np.int64, 1)[0])
        has_val = bool(np.fromfile(f, np.uint8, 1)[0])
        base = f.tell()
    mm = np.memmap(bin_path, mode="r", dtype=np.uint8)
    src = mm[base : base + 4 * n].view(np.int32)
    dst = mm[base + 4 * n : base + 8 * n].view(np.int32)
    val = mm[base + 8 * n : base + 12 * n].view(np.float32) if has_val else None
    for a in range(0, n, chunk_edges):
        b = min(a + chunk_edges, n)
        yield src[a:b], dst[a:b], None if val is None else val[a:b]


# --------------------------------------------------------------------- #
# File -> stream
# --------------------------------------------------------------------- #
class _ValuePacker:
    """Packed value columns for the device-encode path (round-4 verdict
    missing #6): a value-CONSUMING workload previously paid the full
    per-window float32 upload (4 B/edge — one third of the H2D budget on
    top of the mandatory 8 B/edge id columns).

    Real weighted corpora overwhelmingly carry LOW-CARDINALITY values
    (MovieLens ratings: 10 distinct; small integer weights), so the host
    keeps a sorted dictionary of distinct float32 values beside the
    parser and ships uint8 codes (1 B/edge; uint16 above 255 distinct) +
    a tiny LUT that re-uploads only when it changes; the device widens
    with one gather. The TOP code of each width (255 / 65535) is
    reserved: it always decodes to 0.0, preserving the padded-slot
    val==0 invariant every other ingest path guarantees (aggregations
    that scatter-add values without re-masking rely on it). LOSSLESS by
    construction — any window that would exceed 65535 distinct values,
    or contains NaN (unorderable, so the sorted-dictionary probe cannot
    code it), permanently escalates the stream to the raw float32 path.
    """

    __slots__ = ("table", "mode", "_lut_dev", "_lut_stale")

    def __init__(self):
        self.table = np.zeros(0, np.float32)
        self.mode = "u8"  # "u8" | "u16" | "f32"
        self._lut_dev = None
        self._lut_stale = True

    def _probe(self, v):
        codes = np.searchsorted(self.table, v)
        np.minimum(codes, max(len(self.table) - 1, 0), out=codes)
        miss = (
            np.zeros(len(v), bool) if len(self.table) == 0
            else self.table[codes] != v
        )
        if len(self.table) == 0:
            miss[:] = True
        return codes, miss

    def pack(self, v: np.ndarray):
        """-> (codes uint8/uint16, lut jnp or None) or None once
        escalated to raw f32."""
        import jax.numpy as jnp

        if self.mode == "f32":
            return None
        v = np.ascontiguousarray(v, np.float32)
        codes, miss = self._probe(v)
        if miss.any():
            if np.isnan(v).any():
                self.mode = "f32"
                return None
            self.table = np.union1d(self.table, np.unique(v[miss])).astype(
                np.float32
            )
            if len(self.table) > 65535:  # top u16 code reserved for pads
                self.mode = "f32"
                return None
            if len(self.table) > 255 and self.mode == "u8":
                self.mode = "u16"
            self._lut_stale = True
            codes, miss = self._probe(v)
            assert not miss.any()
        dt = np.uint8 if self.mode == "u8" else np.uint16
        if self._lut_stale:
            pad = 256 if self.mode == "u8" else 65536
            lut = np.zeros(pad, np.float32)
            lut[: len(self.table)] = self.table
            self._lut_dev = jnp.asarray(lut)
            self._lut_stale = False
        return codes.astype(dt), self._lut_dev


def _decode_vals(lut, codes):
    return lut[codes]


_decode_vals_jit = None


def _device_encoded_blocks(path, is_binary, policy, vdict, chunk_edges,
                           drop_values=False):
    """Window blocks whose vertex mapping runs ON DEVICE: host work is
    slicing raw columns and device puts; the compaction is the carried
    device hash table (``ops/device_dict.py``). ``policy`` is a
    CountWindow (fixed ``size`` slices) or an EventTimeWindow (ascending
    timestamps from ``timestamp_fn`` over the column tuple — same
    contract as the Windower's array fast path; window boundaries are
    runs of equal time slot, so block capacities bucket by observed
    window size).

    With a declared ``id_bound`` the table covers the id space and every
    window is one unconditional encode dispatch. WITHOUT a bound (general
    arbitrary-id streams) the host tracks the EXACT distinct-id count of
    the raw stream as it parses (``native.NoveltyBitmap`` — first-seen
    distinctness is precisely the device table's count) and grows the
    device table by pure padding BEFORE any window could overflow it.
    Either way the pipeline performs zero device->host reads: even a
    scalar fetch waits for every window dispatched before it, so a "read
    the count back" design drains the pipeline once per window. The
    device-side sticky ``probe`` field still detects a (bug-only)
    overflow at the next natural sync.
    """
    import jax.numpy as jnp

    from .core.edgeblock import EdgeBlock, _cached_mask, _cached_zeros
    from .core.edgeblock import bucket_capacity as bcap

    growth = getattr(vdict, "id_bound", 1) == 0
    if growth:
        if getattr(vdict, "_novelty", None) is None:
            # owned by the dict: novelty state must live exactly as long
            # as the table it mirrors (stream re-iteration reuses both)
            vdict._novelty = native.NoveltyBitmap()
            vdict._novel_seen = 0
        novelty = vdict._novelty

    packer = _ValuePacker()

    def build(si, di, v, n):
        cap = bcap(n)
        if cap != n:
            si = jnp.pad(si, (0, cap - n))
            di = jnp.pad(di, (0, cap - n))
        if v is None or drop_values:
            # value-ignoring workloads (CC, degrees, triangles) on
            # weighted corpora: skip the per-window float32 H2D entirely
            # (ROADMAP #4); the cached zero column is one device constant
            val = _cached_zeros(cap, jnp.float32)
        else:
            packed = packer.pack(v)
            if packed is None:  # high-cardinality / NaN: raw f32 column
                vp = np.zeros(cap, np.float32)
                vp[:n] = v
                val = jnp.asarray(vp)
            else:
                codes, lut = packed
                # pads take the reserved top code, which decodes to 0.0
                # (the padded-val invariant; code 0 would decode to the
                # smallest DISTINCT VALUE and silently weight vertex 0)
                cp = np.full(cap, np.iinfo(codes.dtype).max, codes.dtype)
                cp[:n] = codes
                global _decode_vals_jit
                if _decode_vals_jit is None:
                    import jax

                    _decode_vals_jit = jax.jit(_decode_vals)
                val = _decode_vals_jit(lut, jnp.asarray(cp))
        return EdgeBlock(
            src=si, dst=di, val=val,
            mask=_cached_mask(cap, n), n_vertices=vdict.capacity,
        )

    def emit(s, d, v):
        if growth:
            vdict.ensure_capacity_host(vdict._novel_seen)
            si, di = vdict.encode_pair_spec(s, d)
        else:
            si, di = vdict.encode_pair(s, d)
        return build(si, di, v, len(s))

    read_chunk = (
        policy.size if isinstance(policy, CountWindow) else chunk_edges
    )
    src = (
        iter_binary_chunks(path, read_chunk)
        if is_binary
        else native.iter_edge_chunks_i32(
            path, chunk_edges, id_bound=getattr(vdict, "id_bound", 0)
        )
    )
    if not isinstance(policy, CountWindow):
        yield from _event_time_device_blocks(src, policy, vdict, growth, emit)
        return
    size = policy.size
    pend, have = [], 0
    for s, d, v in src:
        s, d = np.asarray(s), np.asarray(d)
        if growth:
            vdict._novel_seen += novelty.novel2(s, d)
        pend.append((s, d, v))
        have += len(s)
        while have >= size:
            if len(pend) == 1:
                cs, cd, cv = pend[0]
            else:
                cs = np.concatenate([p[0] for p in pend])
                cd = np.concatenate([p[1] for p in pend])
                cv = (
                    np.concatenate(
                        [
                            np.zeros(len(p[0]), np.float32) if p[2] is None
                            else np.asarray(p[2], np.float32)
                            for p in pend
                        ]
                    )
                    if any(p[2] is not None for p in pend)
                    else None
                )
            yield emit(
                cs[:size], cd[:size], None if cv is None else cv[:size]
            )
            pend = [(cs[size:], cd[size:], None if cv is None else cv[size:])]
            have -= size
    if have:
        cs, cd, cv = pend[0] if len(pend) == 1 else (
            np.concatenate([p[0] for p in pend]),
            np.concatenate([p[1] for p in pend]),
            (
                np.concatenate(
                    [
                        np.zeros(len(p[0]), np.float32) if p[2] is None
                        else np.asarray(p[2], np.float32)
                        for p in pend
                    ]
                )
                if any(p[2] is not None for p in pend)
                else None
            ),
        )
        if len(cs):
            yield emit(cs, cd, cv)


def _event_time_device_blocks(src, policy, vdict, growth, emit):
    """Event-time windowing for the device-encode path: the shared
    chunked slot-run splitter (``core.window.iter_time_slot_runs`` — ONE
    implementation of the boundary semantics with the host Windower),
    with novelty tracking applied per raw chunk on the way in."""
    from .core.window import iter_time_slot_runs

    novelty = getattr(vdict, "_novelty", None)

    def tracked(chunks):
        for s, d, v in chunks:
            s, d = np.asarray(s), np.asarray(d)
            if growth:
                vdict._novel_seen += novelty.novel2(s, d)
            yield s, d, v

    for _slot, s, d, v in iter_time_slot_runs(
        tracked(src), policy, val_dtype=np.float32
    ):
        yield emit(s, d, v)


def stream_file(
    path: str,
    window: Optional[WindowPolicy] = None,
    *,
    vertex_dict: Optional[VertexDict] = None,
    chunk_edges: int = 1 << 21,
    prefetch_depth: int = 0,
    min_vertex_capacity: int = 0,
    device_encode: bool = False,
    dense_ids: bool = True,
    drop_values: bool = False,
) -> SimpleEdgeStream:
    """A :class:`SimpleEdgeStream` over an edge file, chunk-parsed natively.

    The returned stream re-reads the file on every iteration (streams are
    lazily re-iterable). ``prefetch_depth > 0`` overlaps parse/window/encode
    against device compute on a background thread; as with
    ``SimpleEdgeStream.prefetched``, the shared vertex dict (including
    ``IdentityDict``'s observed-id watermark) may then run up to ``depth``
    windows ahead of the consumer — only mid-stream ``len(vertex_dict)``
    readers observe the lead. ``min_vertex_capacity``
    pre-sizes the vertex table (e.g. from the corpus spec) so carried device
    state compiles once instead of once per capacity-growth bucket.

    ``device_encode=True`` moves vertex compaction onto the device
    (``ops/device_dict.py``). With ``dense_ids=True`` (default)
    ``min_vertex_capacity`` is also the declared raw-id bound — the table
    covers the id space and never grows. ``dense_ids=False`` is the
    GENERAL arbitrary-id path: ids may be any non-negative int32, the
    table grows proactively from exact host-side novelty tracking (see
    :func:`_device_encoded_blocks`), and ``min_vertex_capacity`` is
    only a pre-sizing hint. Ids beyond int32 need the host ``VertexDict``.
    ``drop_values=True`` skips the per-window value-column upload for
    value-ignoring workloads on weighted corpora (device-encode only).
    """
    policy = window or CountWindow(1 << 20)
    is_binary = path.endswith(".gbin")
    if device_encode:
        # vertex compaction as device state: one encode dispatch per
        # window, no host hash work (ROADMAP #1)
        if not isinstance(policy, (CountWindow, EventTimeWindow)):
            raise ValueError(
                "device_encode supports CountWindow / EventTimeWindow"
            )
        if vertex_dict is not None:
            raise ValueError(
                "device_encode builds its own DeviceVertexDict; a supplied "
                "vertex_dict would be silently ignored"
            )
        from .ops.device_dict import DeviceVertexDict

        vd = DeviceVertexDict(
            min_capacity=max(min_vertex_capacity, 1 << 10),
            id_bound=min_vertex_capacity if dense_ids else 0,
        )

        def device_source():
            it = _device_encoded_blocks(
                path, is_binary, policy, vd, chunk_edges,
                drop_values=drop_values,
            )
            if prefetch_depth > 0:
                from .core.pipeline import prefetch

                return prefetch(it, prefetch_depth)
            return it

        return SimpleEdgeStream(_blocks=device_source, _vdict=vd)
    if vertex_dict is None and min_vertex_capacity > 0:
        vertex_dict = VertexDict(min_capacity=min_vertex_capacity)
    windower = Windower(policy, vertex_dict)

    def block_source():
        vd = windower.vertex_dict
        identity = isinstance(vd, IdentityDict)
        if is_binary:
            raw_chunks = iter_binary_chunks(path, chunk_edges)
            if identity:
                chunks = (
                    (vd.encode(s), vd.encode(d), v) for s, d, v in raw_chunks
                )
            else:
                chunks = (
                    (*vd.encode_pair(s, d), v) for s, d, v in raw_chunks
                )
            pairs = windower.blocks_from_chunks(chunks, encoded=True)
        elif identity:
            # the i32 parser already bound-checks against the id space, so
            # the columns pass through with no further validation/convert;
            # only the observed-id watermark (len(vdict)) needs updating
            def _tracked(chunks, vd=vd):
                for s, d, v in chunks:
                    if len(s):
                        vd.observe(int(max(int(s.max()), int(d.max()))))
                    yield s, d, v

            chunks = _tracked(native.iter_edge_chunks_i32(
                path, chunk_edges, id_bound=vd.id_bound
            ))
            pairs = windower.blocks_from_chunks(chunks, encoded=True)
        elif getattr(vd, "_native", None) is not None:
            # fused native ingest: parse+encode in one C pass per chunk
            chunks = vd.iter_encode_file(path, chunk_edges)
            pairs = windower.blocks_from_chunks(chunks, encoded=True)
        else:
            pairs = windower.blocks_from_chunks(
                native.iter_edge_chunks(path, chunk_edges)
            )
        it = (info_block[1] for info_block in pairs)
        if prefetch_depth > 0:
            from .core.pipeline import prefetch

            return prefetch(it, prefetch_depth)
        return it

    return SimpleEdgeStream(
        _blocks=block_source, _vdict=windower.vertex_dict
    )


def load_movielens(path: str) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(user, item, rating) columns from a MovieLens ``u.data``-format file
    (user \\t item \\t rating \\t timestamp); item ids offset into a
    disjoint range (``MOVIELENS_ITEM_OFFSET``)."""
    src, dst, val = native.parse_edge_file(path)
    if val is None:
        val = np.ones(len(src))
    return src, dst + MOVIELENS_ITEM_OFFSET, val
