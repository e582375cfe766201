"""Typed point queries + the batched vectorized query engine.

A serving tier dies by per-query host loops: 10k concurrent
``connected(u, v)`` queries must not become 10k pointer chases in Python
or 10k device dispatches. The :class:`QueryEngine` answers a whole batch
per query class with ONE jitted lookup:

- CC queries gather the batch's endpoints out of the published pointer
  forest and chase ONLY those lanes to their roots
  (``summaries/forest.py:chase_roots``, the fold's own loop of one
  gather a round, sized by the batch, not the vertex capacity). Flat
  labels are a valid (depth-1) forest, so the one kernel serves every
  CC carry and restored checkpoints alike.
- Degree / rank queries are one table gather; a degree-count query is
  the same gather out of the degree histogram published beside the
  degree table.
- Component-size queries over a snapshot that holds ``sizes`` (a size
  table carried beside the forest, ``ConnectedComponents(
  component_sizes=True)``) share the batch's ONE root chase with the
  ``ConnectedQuery`` ids and gather their sizes at the roots behind it:
  one wait for both. Over a snapshot without it they canonicalize the
  forest once per snapshot version (cached) and bincount, then answer
  any number of batches from the cached size table.

Batch id arrays are padded to power-of-two buckets so a serving session
compiles O(log batch-size) jit signatures, the stream-ingest convention
(``core/edgeblock.py:bucket_capacity``).

Two execution paths, picked per backend (``prefer_host="auto"``):

- **device** (accelerators): the jitted batch kernels run where the
  payload lives; only the batch-sized result crosses the link, where
  the host path would ship a vcap-sized table per snapshot version.
  Each kernel queues behind the folds in flight, so a sweep enqueues
  the kernels of ALL its classes before it fetches the first result
  (``QueryEngine.answer_batch``): it waits out those folds once.
- **host** (the CPU backend): queries answered by the jitted path
  ENQUEUE at the tail of the same XLA dispatch queue the async window
  folds fill, so each batch waits out the whole in-flight pipeline
  (measured ~230 ms p50 behind 1M-edge windows) and its sync stalls
  ingest. Instead the engine lazily materializes ONE host copy of the
  payload table per snapshot version (a wait-on-this-array transfer,
  not a tail-of-queue dispatch) and answers with the same whole-batch
  vectorized chase in numpy — still never per-query loops.

Query ids are RAW vertex ids (what a client knows); the engine maps them
through the payload's vertex dictionary without inserting — unseen
vertices answer like the reference's ``DisjointSet`` would for a vertex
it never saw: connected only to itself, degree 0, rank 0.0, component
size 0.
"""

from __future__ import annotations

import base64
import binascii
import contextlib
import functools
import pickle
import threading
import time
from collections import deque
from dataclasses import dataclass
from typing import (
    Any, Callable, Dict, List, NamedTuple, Optional, Sequence, Tuple,
)

import jax
import jax.numpy as jnp
import numpy as np

from ..core.edgeblock import bucket_capacity
from ..obs import trace as _trace
from ..obs.registry import get_registry
from ..parallel.mesh import replicated, table_vertex_shards, vertex_shards
from ..summaries.forest import TableOps, chase_roots, sharded_table_fn
from .snapshot_store import PublishedSnapshot


# --------------------------------------------------------------------- #
# Query + answer records
# --------------------------------------------------------------------- #
class Query:
    """Marker base for point queries (raw vertex ids)."""

    __slots__ = ()


@dataclass(frozen=True)
class ConnectedQuery(Query):
    """Are ``u`` and ``v`` in one component? (``connected(u, v)``)."""

    u: int
    v: int


@dataclass(frozen=True)
class DegreeQuery(Query):
    """Current degree of ``v``."""

    v: int


@dataclass(frozen=True)
class DegreeCountQuery(Query):
    """How many vertices have degree ``d`` right now? One bin of the
    degree histogram a :class:`~gelly_streaming_tpu.library.degrees.
    DegreeDistribution` publishes beside its degree table (payload key
    ``hist``). Degree 0 is never tracked (a vertex at 0 is removed) and
    answers 0, as does a degree past the histogram's capacity; where
    the capacity is fixed, its last bin counts every degree at or past
    it."""

    d: int


@dataclass(frozen=True)
class RankQuery(Query):
    """Current PageRank mass of ``v``."""

    v: int


@dataclass(frozen=True)
class ComponentSizeQuery(Query):
    """Size of ``v``'s component (0 for a vertex the payload's vertex
    dict cannot decode; over ``IdentityDict`` every id of the id space
    is a vertex, and one the stream never touched answers 1).

    Over a snapshot that holds ``sizes`` (``ConnectedComponents(
    component_sizes=True)``) the answer is ``sizes[root(v)]`` from the
    SAME snapshot ``root(v)`` was chased in: one batched root chase,
    shared with the sweep's ``ConnectedQuery`` ids, and one gather.
    Over a snapshot without it the engine canonicalizes the whole
    ``labels`` table and counts its members once per snapshot version,
    which in a served stream is once per window."""

    v: int


@dataclass(frozen=True)
class SummaryPullQuery(Query):
    """Pull this snapshot's CC forest as a mergeable summary (the
    sharded-serving router's cross-shard union input): per seen slot,
    the RAW vertex id and its component root's RAW id, as packed
    little-endian int64 columns (base64 in the JSON answer value).
    RAW-id space is the join key — per-shard compact ids never leave
    their shard. O(vcap) per snapshot version, cached by the engine, so
    any number of pulls per version cost one canonicalization.

    ``since_version`` is the pull protocol's v2 field: a puller that
    already holds this shard's table at that version asks for only the
    rows whose ROOT assignment changed since then (a ``kind="delta"``
    reply, O(changed rows) on the wire). ``-1`` (the v1 shape — old
    peers never set the field) always answers the full table; a
    ``since_version`` older than the engine's bounded delta ring
    degrades HONESTLY to a full reply tagged with why."""

    since_version: int = -1


@dataclass(frozen=True)
class BipartiteQuery(Query):
    """Is the streamed graph (still) bipartite? Graph-global, like
    :class:`SummaryPullQuery`. The answer value is a typed dict::

        {"bipartite": bool, "witness": raw_id | None}

    ``witness`` is the smallest RAW vertex id whose two signed-cover
    nodes share a component — a vertex on an odd cycle, the conflict
    witness — when the graph is non-bipartite, else None. Answered from
    the published cover forest (``summaries/candidates.py`` layout:
    cover node (v,+) = v, (v,-) = v + vcap in a 2*vcap table), so the
    verdict recomputes from the structural truth rather than trusting a
    carried latch. O(vcap) per snapshot version, cached by the engine.
    """

    __slots__ = ()


@dataclass(frozen=True)
class Answer:
    """One query's result, stamped with the snapshot it was answered
    from: ``window`` is that snapshot's window index, ``staleness`` the
    windows-behind-head gap at answer time (0 = answered at the head),
    ``version`` the snapshot's publish version — the monotone counter a
    routing tier keys its cache invalidation on (reply frames carry it,
    so a router learns of shard progress from ordinary answers).
    ``event_ts`` is the snapshot's EVENT-TIME watermark (``-1`` when
    the pipeline carries no event time): next to ``staleness``'s
    windows-behind-head, it answers "how far behind the world" — the
    data's own clock at the moment the served summaries were true.
    ``shard`` and ``boot`` (ISSUE 20) complete the stamp a
    snapshot-pinned transaction needs: which shard answered (``-1``
    for an unsharded replica; the router re-stamps its fan-outs) and
    the answering store's lineage nonce — together with ``version``
    they are the ``(shard, version, boot)`` triple a
    :class:`~gelly_streaming_tpu.serving.txn.TxnContext` pins from
    ordinary replies, with no extra round trip."""

    value: Any
    window: int
    watermark: int
    staleness: int
    version: int = 0
    event_ts: int = -1
    shard: int = -1
    boot: str = ""


# --------------------------------------------------------------------- #
# Pull-doc wire codec (protocol v2: full | delta reply frames)
# --------------------------------------------------------------------- #
#: how many version-to-version delta segments the engine retains; a
#: ``since_version`` older than the ring reaches degrades to a full
#: reply (tagged ``why="stale"``) — the bounded-memory honesty rule
DELTA_RING = 8


class MalformedPull(ValueError):
    """A pull doc that fails decode, carrying WHICH geometry rule broke
    (``kind`` in {type, missing, b64, geometry, tag, base}) so the
    router can count malformed pulls by failure class instead of
    folding them into a generic pull error."""

    def __init__(self, kind: str, msg: str):
        super().__init__(msg)
        self.kind = kind


def _b64_cols(raws: np.ndarray, roots: np.ndarray) -> Tuple[str, str]:
    return (
        base64.b64encode(
            np.ascontiguousarray(raws, np.int64).tobytes()).decode("ascii"),
        base64.b64encode(
            np.ascontiguousarray(roots, np.int64).tobytes()).decode("ascii"),
    )


def encode_pull_doc(
    raws: np.ndarray,
    roots: np.ndarray,
    *,
    kind: str = "full",
    base: Optional[int] = None,
    why: Optional[str] = None,
) -> dict:
    """Pack ``(vertex, root)`` RAW-id columns as a pull reply doc.

    ``kind="full"`` is the whole-table frame (v1 peers decode it
    unchanged: the tag rides an extra dict key they never read);
    ``kind="delta"`` carries only changed rows plus ``base`` — the
    version the rows are a diff AGAINST, which the puller must already
    hold. ``why`` tags a full reply that a delta request degraded into
    (stale ring, no chain yet, puller ahead). Every key written here is
    read back in :func:`decode_pull_doc` (GL011 symmetry)."""
    u64, r64 = _b64_cols(raws, roots)
    doc = {"kind": kind, "n": int(len(raws)), "u64": u64, "r64": r64}
    if kind == "delta":
        if base is None:
            raise ValueError("delta pull docs must carry base")
        doc["base"] = int(base)
    if why is not None:
        doc["why"] = str(why)
    return doc


def decode_pull_doc(doc) -> dict:
    """Decode a pull reply into host columns::

        {"kind": "full"|"delta", "n": int,
         "u": int64[n], "r": int64[n], "base": int|None, "why": str|None}

    A doc with NO ``kind`` tag decodes as a full frame — that is the v1
    wire shape, so a v2 puller interops with an old shard by treating
    its replies as full tables and resetting its delta baseline.
    Raises :class:`MalformedPull` (kind-tagged) on any geometry
    mismatch; a decoded frame is safe to merge as-is."""
    if not isinstance(doc, dict):
        raise MalformedPull(
            "type", f"pull answer must be a dict, got {type(doc).__name__}"
        )
    kind = doc.get("kind", "full")
    if kind not in ("full", "delta"):
        raise MalformedPull("tag", f"unknown pull frame kind {kind!r}")
    for k in ("n", "u64", "r64"):
        if k not in doc:
            raise MalformedPull("missing", f"pull doc lacks {k!r}")
    n = doc["n"]
    if not isinstance(n, int) or isinstance(n, bool) or n < 0:
        raise MalformedPull("type", f"pull doc n must be an int >= 0, got {n!r}")
    if not isinstance(doc["u64"], str) or not isinstance(doc["r64"], str):
        raise MalformedPull("type", "pull doc u64/r64 must be base64 strings")
    try:
        ub = base64.b64decode(doc["u64"], validate=True)
        rb = base64.b64decode(doc["r64"], validate=True)
    except (binascii.Error, ValueError) as e:
        raise MalformedPull("b64", f"pull doc columns are not base64: {e}")
    if len(ub) != 8 * n or len(rb) != 8 * n:
        raise MalformedPull(
            "geometry",
            f"pull doc geometry mismatch: n={n} but columns carry "
            f"{len(ub)}/{len(rb)} bytes (want {8 * n})",
        )
    base = doc.get("base")
    if kind == "delta":
        if not isinstance(base, int) or isinstance(base, bool):
            raise MalformedPull(
                "base", f"delta pull doc must carry an int base, got {base!r}"
            )
    why = doc.get("why")
    return {
        "kind": kind,
        "n": n,
        "u": np.frombuffer(ub, np.int64),
        "r": np.frombuffer(rb, np.int64),
        "base": base if kind == "delta" else None,
        "why": str(why) if why is not None else None,
    }


# --------------------------------------------------------------------- #
# Vectorized kernels (batch-sized, payload-table-gathering)
# --------------------------------------------------------------------- #
@functools.lru_cache(maxsize=None)
def _batch_roots_fn(mesh=None):
    """The jitted batch chase (program ``jit__batch_roots``) over a
    whole table (no mesh) or over a table split by rows over ``mesh``'s
    ``vertices`` axis: ids replicated in, roots whole out, one
    all-reduce a gather under the scope ``query.exchange``."""
    shards = vertex_shards(mesh)

    def _batch_roots(canon: jax.Array, ids: jax.Array) -> jax.Array:
        """Chase a BATCH of start ids to their forest roots
        (``forest.chase_roots``, the fold's own chase: at a query
        batch's lane count its one plain loop over every lane, the slab
        only from ``forest._SLAB_MIN_LANES`` lanes on). Padding lanes
        chase from 0, always self-rooted. Its ops carry the scope
        ``query.chase`` in a device trace."""
        # under shard_map ``canon`` is this chip's block of rows
        tab = TableOps(canon.shape[0] * shards, shards, "query.exchange")
        with jax.named_scope("query.chase"):
            return chase_roots(canon, tab.gather(canon, ids), tab)

    if shards > 1:
        _batch_roots = sharded_table_fn(_batch_roots, mesh, 1, table_out=False)
    return jax.jit(_batch_roots)


_batch_roots = _batch_roots_fn()


@jax.jit
def _gather(table: jax.Array, ids: jax.Array) -> jax.Array:
    return table[ids]


@jax.jit
def _gather_sizes(lab: jax.Array, sizes: jax.Array, ids: jax.Array) -> jax.Array:
    """Fused root-resolve + size lookup over a canonical table: ONE
    dispatch, only the batch-sized result crosses the link."""
    return sizes[lab[ids]]


@jax.jit
def _component_size_table(canon: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Canonicalize the whole forest once and count members per root.
    O(vcap) — run once per snapshot version, cached by the engine.
    The canonicalization IS ``summaries/forest.py:resolve_flat`` (one
    copy of the kernel; this jit just fuses the bincount after it)."""
    from ..summaries.forest import resolve_flat

    lab = resolve_flat(canon)
    sizes = jnp.zeros(canon.shape[0], jnp.int32).at[lab].add(1)
    return lab, sizes


def _pad_ids(ids: np.ndarray) -> np.ndarray:
    """Bucket a compact-id batch to pow2 (pad with 0 — a safe self-rooted
    lane) so jit signatures stay O(log batch-size)."""
    n = len(ids)
    cap = bucket_capacity(max(n, 1), minimum=8)
    out = np.zeros(cap, np.int32)
    out[:n] = ids
    return out


def _fetch(out, n: int):
    """The first ``n`` lanes of a dispatched batch kernel's result (of
    each result of a tuple dispatched back to back), on the host. The
    copy blocks until the device has run the kernel, which queues behind
    every fold dispatched before it: the span ``serving.device_wait`` is
    that wait alone (the dispatch stays outside it), the fold's part of
    ``serving.answer``. A sweep enqueues EVERY read's kernels before its
    first ``_fetch`` (:meth:`QueryEngine.answer_batch`), so the first
    wait of a sweep is the long one and the others find their result
    there."""
    with _trace.span(
        "serving.device_wait", {"n": n} if _trace.on() else None
    ):
        if isinstance(out, tuple):
            return tuple(np.asarray(x)[:n] for x in out)
        return np.asarray(out)[:n]


#: a read of a sweep, other than its first, that waits longer than this
#: did not find its result there: a fold slipped between two dispatches
LATE_READ_S = 1e-3


class _Read:
    """One table read of a sweep between its two halves. The host half
    (lookup, validity mask, padding, the padded ids' copy to the device)
    has run when this exists. ``dispatch`` enqueues the read's kernels,
    and nothing else, and keeps the un-fetched device result; ``collect`` fetches its first ``n`` lanes, the one
    step that blocks, and ``finish`` makes the class's values of them.
    On the host path there is no ``enqueue``: ``out`` holds the lanes
    from the start, nothing is dispatched and nothing waited for."""

    __slots__ = ("n", "finish", "enqueue", "out", "wait_s")

    def __init__(self, n: int, finish: Callable, *,
                 enqueue: Optional[Callable] = None, out=None):
        self.n = n
        self.finish = finish
        self.enqueue = enqueue
        self.out = out
        self.wait_s = 0.0

    def dispatch(self) -> None:
        if self.enqueue is not None:
            self.out = self.enqueue()

    def collect(self):
        got = self.out
        if self.enqueue is not None:
            t0 = time.perf_counter()
            got = _fetch(got, self.n)
            self.wait_s = time.perf_counter() - t0
        return self.finish(got)


class _SweepRead(NamedTuple):
    """One read of a sweep before its host half: the query classes it
    answers, the engine's method that is the whole read (``READS``),
    the method's id columns, and the span of its own the read runs
    under."""

    classes: Tuple[type, ...]
    method: str
    cols: Tuple[np.ndarray, ...]
    span: Callable = contextlib.nullcontext


def _lookup_batch(vdict, raw: np.ndarray) -> np.ndarray:
    """Raw -> compact ids WITHOUT inserting; -1 marks unseen vertices.
    Uses the dict's vectorized ``lookup_batch`` when it exists, else the
    per-id ``lookup``."""
    raw = np.asarray(raw, np.int64)
    batch = getattr(vdict, "lookup_batch", None)
    if batch is not None:
        return batch(raw)
    lookup = getattr(vdict, "lookup", None)
    if lookup is None:
        raise TypeError(
            f"payload vertex dict {type(vdict).__name__} supports neither "
            "lookup_batch nor lookup"
        )
    out = np.empty(len(raw), np.int32)
    for i, r in enumerate(raw.tolist()):
        c = lookup(r)
        out[i] = -1 if c is None else c
    return out


def _column(qs: Sequence["Query"], field: str) -> np.ndarray:
    """One field of every query of a group, as an id column."""
    return np.asarray([getattr(q, field) for q in qs], np.int64)


def _host_batch_roots(lab: np.ndarray, ids: np.ndarray) -> np.ndarray:
    """Whole-batch vectorized root chase on a host table (the CPU-backend
    fast path; same contract as :func:`_batch_roots`)."""
    r = lab[ids]
    while True:
        nxt = lab[r]
        if np.array_equal(nxt, r):
            return r
        r = nxt


class QueryEngine:
    """Answers homogeneous query batches against one snapshot.

    Stateless except for per-snapshot-version caches: the derived
    component-size table, and (host path) one host materialization of
    each payload table — the O(vcap) costs; everything else is
    batch-sized. One engine instance per server.

    ``prefer_host='auto'`` (default) picks the host path on the CPU
    backend and the jitted device path elsewhere (rationale in the
    module docstring); pass True/False to pin."""

    #: payload key each query class reads (also the capability probe:
    #: a snapshot serves a query class iff the key is present)
    PAYLOAD_KEYS = {
        ConnectedQuery: "labels",
        ComponentSizeQuery: "labels",
        SummaryPullQuery: "labels",
        DegreeQuery: "deg",
        DegreeCountQuery: "hist",
        RankQuery: "ranks",
        BipartiteQuery: "cover",
    }
    #: table-reading query class -> (the method that answers a batch of
    #: it, the query's fields that are the method's id columns). The
    #: method is both halves of ONE read; ``_<method>_read`` is the host
    #: half alone, what a sweep of several reads takes them through
    READS = {
        ConnectedQuery: ("connected", ("u", "v")),
        ComponentSizeQuery: ("component_size", ("v",)),
        DegreeQuery: ("degree", ("v",)),
        DegreeCountQuery: ("degree_count", ("d",)),
        RankQuery: ("rank", ("v",)),
    }

    def __init__(self, prefer_host="auto"):
        if prefer_host == "auto":
            prefer_host = jax.default_backend() == "cpu"
        self.prefer_host = bool(prefer_host)
        #: of the newest ``answer_batch``: the device reads it enqueued
        #: before its first fetch (0 on the host path), and how many of
        #: them, the first aside, still waited over ``LATE_READ_S``
        self.last_sweep: Tuple[int, int] = (0, 0)
        # the device waits of the sweep in progress (None outside one)
        self._waits: Optional[List[float]] = None
        self._size_cache: Tuple[Optional[tuple], Any, Any] = (
            None, None, None,
        )
        self._host_cache: dict = {}  # (epoch, version, payload key) -> np
        # pull docs cache: one dict per (epoch, version), keyed by the
        # effective since_version (-1 = full) — several routers at
        # different baselines share one engine without thrashing
        self._pull_key: Optional[tuple] = None
        self._pull_docs: dict = {}
        # historical (pinned) pull docs: a bounded side cache so
        # transactional merges never thrash the live head's cache
        self._hist_docs: dict = {}
        self._bp_cache: Tuple[Optional[tuple], Optional[dict]] = (
            None, None,
        )
        # delta chain: the canonical table at the last pulled version
        # plus a bounded ring of version-to-version changed-row segments
        self._chain_epoch: Optional[int] = None
        self._chain_version: int = -1
        self._chain_lab: Optional[np.ndarray] = None
        self._chain_n: int = 0
        self._ring: deque = deque(maxlen=DELTA_RING)
        # the chain is touched from the server worker (summary_pull)
        # AND, when a PullRingMirror is attached, from the ingest
        # thread's publish listener (chain_sync) — hence the lock
        self._chain_lock = threading.Lock()

    # -- table access (per-version host cache on the host path) -------- #
    def _table(self, snap: PublishedSnapshot, key: str,
               whole: Optional[str] = None):
        """The payload table, as a host array (host path, cached per
        snapshot (epoch, version)) or the device array as-is (device
        path). A vertex-sharded table always takes the device path and
        is never copied to one device or to the host: ``whole`` names a
        reader that needs it so, which is refused."""
        table = snap.payload[key]
        if table_vertex_shards(table) > 1:
            if whole:
                raise NotImplementedError(
                    f"{whole} canonicalizes the whole `{key}` table on "
                    "one device or on the host; over a table sharded by "
                    "`vertices` that needs a sharded resolve_flat (and a "
                    "sharded size table), which is not built. "
                    "ConnectedQuery is served"
                )
            return table
        if not self.prefer_host:
            return table
        ck = (snap.epoch, snap.version, key)
        cached = self._host_cache.get(ck)
        if cached is None:
            # np.asarray waits for THIS array's producer, not the whole
            # dispatch queue — the property the host path exists for
            cached = np.asarray(table)
            # only the newest version is hot, with every table it holds
            for old in [k for k in self._host_cache if k[:2] != ck[:2]]:
                del self._host_cache[old]
            self._host_cache[ck] = cached
        return cached

    # -- the two halves of a read -------------------------------------- #
    def _run(self, read: _Read):
        """Both halves of one read, back to back."""
        read.dispatch()
        return self._collect(read)

    def _collect(self, read: _Read):
        """The values of a dispatched read; its wait on the device is
        the sweep's to count where a sweep is in progress."""
        vals = read.collect()
        if self._waits is not None and read.enqueue is not None:
            self._waits.append(read.wait_s)
        return vals

    def _roots_read(self, table, ids: np.ndarray, finish,
                    sizes=None) -> _Read:
        """Roots of ``ids`` in ``table``: ONE batched chase, handed to
        ``finish`` once fetched. With ``sizes`` (a size table of the
        same snapshot) also ``sizes[root]`` of every id, ``(roots,
        sizes)``: the gather is enqueued behind the chase with no host
        read between, so one wait brings both back."""
        n = len(ids)
        if table_vertex_shards(table) > 1:
            # the ids go to every chip; the roots come back whole
            mesh = table.sharding.mesh
            placed = jax.device_put(_pad_ids(ids), replicated(mesh))
            return _Read(n, finish, enqueue=lambda: _batch_roots_fn(mesh)(
                table, placed))
        if self.prefer_host:
            roots = _host_batch_roots(table, ids)
            return _Read(n, finish, out=(
                roots if sizes is None else (roots, sizes[roots])))
        placed = jnp.asarray(_pad_ids(ids))

        def enqueue():
            out = _batch_roots(jnp.asarray(table), placed)
            if sizes is not None:
                out = (out, _gather(jnp.asarray(sizes), out))
            return out

        return _Read(n, finish, enqueue=enqueue)

    # -- per-class batch kernels --------------------------------------- #
    # Every class's device read comes in two halves (:class:`_Read`): a
    # ``_<method>_read`` does the host half, the class's own method runs
    # both for a batch of its own, and ``answer_batch`` takes a sweep's
    # several reads through them together (``READS``).
    def connected(
        self, snap: PublishedSnapshot, us: np.ndarray, vs: np.ndarray
    ) -> np.ndarray:
        """bool[n]: same component per (u, v) pair, one batched chase for
        all 2n endpoints."""
        return self._run(self._connected_read(snap, us, vs))

    def _connected_read(self, snap: PublishedSnapshot, us, vs) -> _Read:
        return self._chase_read(
            snap, np.concatenate([np.asarray(us), np.asarray(vs)]),
            lambda valid, roots: self._same_root(us, vs, valid, roots))

    def _chase_read(self, snap: PublishedSnapshot, raw: np.ndarray,
                    finish, sizes: bool = False) -> _Read:
        """The chase of the raw ids, ``finish(valid, roots)`` of it, or
        ``finish(valid, roots, sizes[roots])`` over the snapshot's own
        size table: ONE lookup (the batched native lookup takes the
        encoder mutex once per call, so a call per endpoint column
        would double lock contention with the ingest thread) and ONE
        chase for them all. An id the dict cannot decode chases from 0
        and is not valid."""
        canon = self._table(snap, "labels")
        cv = _lookup_batch(snap.payload["vdict"], raw)
        valid = (cv >= 0) & (cv < int(canon.shape[0]))
        safe = np.where(valid, cv, 0)
        if not sizes:
            return self._roots_read(
                canon, safe, lambda roots: finish(valid, roots))
        return self._roots_read(
            canon, safe, lambda got: finish(valid, *got),
            self._table(snap, "sizes"))

    @staticmethod
    def _same_root(us, vs, valid, roots) -> np.ndarray:
        n = len(us)
        ok = valid[:n] & valid[n:2 * n]
        # an unseen vertex is its own singleton: connected only to itself
        return np.where(ok, roots[:n] == roots[n:2 * n],
                        np.asarray(us) == np.asarray(vs))

    def connected_and_sizes(
        self, snap: PublishedSnapshot, us: np.ndarray, vs: np.ndarray,
        ws: np.ndarray,
    ) -> Tuple[np.ndarray, np.ndarray]:
        """``(bool[n], int[m])``: same component per (u, v) pair and the
        component size of every ``w``, over a snapshot that holds
        ``sizes``: ONE chase for all ``2n + m`` ids, the size lanes'
        gather behind it, one wait (span ``serving.size_lookup``)."""
        with self._size_lookup_span(us, ws):
            return self._run(
                self._connected_and_sizes_read(snap, us, vs, ws))

    @staticmethod
    def _size_lookup_span(us, ws):
        return _trace.span(
            "serving.size_lookup",
            {"n": len(ws), "ids": 2 * len(us) + len(ws)}
            if _trace.on() else None,
        )

    def _connected_and_sizes_read(self, snap: PublishedSnapshot,
                                  us, vs, ws) -> _Read:
        raw = np.concatenate([np.asarray(x, np.int64) for x in (us, vs, ws)])
        m = 2 * len(us)

        def finish(valid, roots, sizes):
            return (self._same_root(us, vs, valid, roots),
                    np.where(valid[m:], sizes[m:], 0).astype(np.int64))

        return self._chase_read(snap, raw, finish, sizes=True)

    def component_size(
        self, snap: PublishedSnapshot, vs: np.ndarray
    ) -> np.ndarray:
        """int[n] component sizes. Over a snapshot that holds ``sizes``:
        one chase and one gather (:meth:`connected_and_sizes`), every
        slot of the table a vertex. Over one without, the size table
        derives once per snapshot version, and sizes count COMPACT ids
        sharing the root — vertices the stream has actually seen (plus
        the queried vertex's own singleton when it is seen but never
        merged)."""
        if "sizes" in snap.payload:
            none = np.zeros(0, np.int64)
            return self.connected_and_sizes(snap, none, none, vs)[1]
        return self._run(self._component_size_read(snap, vs))

    def _component_size_read(self, snap: PublishedSnapshot, vs) -> _Read:
        """The unsized derivation's read: the whole-table size table of
        this snapshot version (made here, in the host half, where it is
        not cached yet), then one gather of the batch's lanes."""
        canon = self._table(snap, "labels", whole="ComponentSizeQuery")
        vdict = snap.payload["vdict"]
        cv = _lookup_batch(vdict, vs)
        key = (snap.epoch, snap.version, id(snap.payload["labels"]))
        cached_key, lab, sizes = self._size_cache
        if cached_key != key:
            if self.prefer_host:
                from ..summaries.forest import resolve_flat_host

                lab = resolve_flat_host(np.asarray(canon))
                sizes = np.bincount(lab, minlength=len(canon))
            else:
                lab, sizes = _component_size_table(jnp.asarray(canon))
            # vcap-sized slots past the seen count are self-rooted
            # singletons; they root themselves, never a seen component,
            # so seen roots count only seen members
            self._size_cache = (key, lab, sizes)
        vcap = int(canon.shape[0])
        valid = (cv >= 0) & (cv < vcap)
        # the cached table is FULLY canonical: every vertex's root is one
        # gather away — no per-batch chase needed here
        safe = np.where(valid, cv, 0)

        def finish(got):
            return np.where(valid, got, 0).astype(np.int64)

        if self.prefer_host:
            return _Read(len(cv), finish,
                         out=np.asarray(sizes)[np.asarray(lab)[safe]])
        placed = jnp.asarray(_pad_ids(safe))
        return _Read(len(cv), finish, enqueue=lambda: _gather_sizes(
            lab, sizes, placed))

    def summary_pull(
        self, snap: PublishedSnapshot, since_version: int = -1
    ) -> dict:
        """The snapshot's CC forest as a mergeable raw-id summary (the
        :class:`SummaryPullQuery` answer value; wire shape in
        :func:`encode_pull_doc`).

        ``since_version < 0`` answers the FULL table — slot coverage is
        what the payload's vertex dict can decode (``len(vdict)``
        slots): the shard's SEEN keyspace. Deployments that want
        untouched in-bound ids to count as singletons (the
        ``IdentityDict`` single-host semantics) observe their bound up
        front, like the serving demos do.

        ``since_version >= 0`` asks for only the rows whose root
        assignment changed since that version. The engine maintains a
        delta CHAIN: per pulled version it diffs the canonical table
        against the previous one over the TouchLog-seen candidate set
        (root changes only ever land on vertices some edge touched) and
        keeps the last :data:`DELTA_RING` segments. A covered
        ``since_version`` answers the deduped union of the covering
        segments (newest root per raw id); an uncovered one degrades
        honestly to a full reply tagged ``why`` (stale ring, no chain,
        or a puller ahead of this store — the restarted-shard case).
        Stale rows across segments stay sound to merge because the
        stream is add-only: a ``(vertex, root)`` pair once true is a
        connectivity fact forever. Docs are cached per
        ``(epoch, version, since)`` — the O(vcap) canonicalize + decode
        runs once however many routers pull.

        A pull against a snapshot BEHIND the chain head (a pinned
        transactional read from the retention ring, ISSUE 20) takes a
        read-only historical path: advancing the chain to an older
        version would CLEAR the ring (the backward-version reset), so
        the live chain is never touched — the historical version is
        served from the covering ring segments when they reach it,
        else from a full canonicalization of that snapshot's own
        payload (the ring retains payloads, so the table is right
        there)."""
        with self._chain_lock:
            key = (snap.epoch, snap.version)
            if (
                self._chain_lab is not None
                and self._chain_epoch == snap.epoch
                and snap.version < self._chain_version
            ):
                return self._historical_pull_locked(
                    snap, int(since_version))
            if self._pull_key != key:
                self._advance_chain_locked(snap)
                self._pull_key = key
                self._pull_docs = {}
            since = int(since_version)
            eff = since if since >= 0 else -1
            cached = self._pull_docs.get(eff)
            if cached is None:
                cached = self._build_pull_doc(snap, eff)
                self._pull_docs[eff] = cached
            return cached

    def chain_sync(self, snap: PublishedSnapshot) -> None:
        """Advance the delta chain to ``snap`` without answering a
        pull — the :class:`PullRingMirror` hook.  Runs on the ingest
        thread (publish listener); idempotent per (epoch, version), so
        a later ``summary_pull`` at the same snapshot reuses the
        already-advanced chain."""
        with self._chain_lock:
            key = (snap.epoch, snap.version)
            if self._pull_key != key:
                self._advance_chain_locked(snap)
                self._pull_key = key
                self._pull_docs = {}

    def _advance_chain_locked(self, snap: PublishedSnapshot) -> None:
        """Canonicalize this snapshot's forest and record the changed
        rows since the previous pulled version as one ring segment.
        Resets the chain (no segment) on a store swap — a new epoch or
        a version that went BACKWARD means the diff base is gone."""
        from ..summaries.forest import resolve_flat_host

        canon = np.asarray(
            self._table(snap, "labels", whole="SummaryPullQuery"))
        vdict = snap.payload["vdict"]
        lab = resolve_flat_host(canon)
        n = min(int(lab.shape[0]), len(vdict))
        if (
            self._chain_lab is None
            or self._chain_epoch != snap.epoch
            or snap.version < self._chain_version
        ):
            self._ring.clear()
        else:
            n_old = self._chain_n
            old = self._chain_lab
            if "tids" in snap.payload:
                # the TouchLog novelty shadow bounds the diff: a root
                # can only change on a vertex some edge ever touched
                cand = np.asarray(
                    snap.payload["tids"][: snap.payload["tcount"]],
                    np.int64,
                )
                cand = cand[cand < n_old]
            else:
                cand = np.arange(n_old, dtype=np.int64)
            changed = cand[lab[cand] != old[cand]]
            if n > n_old:
                changed = np.concatenate(
                    [changed, np.arange(n_old, n, dtype=np.int64)]
                )
            changed = np.unique(changed)
            raws = np.asarray(vdict.decode(changed), np.int64)
            roots = np.asarray(
                vdict.decode(lab[changed].astype(np.int64)), np.int64
            )
            self._ring.append(
                {"base": self._chain_version, "to": snap.version,
                 "u": raws, "r": roots}
            )
        self._chain_epoch = snap.epoch
        self._chain_version = snap.version
        self._chain_lab = np.array(lab, copy=True)
        self._chain_n = n

    def _historical_pull_locked(
        self, snap: PublishedSnapshot, since: int
    ) -> dict:
        """Serve a pull pinned at a version BEHIND the chain head
        without touching the live chain (see :meth:`summary_pull`).
        Delta when the ring's consecutive segments span exactly
        ``(since, snap.version]``; else a full table canonicalized
        from the historical snapshot's own payload, tagged
        ``why="pinned"`` (or ``"ahead"`` for a baseline past the pin).
        Cached per ``(epoch, version, since)`` in a small side cache so
        a transaction's repeated merges cost one canonicalization."""
        eff = since if since >= 0 else -1
        hkey = (snap.epoch, snap.version, eff)
        cached = self._hist_docs.get(hkey)
        if cached is not None:
            return cached
        doc = None
        why = "pinned"
        if eff == snap.version:
            empty = np.zeros(0, np.int64)
            doc = encode_pull_doc(empty, empty, kind="delta", base=eff)
        elif eff > snap.version:
            why = "ahead"
        elif eff >= 0:
            segs = [s for s in self._ring
                    if eff < s["to"] <= snap.version]
            if (segs and segs[0]["base"] <= eff
                    and segs[-1]["to"] == snap.version):
                ru = np.concatenate([s["u"] for s in reversed(segs)])
                rr = np.concatenate([s["r"] for s in reversed(segs)])
                _, idx = np.unique(ru, return_index=True)
                doc = encode_pull_doc(
                    ru[idx], rr[idx], kind="delta", base=eff)
        if doc is None:
            from ..summaries.forest import resolve_flat_host

            # straight off the historical payload — NOT via _table's
            # single-slot host cache, which must stay hot for the head
            canon = np.asarray(snap.payload["labels"])
            vdict = snap.payload["vdict"]
            lab = resolve_flat_host(canon)
            n = min(int(lab.shape[0]), len(vdict))
            slots = np.arange(n, dtype=np.int64)
            raws = np.asarray(vdict.decode(slots), np.int64)
            roots = np.asarray(
                vdict.decode(lab[:n].astype(np.int64)), np.int64)
            doc = encode_pull_doc(raws, roots, kind="full", why=why)
        while len(self._hist_docs) >= 8:
            self._hist_docs.pop(next(iter(self._hist_docs)))
        self._hist_docs[hkey] = doc
        return doc

    def _build_pull_doc(self, snap: PublishedSnapshot, since: int) -> dict:
        vdict = snap.payload["vdict"]
        lab = self._chain_lab
        n = self._chain_n
        why = None
        if since >= 0:
            if since > snap.version:
                why = "ahead"
            elif since == snap.version:
                empty = np.zeros(0, np.int64)
                return encode_pull_doc(
                    empty, empty, kind="delta", base=since
                )
            else:
                segs = [s for s in self._ring if s["to"] > since]
                if segs and segs[0]["base"] <= since:
                    # newest-first concat + unique keeps the NEWEST
                    # root per raw id (unique returns first occurrence)
                    ru = np.concatenate(
                        [s["u"] for s in reversed(segs)])
                    rr = np.concatenate(
                        [s["r"] for s in reversed(segs)])
                    _, idx = np.unique(ru, return_index=True)
                    return encode_pull_doc(
                        ru[idx], rr[idx], kind="delta", base=since
                    )
                why = "stale" if self._ring else "no_chain"
        slots = np.arange(n, dtype=np.int64)
        raws = np.asarray(vdict.decode(slots), np.int64)
        # min-rooted invariant: lab[i] <= i, so every root of the first
        # n slots is itself within the first n slots
        roots = np.asarray(vdict.decode(lab[:n].astype(np.int64)),
                           np.int64)
        return encode_pull_doc(raws, roots, kind="full", why=why)

    # -- delta-ring persistence (ISSUE 19 satellite, PR 17 residual) --- #
    def chain_state(self) -> dict:
        """A picklable copy of the delta chain: the canonical table at
        the last pulled version plus the ring segments.  Empty dict
        before the chain exists.  The copy is what
        :class:`PullRingMirror` persists so a RESTARTED shard can keep
        serving delta pulls instead of always paying one full pull."""
        with self._chain_lock:
            if self._chain_lab is None:
                return {}
            return {
                "version": int(self._chain_version),
                "n": int(self._chain_n),
                "lab": np.array(self._chain_lab, copy=True),
                "ring": [
                    {"base": int(s["base"]), "to": int(s["to"]),
                     "u": np.array(s["u"], copy=True),
                     "r": np.array(s["r"], copy=True)}
                    for s in self._ring
                ],
            }

    def restore_chain(self, state: dict, epoch: int,
                      boot_version: int) -> bool:
        """Adopt a persisted chain after a restart.

        Accepted ONLY when the persisted chain head equals
        ``boot_version`` — the version the restarted store republished
        at boot (snapshot-mirror adoption with the version override).
        Any mismatch means the ring and the served state diverged
        (snapshot newer than the ring, or vice versa) and a delta
        built on it could claim coverage it does not have; the engine
        then keeps its empty chain and the next pull degrades to the
        existing full fallback, counted
        (``serving.pullring_rejected{reason}``)."""
        reason = None
        if not state or "lab" not in state:
            reason = "empty"
        elif int(state.get("version", -2)) != int(boot_version):
            reason = "version"
        if reason is not None:
            get_registry().counter(
                "serving.pullring_rejected", reason=reason).inc()
            return False
        with self._chain_lock:
            self._chain_epoch = int(epoch)
            self._chain_version = int(state["version"])
            self._chain_lab = np.asarray(state["lab"]).copy()
            self._chain_n = int(state["n"])
            self._ring.clear()
            for s in state.get("ring", []):
                self._ring.append(
                    {"base": int(s["base"]), "to": int(s["to"]),
                     "u": np.asarray(s["u"], np.int64),
                     "r": np.asarray(s["r"], np.int64)}
                )
            # the boot snapshot IS the restored chain head: mark it
            # current so the first pull serves from the ring instead
            # of appending a degenerate (V -> V) segment
            self._pull_key = (int(epoch), int(boot_version))
            self._pull_docs = {}
        return True

    def bipartite(self, snap: PublishedSnapshot) -> dict:
        """The :class:`BipartiteQuery` answer value (see its docstring).

        Seen base vertices come from the payload's touch evidence —
        either the append-only log view (``tids``/``tcount``, the
        forest-carry publish shape: the first ``tcount`` entries of an
        append-only log never change, so the published ref is a valid
        snapshot) or a ``touched`` bool table (the dense carry /
        restored-checkpoint shape). Cached per snapshot version: the
        O(vcap) canonicalize + conflict scan runs once however many
        clients ask."""
        bkey = (snap.epoch, snap.version)
        ver, cached = self._bp_cache
        if ver == bkey and cached is not None:
            return cached
        from ..summaries.forest import resolve_flat_host

        cover = np.asarray(self._table(snap, "cover"))
        vdict = snap.payload["vdict"]
        vcap = cover.shape[0] // 2
        lab = resolve_flat_host(cover)
        if "tids" in snap.payload:
            tids = np.asarray(
                snap.payload["tids"][: snap.payload["tcount"]], np.int64
            )
            tids = tids[tids < vcap]
        else:
            touched = np.asarray(snap.payload["touched"])
            tids = np.nonzero(touched[:vcap])[0]
        conflicted = tids[lab[tids] == lab[tids + vcap]]
        if len(conflicted):
            witness = int(
                np.min(np.asarray(vdict.decode(conflicted), np.int64))
            )
            doc = {"bipartite": False, "witness": witness}
        else:
            doc = {"bipartite": True, "witness": None}
        self._bp_cache = (bkey, doc)
        return doc

    def degree(self, snap: PublishedSnapshot, vs: np.ndarray) -> np.ndarray:
        return self._run(self._degree_read(snap, vs))

    def _degree_read(self, snap: PublishedSnapshot, vs) -> _Read:
        return self._table_read(snap, "deg", vs, fill=0)

    def rank(self, snap: PublishedSnapshot, vs: np.ndarray) -> np.ndarray:
        return self._run(self._rank_read(snap, vs))

    def _rank_read(self, snap: PublishedSnapshot, vs) -> _Read:
        return self._table_read(snap, "ranks", vs, fill=0.0)

    def degree_count(
        self, snap: PublishedSnapshot, ds: np.ndarray
    ) -> np.ndarray:
        """int[n]: the histogram's bin of every degree in ``ds`` (no
        vertex dictionary: a degree is its own row)."""
        return self._run(self._degree_count_read(snap, ds))

    def _degree_count_read(self, snap: PublishedSnapshot, ds) -> _Read:
        hist = self._table(snap, "hist")
        ds = np.asarray(ds, np.int64)
        return self._gather_read(
            hist, ds, (ds >= 1) & (ds < int(hist.shape[0])), fill=0)

    def _table_read(
        self, snap: PublishedSnapshot, key: str, vs: np.ndarray, fill
    ) -> _Read:
        table = self._table(snap, key)
        cv = _lookup_batch(snap.payload["vdict"], vs)
        return self._gather_read(
            table, cv, (cv >= 0) & (cv < int(table.shape[0])), fill)

    def _gather_read(self, table, rows: np.ndarray, valid: np.ndarray,
                     fill) -> _Read:
        """``table[rows]`` where ``valid``, ``fill`` elsewhere: one
        batch-sized gather on the path the engine takes."""
        safe = np.where(valid, rows, 0)

        def finish(got):
            return np.where(valid, got, fill)

        if self.prefer_host:
            return _Read(len(rows), finish, out=table[safe])
        placed = jnp.asarray(_pad_ids(safe))
        return _Read(len(rows), finish, enqueue=lambda: _gather(
            jnp.asarray(table), placed))

    # -- heterogeneous batch ------------------------------------------- #
    def answer_batch(
        self,
        snap: PublishedSnapshot,
        queries: Sequence[Query],
        head_window: Optional[int] = None,
    ) -> List[Answer]:
        """Answer a mixed batch: group by query class, one vectorized
        kernel per class present, answers re-ordered to match the input.
        ``head_window`` (default: this snapshot's window) stamps each
        answer's staleness gauge.

        The order of a sweep, all of it over the ONE snapshot ``snap``:
        the cached documents (``SummaryPullQuery``, ``BipartiteQuery``);
        then the host half of every table-reading class (every lookup,
        mask and padding, and the ids' copy to the device: every call
        that hands the interpreter to the ingest thread); then every
        read's kernels, enqueued back to back, microseconds apart, so
        that a fold of the ingest thread's can hardly land between
        them; and only then the fetches. The
        first fetch waits out the folds in flight, the others find
        their result there: a sweep pays for one fold, not one a class.
        ``ConnectedQuery`` and ``ComponentSizeQuery`` over a snapshot
        that holds ``sizes`` read roots of ONE forest and are ONE read
        (one chase, one gather, one wait, under the span
        ``serving.size_lookup``). A sweep of one read is that class's
        own method, which is the same two halves. On the host path a
        read holds its values after the first half and the rest does
        nothing. ``last_sweep`` says how it went."""
        head = snap.window if head_window is None else head_window
        staleness = max(0, head - snap.window)
        groups: Dict[type, List[int]] = {}
        for i, q in enumerate(queries):
            groups.setdefault(type(q), []).append(i)
        for qcls in groups:
            key = self.PAYLOAD_KEYS.get(qcls)
            if key is None or key not in snap.payload:
                raise TypeError(
                    f"snapshot payload (keys {sorted(snap.payload)}) does "
                    f"not serve {qcls.__name__}"
                )
        values: Dict[type, list] = {}
        for qcls in (SummaryPullQuery, BipartiteQuery):
            # cached docs answer the whole group; pulls key the cache
            # per since_version, so mixed baselines in one batch still
            # cost one canonicalization
            if qcls in groups:
                values[qcls] = [
                    self.summary_pull(snap, queries[i].since_version)
                    if qcls is SummaryPullQuery else self.bipartite(snap)
                    for i in groups[qcls]]

        def columns(qcls: type) -> tuple:
            qs = [queries[i] for i in groups.get(qcls, ())]
            return tuple(_column(qs, f) for f in self.READS[qcls][1])

        todo = [c for c in groups if c not in values]
        reads: List[_SweepRead] = []
        if "sizes" in snap.payload and ComponentSizeQuery in todo:
            # with the sweep's pairs, where it has any: the FIRST read,
            # so that its span closes with its own wait
            both = (ConnectedQuery, ComponentSizeQuery)
            us, vs, ws = columns(both[0]) + columns(both[1])
            reads.append(_SweepRead(
                both, "connected_and_sizes", (us, vs, ws),
                lambda: self._size_lookup_span(us, ws)))
            todo = [c for c in todo if c not in both]
        reads += [_SweepRead((c,), self.READS[c][0], columns(c))
                  for c in todo]
        self._waits = []
        try:
            if len(reads) == 1:
                # the class's own method: what stands in its place (a
                # test's planted fault) answers the sweep
                got = [getattr(self, reads[0].method)(snap, *reads[0].cols)]
            else:
                got = self._two_passes(snap, reads) if reads else []
        finally:
            waits, self._waits = self._waits, None
        self.last_sweep = (
            len(waits), sum(w > LATE_READ_S for w in waits[1:]))
        for read, vals in zip(reads, got):
            values.update(zip(read.classes, (
                v.tolist()
                for v in (vals if len(read.classes) > 1 else (vals,)))))
        out: List[Optional[Answer]] = [None] * len(queries)
        boot = getattr(snap, "boot", "")
        for qcls, idxs in groups.items():
            for i, v in zip(idxs, values[qcls]):
                out[i] = Answer(
                    value=v, window=snap.window,
                    watermark=snap.watermark, staleness=staleness,
                    version=snap.version, event_ts=snap.event_ts,
                    boot=boot,
                )
        return out  # type: ignore[return-value]

    def _two_passes(self, snap: PublishedSnapshot,
                    reads: List[_SweepRead]) -> list:
        """The values of each of a sweep's ``reads``: every host half,
        then every dispatch, then every collect. The first read's own
        span (the sized pair's, which is first where it is there) ends
        with the first wait."""
        with reads[0].span():
            halves = [getattr(self, f"_{r.method}_read")(snap, *r.cols)
                      for r in reads]
            for half in halves:
                half.dispatch()
            first = self._collect(halves[0])
        return [first] + [self._collect(half) for half in halves[1:]]


# --------------------------------------------------------------------- #
# Pull-ring persistence (ISSUE 19 satellite): checkpoint the delta
# chain alongside the snapshot mirror so a RESTARTED shard bridges
# routers with a delta pull instead of always paying one full pull.
# --------------------------------------------------------------------- #

PULL_RING_TAG = "pullring.bin"


class PullRingMirror:
    """Snapshot-store listener that keeps an engine's delta chain
    advancing with every publish and persists it next to the snapshot
    mirror (CRC-framed, overwrite — only the newest chain matters).

    ``every`` throttles the O(n) persist the same way
    ``SnapshotMirror(every=...)`` throttles snapshot writes; the chain
    itself advances on EVERY publish (ring segments are per-version,
    skipping one would tear the chain).  A failed persist is counted
    (``serving.swallowed{site=pullring_write}``) and retried on the
    next publish — the in-memory chain is still intact, only restart
    bridging is at stake."""

    def __init__(self, engine: QueryEngine, dirpath: str, *,
                 every: int = 1) -> None:
        self.engine = engine
        self.dirpath = dirpath
        self.every = max(1, int(every))
        self._published = 0

    def __call__(self, snap: PublishedSnapshot) -> None:
        from ..fabric import as_transport

        self.engine.chain_sync(snap)
        self._published += 1
        if self._published % self.every:
            return
        try:
            blob = pickle.dumps(self.engine.chain_state(), protocol=4)
            as_transport(self.dirpath).put_framed(
                PULL_RING_TAG, blob, overwrite=True)
        except Exception:
            get_registry().counter(
                "serving.swallowed", site="pullring_write").inc()


def load_pull_ring(dirpath: str) -> dict:
    """The persisted delta chain from ``dirpath`` (empty dict when
    absent, torn, or undecodable — torn/undecodable are recorded, and
    :meth:`QueryEngine.restore_chain` turns an empty dict into the
    counted full-fallback degrade)."""
    from ..fabric import as_transport
    from ..resilience.integrity import record_rejection

    tr = as_transport(dirpath)
    data = tr.get_framed(PULL_RING_TAG)
    if data is None:
        return {}
    try:
        state = pickle.loads(data)
    except Exception as e:
        record_rejection(tr.describe(PULL_RING_TAG),
                         f"undecodable pull ring: {e!r}")
        return {}
    return state if isinstance(state, dict) else {}
