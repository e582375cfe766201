"""Device-resident vertex dictionary: the keyBy ON the accelerator.

Reference analog: the raw-id keyed state behind every ``keyBy(vertex)``
(``SimpleEdgeStream.java:119,303,537``; ``summaries/DisjointSet.java:30``
keys HashMaps by raw ``Long`` directly). The TPU form needs dense compact
ids; this module produces them without host hashing.

The host ``VertexDict`` (C++ hash map) costs ~20 ns per id on the single
host core — at corpus scale that is the end-to-end ceiling (ROADMAP #1).
This module keeps the raw-id -> compact-id mapping AS DEVICE STATE and
encodes whole windows in one compiled step, so the host's only ingest work
is handing raw columns to the device (memmap slice + put on the binary
path).

Design — sort-based, not hash-probe-based: an open-addressing table needs
data-dependent probe ROUNDS (a ``while_loop`` whose trip count is the
longest chain — the tail serializes the whole batch), which measured ~100x
slower than the host dict. The TPU-native shape is static:

- State: ``keys[Kcap]`` sorted ascending (+INT32_MAX padding) with aligned
  ``idx[Kcap]``, reverse table ``rev[Kcap]``, and the assigned count.
- Per batch (one jitted dispatch): binary-search every id against the
  sorted table (known ids resolve immediately); sort the unknown ids with
  their arrival positions (two-key ``lax.sort``) so each novel key is one
  run whose head is its FIRST arrival; rank run heads by arrival
  (argsort + scatter) to assign ``count + rank`` — bit-identical to the
  sequential first-seen host dict; propagate ids down runs with
  ``cummax``; merge the novel keys into the table by concat + sort.
  Everything is fixed-shape vector work: O((K + B) log(K + B)) with no
  data-dependent control flow.
- Growth: padding a sorted table is appending +INT32_MAX — the host just
  re-pads to the next capacity bucket (no rehash at all).

Raw ids must be non-negative int32 below INT32_MAX (the framework-wide
raw-table contract; ``VertexDict`` remains the general path for 64-bit id
spaces).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.edgeblock import bucket_capacity

_BIG = jnp.iinfo(jnp.int32).max


def init_table(cap: int):
    """Fresh device dictionary state (``cap`` keys capacity).

    ``probe`` is the sticky overflow telltale read by the speculative
    growth-mode ingest: ``count`` while every batch so far fit the table,
    ``-(count)-1`` forever after the first one that did not (its
    ``state``/outputs are then poisoned and must be replayed). It lives
    INSIDE the state dict on purpose: an extra scalar state field is free,
    where a separate executable output is one more buffer to hand back
    per window (whether that still costs anything on the chip is not
    measured).
    """
    return {
        "keys": jnp.full(cap, _BIG, jnp.int32),  # sorted ascending
        "idx": jnp.zeros(cap, jnp.int32),
        "rev": jnp.full(cap, -1, jnp.int32),
        "count": jnp.int32(0),
        "probe": jnp.int32(0),
    }


@jax.jit
def encode_pair_batch(state, src, dst):
    """Edge-column encode as ONE executable: interleave, encode, split.

    The unfused form (host-side ``stack``/``reshape``/column slicing
    around :func:`encode_batch`) costs ~4 extra dispatches per window,
    each with its own launch overhead on the ingest path."""
    n = src.shape[0]
    raw = jnp.stack([src, dst], axis=1).reshape(-1)
    state, out = encode_batch(state, raw)
    pair = out.reshape(n, 2)
    return state, pair[:, 0], pair[:, 1]


@jax.jit
def encode_batch(state, raw):
    """Map a batch of raw ids (arrival order) to compact ids, inserting
    novel ids first-seen-first. Returns ``(state, out_idx)``.

    The caller guarantees capacity: ``count + batch-unique-count`` must
    fit ``keys.shape[0]`` (the host grows by bucket beforehand).
    """
    keys, idxv, rev, count = (
        state["keys"], state["idx"], state["rev"], state["count"],
    )
    kcap = keys.shape[0]
    n = raw.shape[0]
    arange = jnp.arange(n, dtype=jnp.int32)

    # 1. resolve known ids by binary search
    pos = jnp.clip(jnp.searchsorted(keys, raw), 0, kcap - 1)
    found = keys[pos] == raw
    out = jnp.where(found, idxv[pos], -1)

    # 2. group unknown ids into runs ordered by (key, arrival)
    nr = jnp.where(found, _BIG, raw)
    sk, sa = jax.lax.sort((nr, arange), num_keys=2)
    real = sk != _BIG
    first = real & jnp.concatenate(
        [jnp.ones(1, bool), sk[1:] != sk[:-1]]
    )
    # 3. run heads get ids by global first-arrival order. Sort-based rank
    # (argsort of the argsort) instead of an inverse-permutation scatter:
    # this runtime degrades badly on large random scatters, while its sort
    # path measures at memory-bound rates (triangle kernels).
    head_arrival = jnp.where(first, sa, _BIG)
    order = jnp.argsort(head_arrival)
    rank = jnp.argsort(order).astype(jnp.int32)
    head_id = count + rank  # valid where `first`
    # 4. propagate each run's id to all members via the run-head POSITION
    # (cummax over positions is monotone, so it cannot leak across runs
    # the way cummax over ids would), then map back to arrival slots with
    # one more inverse-permutation argsort — again, no scatter.
    head_pos = jax.lax.cummax(jnp.where(first, arange, -1))
    ids_sorted = head_id[jnp.clip(head_pos, 0, n - 1)]
    inv_sa = jnp.argsort(sa)
    arrival_vals = jnp.where(real, ids_sorted, -1)[inv_sa]
    out = jnp.maximum(out, arrival_vals)
    n_new = first.sum().astype(jnp.int32)

    # 5. merge the novel (key, id) pairs into the sorted table
    nk = jnp.where(first, sk, _BIG)
    nv = jnp.where(first, ids_sorted, 0)
    mk, mv = jax.lax.sort(
        (jnp.concatenate([keys, nk]), jnp.concatenate([idxv, nv])),
        num_keys=1,
    )
    new_count = count + n_new
    still_ok = (state["probe"] >= 0) & (new_count <= kcap)
    new_state = {
        "keys": mk[:kcap],
        "idx": mv[:kcap],
        "rev": rev.at[jnp.where(first, head_id, kcap)].set(sk, mode="drop"),
        "count": new_count,
        "probe": jnp.where(still_ok, new_count, -new_count - 1),
    }
    return new_state, out


class DeviceVertexDict:
    """VertexDict-compatible facade over the device sorted table.

    ``encode_pair`` runs ON DEVICE and returns device index arrays (unlike
    the host dict's numpy): the device-encode ingest path feeds them
    straight into EdgeBlocks with zero host hash work. ``decode``/
    ``__len__`` sync lazily (emission-time only).
    """

    def __init__(self, min_capacity: int = 1 << 10, id_bound: int = 0):
        """``id_bound``: when the raw id space is known to be < bound, the
        table allocates for it once and NEVER grows or syncs — growth
        decisions otherwise need a pessimistic fill bound whose per-window
        count sync stalls the device pipeline (~100ms+ through a remote
        runtime)."""
        self.id_bound = int(id_bound)
        cap = bucket_capacity(max(min_capacity, self.id_bound, 16))
        self._state = init_table(cap)
        self._synced_count = 0  # host-known lower bound (lazy)
        self._pending = 0  # ids encoded since the last count sync

    # ------------------------------------------------------------------ #
    @property
    def capacity(self) -> int:
        return int(self._state["keys"].shape[0])

    def __len__(self) -> int:
        self._sync()
        return self._synced_count

    def _sync(self) -> None:
        probe = int(self._state["probe"])
        if probe < 0:
            raise RuntimeError(
                "device dictionary overflowed its table — the host-side "
                "novelty bound failed to grow it in time (bug); compact "
                "ids since the overflow are unreliable"
            )
        self._synced_count = probe
        self._pending = 0

    def _ensure(self, incoming: int) -> None:
        """Grow (by re-padding — the table is sorted, growth is appending
        +INT32_MAX) so the worst case ``count + incoming`` fits."""
        if self.id_bound:  # capacity covers the whole id space: no-op
            return
        ub = self._synced_count + self._pending + incoming
        cap = self.capacity
        if ub <= cap:
            return
        self._sync()  # one round trip, only near a growth boundary
        need = self._synced_count + incoming
        if need <= cap:
            return
        self._repad(bucket_capacity(need))

    def _repad(self, new_cap: int) -> None:
        """Growth is appending +INT32_MAX padding to the sorted table."""
        grow = new_cap - self.capacity
        if grow <= 0:
            return
        self._state = {
            "keys": jnp.concatenate(
                [self._state["keys"], jnp.full(grow, _BIG, jnp.int32)]
            ),
            "idx": jnp.concatenate(
                [self._state["idx"], jnp.zeros(grow, jnp.int32)]
            ),
            "rev": jnp.concatenate(
                [self._state["rev"], jnp.full(grow, -1, jnp.int32)]
            ),
            "count": self._state["count"],
            "probe": self._state["probe"],
        }

    # ------------------------------------------------------------------ #
    def _validate(self, *arrays) -> None:
        """With ``id_bound`` set, out-of-range raw ids would silently
        corrupt the fixed-capacity table (the merge truncates) — reject
        them like ``IdentityDict.encode`` does. Host arrays only; device
        arrays are produced by our own ingest paths from validated or
        host-checked sources."""
        if not self.id_bound:
            return
        for a in arrays:
            if isinstance(a, np.ndarray) and a.size and (
                int(a.min()) < 0 or int(a.max()) >= self.id_bound
            ):
                raise ValueError(
                    f"raw id outside [0, {self.id_bound}) — not a dense-id "
                    "corpus; drop id_bound (growth mode) or use VertexDict"
                )

    # ------------------------------------------------------------------ #
    # Growth-mode encode driven by host-side novelty tracking (round 3)
    # ------------------------------------------------------------------ #
    # The general arbitrary-id ingest keeps an EXACT host-side upper
    # bound on the table count (``native.NoveltyBitmap`` over the raw id
    # stream — first-seen distinctness is the same quantity the device
    # table counts) and calls :meth:`ensure_capacity_host` before each
    # window. Growth is pure padding, so the whole pipeline runs with
    # ZERO device->host reads; the sticky ``probe`` state field is a
    # defense-in-depth telltale asserted at the next natural sync.

    def ensure_capacity_host(self, count_bound: int) -> None:
        """Grow (no sync — pure padding) so ``count_bound`` entries fit."""
        if count_bound > self.capacity:
            self._repad(bucket_capacity(max(count_bound, 2 * self.capacity)))

    def encode_pair_spec(self, src, dst):
        """Growth-mode device encode: one dispatch, NO host sync, no
        validation. The caller guarantees capacity via
        :meth:`ensure_capacity_host` (host novelty tracking)."""
        src = jnp.asarray(src, jnp.int32)
        dst = jnp.asarray(dst, jnp.int32)
        self._state, si, di = encode_pair_batch(self._state, src, dst)
        self._pending += 2 * int(src.shape[0])
        return si, di

    def encode_pair(self, src, dst) -> Tuple[jax.Array, jax.Array]:
        """Device-encode edge columns in arrival order (src before dst per
        edge). Accepts numpy or device int32 arrays; returns device index
        columns."""
        self._validate(np.asarray(src) if isinstance(src, np.ndarray) else src,
                       np.asarray(dst) if isinstance(dst, np.ndarray) else dst)
        src = jnp.asarray(src, jnp.int32)
        dst = jnp.asarray(dst, jnp.int32)
        n = src.shape[0]
        self._ensure(2 * n)
        self._state, si, di = encode_pair_batch(self._state, src, dst)
        self._pending += 2 * n
        return si, di

    def encode(self, raw) -> np.ndarray:
        host = np.asarray(raw, np.int64).ravel()
        self._validate(host)
        arr = jnp.asarray(host, jnp.int32)
        self._ensure(int(arr.shape[0]))
        self._state, out = encode_batch(self._state, arr)
        self._pending += int(arr.shape[0])
        return np.asarray(out)

    def _rev_array(self) -> np.ndarray:
        """Host copy of the reverse table, cached by synced count (a full
        download per decode would move the whole table every emission)."""
        self._sync()
        cached = getattr(self, "_rev_cache", None)
        if cached is not None and cached[0] == self._synced_count:
            return cached[1]
        rev = np.asarray(self._state["rev"])
        self._rev_cache = (self._synced_count, rev)
        return rev

    def decode(self, idx) -> np.ndarray:
        return self._rev_array()[np.asarray(idx, np.int64)].astype(np.int64)

    def decode_one(self, idx: int) -> int:
        return int(self.decode(np.asarray([idx]))[0])

    def lookup(self, raw: int):
        """Query without inserting (host binary search — emission/API
        path, not the ingest hot path)."""
        keys = np.asarray(self._state["keys"])
        pos = int(np.searchsorted(keys, np.int32(raw)))
        if pos < keys.shape[0] and keys[pos] == int(raw):
            return int(np.asarray(self._state["idx"])[pos])
        return None

    def raw_ids(self) -> np.ndarray:
        n = len(self)
        return np.asarray(self._state["rev"][:n]).astype(np.int64)

    def raw_table(self) -> jax.Array:
        return jnp.where(self._state["rev"] == -1, 0, self._state["rev"])
