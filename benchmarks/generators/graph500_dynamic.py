"""Graph500 Kronecker edges as a stream of additions AND deletions.

A configuration names this module (``"generator": "graph500_dynamic"``)
and gives it the ``graph500`` block of ``generators/graph500.py`` (whose
Kronecker chunks, scramble and per-seed ordering are used as they are,
by import) and an ``events`` block that says what a window holds:

    {"additions": 49152, "deletions_of_added": 15360,
     "deletions_never_added": 1024, "lag_windows": 8,
     "closing_lag_windows": 4}

Window ``k`` is, before the seed orders it: ``additions`` fresh edges of
the Kronecker stream; ``deletions_of_added`` deletions of the FIRST so
many additions of window ``k - lag_windows`` (in the first
``lag_windows`` windows there is nothing to take back yet, and these
places hold fresh additions too); ``deletions_never_added`` deletions
of fresh edges that no window added (upstream checks no edge's
existence: they decrement what is there and meet the clamp at zero
where nothing is). Window ``k`` depends on ``k`` and the graph alone, so
a longer stream is the shorter one and a tail, and every ``--seed``
folds the same windows: the seed draws one permutation of a window's
places (``graph500.order_by_seed``), which interleaves additions and
deletions. A deletion still comes ``lag_windows - 1`` whole windows or
more after its addition, whatever the order.

The harness carries two int32 columns and no third, so the event's sign
rides in the first: a deletion has bit 30 of its ``src`` set (ids are
under 2^29). :func:`unpack` is the one decoder: the algorithm module's
stream, reference and queries all go through it.
"""

from __future__ import annotations

import numpy as np

from . import graph500

DELETE_BIT = 1 << 30
MAX_SCALE = 29  # ids and the bit above them have to fit an int32


def unpack(src, dst):
    """``(src, dst, sign)`` of packed columns: the ids, and +1 for an
    addition, -1 for a deletion, int32."""
    src = np.asarray(src)
    deleted = (src & DELETE_BIT) != 0
    sign = np.where(deleted, -1, 1).astype(np.int32)
    return src & (DELETE_BIT - 1), np.asarray(dst), sign


def _make_up(config: dict) -> tuple:
    ev = config["events"]
    a, d, f = (int(ev[k]) for k in (
        "additions", "deletions_of_added", "deletions_never_added"))
    w = int(config["window_edges"])
    if a + d + f != w or d > a:
        raise ValueError(f"a window of {w} events cannot hold {a} additions, "
                         f"{d} deletions of added and {f} of unadded edges")
    if int(config["scale"]) > MAX_SCALE:
        raise ValueError(f"scale {config['scale']} leaves no bit for the sign")
    return w, a, d, f


def fresh_edges_needed(n_windows: int, lag: int, w: int, a: int, f: int):
    head = min(lag, n_windows)
    return head * w + (n_windows - head) * (a + f)


def lay_out(pool_src, pool_dst, n_windows: int, lag: int, make_up: tuple):
    """``n_windows`` windows of packed events from a pool of fresh
    Kronecker edges, as ``[n_windows, w]`` arrays: places ``[:a]`` the
    additions, ``[a:a + d]`` the deletions of window ``k - lag``'s first
    ``d`` additions, ``[a + d:]`` the deletions of unadded edges."""
    w, a, d, f = make_up
    head = min(lag, n_windows)
    rest = n_windows - head
    src = np.empty((n_windows, w), np.int32)
    dst = np.empty((n_windows, w), np.int32)
    n0 = head * w
    src[:head] = pool_src[:n0].reshape(head, w)
    dst[:head] = pool_dst[:n0].reshape(head, w)
    if rest:
        n1 = n0 + rest * (a + f)
        body_s = pool_src[n0:n1].reshape(rest, a + f)
        body_d = pool_dst[n0:n1].reshape(rest, a + f)
        src[head:, :a], dst[head:, :a] = body_s[:, :a], body_d[:, :a]
        src[head:, a + d:], dst[head:, a + d:] = body_s[:, a:], body_d[:, a:]
        # the first d places of every window are additions (d <= a)
        src[head:, a:a + d] = src[:rest, :d] | DELETE_BIT
        dst[head:, a:a + d] = dst[:rest, :d]
    src[:, a + d:] |= DELETE_BIT
    return src, dst


def _stream(config: dict, graph_seed: int, n_windows: int, lag: int,
            order_seed: int):
    make_up = _make_up(config)
    w, a, _d, f = make_up
    pool = graph500.kronecker_edges(
        graph_seed, int(config["scale"]),
        fresh_edges_needed(n_windows, lag, w, a, f),
        **graph500._kron_args(config))
    if int(max(pool[0].max(), pool[1].max())) >> int(config["scale"]):
        raise ValueError("generated ids pass 2^scale")
    src, dst = lay_out(*pool, n_windows, lag, make_up)
    del pool
    # graph500.order_by_seed's permutation of a window's places, read
    # off one window of place numbers and applied to all of them by one
    # ``take`` along the rows (its own fancy index takes four times as
    # long over half a billion events)
    places = np.arange(w, dtype=np.int32)
    perm = graph500.order_by_seed(places, places, w, order_seed)[0]
    return (np.take(src, perm, axis=1).reshape(-1),
            np.take(dst, perm, axis=1).reshape(-1))


def edges(config: dict, n_edges: int, seed: int, warm_edges: int):
    """The run's stream: host int32 ``(src, dst)`` columns of
    ``n_edges`` packed events (whole windows), the configuration's
    graph (``graph_seed``) with every window's places ordered by the
    seed."""
    w = int(config["window_edges"])
    if n_edges % w:
        raise ValueError(f"{n_edges} events are not whole windows of {w}")
    return _stream(config, int(config["graph500"]["graph_seed"]),
                   n_edges // w, int(config["events"]["lag_windows"]), seed)


def closing_edges(config: dict, seed: int):
    """``seeded_closing_windows`` windows of the same make-up over a
    Kronecker graph OF THE SEED'S OWN (``graph500.closing_edges``'s
    reasons), handed out once the measured window has closed. They are
    a stream of their own: a deletion of an added edge names an edge
    that closing window ``k - closing_lag_windows`` added."""
    n = int(config["graph500"].get("seeded_closing_windows", 0))
    if not n:
        return None
    return _stream(config, int(seed), n,
                   int(config["events"]["closing_lag_windows"]), seed)
