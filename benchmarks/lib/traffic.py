"""The one general traffic generator: an edge source that hands out
count windows, closed or open loop, and an open-loop query schedule.

Everything a mix fixes is a number in its file under
``benchmarks/traffic/``:

    {"ingest":  {"mode": "closed", "outstanding": 2}
              | {"mode": "open", "edges_per_s": 1.0e6, "max_backlog": 8},
     "queries": {"batch": 256, "period_ms": 100, "recent_windows": 8,
                 "warm_sweeps": [1, 2, 4], "closing_batches": 1},
     "warm_windows": 8,
     "stream_edges_per_s": 3.0e6}

A batch's queries are drawn from the ``recent_windows`` windows handed
out last (a client asks about what it has just written).
``warm_sweeps`` lists, in batches, the sizes of coalesced sweep that
set-up sends once each (the server answers everything pending in one
sweep, and its kernels are jitted per power-of-two bucket).
``stream_edges_per_s`` sizes the pre-generated stream
(``cellrun.stream_length``: the windows that rate would fold in the
run's seconds, the warm-up's and four to spare). It is no rate at which
anything is sent: it is the ceiling of what a run can read, since a
stream that ends inside the window gives no result (exit 2). The rule:
at least twice the fastest ``edges_per_s`` on record in any cell of the
mix (an open loop: above its own ``edges_per_s``, which it cannot
outrun); a closed-loop run that hands out over 60% of its stream logs
a warning that names the file, and a ``benchmark`` PR raises the number
then. A longer stream is the shorter one and a tail (the generator keys
every chunk by its index), so raising it changes no window that was
timed before. Generating the stream is the benchmark's own work: its seconds
are given as ``stream_s`` and are no part of ``setup_s``.
``closing_batches`` batches are sent once the measured window has
closed and the generator's closing windows (below) are ready; their
answers are compared and not timed.
Times are ``time.perf_counter()`` seconds throughout.
"""

from __future__ import annotations

import threading
import time

import numpy as np


class WindowSource:
    """Hands the stream out as whole windows through ``iter_chunks()``
    (the column-chunk protocol ``SimpleEdgeStream`` takes), and keeps
    the generator's side of every window's record.

    Warm-up and a closed-loop mix hand window ``n + outstanding`` out
    only once window ``n`` is ready on the device (``mark_ready``): a
    bounded prefetch, as a consumer of a log has. An open-loop mix,
    from ``start_measuring`` on, hands each window out when its last
    edge is due, however far behind the system is, up to
    ``max_backlog`` windows handed out and not ready (a log's consumer
    reads no further ahead; every window run ahead holds a table on the
    device, and a host that stalled for 3.4 s left 14 of them there,
    14 GiB of 16: PERF.md, section 6). A window held back keeps its due
    time, so the wait counts in its latency.

    ``closing`` is a second pair of columns: whole windows that
    ``finish()`` hands out, closed loop, once the measured window has
    closed, wherever in the stream that was. They are compared and
    never timed.
    """

    def __init__(self, src, dst, window_edges: int, ingest: dict,
                 closing=None):
        self.src, self.dst = src, dst
        self.window_edges = int(window_edges)
        self.n_windows = len(src) // self.window_edges
        self.closing = closing
        self.n_closing = (0 if closing is None
                          else len(closing[0]) // self.window_edges)
        self.n_main = None              # stream windows handed out, once
        #                                 finish() has ended them
        self.mode = ingest["mode"]
        if self.mode not in ("closed", "open"):
            raise ValueError(f"ingest mode {self.mode!r}")
        self.outstanding = int(ingest.get("outstanding", 2))
        self.max_backlog = int(ingest.get("max_backlog", 0))   # 0: none
        self.period = (
            self.window_edges / float(ingest["edges_per_s"])
            if self.mode == "open" else None
        )
        self.handed = 0                 # windows handed out so far
        self.ready = 0                  # windows ready on the device
        self.max_outstanding = 0
        self.handed_t: list = []        # when each window was handed out
        self.due_t: list = []           # when its last edge was due
        self.gate_wait_s = 0.0          # time iter_chunks spent waiting
        self.exhausted = False
        self._cond = threading.Condition()
        self._stop = False
        self._finishing = False
        self._granted = 0               # windows the gate has let through
        self._hold = False              # quiesced: nothing is handed out
        self._t0 = None                 # start of the measured window
        self._first_paced = None

    # -- the benchmark's side ------------------------------------------ #
    def mark_ready(self, n_ready: int) -> None:
        with self._cond:
            self.ready = max(self.ready, n_ready)
            self._cond.notify_all()

    def start_measuring(self, t0: float) -> None:
        with self._cond:
            self._t0 = t0
            self._hold = False
            self._first_paced = self.handed
            self._cond.notify_all()

    def stop(self) -> None:
        with self._cond:
            self._stop = True
            self._cond.notify_all()

    def finish(self) -> None:
        """The measured window has closed: no more of the stream; the
        closing windows follow, closed loop, and then the end."""
        with self._cond:
            self._finishing = True
            self._cond.notify_all()

    def quiesce(self, timeout: float) -> bool:
        """Hand nothing more out until ``start_measuring``, and wait
        until every window handed out is ready."""
        with self._cond:
            self._hold = True
            granted = self._granted
        return self.wait_ready(granted, timeout)

    def wait_ready(self, n: int, timeout: float) -> bool:
        deadline = time.perf_counter() + timeout
        with self._cond:
            while self.ready < n:
                left = deadline - time.perf_counter()
                if left <= 0:
                    return False
                self._cond.wait(left)
        return True

    # -- the system's side: called on its ingest thread ---------------- #
    def _gate(self, closing: bool = False) -> bool:
        """Block until the next window may be handed out; False at the
        end of the run (or, for a window of the stream, once
        ``finish()`` was called). Returns with the window's due time
        appended."""
        k = self.handed
        t_in = time.perf_counter()
        with self._cond:
            while not (self._stop or (self._finishing and not closing)):
                paced = (self.mode == "open" and self._t0 is not None
                         and not closing)
                if paced:
                    due = self._t0 + (k - self._first_paced + 1) * self.period
                    left = due - time.perf_counter()
                    full = (self.max_backlog
                            and k - self.ready >= self.max_backlog)
                    if left <= 0 and not full:
                        break
                    self._cond.wait(left if left > 0 else 0.5)
                elif not self._hold and k - self.ready < self.outstanding:
                    due = None
                    break
                else:
                    self._cond.wait(0.5)
            if self._stop or (self._finishing and not closing):
                return False
            self._granted = k + 1      # under the lock: quiesce reads it
        now = time.perf_counter()
        self.gate_wait_s += now - t_in
        self.due_t.append(now if due is None else due)
        self.handed_t.append(now)
        return True

    def iter_chunks(self):
        while self.handed < self.n_windows:
            if not self._gate():
                break
            yield self._hand_out()
        else:
            self.exhausted = True
        self.n_main = self.handed
        for _ in range(self.n_closing):
            if not self._gate(closing=True):
                return
            yield self._hand_out()

    def _hand_out(self):
        k = self.handed
        self.handed = k + 1
        self.max_outstanding = max(self.max_outstanding,
                                   self.handed - self.ready)
        return self.window(k)

    def window(self, k: int):
        """Columns of window ``k`` as it was handed out: of the stream,
        or, past its last, of the closing windows."""
        w = self.window_edges
        src, dst = self.src, self.dst
        if self.n_main is not None and k >= self.n_main:
            k -= self.n_main
            src, dst = self.closing
        return src[k * w:(k + 1) * w], dst[k * w:(k + 1) * w]

    def __iter__(self):  # the record protocol is not the benchmark's path
        raise TypeError("WindowSource is consumed through iter_chunks()")

    def recent(self, n_windows: int):
        """Columns of the last ``n_windows`` windows handed out."""
        if self.n_main is not None and self.handed > self.n_main:
            cols = [self.window(k) for k in range(
                max(0, self.handed - n_windows), self.handed)]
            return (np.concatenate([c[0] for c in cols]),
                    np.concatenate([c[1] for c in cols]))
        hi = max(1, self.handed) * self.window_edges
        lo = max(0, hi - n_windows * self.window_edges)
        return self.src[lo:hi], self.dst[lo:hi]


def query_schedule(t0: float, seconds: float, period_s: float) -> np.ndarray:
    """Due times of the open-loop query batches: ``t0 + i * period`` for
    every ``i`` whose due time lies inside the window."""
    n = int(np.ceil(seconds / period_s - 1e-9))
    return t0 + period_s * np.arange(n)


class QueryLoad(threading.Thread):
    """The open-loop client: one thread sends batch ``i`` at its due
    time whether or not earlier batches have been answered, stamps each
    answer as it arrives, and never waits for one. Latency is taken
    from the DUE time, so a stall is charged to every batch it delays."""

    def __init__(self, submit, draw, due: np.ndarray, head_fn):
        super().__init__(name="bench-query-load", daemon=True)
        self._submit, self._draw, self._head = submit, draw, head_fn
        self.due = due
        self.sent = np.full(len(due), np.nan)
        self.head_at_submit = np.full(len(due), -1, np.int64)
        self.records: list = [None] * len(due)   # per batch: query rows
        self.answers: list = [None] * len(due)   # per batch: Answer|exc
        self.done_t: list = [None] * len(due)    # per batch: arrival times
        self.rejected: list = []                 # (batch, repr(exc))
        self._halt = threading.Event()

    def halt(self) -> None:
        self._halt.set()

    def run(self) -> None:
        for i, due in enumerate(self.due):
            wait = due - time.perf_counter()
            if wait > 0 and self._halt.wait(wait):
                return
            if self._halt.is_set():
                return
            queries, rows = self._draw()
            n = len(queries)
            done = np.full(n, np.nan)
            got = [None] * n
            self.records[i], self.done_t[i], self.answers[i] = rows, done, got
            self.head_at_submit[i] = self._head()
            self.sent[i] = time.perf_counter()
            try:
                futures = self._submit(queries)
            except Exception as e:  # Overloaded, closed: the batch failed
                self.rejected.append((i, repr(e)))
                self.done_t[i] = self.answers[i] = None
                continue
            for j, f in enumerate(futures):
                f.add_done_callback(_stamp(done, got, j))

    def wait_answers(self, timeout: float) -> None:
        """Wait (bounded) until every admitted query has its answer."""
        deadline = time.perf_counter() + timeout
        for done in self.done_t:
            if done is None:
                continue
            while np.isnan(done).any() and time.perf_counter() < deadline:
                time.sleep(0.01)


def _stamp(done, got, j):
    def cb(f):
        done[j] = time.perf_counter()
        try:
            got[j] = f.result()
        except Exception as e:
            got[j] = e
    return cb
