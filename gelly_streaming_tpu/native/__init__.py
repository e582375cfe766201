"""Native host-runtime components (C++ via ctypes, no pybind11).

The compute path is JAX/XLA; the runtime AROUND it is native where it
matters. Today that is file ingest (``ingest.cpp``): parsing large edge
lists in Python is ~50x slower than the device consumes them. Reference
analog: Flink's parallel text sources + per-line split mappers
(``env.readTextFile``, ``ConnectedComponentsExample.java:106-118``) — the
reference itself is 100% Java with no native code (SURVEY.md §2), so this
layer replaces the JVM runtime, not a C++ one.

The shared library builds lazily on first use with ``g++ -O3`` and is
cached next to the source; every entry point has a pure-numpy twin so
the package works without a toolchain. A failed build is remembered with
the compiler's message (:func:`build_error`), so a caller that must not
run on the numpy twins — the chip smoke, a benchmark — can refuse to.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import Iterator, Optional, Tuple

import numpy as np

_HERE = os.path.dirname(__file__)
_SRC = os.path.join(_HERE, "ingest.cpp")
_SO = os.path.join(_HERE, "_ingest.so")
_lock = threading.Lock()
_lib = None
#: why the library is unavailable (compiler or loader message); None
#: while no build has failed
_lib_error: Optional[str] = None


def _host_isa() -> str:
    """Fingerprint of the host ISA the cached .so must match.

    The build uses ``-march=native``, so a cached binary is only valid on
    a host with the same CPU feature set — reusing an AVX-512-specialized
    .so on a host without AVX-512 dies with SIGILL, which no exception
    handler can catch. A checkout can move between machines (NFS, docker
    bake), so the sidecar carries this fingerprint too."""
    import platform

    flags = ""
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith("flags"):
                    flags = " ".join(sorted(line.split(":", 1)[1].split()))
                    break
    except OSError:
        pass
    return hashlib.sha256(
        (platform.machine() + "|" + flags).encode()
    ).hexdigest()[:16]


def _stale(digest: str) -> bool:
    """The build is stale unless the .so's hash sidecar matches the source
    AND the host ISA.

    Content hash, not mtime: a checkout or copy can leave any mtime order,
    and a binary silently out of sync with its source is worse than a
    rebuild."""
    if not os.path.exists(_SO):
        return True
    try:
        with open(_SO + ".hash") as f:
            return f.read().strip() != digest + ":" + _host_isa()
    except OSError:
        return True


def _build(digest: str) -> None:
    """Compile ``ingest.cpp`` to ``_SO``. The output goes to a per-process
    temporary name and is renamed into place, so two processes that start
    together each finish a whole file (the later rename wins; both are
    the same build). Raises ``RuntimeError`` with the compiler's message."""
    tmp = f"{os.path.splitext(_SO)[0]}.{os.getpid()}.so.tmp"
    # -march=native unlocks the AVX-512 line scanner where the host
    # supports it; fall back to a generic build elsewhere (the source
    # guards all intrinsics with __AVX512BW__)
    base = ["g++", "-O3", "-shared", "-fPIC", "-pthread", "-o", tmp, _SRC]
    try:
        r = subprocess.run(
            base[:1] + ["-march=native"] + base[1:], capture_output=True
        )
        if r.returncode != 0:
            r = subprocess.run(base, capture_output=True)
        if r.returncode != 0:
            raise RuntimeError(
                f"{' '.join(base)} exited {r.returncode}:\n"
                + r.stderr.decode(errors="replace")[-4000:]
            )
        os.replace(tmp, _SO)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    with open(_SO + ".hash", "w") as f:
        f.write(digest + ":" + _host_isa())


def _load() -> Optional[ctypes.CDLL]:
    """Compile (once) and load the ingest library; None if unavailable
    (:func:`build_error` then says why)."""
    global _lib, _lib_error
    if _lib is not None or _lib_error is not None:
        return _lib
    with _lock:
        if _lib is not None or _lib_error is not None:
            return _lib
        try:
            # graftlint: disable=GL009 (one-time double-checked compile-and-load; a thread that needs the library MUST wait for the build — the lock exists to make everyone wait exactly once)
            with open(_SRC, "rb") as f:
                digest = hashlib.sha256(f.read()).hexdigest()
            # graftlint: disable=GL009 (one-time double-checked compile-and-load; a thread that needs the library MUST wait for the build — the lock exists to make everyone wait exactly once)
            if _stale(digest):
                # graftlint: disable=GL009 (one-time double-checked compile-and-load; a thread that needs the library MUST wait for the build — the lock exists to make everyone wait exactly once)
                _build(digest)
            lib = ctypes.CDLL(_SO)
            i64 = ctypes.c_int64
            p64 = np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")
            pf64 = np.ctypeslib.ndpointer(np.float64, flags="C_CONTIGUOUS")
            pi32 = ctypes.POINTER(ctypes.c_int32)
            lib.write_edge_file.restype = i64
            lib.write_edge_file.argtypes = [
                ctypes.c_char_p, p64, p64, i64, ctypes.c_int32,
                ctypes.c_int32,
            ]
            lib.cc_baseline_run.restype = i64
            lib.cc_baseline_run.argtypes = [
                p64, p64, i64, i64, ctypes.c_int32, ctypes.POINTER(i64),
            ]
            lib.flink_proxy_run.restype = i64
            lib.flink_proxy_run.argtypes = [
                p64, p64, i64, i64, ctypes.c_int32, ctypes.POINTER(i64),
            ]
            pi32a = np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS")
            lib.encoder_create.restype = ctypes.c_void_p
            lib.encoder_destroy.argtypes = [ctypes.c_void_p]
            lib.encoder_encode.restype = i64
            lib.encoder_encode.argtypes = [ctypes.c_void_p, p64, i64, pi32a, p64]
            lib.encoder_encode2.restype = i64
            lib.encoder_encode2.argtypes = [
                ctypes.c_void_p, p64, p64, i64, pi32a, pi32a, p64,
            ]
            lib.reader_open.restype = ctypes.c_void_p
            lib.reader_open.argtypes = [ctypes.c_char_p, i64]
            lib.reader_close.argtypes = [ctypes.c_void_p]
            lib.reader_offset.restype = i64
            lib.reader_offset.argtypes = [ctypes.c_void_p]
            lib.reader_next_span.restype = i64
            lib.reader_next_span.argtypes = [
                ctypes.c_void_p, p64, p64, pf64, i64, pi32, pi32,
                ctypes.c_int32,
            ]
            lib.reader_next_encoded.restype = i64
            lib.reader_next_encoded.argtypes = [
                ctypes.c_void_p, ctypes.c_void_p, pi32a, pi32a, pf64, i64,
                p64, ctypes.POINTER(i64), pi32, pi32,
            ]
            lib.reader_next_span_i32.restype = i64
            lib.reader_next_span_i32.argtypes = [
                ctypes.c_void_p, pi32a, pi32a, pf64, i64, i64, pi32, pi32,
                ctypes.POINTER(i64),
            ]
            lib.encoder_lookup.restype = ctypes.c_int32
            lib.encoder_lookup.argtypes = [ctypes.c_void_p, i64]
            lib.encoder_lookup_batch.restype = None
            lib.encoder_lookup_batch.argtypes = [
                ctypes.c_void_p, p64, i64, pi32a,
            ]
            lib.encoder_size.restype = i64
            lib.encoder_size.argtypes = [ctypes.c_void_p]
            lib.vbitmap_create.restype = ctypes.c_void_p
            lib.vbitmap_destroy.argtypes = [ctypes.c_void_p]
            lib.vbitmap_novel2.restype = i64
            lib.vbitmap_novel2.argtypes = [
                ctypes.c_void_p, pi32a, pi32a, i64,
            ]
            lib.cuf_create.restype = ctypes.c_void_p
            lib.cuf_destroy.argtypes = [ctypes.c_void_p]
            lib.cuf_fold_window.restype = i64
            lib.cuf_fold_window.argtypes = [
                ctypes.c_void_p, pi32a, pi32a, i64, i64,
                pi32a, pi32a, pi32a, pi32a, ctypes.POINTER(i64),
            ]
            lib.cuf_fold_group.restype = i64
            lib.cuf_fold_group.argtypes = [
                ctypes.c_void_p, pi32a, pi32a, p64, i64, i64,
                pi32a, pi32a, pi32a, pi32a, p64, p64, pi32a, pi32a,
                p64, ctypes.POINTER(i64),
            ]
            lib.cuf_flatten.argtypes = [ctypes.c_void_p, pi32a, i64]
            lib.cuf_load.restype = i64
            lib.cuf_load.argtypes = [ctypes.c_void_p, pi32a, i64]
            lib.wprep_create.restype = ctypes.c_void_p
            lib.wprep_destroy.argtypes = [ctypes.c_void_p]
            lib.wprep_run.restype = i64
            lib.wprep_run.argtypes = [
                ctypes.c_void_p, pi32a, pi32a, i64, i64, pi32a, pi32a, pi32a,
            ]
            lib.decode_edge_frame.restype = i64
            lib.decode_edge_frame.argtypes = [
                ctypes.c_char_p, i64, i64, ctypes.c_int32, ctypes.c_int32,
                p64, p64, pf64,
            ]
            lib.parse_edge_lines.restype = i64
            lib.parse_edge_lines.argtypes = [
                ctypes.c_char_p, i64, p64, p64, pf64, i64, pi32,
                ctypes.POINTER(i64),
            ]
            _lib = lib
        except Exception as e:
            _lib_error = f"{type(e).__name__}: {e}"
    return _lib


def native_available() -> bool:
    return _load() is not None


def build_error() -> Optional[str]:
    """Why the native library is unavailable — the compiler's (or the
    loader's) message from the one build attempt — or None when it
    loaded or has not been tried yet."""
    return _lib_error


def parse_edge_file(path: str) -> Tuple[np.ndarray, np.ndarray, Optional[np.ndarray]]:
    """Parse a whole edge-list file into (src, dst, val|None) columns.

    Third column (value/timestamp/±flag as ±1.0) is returned when present.
    One span-parse pass (no separate counting pass): chunks concatenate.
    """
    lib = _load()
    if lib is None:
        return _parse_python(path)
    srcs, dsts, vals = [], [], []
    any_val = False
    for s, d, v in iter_edge_chunks(path, chunk_edges=1 << 22):
        srcs.append(s)
        dsts.append(d)
        vals.append(v)
        any_val = any_val or v is not None
    if not srcs:
        return np.zeros(0, np.int64), np.zeros(0, np.int64), None
    src = np.concatenate(srcs)
    dst = np.concatenate(dsts)
    if not any_val:
        return src, dst, None
    val = np.concatenate(
        [np.zeros(len(s), np.float64) if v is None else v
         for s, v in zip(srcs, vals)]
    )
    return src, dst, val


def iter_edge_chunks(
    path: str, chunk_edges: int = 1 << 20, threads: Optional[int] = None
) -> Iterator[Tuple[np.ndarray, np.ndarray, Optional[np.ndarray]]]:
    """Stream (src, dst, val|None) column chunks from a file — the bounded-
    memory ingest path for streams larger than RAM.

    Chunk boundaries are byte-budgeted (``chunk_edges`` times an average
    line-length estimate), so yields carry *approximately* ``chunk_edges``
    edges; exact window discretization is the Windower's job downstream.
    Each span is parsed by ``threads`` workers (default: all cores).
    """
    lib = _load()
    if lib is None:
        src, dst, val = _parse_python(path)
        for a in range(0, len(src), chunk_edges):
            b = a + chunk_edges
            yield src[a:b], dst[a:b], None if val is None else val[a:b]
        return
    if threads is None:
        threads = os.cpu_count() or 1
    budget = min(max(chunk_edges * 20, 4096), 1 << 28)
    cap = budget // 4 + 64
    handle = lib.reader_open(path.encode(), budget)
    if not handle:
        raise IOError(f"cannot read {path}")
    try:
        src = np.empty(cap, np.int64)
        dst = np.empty(cap, np.int64)
        val = np.empty(cap, np.float64)
        has_val = ctypes.c_int32(0)
        at_eof = ctypes.c_int32(0)
        while True:
            prev = lib.reader_offset(handle)
            got = lib.reader_next_span(
                handle, src, dst, val, cap,
                ctypes.byref(has_val), ctypes.byref(at_eof), threads,
            )
            if got < 0:
                raise IOError(f"cannot read {path}")
            if got:
                yield (
                    src[:got].copy(),
                    dst[:got].copy(),
                    val[:got].copy() if has_val.value else None,
                )
            if at_eof.value:
                return
            # got == 0 with more file left is fine as long as the offset
            # moved (a span of comments/blanks); no progress means a single
            # line larger than the byte budget — error, don't drop the rest.
            if got == 0 and lib.reader_offset(handle) == prev:
                raise IOError(
                    f"{path}: line at byte {prev} exceeds the span read "
                    "budget"
                )
    finally:
        lib.reader_close(handle)


def iter_edge_chunks_i32(
    path: str, chunk_edges: int = 1 << 20, id_bound: int = 0
) -> Iterator[Tuple[np.ndarray, np.ndarray, Optional[np.ndarray]]]:
    """Like :func:`iter_edge_chunks` but yields int32 endpoint columns
    directly (dense-id corpora: half the column traffic, no convert or
    validation pass downstream). Raises when any id falls outside
    ``[0, id_bound)`` (or outside int32 when ``id_bound`` is 0)."""
    lib = _load()
    if lib is None:
        for s, d, v in iter_edge_chunks(path, chunk_edges):
            hi = id_bound if id_bound else np.iinfo(np.int32).max
            if len(s) and (
                int(s.min()) < 0 or int(s.max()) >= hi
                or int(d.min()) < 0 or int(d.max()) >= hi
            ):
                raise ValueError(
                    f"{path}: raw id outside [0, {hi}) — not a dense-id "
                    "corpus"
                )
            yield s.astype(np.int32), d.astype(np.int32), v
        return
    budget = min(max(chunk_edges * 20, 4096), 1 << 28)
    cap = budget // 4 + 64
    handle = lib.reader_open(path.encode(), budget)
    if not handle:
        raise IOError(f"cannot read {path}")
    try:
        src = np.empty(cap, np.int32)
        dst = np.empty(cap, np.int32)
        val = np.empty(cap, np.float64)
        has_val = ctypes.c_int32(0)
        at_eof = ctypes.c_int32(0)
        oob = ctypes.c_int64(0)
        while True:
            prev = lib.reader_offset(handle)
            got = lib.reader_next_span_i32(
                handle, src, dst, val, cap, id_bound,
                ctypes.byref(has_val), ctypes.byref(at_eof),
                ctypes.byref(oob),
            )
            if got < 0:
                raise IOError(f"cannot read {path}")
            if oob.value:
                hi = id_bound if id_bound else np.iinfo(np.int32).max
                raise ValueError(
                    f"{path}: {oob.value} ids outside [0, {hi}) — not a "
                    "dense-id corpus"
                )
            if got:
                yield (
                    src[:got].copy(),
                    dst[:got].copy(),
                    val[:got].copy() if has_val.value else None,
                )
            if at_eof.value:
                return
            if got == 0 and lib.reader_offset(handle) == prev:
                raise IOError(
                    f"{path}: line at byte {prev} exceeds the span read "
                    "budget"
                )
    finally:
        lib.reader_close(handle)


def write_edge_file(
    path: str,
    src: np.ndarray,
    dst: np.ndarray,
    append: bool = False,
    threads: Optional[int] = None,
) -> None:
    """Write a tab-separated edge list (corpus synthesis at scale).

    ~100x ``np.savetxt``: per-thread integer formatting into string
    buffers, written sequentially. Non-negative ids only (the formats of
    the BASELINE corpora)."""
    src = np.ascontiguousarray(src, np.int64)
    dst = np.ascontiguousarray(dst, np.int64)
    lib = _load()
    if lib is None:
        with open(path, "a" if append else "w") as f:
            for s, d in zip(src.tolist(), dst.tolist()):
                f.write(f"{s}\t{d}\n")
        return
    if threads is None:
        threads = os.cpu_count() or 1
    rc = lib.write_edge_file(
        path.encode(), src, dst, src.size, 1 if append else 0, threads
    )
    if rc != 0:
        raise IOError(f"cannot write {path}")


def cc_baseline(
    src: np.ndarray,
    dst: np.ndarray,
    window: int,
    partitions: Optional[int] = None,
) -> Tuple[float, int]:
    """Run the compiled streaming-CC baseline (the reference's execution
    model — per-partition window folds into hash-map union-find +
    sequential merge — compiled to native code). Returns (seconds,
    component_count). Raises when the native library is unavailable: a
    Python fallback would not be a meaningful baseline."""
    lib = _load()
    if lib is None:
        raise RuntimeError("native toolchain unavailable for the baseline")
    src = np.ascontiguousarray(src, np.int64)
    dst = np.ascontiguousarray(dst, np.int64)
    if partitions is None:
        partitions = min(8, os.cpu_count() or 1)
    comps = ctypes.c_int64(0)
    ns = lib.cc_baseline_run(
        src, dst, src.size, window, partitions, ctypes.byref(comps)
    )
    return ns / 1e9, int(comps.value)


def flink_proxy(
    src: np.ndarray,
    dst: np.ndarray,
    window: int,
    partitions: Optional[int] = None,
) -> Tuple[float, int]:
    """Run the Flink-representative streaming-CC proxy: the reference's
    job graph with per-record serialized shuffles and a serialized
    partial-merge boundary (``ingest.cpp:flink_proxy_run``). An UPPER
    bound on real single-host Flink throughput for this job — no JVM,
    no netty, no GC — so ratios against it are conservative. Returns
    (seconds, component_count)."""
    lib = _load()
    if lib is None:
        raise RuntimeError("native toolchain unavailable for the proxy")
    src = np.ascontiguousarray(src, np.int64)
    dst = np.ascontiguousarray(dst, np.int64)
    if partitions is None:
        partitions = min(8, os.cpu_count() or 1)
    comps = ctypes.c_int64(0)
    ns = lib.flink_proxy_run(
        src, dst, src.size, window, partitions, ctypes.byref(comps)
    )
    return ns / 1e9, int(comps.value)


_I64_MAX = 2**63 - 1


def _saturate_i64(token: str) -> int:
    """Signed decimal with C-parser saturation: |value| clamps to
    INT64_MAX before the sign is applied."""
    neg = token.startswith("-")
    mag = min(int(token.lstrip("+-")), _I64_MAX)
    return -mag if neg else mag


_LINE_RE = None


def _parse_text_lines(lines):
    """The shared python-fallback line grammar (mirrors the C parser
    char-for-char — see :func:`_parse_python`). Consumes an iterable of
    text lines; returns ``(srcs, dsts, vals, any_val, malformed)`` with
    ``malformed`` counting non-blank, non-comment lines the grammar
    rejected (the file path ignores the count; the socket path reports
    it)."""
    global _LINE_RE
    import re

    if _LINE_RE is None:
        _LINE_RE = (
            re.compile(r"^[ \t,\r]*([+-]?\d+)[ \t,\r]+([+-]?\d+)(.*)$"),
            re.compile(r"^[+-]?(?:\d+\.?\d*|\.\d+)(?:[eE][+-]?\d+)?"),
        )
    line_re, float_re = _LINE_RE
    srcs, dsts, vals = [], [], []
    any_val = False
    malformed = 0
    for line in lines:
        stripped = line.lstrip(" \t,\r")
        if not stripped or stripped[0] in "#%\n":
            continue
        m = line_re.match(line.rstrip("\n"))
        if not m:
            malformed += 1
            continue
        # ids beyond int64 saturate (sign applied after), matching the
        # C parser's digit-counted saturation — so oob/id-bound checks
        # fire identically on both paths instead of OverflowError here
        # vs a silent wrap there (round-2 advisor finding)
        srcs.append(_saturate_i64(m.group(1)))
        dsts.append(_saturate_i64(m.group(2)))
        rest = m.group(3).lstrip(" \t,\r")
        v = 0.0
        if rest:
            c0 = rest[0]
            follows = rest[1:2]
            if c0 == "+" and follows in ("", " ", "\t", "\r"):
                v = 1.0
                any_val = True
            elif c0 == "-" and follows in ("", " ", "\t", "\r"):
                v = -1.0
                any_val = True
            else:
                fm = float_re.match(rest)
                if fm:
                    v = float(fm.group(0))
                    any_val = True
        vals.append(v)
    return srcs, dsts, vals, any_val, malformed


def _parse_python(path: str):
    """Numpy fallback when no C++ toolchain is available.

    Mirrors the C grammar char-for-char (prefix number parsing, not token
    splitting): two integers separated by space/tab/comma runs, trailing
    junk after a number tolerated, an unparseable THIRD column leaves the
    edge valid with value 0 (the strtod-failure behavior). Never raises
    on noise — the fuzz suite holds the two parsers byte-equivalent."""
    with open(path) as f:
        srcs, dsts, vals, any_val, _malformed = _parse_text_lines(f)
    src = np.asarray(srcs, np.int64)
    dst = np.asarray(dsts, np.int64)
    return src, dst, (np.asarray(vals, np.float64) if any_val else None)


def parse_edge_lines(
    data: bytes,
) -> Tuple[np.ndarray, np.ndarray, Optional[np.ndarray], int]:
    """Parse a buffer of complete text edge lines into ``(src, dst,
    val|None, malformed)`` columns — the socket text hot path's
    one-call-per-recv chunk parse (ISSUE 11 satellite), replacing
    per-line Python ``split()``/``int()``.

    The accepted grammar is the FILE parser's (native fast parser, or
    the byte-equivalent regex fallback without the toolchain), so a
    socket stream and the same bytes on disk parse identically.
    ``malformed`` counts non-blank, non-comment lines the grammar
    rejected; the caller owns the counter semantics
    (``source.malformed_lines``). ``data`` need not end with a newline
    (a terminator is supplied), but must contain only COMPLETE lines —
    the caller keeps any partial trailing line in its recv buffer."""
    lib = _load()
    if lib is None:
        srcs, dsts, vals, any_val, malformed = _parse_text_lines(
            data.decode("latin-1").split("\n")
        )
        return (
            np.asarray(srcs, np.int64),
            np.asarray(dsts, np.int64),
            np.asarray(vals, np.float64) if any_val else None,
            malformed,
        )
    cap = data.count(b"\n") + 2
    src = np.empty(cap, np.int64)
    dst = np.empty(cap, np.int64)
    val = np.empty(cap, np.float64)
    has_val = ctypes.c_int32(0)
    malformed = ctypes.c_int64(0)
    # newline-terminate the final line + READ_PAD zeros for SWAR loads
    buf = data + b"\n" + bytes(64)
    got = lib.parse_edge_lines(
        buf, len(data) + 1, src, dst, val, cap,
        ctypes.byref(has_val), ctypes.byref(malformed),
    )
    return (
        src[:got].copy(),
        dst[:got].copy(),
        val[:got].copy() if has_val.value else None,
        int(malformed.value),
    )


def decode_edge_frame(
    payload: bytes, n_edges: int, wide: bool, has_val: bool
) -> Tuple[np.ndarray, np.ndarray, Optional[np.ndarray]]:
    """Decode one GSEW binary frame payload (``core/ingest.py``) into
    engine-ready columns ``(src i64, dst i64, val f64|None)`` — ONE
    native call per frame (geometry check + int32 widen + copy into
    fresh buffers), replacing the text path's per-line integer parsing
    entirely. Numpy ``frombuffer`` fallback without the toolchain.
    Raises ``ValueError`` when the payload size disagrees with the
    header-declared geometry (the caller counts a malformed frame)."""
    n = int(n_edges)
    isz = 8 if wide else 4
    want = n * isz * 2 + (8 * n if has_val else 0)
    lib = _load()
    if lib is None or n == 0:
        if len(payload) != want:
            raise ValueError(
                f"frame payload is {len(payload)} bytes; declared "
                f"geometry (n={n}, wide={bool(wide)}, "
                f"val={bool(has_val)}) wants {want}"
            )
        dt = np.int64 if wide else np.int32
        src = np.frombuffer(payload, dt, n, 0).astype(np.int64)
        dst = np.frombuffer(payload, dt, n, n * isz).astype(np.int64)
        val = (
            np.frombuffer(payload, np.float64, n, 2 * n * isz).copy()
            if has_val else None
        )
        return src, dst, val
    src = np.empty(n, np.int64)
    dst = np.empty(n, np.int64)
    val = np.empty(n if has_val else 0, np.float64)
    rc = lib.decode_edge_frame(
        payload, len(payload), n, 1 if wide else 0, 1 if has_val else 0,
        src, dst, val,
    )
    if rc != 0:
        raise ValueError(
            f"frame payload is {len(payload)} bytes; declared geometry "
            f"(n={n}, wide={bool(wide)}, val={bool(has_val)}) wants {want}"
        )
    return src, dst, (val if has_val else None)


class NoveltyBitmap:
    """First-seen counter over the non-negative int32 id space.

    ``novel2(src, dst)`` records both endpoint columns (interleaved
    arrival order) and returns how many ids were never seen before —
    EXACT distinctness, which lets the device-encode ingest grow its
    on-device dictionary proactively from host knowledge alone instead of
    reading a count back from the device (a pipeline drain per window).
    Native: a lazily-committed 2^31-bit anonymous mmap.
    Fallback: a numpy byte map grown to the observed id range.
    """

    def __init__(self):
        self._lib = _load()
        self._h = self._lib.vbitmap_create() if self._lib is not None else None
        if self._lib is not None and not self._h:
            self._lib = None  # mmap failed: numpy fallback
        self._bits: Optional[np.ndarray] = None  # fallback storage

    def novel2(self, src: np.ndarray, dst: np.ndarray) -> int:
        src = np.ascontiguousarray(src, np.int32)
        dst = np.ascontiguousarray(dst, np.int32)
        if self._lib is not None:
            return int(self._lib.vbitmap_novel2(self._h, src, dst, src.size))
        ids = np.stack([src, dst], axis=1).ravel()
        ids = ids[ids >= 0]
        if ids.size == 0:
            return 0
        uniq = np.unique(ids).astype(np.int64)
        # bit-packed like the native mmap (max 256 MB at the int32
        # extreme, not 2 GB byte-per-id)
        hi = (int(uniq[-1]) >> 3) + 1
        if self._bits is None or self._bits.size < hi:
            grown = np.zeros(max(hi, 1024), np.uint8)
            if self._bits is not None:
                grown[: self._bits.size] = self._bits
            self._bits = grown
        cell = uniq >> 3
        mask = np.uint8(1) << (uniq & 7).astype(np.uint8)
        fresh = (self._bits[cell] & mask) == 0
        np.bitwise_or.at(self._bits, cell[fresh], mask[fresh])
        return int(fresh.sum())

    def __del__(self):
        lib = getattr(self, "_lib", None)
        h = getattr(self, "_h", None)
        if lib is not None and h:
            lib.vbitmap_destroy(h)


class CompactUnionFind:
    """Incremental union-find over compact int32 ids — the host CC carry
    (``ingest.cpp: cuf_*``; placement rationale in
    ``library/connected_components.py``).

    ``fold(src, dst, vcap)`` unions one window and returns
    ``(touched, roots, changed, changed_roots)``: the window's distinct
    endpoints with their post-window roots, plus every root demoted by
    this window with its post-window root — exactly the scatter a device
    pointer-forest mirror needs to stay resolvable.

    Raises ``RuntimeError`` at construction when the native toolchain is
    unavailable; callers fall back to the device forest carry.
    """

    def __init__(self):
        lib = _load()
        if lib is None:
            raise RuntimeError("native toolchain unavailable")
        self._lib = lib
        self._h = lib.cuf_create()
        if not self._h:
            raise RuntimeError("cuf_create failed")
        self._tbuf = np.zeros(1024, np.int32)
        self._rbuf = np.zeros(1024, np.int32)
        self._cbuf = np.zeros(1024, np.int32)
        self._crbuf = np.zeros(1024, np.int32)

    def fold(self, src: np.ndarray, dst: np.ndarray, vcap: int):
        src = np.ascontiguousarray(src, np.int32)
        dst = np.ascontiguousarray(dst, np.int32)
        n = src.size
        if self._tbuf.size < 2 * n:
            self._tbuf = np.zeros(2 * n, np.int32)
            self._rbuf = np.zeros(2 * n, np.int32)
        if self._cbuf.size < max(n, 1):
            self._cbuf = np.zeros(n, np.int32)
            self._crbuf = np.zeros(n, np.int32)
        nc = ctypes.c_int64(0)
        nt = self._lib.cuf_fold_window(
            self._h, src, dst, n, int(vcap),
            self._tbuf, self._rbuf, self._cbuf, self._crbuf,
            ctypes.byref(nc),
        )
        if nt < 0:
            raise ValueError("edge ids out of range for vcap")
        nc = nc.value
        return (
            self._tbuf[:nt].copy(), self._rbuf[:nt].copy(),
            self._cbuf[:nc].copy(), self._crbuf[:nc].copy(),
        )

    def fold_group(self, cols, vcap: int):
        """Union K windows in ONE native call (``cuf_fold_group``) — the
        host-carry superbatch path. ``cols`` is a list of per-window
        column tuples ``(src, dst, ...)``; per-window python/ctypes
        overhead measured ~0.3 ms via :meth:`fold`, which dominates
        sub-8k windows.

        Returns ``(windows, group_ids, group_roots, gt_counts)``:
        ``windows`` holds per-window ``(touched, roots, changed,
        changed_roots)`` views into freshly-allocated group buffers
        (safe to keep — nothing is reused across calls);
        ``group_ids``/``group_roots`` is the C-deduped union of every id
        the group re-rooted with its POST-GROUP root — the single masked
        scatter a device mirror needs per group — ordered group-unique
        touched ids FIRST (window first-seen order, per-window counts in
        ``gt_counts``, so a first-seen emission log can batch on the
        prefix) with the demoted-roots remainder after."""
        k = len(cols)
        offsets = np.zeros(k + 1, np.int64)
        for i, c in enumerate(cols):
            offsets[i + 1] = offsets[i] + len(c[0])
        n = int(offsets[-1])
        src = np.empty(n, np.int32)
        dst = np.empty(n, np.int32)
        for i, c in enumerate(cols):
            src[offsets[i]:offsets[i + 1]] = c[0]
            dst[offsets[i]:offsets[i + 1]] = c[1]
        tbuf = np.empty(2 * n, np.int32)
        rbuf = np.empty(2 * n, np.int32)
        cbuf = np.empty(max(n, 1), np.int32)
        crbuf = np.empty(max(n, 1), np.int32)
        gid = np.empty(max(3 * n, 1), np.int32)
        grt = np.empty(max(3 * n, 1), np.int32)
        tcnt = np.zeros(k, np.int64)
        ccnt = np.zeros(k, np.int64)
        gtcnt = np.zeros(k, np.int64)
        ngrp = ctypes.c_int64(0)
        tt = self._lib.cuf_fold_group(
            self._h, src, dst, offsets, k, int(vcap),
            tbuf, rbuf, cbuf, crbuf, tcnt, ccnt, gid, grt, gtcnt,
            ctypes.byref(ngrp),
        )
        if tt < 0:
            raise ValueError("edge ids out of range for vcap")
        wins = []
        t0 = c0 = 0
        for w in range(k):
            t1 = t0 + int(tcnt[w])
            c1 = c0 + int(ccnt[w])
            wins.append((tbuf[t0:t1], rbuf[t0:t1], cbuf[c0:c1], crbuf[c0:c1]))
            t0, c0 = t1, c1
        ng = ngrp.value
        return wins, gid[:ng], grt[:ng], gtcnt

    def flatten(self, vcap: int) -> np.ndarray:
        out = np.zeros(vcap, np.int32)
        self._lib.cuf_flatten(self._h, out, vcap)
        return out

    def load(self, labels: np.ndarray) -> None:
        labels = np.ascontiguousarray(labels, np.int32)
        if self._lib.cuf_load(self._h, labels, labels.size) != 0:
            raise ValueError("labels are not a min-rooted forest")

    def __del__(self):
        lib = getattr(self, "_lib", None)
        h = getattr(self, "_h", None)
        if lib is not None and h:
            lib.cuf_destroy(h)


class NativeWindowPrep:
    """Single-pass touched-set + local-renumbering for the forest CC
    carry (``ingest.cpp: wprep_*``): epoch-stamped, no clearing, cost
    scales with the window alone. ``run(src, dst, vcap)`` returns
    ``(tids, lu, lv)`` with touched ids in ARRIVAL order. Raises
    ``RuntimeError`` at construction when the toolchain is unavailable
    (callers keep the numpy bitmap+LUT path)."""

    def __init__(self):
        lib = _load()
        if lib is None:
            raise RuntimeError("native toolchain unavailable")
        self._lib = lib
        self._h = lib.wprep_create()
        if not self._h:
            raise RuntimeError("wprep_create failed")
        self._tbuf = np.zeros(1024, np.int32)
        self._lu = np.zeros(512, np.int32)
        self._lv = np.zeros(512, np.int32)

    def run(self, src: np.ndarray, dst: np.ndarray, vcap: int):
        src = np.ascontiguousarray(src, np.int32)
        dst = np.ascontiguousarray(dst, np.int32)
        n = src.size
        if self._tbuf.size < 2 * n:
            self._tbuf = np.zeros(max(2 * n, 1024), np.int32)
        if self._lu.size < max(n, 1):
            self._lu = np.zeros(n, np.int32)
            self._lv = np.zeros(n, np.int32)
        t = self._lib.wprep_run(
            self._h, src, dst, n, int(vcap),
            self._tbuf, self._lu, self._lv,
        )
        if t < 0:
            raise ValueError("edge ids out of range for vcap")
        return self._tbuf[:t].copy(), self._lu[:n].copy(), self._lv[:n].copy()

    def __del__(self):
        lib = getattr(self, "_lib", None)
        h = getattr(self, "_h", None)
        if lib is not None and h:
            lib.wprep_destroy(h)


class NativeEncoder:
    """C++ first-seen id compactor (the ``VertexDict.encode`` hot path).

    ``encode(raw)`` returns ``(idx[i32], novel_raw[i64])`` — compact ids
    for every input and the never-seen-before raw ids in first-appearance
    order. Falls back is handled by the caller (``VertexDict`` keeps its
    numpy path when the toolchain is absent).
    """

    def __init__(self):
        lib = _load()
        if lib is None:
            raise RuntimeError("native toolchain unavailable")
        self._lib = lib
        self._h = lib.encoder_create()
        # ctypes calls release the GIL; without this lock a prefetch-thread
        # encode's rehash could free buffers mid-lookup (use-after-free)
        self._mu = threading.Lock()

    def encode(self, raw: np.ndarray):
        raw = np.ascontiguousarray(raw, np.int64)
        idx = np.empty(raw.size, np.int32)
        novel = np.empty(raw.size, np.int64)
        with self._mu:
            n_novel = self._lib.encoder_encode(
                self._h, raw, raw.size, idx, novel
            )
        return idx, novel[:n_novel]

    def encode_pair(self, a: np.ndarray, b: np.ndarray):
        """Encode edge columns as the interleaved a0,b0,a1,b1,... sequence
        (first-seen order by edge arrival) without the interleaved copy."""
        a = np.ascontiguousarray(a, np.int64)
        b = np.ascontiguousarray(b, np.int64)
        ia = np.empty(a.size, np.int32)
        ib = np.empty(b.size, np.int32)
        novel = np.empty(a.size + b.size, np.int64)
        with self._mu:
            n_novel = self._lib.encoder_encode2(
                self._h, a, b, a.size, ia, ib, novel
            )
        return ia, ib, novel[:n_novel]

    def parse_encode_chunks(self, path: str, chunk_edges: int = 1 << 20):
        """Fused file ingest: yield (src_idx, dst_idx, val|None, novel_raw)
        chunks with endpoints already compact-encoded — the file bytes are
        parsed and hashed in one C pass, no int64 columns round trip."""
        budget = min(max(chunk_edges * 20, 4096), 1 << 28)
        cap = budget // 4 + 64
        lib = self._lib
        handle = lib.reader_open(path.encode(), budget)
        if not handle:
            raise IOError(f"cannot read {path}")
        try:
            src = np.empty(cap, np.int32)
            dst = np.empty(cap, np.int32)
            val = np.empty(cap, np.float64)
            novel = np.empty(2 * cap, np.int64)
            n_novel = ctypes.c_int64(0)
            has_val = ctypes.c_int32(0)
            at_eof = ctypes.c_int32(0)
            while True:
                prev = lib.reader_offset(handle)
                with self._mu:
                    got = lib.reader_next_encoded(
                        handle, self._h, src, dst, val, cap, novel,
                        ctypes.byref(n_novel), ctypes.byref(has_val),
                        ctypes.byref(at_eof),
                    )
                if got < 0:
                    raise IOError(f"cannot read {path}")
                if got:
                    yield (
                        src[:got].copy(),
                        dst[:got].copy(),
                        val[:got].copy() if has_val.value else None,
                        novel[: n_novel.value].copy(),
                    )
                if at_eof.value:
                    return
                if got == 0 and lib.reader_offset(handle) == prev:
                    raise IOError(
                        f"{path}: line at byte {prev} exceeds the span "
                        "read budget"
                    )
        finally:
            lib.reader_close(handle)

    def lookup(self, k: int):
        with self._mu:
            v = self._lib.encoder_lookup(self._h, int(k))
        return None if v < 0 else int(v)

    def lookup_batch(self, ks: np.ndarray) -> np.ndarray:
        """Batched query-without-insert: int32 compact ids, -1 for
        unseen. ONE C call (and one mutex acquisition) for the whole
        batch — the serving read path must not pay a ctypes round trip
        per id."""
        ks = np.ascontiguousarray(ks, np.int64)
        out = np.empty(ks.size, np.int32)
        with self._mu:
            self._lib.encoder_lookup_batch(self._h, ks, ks.size, out)
        return out

    def __len__(self) -> int:
        return int(self._lib.encoder_size(self._h))

    def __del__(self):
        lib = getattr(self, "_lib", None)
        h = getattr(self, "_h", None)
        if lib is not None and h:
            lib.encoder_destroy(h)
