// Fast edge-file ingest for the host layer.
//
// The reference delegates file ingest to Flink's JVM text sources
// (env.readTextFile + per-line split mappers, e.g.
// ConnectedComponentsExample.java:106-118). Here the host layer owns
// ingestion (SURVEY.md §7), and for file-backed streams the Python-side
// line parsing is the bottleneck long before the device is busy — this
// translation unit parses whitespace-separated edge lists straight into
// caller-provided numpy buffers at C speed.
//
// Exposed via ctypes (extern "C"), no pybind11 dependency:
//   reader_open/next_span/next_encoded/close  -> chunked streaming reads
//   encoder_*                                 -> first-seen id compaction
//   write_edge_file                           -> fast corpus writer
//   cc_baseline_run                           -> compiled CC baseline
//   decode_edge_frame                         -> GSEW binary wire decode
//   parse_edge_lines                          -> socket text chunk parse
//
// Format per line: "src dst [third]" where third may be a value,
// timestamp, or +/- event flag (returned as +1/-1). '#'/'%' lines and
// blanks are skipped. Separators: spaces, tabs, commas.

#include <cstdint>
#include <cstdio>
#include <cstdlib>
#include <cstring>

#include <atomic>
#include <ctime>
#include <string>
#include <thread>
#include <vector>

#include <sys/mman.h>

#if defined(__AVX512BW__)
#include <immintrin.h>
#endif

// Read buffers are over-allocated and zero-padded by PAD bytes so the
// SWAR parsers can load 8 bytes and the AVX-512 newline scanner 64 bytes
// at any position < len without reading out of bounds.
#define READ_PAD 64

namespace {

inline const char* skip_sep(const char* p, const char* end) {
    while (p < end && (*p == ' ' || *p == '\t' || *p == ',' || *p == '\r')) ++p;
    return p;
}

inline const char* skip_line(const char* p, const char* end) {
    const char* nl =
        (const char*)memchr(p, '\n', (size_t)(end - p));
    return nl ? nl + 1 : end;
}

// Parse one line into (s, d, v, has_third). Returns false for
// blank/comment/malformed lines.
inline bool parse_line(const char*& p, const char* end, int64_t* s, int64_t* d,
                       double* v, bool* has_third) {
    p = skip_sep(p, end);
    if (p >= end) return false;
    if (*p == '#' || *p == '%' || *p == '\n') {
        p = skip_line(p, end);
        return false;
    }
    char* q;
    long long a = strtoll(p, &q, 10);
    if (q == p) { p = skip_line(p, end); return false; }
    p = skip_sep(q, end);
    long long b = strtoll(p, &q, 10);
    if (q == p) { p = skip_line(p, end); return false; }
    p = skip_sep(q, end);
    *has_third = false;
    *v = 0.0;
    if (p < end && *p != '\n') {
        if (*p == '+') { *v = 1.0; *has_third = true; p = skip_line(p, end); }
        else if (*p == '-' && (p + 1 >= end || *(p + 1) == '\n' || *(p + 1) == ' ' || *(p + 1) == '\r')) {
            *v = -1.0; *has_third = true; p = skip_line(p, end);
        } else {
            double x = strtod(p, &q);
            if (q != p) { *v = x; *has_third = true; }
            p = skip_line(q, end);
        }
    } else {
        p = skip_line(p, end);
    }
    *s = (int64_t)a;
    *d = (int64_t)b;
    return true;
}

// Read [offset, offset+len) of the file into a malloc'd buffer.
// *at_eof is set when the span reaches the end of the file.
// The buffer is over-allocated by READ_PAD zero bytes (see above).
char* read_span(const char* path, int64_t offset, int64_t* len, bool* at_eof) {
    FILE* f = fopen(path, "rb");
    if (!f) { *len = -1; return nullptr; }  // signal IO error to callers
    if (fseek(f, 0, SEEK_END) != 0) { fclose(f); *len = -1; return nullptr; }
    int64_t size = ftell(f);
    if (offset >= size) { fclose(f); *len = 0; *at_eof = true; return nullptr; }
    int64_t want = (*len <= 0 || offset + *len > size) ? size - offset : *len;
    *at_eof = (offset + want) >= size;
    char* buf = (char*)malloc(want + READ_PAD);
    if (!buf) { fclose(f); return nullptr; }
    memset(buf + want, 0, READ_PAD);
    fseek(f, offset, SEEK_SET);
    int64_t got = (int64_t)fread(buf, 1, want, f);
    fclose(f);
    *len = got;
    return buf;
}

// ----- SWAR digit parsing (safe: read_span pads 8 bytes past len) ----- //

inline uint32_t parse_eight(uint64_t w) {
    w = (w & 0x0F0F0F0F0F0F0F0FULL) * 2561 >> 8;
    w = (w & 0x00FF00FF00FF00FFULL) * 6553601 >> 16;
    return (uint32_t)((w & 0x0000FFFF0000FFFFULL) * 42949672960001ULL >> 32);
}

// Parse an unsigned decimal run at p (8 bytes at a time); advances p past
// the digits. Returns false when *p is not a digit. Runs whose value
// exceeds INT64_MAX saturate to INT64_MAX (digit count tracked, plus an
// exact check for 19-digit runs) so downstream id-bound/oob checks fire —
// a silent uint64 wrap would let corrupted edges into validated ingest
// paths, and the Python fallback must agree byte-for-byte.
inline bool parse_uint_swar(const char*& p, uint64_t* out) {
    uint64_t w;
    memcpy(&w, p, 8);
    uint64_t nd_mask = ((w - 0x3030303030303030ULL) |
                        (w + 0x4646464646464646ULL)) &
                       0x8080808080808080ULL;
    if (nd_mask == 0) {  // >= 8 digits: full block, then continue
        uint64_t v = parse_eight(w);
        int64_t digits = 8;
        p += 8;
        while (true) {
            memcpy(&w, p, 8);
            nd_mask = ((w - 0x3030303030303030ULL) |
                       (w + 0x4646464646464646ULL)) &
                      0x8080808080808080ULL;
            if (nd_mask == 0) {
                v = v * 100000000ULL + parse_eight(w);
                digits += 8;
                p += 8;
                continue;
            }
            int nd = __builtin_ctzll(nd_mask) >> 3;
            if (nd) {
                // left-align the nd digits behind '0' padding
                uint64_t w2 = (w << ((8 - nd) * 8)) |
                              (0x3030303030303030ULL >> (nd * 8));
                static const uint64_t pow10[8] = {1, 10, 100, 1000, 10000,
                                                  100000, 1000000, 10000000};
                v = v * pow10[nd] + parse_eight(w2);
                digits += nd;
                p += nd;
            }
            // 20+ digits always exceed INT64_MAX; 19 digits fit uint64
            // exactly, so the comparison below is wrap-free
            if (digits > 19 || (digits == 19 && v > (uint64_t)INT64_MAX))
                v = (uint64_t)INT64_MAX;
            *out = v;
            return true;
        }
    }
    int nd = __builtin_ctzll(nd_mask) >> 3;
    if (nd == 0) return false;
    uint64_t w2 = (w << ((8 - nd) * 8)) | (0x3030303030303030ULL >> (nd * 8));
    *out = parse_eight(w2);
    p += nd;
    return true;
}

}  // namespace

// --------------------------------------------------------------------- //
// Fast span parser: hand-rolled digit scanning + thread-parallel spans.
//
// strtoll tops out around 35 MB/s on edge lists; the inline parser below
// runs ~10x that per core and sub-spans parse independently (each thread
// starts at the first line boundary past its slice start), so a single
// read_span turns into all-core parsing. This is the host half of the
// "host feeds the device" contract (SURVEY.md §7 hard part #6); the
// reference's equivalent stage is Flink's parallel text source +
// per-line split mappers (ConnectedComponentsExample.java:106-118).
// --------------------------------------------------------------------- //

namespace {

// Parse one line fast. Same accepted grammar as parse_line above:
// "src dst [third]" with space/tab/comma separators, '#'/'%' comments,
// third column as number or +/- event flag. Returns false for non-edge
// lines; p always advances past the line.
inline bool parse_line_fast(const char*& p, const char* end, int64_t* s,
                            int64_t* d, double* v, bool* has_third) {
    p = skip_sep(p, end);
    if (p >= end) return false;
    char c = *p;
    if (c == '#' || c == '%' || c == '\n') { p = skip_line(p, end); return false; }
    // first integer (SWAR digit runs; sign prefixes handled here)
    bool neg = false;
    if (c == '-' || c == '+') { neg = (c == '-'); ++p; }
    uint64_t a;
    if (p >= end || !parse_uint_swar(p, &a)) {
        p = skip_line(p, end);
        return false;
    }
    int64_t sa = neg ? -(int64_t)a : (int64_t)a;
    p = skip_sep(p, end);
    // second integer
    if (p >= end) return false;
    c = *p; neg = false;
    if (c == '-' || c == '+') { neg = (c == '-'); ++p; }
    uint64_t b;
    if (p >= end || !parse_uint_swar(p, &b)) {
        p = skip_line(p, end);
        return false;
    }
    int64_t sb = neg ? -(int64_t)b : (int64_t)b;
    p = skip_sep(p, end);
    *has_third = false;
    *v = 0.0;
    if (p < end && *p != '\n') {
        c = *p;
        if (c == '+' && (p + 1 >= end || *(p + 1) == '\n' || *(p + 1) == ' ' ||
                         *(p + 1) == '\r' || *(p + 1) == '\t')) {
            *v = 1.0; *has_third = true; p = skip_line(p, end);
        } else if (c == '-' && (p + 1 >= end || *(p + 1) == '\n' ||
                                *(p + 1) == ' ' || *(p + 1) == '\r' ||
                                *(p + 1) == '\t')) {
            *v = -1.0; *has_third = true; p = skip_line(p, end);
        } else {
            // integer fast path; anything else falls back to strtod
            bool vneg = false; const char* q0 = p;
            if (c == '-' || c == '+') { vneg = (c == '-'); ++p; }
            uint64_t iv = 0; const char* digs = p;
            while (p < end && *p >= '0' && *p <= '9') iv = iv * 10 + (*p++ - '0');
            if (p > digs && (p >= end || *p == '\n' || *p == ' ' ||
                             *p == '\t' || *p == ',' || *p == '\r')) {
                *v = vneg ? -(double)iv : (double)iv;
                *has_third = true;
                p = skip_line(p, end);
            } else {
                char* qe;
                double x = strtod(q0, &qe);
                if (qe != q0) { *v = x; *has_third = true; }
                p = skip_line(qe > q0 ? qe : q0, end);
            }
        }
    } else {
        p = skip_line(p, end);
    }
    *s = sa;
    *d = sb;
    return true;
}

// Fast path for the dominant unweighted line shape "digits SEP digits\n"
// (measured ~1.8x the general parser): advances p and returns true on an
// exact match; leaves p untouched otherwise so the caller falls back to
// the general parser — accepted grammar is unchanged. Caller guarantees
// p < end (the 8-byte pad covers SWAR loads).
inline bool parse_two_col_fast(const char*& p, int64_t* a_out,
                               int64_t* b_out) {
    if ((uint8_t)(*p - '0') > 9) return false;
    const char* save = p;
    uint64_t a, b;
    if (parse_uint_swar(p, &a)) {
        char sep = *p;
        if ((sep == ' ' || sep == '\t' || sep == ',') &&
            (uint8_t)(p[1] - '0') <= 9) {
            ++p;
            if (parse_uint_swar(p, &b) && *p == '\n') {
                ++p;
                *a_out = (int64_t)a;
                *b_out = (int64_t)b;
                return true;
            }
        }
    }
    p = save;
    return false;
}

// Parse one already-delimited line [s, nl) of the dominant unweighted
// shape "digits SEP digits [\r]" with both ids <= 8 digits (so they fit
// int32 by construction: max 99,999,999 < 2^31). Returns false — without
// consuming anything — for any other shape; the caller falls back to the
// general grammar parser for that line. Two 8-byte SWAR loads, no scan
// loop: the line boundaries come from the caller's newline mask.
inline bool parse_line_i32_quick(const char* s, const char* nl, int32_t* a_out,
                                 int32_t* b_out) {
    uint64_t w;
    memcpy(&w, s, 8);
    uint64_t ndm = ((w - 0x3030303030303030ULL) |
                    (w + 0x4646464646464646ULL)) &
                   0x8080808080808080ULL;
    int nd1 = ndm ? (__builtin_ctzll(ndm) >> 3) : 8;
    if (nd1 == 0) return false;
    uint64_t v1 = parse_eight(
        nd1 == 8 ? w
                 : ((w << ((8 - nd1) * 8)) |
                    (0x3030303030303030ULL >> (nd1 * 8))));
    const char* q = s + nd1;
    if (q >= nl) return false;
    char sep = *q;
    if (sep != '\t' && sep != ' ' && sep != ',') return false;  // 9+ digits land here
    ++q;
    memcpy(&w, q, 8);
    ndm = ((w - 0x3030303030303030ULL) |
           (w + 0x4646464646464646ULL)) &
          0x8080808080808080ULL;
    int nd2 = ndm ? (__builtin_ctzll(ndm) >> 3) : 8;
    if (nd2 == 0) return false;
    const char* e2 = q + nd2;
    if (e2 != nl && !(e2 + 1 == nl && *e2 == '\r')) return false;
    uint64_t v2 = parse_eight(
        nd2 == 8 ? w
                 : ((w << ((8 - nd2) * 8)) |
                    (0x3030303030303030ULL >> (nd2 * 8))));
    *a_out = (int32_t)v1;
    *b_out = (int32_t)v2;
    return true;
}

#if defined(__AVX512BW__)
// Newline-driven int32 region parse: one AVX-512 compare finds the
// newlines of 64 input bytes (~4-5 lines) at once, and each line is then
// parsed branch-lean by parse_line_i32_quick — the per-line separator
// scanning, comment tests, and third-column probing of the scalar loop
// vanish from the hot path. Lines that are not simple two-column edges
// fall back to parse_line_fast one line at a time (accepted grammar is
// identical). ~3x the scalar loop on SNAP-shaped corpora (measured round
// 3: 26.6M -> ~80M edges/s single core).
//
// [buf, end) must end at a line boundary or EOF (reader_fill contract)
// and carry READ_PAD zero bytes past `end`. Returns edges written;
// *consumed gets the byte count consumed (always the full span unless
// `cap` fills).
int64_t parse_region_i32_simd(const char* buf, const char* end, int32_t* src,
                              int32_t* dst, double* val, int64_t cap,
                              int64_t bound, int64_t* oob_out, bool* any_val,
                              int64_t* consumed) {
    int64_t n = 0, oob = 0;
    bool av = false;
    const char* line = buf;  // start of the current (unconsumed) line
    const char* p = buf;     // 64-byte scan cursor
    const __m512i NL = _mm512_set1_epi8('\n');
    while (p < end && n < cap) {
        __m512i v = _mm512_loadu_si512((const void*)p);
        uint64_t m = _mm512_cmpeq_epi8_mask(v, NL);
        if (end - p < 64) m &= (((uint64_t)1) << (end - p)) - 1;
        while (m) {
            if (n >= cap) goto done;
            const char* nl = p + __builtin_ctzll(m);
            m &= m - 1;
            if (nl == line) { ++line; continue; }  // blank line
            int32_t a, b;
            if (parse_line_i32_quick(line, nl, &a, &b)) {
                oob += (a >= bound) | (b >= bound);
                src[n] = a;
                dst[n] = b;
                val[n] = 0.0;
                ++n;
            } else {
                const char* q = line;
                int64_t s, d;
                double w;
                bool h;
                if (parse_line_fast(q, nl + 1, &s, &d, &w, &h)) {
                    oob += (s < 0) | (s >= bound) | (d < 0) | (d >= bound);
                    src[n] = (int32_t)s;
                    dst[n] = (int32_t)d;
                    val[n] = w;
                    av |= h;
                    ++n;
                }
            }
            line = nl + 1;
        }
        p += 64;
    }
    // ragged tail (EOF without a trailing newline)
    while (line < end && n < cap) {
        const char* q = line;
        int64_t s, d;
        double w;
        bool h;
        if (parse_line_fast(q, end, &s, &d, &w, &h)) {
            oob += (s < 0) | (s >= bound) | (d < 0) | (d >= bound);
            src[n] = (int32_t)s;
            dst[n] = (int32_t)d;
            val[n] = w;
            av |= h;
            ++n;
        }
        line = q;
    }
done:
    *oob_out = oob;
    *any_val = av;
    *consumed = line - buf;
    return n;
}
#endif  // __AVX512BW__

// Parse every complete line of [p, end) into the output slices.
int64_t parse_region(const char* p, const char* end, int64_t* src,
                     int64_t* dst, double* val, int64_t cap, bool* any_val) {
    int64_t n = 0;
    int64_t s, d; double v; bool h;
    bool av = false;
    while (p < end && n < cap) {
        if (parse_line_fast(p, end, &s, &d, &v, &h)) {
            src[n] = s; dst[n] = d; val[n] = v;
            av |= h;
            ++n;
        }
    }
    *any_val = av;
    return n;
}

}  // namespace

extern "C" {

// --------------------------------------------------------------------- //
// First-seen bitmap over the non-negative int32 id space.
//
// The general (arbitrary-id) device-encode ingest needs to know, per
// chunk, how many ids the device dictionary has never seen — growing the
// device table proactively keeps the whole pipeline free of
// device->host reads (even a scalar fetch waits for every window
// dispatched before it). A 2^31-bit anonymous mmap commits
// lazily page by page, so clustered real-world id spaces stay a few
// hundred KB resident and the test-and-set rides the L2 cache.
// --------------------------------------------------------------------- //

#define VBITMAP_BYTES (((size_t)1 << 31) / 8)  // 256 MB virtual

void* vbitmap_create() {
    void* bits = mmap(nullptr, VBITMAP_BYTES, PROT_READ | PROT_WRITE,
                      MAP_PRIVATE | MAP_ANONYMOUS, -1, 0);
    return bits == MAP_FAILED ? nullptr : bits;
}

void vbitmap_destroy(void* ptr) {
    if (ptr) munmap(ptr, VBITMAP_BYTES);
}

// Count and record first-seen ids among (a[i], b[i]) in interleaved
// arrival order; ids outside [0, 2^31) are ignored (the caller's oob
// check rejects those edges anyway).
int64_t vbitmap_novel2(void* bitmap, const int32_t* a, const int32_t* b,
                       int64_t n) {
    uint8_t* bits = (uint8_t*)bitmap;
    int64_t novel = 0;
    for (int64_t i = 0; i < n; ++i) {
        uint32_t x = (uint32_t)a[i];
        if (a[i] >= 0) {
            uint8_t m = (uint8_t)(1u << (x & 7));
            uint8_t& cell = bits[x >> 3];
            novel += !(cell & m);
            cell |= m;
        }
        uint32_t y = (uint32_t)b[i];
        if (b[i] >= 0) {
            uint8_t m = (uint8_t)(1u << (y & 7));
            uint8_t& cell = bits[y >> 3];
            novel += !(cell & m);
            cell |= m;
        }
    }
    return novel;
}

// Persistent reader session: reuses one file handle and one read buffer
// across span calls. A fresh 40MB malloc per chunk costs ~8-10ns/edge in
// soft page faults alone (measured); the session touches its pages once.
struct SpanReader {
    FILE* f;
    char* buf;
    int64_t buf_cap;
    int64_t size;    // file size
    int64_t offset;  // next unread byte
};

void* reader_open(const char* path, int64_t budget) {
    FILE* f = fopen(path, "rb");
    if (!f) return nullptr;
    if (fseek(f, 0, SEEK_END) != 0) { fclose(f); return nullptr; }
    int64_t size = ftell(f);
    fseek(f, 0, SEEK_SET);
    char* buf = (char*)malloc(budget + READ_PAD);
    if (!buf) { fclose(f); return nullptr; }
    SpanReader* r = (SpanReader*)malloc(sizeof(SpanReader));
    r->f = f; r->buf = buf; r->buf_cap = budget; r->size = size;
    r->offset = 0;
    return r;
}

void reader_close(void* ptr) {
    SpanReader* r = (SpanReader*)ptr;
    if (!r) return;
    fclose(r->f);
    free(r->buf);
    free(r);
}

int64_t reader_offset(void* ptr) { return ((SpanReader*)ptr)->offset; }

namespace {

// Fill the session buffer with the next complete-line span.
// Returns span length (0 at EOF or when one line exceeds the buffer;
// distinguish via *at_eof), -1 on IO error. The span always ends at a
// line boundary unless it reaches EOF.
int64_t reader_fill(SpanReader* r, const char** span_end, bool* at_eof) {
    if (r->offset >= r->size) { *at_eof = true; return 0; }
    int64_t want = r->size - r->offset;
    if (want > r->buf_cap) want = r->buf_cap;
    *at_eof = (r->offset + want) >= r->size;
    if (fseek(r->f, r->offset, SEEK_SET) != 0) return -1;
    int64_t got = (int64_t)fread(r->buf, 1, want, r->f);
    if (got <= 0) return -1;
    memset(r->buf + got, 0, READ_PAD);
    const char* end = r->buf + got;
    if (!*at_eof) {
        while (end > r->buf && *(end - 1) != '\n') --end;
        if (end == r->buf) return 0;  // one line > buffer
    }
    *span_end = end;
    return end - r->buf;
}

}  // namespace

// Session-based span parse (same output contract as parse_edge_span).
int64_t reader_next_span(void* ptr, int64_t* src, int64_t* dst, double* val,
                         int64_t cap, int32_t* has_val, int32_t* at_eof_out,
                         int32_t n_threads) {
    SpanReader* r = (SpanReader*)ptr;
    bool at_eof = false;
    *at_eof_out = 0;
    *has_val = 0;
    const char* end = nullptr;
    int64_t span = reader_fill(r, &end, &at_eof);
    if (span < 0) return -1;
    if (span == 0) {
        if (at_eof) *at_eof_out = 1;
        return 0;
    }
    char* buf = r->buf;
    int64_t t = n_threads < 1 ? 1 : n_threads;
    if (t > span / (1 << 16)) t = span / (1 << 16) ? span / (1 << 16) : 1;
    std::vector<const char*> starts(t + 1);
    starts[0] = buf;
    starts[t] = end;
    for (int64_t i = 1; i < t; ++i) {
        const char* p = buf + (span * i) / t;
        while (p < end && *p != '\n') ++p;
        starts[i] = p < end ? p + 1 : end;
    }
    std::vector<int64_t> counts(t, 0);
    std::vector<int64_t> offs(t + 1);
    for (int64_t i = 0; i < t; ++i) offs[i] = (starts[i] - buf) >> 2;
    offs[t] = cap;
    std::vector<char> anyv(t, 0);
    std::vector<std::thread> workers;
    for (int64_t i = 0; i < t; ++i) {
        workers.emplace_back([&, i] {
            bool av = false;
            counts[i] = parse_region(starts[i], starts[i + 1],
                                     src + offs[i], dst + offs[i],
                                     val + offs[i], offs[i + 1] - offs[i],
                                     &av);
            anyv[i] = av;
        });
    }
    for (auto& w : workers) w.join();
    int64_t n = counts[0];
    for (int64_t i = 1; i < t; ++i) {
        if (counts[i] && n != offs[i]) {
            memmove(src + n, src + offs[i], counts[i] * sizeof(int64_t));
            memmove(dst + n, dst + offs[i], counts[i] * sizeof(int64_t));
            memmove(val + n, val + offs[i], counts[i] * sizeof(double));
        }
        n += counts[i];
    }
    for (int64_t i = 0; i < t; ++i)
        if (anyv[i]) *has_val = 1;
    r->offset += end - buf;
    if (at_eof && r->offset >= r->size) *at_eof_out = 1;
    return n;
}

// Session-based fused parse+encode (contract of parse_encode_span).
int64_t reader_next_encoded(void* ptr, void* enc_ptr, int32_t* src32,
                            int32_t* dst32, double* val, int64_t cap,
                            int64_t* novel_out, int64_t* n_novel_out,
                            int32_t* has_val, int32_t* at_eof_out);

// int32-direct span parse for dense-id corpora: writes int32 columns
// (half the memory traffic of the int64 path, no convert pass) and counts
// ids outside [0, id_bound) (bound 0 = only require int32 range) so the
// caller can reject bad corpora instead of truncating silently.
int64_t reader_next_span_i32(void* ptr, int32_t* src, int32_t* dst,
                             double* val, int64_t cap, int64_t id_bound,
                             int32_t* has_val, int32_t* at_eof_out,
                             int64_t* oob_out) {
    SpanReader* r = (SpanReader*)ptr;
    bool at_eof = false;
    *at_eof_out = 0;
    *has_val = 0;
    *oob_out = 0;
    const char* end = nullptr;
    int64_t span = reader_fill(r, &end, &at_eof);
    if (span < 0) return -1;
    if (span == 0) {
        if (at_eof) *at_eof_out = 1;
        return 0;
    }
    int64_t bound = id_bound > 0 ? id_bound : (int64_t)1 << 31;
    int64_t n, oob = 0;
    bool any_val = false;
#if defined(__AVX512BW__)
    int64_t used = 0;
    n = parse_region_i32_simd(r->buf, end, src, dst, val, cap, bound, &oob,
                              &any_val, &used);
    r->offset += used;
#else
    const char* p = r->buf;
    n = 0;
    int64_t s, d; double v; bool h;
    while (p < end && n < cap) {
        if (parse_two_col_fast(p, &s, &d)) {
            oob += (s >= bound) | (d >= bound);
            src[n] = (int32_t)s;
            dst[n] = (int32_t)d;
            val[n] = 0.0;
            ++n;
            continue;
        }
        if (parse_line_fast(p, end, &s, &d, &v, &h)) {
            oob += (s < 0) | (s >= bound) | (d < 0) | (d >= bound);
            src[n] = (int32_t)s;
            dst[n] = (int32_t)d;
            val[n] = v;
            any_val |= h;
            ++n;
        }
    }
    r->offset += p - r->buf;
#endif
    if (at_eof && r->offset >= r->size) *at_eof_out = 1;
    *has_val = any_val ? 1 : 0;
    *oob_out = oob;
    return n;
}

// Fast tab-separated edge-file writer (for corpus synthesis at scale —
// np.savetxt measures ~0.5M edges/s; this runs ~100x that across cores).
// Appends when append != 0. Returns 0, or -1 on IO error.
int64_t write_edge_file(const char* path, const int64_t* src,
                        const int64_t* dst, int64_t n, int32_t append,
                        int32_t n_threads) {
    int64_t t = n_threads < 1 ? 1 : n_threads;
    if (t > n / (1 << 16)) t = n / (1 << 16) ? n / (1 << 16) : 1;
    // format each slice into its own buffer, then write sequentially
    std::vector<std::string> bufs((size_t)t);
    std::vector<std::thread> workers;
    for (int64_t i = 0; i < t; ++i) {
        workers.emplace_back([&, i] {
            int64_t a = (n * i) / t, b = (n * (i + 1)) / t;
            std::string& out = bufs[(size_t)i];
            out.reserve((size_t)(b - a) * 16);
            char tmp[48];
            for (int64_t j = a; j < b; ++j) {
                char* p = tmp + sizeof(tmp);
                *--p = '\n';
                uint64_t y = (uint64_t)dst[j];
                do { *--p = '0' + (char)(y % 10); y /= 10; } while (y);
                *--p = '\t';
                uint64_t x = (uint64_t)src[j];
                do { *--p = '0' + (char)(x % 10); x /= 10; } while (x);
                out.append(p, (size_t)(tmp + sizeof(tmp) - p));
            }
        });
    }
    for (auto& w : workers) w.join();
    FILE* f = fopen(path, append ? "ab" : "wb");
    if (!f) return -1;
    for (auto& b : bufs) {
        if (b.size() && fwrite(b.data(), 1, b.size(), f) != b.size()) {
            fclose(f);
            return -1;
        }
    }
    fclose(f);
    return 0;
}

// Binary wire-frame column decode (the GSEW ingest wire format,
// core/ingest.py). One call replaces the per-line strtoll/int() work of
// the text path entirely: the payload already IS little-endian columns,
// so decoding is a geometry check plus a widen/copy into the caller's
// int64/double buffers. Layout: src column, then dst column (int32 when
// wide == 0, int64 otherwise), then an optional float64 value column.
// Returns 0, or -1 when the payload size disagrees with (n, wide,
// has_val) — the caller counts that as a malformed frame.
int64_t decode_edge_frame(const char* payload, int64_t nbytes, int64_t n,
                          int32_t wide, int32_t has_val, int64_t* src,
                          int64_t* dst, double* val) {
    if (n < 0) return -1;
    int64_t isz = wide ? 8 : 4;
    int64_t want = n * isz * 2 + (has_val ? n * 8 : 0);
    if (nbytes != want) return -1;
    if (wide) {
        memcpy(src, payload, (size_t)(n * 8));
        memcpy(dst, payload + n * 8, (size_t)(n * 8));
    } else {
        // widen int32 -> int64 (the engine's raw-id dtype) in one pass
        int32_t s32, d32;
        const char* ps = payload;
        const char* pd = payload + n * 4;
        for (int64_t i = 0; i < n; ++i) {
            memcpy(&s32, ps + i * 4, 4);
            memcpy(&d32, pd + i * 4, 4);
            src[i] = s32;
            dst[i] = d32;
        }
    }
    if (has_val) memcpy(val, payload + n * isz * 2, (size_t)(n * 8));
    return 0;
}

// Parse a memory buffer of complete text edge lines (the socket text hot
// path, core/sources.py): same accepted grammar as the file reader
// (parse_line_fast), one call per recv batch instead of per-line Python
// split()/int(). Unlike the file path, MALFORMED lines are counted —
// a live socket's noise is data the operator should know about — where
// malformed means a non-blank, non-comment line the grammar rejects.
// [buf, buf+len) must carry READ_PAD zero bytes past len (SWAR loads).
// Returns edges written (never exceeds cap; the caller sizes cap at the
// line count), with *malformed_out the rejected-line count.
int64_t parse_edge_lines(const char* buf, int64_t len, int64_t* src,
                         int64_t* dst, double* val, int64_t cap,
                         int32_t* has_val, int64_t* malformed_out) {
    const char* p = buf;
    const char* end = buf + len;
    int64_t n = 0, malformed = 0;
    bool av = false;
    int64_t s, d;
    double v;
    bool h;
    while (p < end && n < cap) {
        const char* q = skip_sep(p, end);
        if (q >= end) break;
        if (*q == '#' || *q == '%' || *q == '\n') {
            p = skip_line(q, end);
            continue;
        }
        if (parse_line_fast(p, end, &s, &d, &v, &h)) {
            src[n] = s;
            dst[n] = d;
            val[n] = v;
            av |= h;
            ++n;
        } else {
            ++malformed;  // non-blank, non-comment, rejected: counted
        }
    }
    *has_val = av ? 1 : 0;
    *malformed_out = malformed;
    return n;
}

}  // extern "C"

// --------------------------------------------------------------------- //
// First-seen vertex compaction (the VertexDict.encode hot path).
//
// Open-addressing int64 -> int32 hash map with linear probing; the
// Python VertexDict keeps the reverse (idx -> raw) table and hands the
// encoder only the forward mapping. ~10x the numpy sorted-merge path.
// --------------------------------------------------------------------- //

namespace {

struct Encoder {
    int64_t* keys;    // EMPTY_KEY = sentinel
    int32_t* vals;
    int64_t cap;      // power of two
    int64_t size;
    int32_t min_idx;  // slot for the raw id == EMPTY_KEY itself (-1 = unseen)
};

constexpr int64_t EMPTY_KEY = INT64_MIN;

inline uint64_t mix_hash(uint64_t x) {
    x ^= x >> 33; x *= 0xff51afd7ed558ccdULL;
    x ^= x >> 33; x *= 0xc4ceb9fe1a85ec53ULL;
    x ^= x >> 33; return x;
}

void encoder_rehash(Encoder* e, int64_t new_cap) {
    int64_t* nk = (int64_t*)malloc(new_cap * sizeof(int64_t));
    int32_t* nv = (int32_t*)malloc(new_cap * sizeof(int32_t));
    for (int64_t i = 0; i < new_cap; ++i) nk[i] = EMPTY_KEY;
    for (int64_t i = 0; i < e->cap; ++i) {
        if (e->keys[i] == EMPTY_KEY) continue;
        uint64_t h = mix_hash((uint64_t)e->keys[i]) & (new_cap - 1);
        while (nk[h] != EMPTY_KEY) h = (h + 1) & (new_cap - 1);
        nk[h] = e->keys[i];
        nv[h] = e->vals[i];
    }
    free(e->keys); free(e->vals);
    e->keys = nk; e->vals = nv; e->cap = new_cap;
}

}  // namespace

extern "C" {

void* encoder_create() {
    Encoder* e = (Encoder*)malloc(sizeof(Encoder));
    e->cap = 1024; e->size = 0; e->min_idx = -1;
    e->keys = (int64_t*)malloc(e->cap * sizeof(int64_t));
    e->vals = (int32_t*)malloc(e->cap * sizeof(int32_t));
    for (int64_t i = 0; i < e->cap; ++i) e->keys[i] = EMPTY_KEY;
    return e;
}

void encoder_destroy(void* ptr) {
    Encoder* e = (Encoder*)ptr;
    free(e->keys); free(e->vals); free(e);
}

namespace {

inline int32_t encode_one(Encoder* e, int64_t k, int64_t* novel_out,
                          int64_t* n_novel) {
    if ((e->size + 1) * 10 >= e->cap * 7) encoder_rehash(e, e->cap * 2);
    if (k == EMPTY_KEY) {  // the sentinel value is a legal raw id
        if (e->min_idx < 0) {
            e->min_idx = (int32_t)e->size;
            novel_out[(*n_novel)++] = k;
            e->size++;
        }
        return e->min_idx;
    }
    uint64_t h = mix_hash((uint64_t)k) & (e->cap - 1);
    while (true) {
        if (e->keys[h] == k) return e->vals[h];
        if (e->keys[h] == EMPTY_KEY) {
            e->keys[h] = k;
            e->vals[h] = (int32_t)e->size;
            novel_out[(*n_novel)++] = k;
            return (int32_t)e->size++;
        }
        h = (h + 1) & (e->cap - 1);
    }
}

inline void prefetch_slot(const Encoder* e, int64_t k) {
    uint64_t hp = mix_hash((uint64_t)k) & (e->cap - 1);
    __builtin_prefetch(&e->keys[hp]);
    __builtin_prefetch(&e->vals[hp]);
}

}  // namespace

// Encode n raw ids to compact indices (first-seen-first). Novel raw ids,
// in first-appearance order, are appended to novel_out (caller-sized >= n).
// Returns the number of novel ids.
int64_t encoder_encode(void* ptr, const int64_t* raw, int64_t n,
                       int32_t* idx_out, int64_t* novel_out) {
    Encoder* e = (Encoder*)ptr;
    int64_t n_novel = 0;
    // Random probes into a table larger than L2 are memory-latency bound
    // (~20M ids/s); issuing the hash-slot prefetch a few elements ahead
    // overlaps the misses and roughly triples throughput.
    constexpr int64_t PD = 16;
    for (int64_t i = 0; i < n; ++i) {
        if (i + PD < n) prefetch_slot(e, raw[i + PD]);
        idx_out[i] = encode_one(e, raw[i], novel_out, &n_novel);
    }
    return n_novel;
}

// Paired encode for edge columns: equivalent to encoding the interleaved
// sequence a0,b0,a1,b1,... (first-seen order follows edge arrival, matching
// the reference's per-record processing) without the caller materializing
// the interleaved copy.
int64_t encoder_encode2(void* ptr, const int64_t* a, const int64_t* b,
                        int64_t n, int32_t* ia, int32_t* ib,
                        int64_t* novel_out) {
    Encoder* e = (Encoder*)ptr;
    int64_t n_novel = 0;
    constexpr int64_t PD = 8;
    for (int64_t i = 0; i < n; ++i) {
        if (i + PD < n) {
            prefetch_slot(e, a[i + PD]);
            prefetch_slot(e, b[i + PD]);
        }
        ia[i] = encode_one(e, a[i], novel_out, &n_novel);
        ib[i] = encode_one(e, b[i], novel_out, &n_novel);
    }
    return n_novel;
}

// Session-based fused parse+encode (same loop as parse_encode_span over
// the persistent reader buffer — no per-chunk allocation or page faults).
int64_t reader_next_encoded(void* ptr, void* enc_ptr, int32_t* src32,
                            int32_t* dst32, double* val, int64_t cap,
                            int64_t* novel_out, int64_t* n_novel_out,
                            int32_t* has_val, int32_t* at_eof_out) {
    SpanReader* r = (SpanReader*)ptr;
    bool at_eof = false;
    *at_eof_out = 0;
    *has_val = 0;
    *n_novel_out = 0;
    const char* end = nullptr;
    int64_t span = reader_fill(r, &end, &at_eof);
    if (span < 0) return -1;
    if (span == 0) {
        if (at_eof) *at_eof_out = 1;
        return 0;
    }
    Encoder* e = (Encoder*)enc_ptr;
    const char* p = r->buf;
    int64_t n = 0, n_novel = 0;
    bool any_val = false;
    constexpr int B = 128;
    int64_t ss[2][B], dd[2][B];
    double vv[2][B];
    int m[2] = {0, 0};
    auto parse_batch = [&](int which) {
        int k = 0;
        int64_t s, d; double v; bool h;
        while (k < B && p < end && n + m[which ^ 1] + k < cap) {
            if (parse_two_col_fast(p, &s, &d)) {
                ss[which][k] = s; dd[which][k] = d; vv[which][k] = 0.0;
                ++k;
                continue;
            }
            if (parse_line_fast(p, end, &s, &d, &v, &h)) {
                ss[which][k] = s; dd[which][k] = d; vv[which][k] = v;
                any_val |= h;
                ++k;
            }
        }
        m[which] = k;
        for (int i = 0; i < k; ++i) {
            prefetch_slot(e, ss[which][i]);
            prefetch_slot(e, dd[which][i]);
        }
    };
    parse_batch(0);
    int cur = 0;
    while (m[cur]) {
        parse_batch(cur ^ 1);
        for (int i = 0; i < m[cur]; ++i) {
            src32[n] = encode_one(e, ss[cur][i], novel_out, &n_novel);
            dst32[n] = encode_one(e, dd[cur][i], novel_out, &n_novel);
            val[n] = vv[cur][i];
            ++n;
        }
        cur ^= 1;
    }
    r->offset += p - r->buf;
    if (at_eof && r->offset >= r->size) *at_eof_out = 1;
    *has_val = any_val ? 1 : 0;
    *n_novel_out = n_novel;
    return n;
}

// Lookup without insert; returns -1 when unseen.
int32_t encoder_lookup(void* ptr, int64_t k) {
    Encoder* e = (Encoder*)ptr;
    if (k == EMPTY_KEY) return e->min_idx;
    uint64_t h = mix_hash((uint64_t)k) & (e->cap - 1);
    while (true) {
        if (e->keys[h] == k) return e->vals[h];
        if (e->keys[h] == EMPTY_KEY) return -1;
        h = (h + 1) & (e->cap - 1);
    }
}

// Batched lookup without insert (the serving read path): out[i] = compact
// id or -1. One C call per query batch — a Python-side loop over
// encoder_lookup costs a GIL/ctypes round trip per id, which is exactly
// the per-query host loop the query engine forbids.
void encoder_lookup_batch(void* ptr, const int64_t* ks, int64_t n,
                          int32_t* out) {
    Encoder* e = (Encoder*)ptr;
    for (int64_t i = 0; i < n; ++i) {
        if (i + 8 < n) prefetch_slot(e, ks[i + 8]);
        int64_t k = ks[i];
        if (k == EMPTY_KEY) { out[i] = e->min_idx; continue; }
        uint64_t h = mix_hash((uint64_t)k) & (e->cap - 1);
        while (true) {
            if (e->keys[h] == k) { out[i] = e->vals[h]; break; }
            if (e->keys[h] == EMPTY_KEY) { out[i] = -1; break; }
            h = (h + 1) & (e->cap - 1);
        }
    }
}

int64_t encoder_size(void* ptr) { return ((Encoder*)ptr)->size; }

}  // extern "C"

// --------------------------------------------------------------------- //
// Compiled streaming-CC baseline (the honest comparator for bench.py).
//
// This is the reference's execution model compiled to native code: edges
// round-robin across P partitions (PartitionMapper stamping subtask
// indices, SummaryBulkAggregation.java:93-106), each partition folds its
// window slice into its own union-find keyed by RAW vertex id — hash-map
// state, exactly the shape of the reference's DisjointSet-over-HashMaps
// (summaries/DisjointSet.java:30-154) — and at window end the partials
// merge pairwise into a running global summary on one thread (the
// parallelism-1 Merger, SummaryAggregation.java:107-119). It is strictly
// faster than the JVM original (no Flink runtime, no serialization, no
// network) — beating it by 10x is therefore a conservative proof of the
// north-star target.
// --------------------------------------------------------------------- //

namespace {

// Open-addressing union-find over raw int64 ids: map id -> slot, with
// parent/rank arrays indexed by slot (path halving).
struct UnionFind {
    std::vector<int64_t> keys;   // EMPTY_KEY = empty
    std::vector<int32_t> slot;   // key -> dense slot
    std::vector<int32_t> parent;
    std::vector<uint8_t> rnk;
    int64_t mask;

    explicit UnionFind(int64_t cap_hint = 1024) {
        int64_t cap = 1024;
        while (cap < cap_hint * 2) cap <<= 1;
        keys.assign(cap, EMPTY_KEY);
        slot.assign(cap, -1);
        mask = cap - 1;
    }
    void maybe_grow() {
        if ((int64_t)parent.size() * 10 < (mask + 1) * 7) return;
        int64_t ncap = (mask + 1) << 1;
        std::vector<int64_t> nk(ncap, EMPTY_KEY);
        std::vector<int32_t> ns(ncap, -1);
        for (int64_t i = 0; i <= mask; ++i) {
            if (keys[i] == EMPTY_KEY) continue;
            uint64_t h = mix_hash((uint64_t)keys[i]) & (ncap - 1);
            while (nk[h] != EMPTY_KEY) h = (h + 1) & (ncap - 1);
            nk[h] = keys[i];
            ns[h] = slot[i];
        }
        keys.swap(nk);
        slot.swap(ns);
        mask = ncap - 1;
    }
    int32_t lookup_or_insert(int64_t k) {
        maybe_grow();
        uint64_t h = mix_hash((uint64_t)k) & mask;
        while (true) {
            if (keys[h] == k) return slot[h];
            if (keys[h] == EMPTY_KEY) {
                int32_t s = (int32_t)parent.size();
                keys[h] = k;
                slot[h] = s;
                parent.push_back(s);
                rnk.push_back(0);
                return s;
            }
            h = (h + 1) & mask;
        }
    }
    int32_t find(int32_t x) {
        while (parent[x] != x) {
            parent[x] = parent[parent[x]];  // path halving
            x = parent[x];
        }
        return x;
    }
    void union_ids(int64_t a, int64_t b) {
        int32_t ra = find(lookup_or_insert(a));
        int32_t rb = find(lookup_or_insert(b));
        if (ra == rb) return;
        if (rnk[ra] < rnk[rb]) { int32_t t = ra; ra = rb; rb = t; }
        parent[rb] = ra;
        if (rnk[ra] == rnk[rb]) ++rnk[ra];
    }
    // DisjointSet.merge analog: fold every (element, root) pair of one
    // structure into the other (ConnectedComponents.java:116-125).
    void merge_from(UnionFind& o) {
        std::vector<int64_t> slot_to_key(o.parent.size(), EMPTY_KEY);
        for (int64_t i = 0; i <= o.mask; ++i)
            if (o.keys[i] != EMPTY_KEY) slot_to_key[o.slot[i]] = o.keys[i];
        for (int64_t i = 0; i <= o.mask; ++i) {
            if (o.keys[i] == EMPTY_KEY) continue;
            union_ids(o.keys[i], slot_to_key[o.find(o.slot[i])]);
        }
    }
};

}  // namespace

extern "C" {

// Streaming-model CC over a parsed edge array: `partitions` parallel
// window folds + sequential merge per window, `window` edges per window.
// Returns elapsed nanoseconds; *components_out gets the final component
// count (for correctness cross-checks against the device path).
int64_t cc_baseline_run(const int64_t* src, const int64_t* dst, int64_t n,
                        int64_t window, int32_t partitions,
                        int64_t* components_out) {
    struct timespec t0, t1;
    clock_gettime(CLOCK_MONOTONIC, &t0);
    int64_t p = partitions < 1 ? 1 : partitions;
    UnionFind global(1024);
    for (int64_t w0 = 0; w0 < n; w0 += window) {
        int64_t w1 = w0 + window < n ? w0 + window : n;
        std::vector<UnionFind> parts;
        parts.reserve((size_t)p);
        for (int64_t i = 0; i < p; ++i) parts.emplace_back(256);
        std::vector<std::thread> workers;
        for (int64_t i = 0; i < p; ++i) {
            workers.emplace_back([&, i] {
                UnionFind& uf = parts[(size_t)i];
                // round-robin partition stamping, as PartitionMapper does
                for (int64_t j = w0 + i; j < w1; j += p)
                    uf.union_ids(src[j], dst[j]);
            });
        }
        for (auto& w : workers) w.join();
        for (auto& part : parts) global.merge_from(part);
    }
    // component count = number of root slots
    int64_t comps = 0;
    for (size_t s = 0; s < global.parent.size(); ++s)
        if (global.find((int32_t)s) == (int32_t)s) ++comps;
    *components_out = comps;
    clock_gettime(CLOCK_MONOTONIC, &t1);
    return (t1.tv_sec - t0.tv_sec) * 1000000000LL + (t1.tv_nsec - t0.tv_nsec);
}

// Flink-representative proxy (round-3 verdict #4): the same job graph as
// the reference's streaming-CC plan, with the runtime costs Flink adds on
// top of the bare algorithm made explicit — every record crosses the
// partitioner as SERIALIZED bytes (Flink's network shuffle: a
// StreamRecord tag byte + two big-endian longs, the Tuple2<Long,Long>
// wire shape of DataOutputView), and each window's partials cross a
// second serialized boundary to the parallelism-1 Merger (the DisjointSet
// serializer writes (element, parent) pairs; SummaryAggregation.java
// routes partials through a keyed shuffle to the single Merger subtask).
// Deliberately NOT modeled: JVM object churn/GC, Flink's actual netty
// stack, credit-based flow control, task-thread handover — all of which
// only slow the real system further. This proxy is therefore an UPPER
// bound on real single-host Flink throughput for this job, so
// headline/proxy is a conservative lower bound on the true advantage;
// it must land between the interpreted-Python union-find tier and the
// zero-overhead compiled baseline above to be credible (bench.py asserts
// exactly that bracket).
int64_t flink_proxy_run(const int64_t* src, const int64_t* dst, int64_t n,
                        int64_t window, int32_t partitions,
                        int64_t* components_out) {
    struct timespec t0, t1;
    clock_gettime(CLOCK_MONOTONIC, &t0);
    int64_t p = partitions < 1 ? 1 : partitions;
    UnionFind global(1024);
    std::vector<std::vector<uint8_t>> queues((size_t)p);
    for (int64_t w0 = 0; w0 < n; w0 += window) {
        int64_t w1 = w0 + window < n ? w0 + window : n;
        // --- shuffle boundary 1: source -> window fold -------------------
        // round-robin partition stamping (PartitionMapper), then each
        // record is serialized onto its partition's in-flight buffer.
        for (auto& q : queues) q.clear();
        for (int64_t j = w0; j < w1; ++j) {
            std::vector<uint8_t>& q = queues[(size_t)((j - w0) % p)];
            size_t off = q.size();
            q.resize(off + 17);
            q[off] = 0;  // StreamRecord tag (element, no timestamp)
            uint64_t a = __builtin_bswap64((uint64_t)src[j]);
            uint64_t b = __builtin_bswap64((uint64_t)dst[j]);
            memcpy(q.data() + off + 1, &a, 8);
            memcpy(q.data() + off + 9, &b, 8);
        }
        // --- per-partition window folds (deserialize + union) -----------
        std::vector<UnionFind> parts;
        parts.reserve((size_t)p);
        for (int64_t i = 0; i < p; ++i) parts.emplace_back(256);
        std::vector<std::thread> workers;
        for (int64_t i = 0; i < p; ++i) {
            workers.emplace_back([&, i] {
                UnionFind& uf = parts[(size_t)i];
                const std::vector<uint8_t>& q = queues[(size_t)i];
                for (size_t off = 0; off + 17 <= q.size(); off += 17) {
                    uint64_t a, b;
                    memcpy(&a, q.data() + off + 1, 8);
                    memcpy(&b, q.data() + off + 9, 8);
                    uf.union_ids((int64_t)__builtin_bswap64(a),
                                 (int64_t)__builtin_bswap64(b));
                }
            });
        }
        for (auto& w : workers) w.join();
        // --- shuffle boundary 2: partials -> parallelism-1 Merger --------
        // each partial DisjointSet serializes as (element, root) pairs and
        // the Merger deserializes and re-unions them.
        for (auto& part : parts) {
            std::vector<int64_t> slot_to_key(part.parent.size(), EMPTY_KEY);
            for (int64_t i = 0; i <= part.mask; ++i)
                if (part.keys[i] != EMPTY_KEY)
                    slot_to_key[part.slot[i]] = part.keys[i];
            std::vector<uint8_t> wire;
            wire.reserve(part.parent.size() * 16);
            for (int64_t i = 0; i <= part.mask; ++i) {
                if (part.keys[i] == EMPTY_KEY) continue;
                uint64_t e = __builtin_bswap64((uint64_t)part.keys[i]);
                uint64_t r = __builtin_bswap64(
                    (uint64_t)slot_to_key[part.find(part.slot[i])]);
                size_t off = wire.size();
                wire.resize(off + 16);
                memcpy(wire.data() + off, &e, 8);
                memcpy(wire.data() + off + 8, &r, 8);
            }
            for (size_t off = 0; off + 16 <= wire.size(); off += 16) {
                uint64_t e, r;
                memcpy(&e, wire.data() + off, 8);
                memcpy(&r, wire.data() + off + 8, 8);
                global.union_ids((int64_t)__builtin_bswap64(e),
                                 (int64_t)__builtin_bswap64(r));
            }
        }
    }
    int64_t comps = 0;
    for (size_t s = 0; s < global.parent.size(); ++s)
        if (global.find((int32_t)s) == (int32_t)s) ++comps;
    *components_out = comps;
    clock_gettime(CLOCK_MONOTONIC, &t1);
    return (t1.tv_sec - t0.tv_sec) * 1000000000LL + (t1.tv_nsec - t0.tv_nsec);
}

}  // extern "C"

// ===========================================================================
// Compact-id incremental union-find: the host CC carry (round 5).
//
// The streaming-CC merge is control-flow-heavy pointer chasing — the one
// graph kernel that maps better onto a scalar core beside the parser than
// onto dense vector passes (the reference's own fold is a CPU hashmap,
// library/ConnectedComponents.java:83-126). This carry runs union-find
// with path-halving over COMPACT int32 ids (the vertex dictionary already
// made the id space dense, so no hash keys are needed — cf. the keyed
// UnionFind above used by the baselines), and per window reports exactly
// what the device mirror needs to stay a resolvable pointer forest:
//
//   * the window's touched ids + their post-window roots (epoch-stamped
//     first-touch detection, no per-window clears), and
//   * every root DEMOTED this window + its post-window root — a vertex
//     never touched again still resolves on the device mirror because
//     each pointer target was once a root and every demotion is mirrored.
//
// Union is by MIN ROOT (parent[max_root] = min_root), preserving the
// invariant the device carries share: a component's canonical root is its
// minimum compact id.
// ===========================================================================

struct CompactUF {
    std::vector<int32_t> parent;
    std::vector<uint32_t> stamp;   // epoch of last touch
    uint32_t epoch = 0;

    void ensure(int64_t vcap) {
        int64_t old = (int64_t)parent.size();
        if (vcap <= old) return;
        parent.resize((size_t)vcap);
        stamp.resize((size_t)vcap, 0);
        for (int64_t v = old; v < vcap; ++v) parent[(size_t)v] = (int32_t)v;
    }

    int32_t find(int32_t x) {
        while (parent[(size_t)x] != x) {
            int32_t p = parent[(size_t)x];
            int32_t g = parent[(size_t)p];
            parent[(size_t)x] = g;  // path halving
            x = g;
        }
        return x;
    }
};

extern "C" {

void* cuf_create() { return new (std::nothrow) CompactUF(); }

void cuf_destroy(void* h) { delete (CompactUF*)h; }

// Fold one window of compact edges. touched_out/roots_out need capacity
// 2n; changed_out/changed_roots_out need capacity n. Returns the touched
// count (>= 0) and writes the demoted-root count to *n_changed_out.
// Ids are validated in a PREPASS before any union is applied: a mid-loop
// bail-out would leave the union-find partially mutated with the applied
// unions' touched/changed outputs discarded, permanently desyncing a
// device pointer-forest mirror from this state for callers that catch
// the error and keep streaming. A -1 return therefore guarantees the
// carry is untouched (the wprep epoch scheme self-heals on the next
// window; a union does not).
int64_t cuf_fold_window(void* h, const int32_t* src, const int32_t* dst,
                        int64_t n, int64_t vcap,
                        int32_t* touched_out, int32_t* roots_out,
                        int32_t* changed_out, int32_t* changed_roots_out,
                        int64_t* n_changed_out) {
    CompactUF& uf = *(CompactUF*)h;
    for (int64_t i = 0; i < n; ++i) {
        int32_t a = src[i], b = dst[i];
        if (a < 0 || b < 0 || a >= vcap || b >= vcap) return -1;
    }
    uf.ensure(vcap);
    if (++uf.epoch == 0) {  // uint32 wrap: see wprep_run
        std::fill(uf.stamp.begin(), uf.stamp.end(), 0u);
        uf.epoch = 1;
    }
    int64_t nt = 0, nc = 0;
    for (int64_t i = 0; i < n; ++i) {
        int32_t a = src[i], b = dst[i];
        if (uf.stamp[(size_t)a] != uf.epoch) {
            uf.stamp[(size_t)a] = uf.epoch;
            touched_out[nt++] = a;
        }
        if (uf.stamp[(size_t)b] != uf.epoch) {
            uf.stamp[(size_t)b] = uf.epoch;
            touched_out[nt++] = b;
        }
        int32_t ra = uf.find(a), rb = uf.find(b);
        if (ra == rb) continue;
        int32_t lo = ra < rb ? ra : rb;
        int32_t hi = ra < rb ? rb : ra;
        uf.parent[(size_t)hi] = lo;   // union by min root
        changed_out[nc++] = hi;       // hi was a root until now: unique
    }
    for (int64_t i = 0; i < nt; ++i)
        roots_out[i] = uf.find(touched_out[i]);
    for (int64_t i = 0; i < nc; ++i)
        changed_roots_out[i] = uf.find(changed_out[i]);
    *n_changed_out = nc;
    return nt;
}

// Fold K windows in ONE call (the superbatch host-carry path): columns
// are concatenated with offsets[w]..offsets[w+1] delimiting window w
// (offsets has k+1 entries). Per-window outputs land back to back in
// the shared buffers with lengths in t_counts/c_counts (same capacity
// contract as k cuf_fold_window calls: touched/roots 2n total,
// changed/changed_roots n total, n = offsets[k]). Additionally emits
// the GROUP-deduped commit delta — the union of every touched or
// demoted id with its POST-GROUP root — into group_ids/group_roots
// (capacity 3n; count to *n_group_out): exactly the single masked
// scatter a device mirror needs per group, deduped here because a
// python-side unique() measured 26 ms per 64-window group. Ids are
// validated across the WHOLE group before any union (same no-partial-
// mutation guarantee as cuf_fold_window, extended to the group).
int64_t cuf_fold_group(void* h, const int32_t* src, const int32_t* dst,
                       const int64_t* offsets, int64_t k, int64_t vcap,
                       int32_t* touched_out, int32_t* roots_out,
                       int32_t* changed_out, int32_t* changed_roots_out,
                       int64_t* t_counts, int64_t* c_counts,
                       int32_t* group_ids, int32_t* group_roots,
                       int64_t* gt_counts, int64_t* n_group_out) {
    CompactUF& uf = *(CompactUF*)h;
    const int64_t n = offsets[k];
    for (int64_t i = 0; i < n; ++i) {
        int32_t a = src[i], b = dst[i];
        if (a < 0 || b < 0 || a >= vcap || b >= vcap) return -1;
    }
    int64_t tt = 0, tc = 0;
    for (int64_t w = 0; w < k; ++w) {
        const int64_t a = offsets[w];
        int64_t nc = 0;
        int64_t nt = cuf_fold_window(
            h, src + a, dst + a, offsets[w + 1] - a, vcap,
            touched_out + tt, roots_out + tt,
            changed_out + tc, changed_roots_out + tc, &nc);
        if (nt < 0) return -1;  // unreachable: ids validated above
        t_counts[w] = nt;
        c_counts[w] = nc;
        tt += nt;
        tc += nc;
    }
    // group dedup pass: group-unique TOUCHED ids first, in window order
    // (first-seen) with per-window counts in gt_counts — the caller's
    // first-seen emission log batches on this — then any demoted roots
    // not already present complete the commit delta.
    if (++uf.epoch == 0) {
        std::fill(uf.stamp.begin(), uf.stamp.end(), 0u);
        uf.epoch = 1;
    }
    int64_t ng = 0, toff = 0;
    for (int64_t w = 0; w < k; ++w) {
        const int64_t start = ng;
        for (int64_t i = toff; i < toff + t_counts[w]; ++i) {
            int32_t v = touched_out[i];
            if (uf.stamp[(size_t)v] != uf.epoch) {
                uf.stamp[(size_t)v] = uf.epoch;
                group_ids[ng++] = v;
            }
        }
        toff += t_counts[w];
        gt_counts[w] = ng - start;
    }
    for (int64_t i = 0; i < tc; ++i) {
        int32_t v = changed_out[i];
        if (uf.stamp[(size_t)v] != uf.epoch) {
            uf.stamp[(size_t)v] = uf.epoch;
            group_ids[ng++] = v;
        }
    }
    for (int64_t i = 0; i < ng; ++i)
        group_roots[i] = uf.find(group_ids[i]);
    *n_group_out = ng;
    return tt;
}

// Canonical flat labels for [0, vcap) (checkpoint sync point).
void cuf_flatten(void* h, int32_t* out, int64_t vcap) {
    CompactUF& uf = *(CompactUF*)h;
    uf.ensure(vcap);
    for (int64_t v = 0; v < vcap; ++v)
        out[v] = uf.find((int32_t)v);
}

// Restore from flat labels (a valid forest; roots must be component
// minima, which cuf_flatten and the device carries both guarantee).
int64_t cuf_load(void* h, const int32_t* labels, int64_t vcap) {
    CompactUF& uf = *(CompactUF*)h;
    uf.parent.assign((size_t)vcap, 0);
    uf.stamp.assign((size_t)vcap, 0);
    uf.epoch = 0;
    for (int64_t v = 0; v < vcap; ++v) {
        int32_t l = labels[v];
        if (l < 0 || l > v) return -1;  // not a min-rooted forest
        uf.parent[(size_t)v] = l;
    }
    return 0;
}

}  // extern "C"

// ===========================================================================
// Window prep for the forest CC carry (round 5): touched set + local
// renumbering in ONE pass. The numpy bitmap+LUT version costs ~50 ms per
// 1M-edge window (three passes + an O(V) nonzero scan); this epoch-
// stamped single pass touches each edge once and never clears state, so
// the cost scales with the window alone (~10-15 ms at 1M edges on one
// core). Touched ids come out in ARRIVAL order — the device kernels
// index by position, not value, so any consistent order works.
// ===========================================================================

struct WindowPrep {
    // stamp+code interleaved in one 8-byte entry: each endpoint costs a
    // single random cache-line touch instead of two (the pass is
    // memory-latency bound; measured 36 -> ~25 ms per 1M-edge window)
    struct Entry { uint32_t stamp; int32_t code; };
    std::vector<Entry> tab;
    uint32_t epoch = 0;

    void ensure(int64_t vcap) {
        if ((int64_t)tab.size() < vcap) tab.resize((size_t)vcap, Entry{0, 0});
    }
};

extern "C" {

void* wprep_create() { return new (std::nothrow) WindowPrep(); }

void wprep_destroy(void* h) { delete (WindowPrep*)h; }

// tids_out needs capacity 2n; lu_out/lv_out capacity n. Returns the
// touched count, or -1 on out-of-range ids.
int64_t wprep_run(void* h, const int32_t* src, const int32_t* dst,
                  int64_t n, int64_t vcap,
                  int32_t* tids_out, int32_t* lu_out, int32_t* lv_out) {
    WindowPrep& w = *(WindowPrep*)h;
    w.ensure(vcap);
    if (++w.epoch == 0) {
        // uint32 epoch wrapped (one in 2^32 windows): stale stamps from
        // 4.3e9 windows ago would read as current — reset and burn
        // epoch 0 (the default stamp value)
        std::fill(w.tab.begin(), w.tab.end(), WindowPrep::Entry{0, 0});
        w.epoch = 1;
    }
    int32_t t = 0;
    const int64_t PF = 16;  // unlike the union-find's dependent chains,
                            // these table accesses are independent
                            // across edges, so prefetch hides the misses
    WindowPrep::Entry* tab = w.tab.data();
    for (int64_t i = 0; i < n; ++i) {
        if (i + PF < n) {
            // ids at the prefetch distance are NOT yet validated: clamp
            // before forming the address (an out-of-range vector index
            // is UB even for a prefetch)
            size_t pa = (size_t)(uint32_t)src[i + PF];
            size_t pb = (size_t)(uint32_t)dst[i + PF];
            if (pa < (size_t)vcap) __builtin_prefetch(tab + pa, 1, 1);
            if (pb < (size_t)vcap) __builtin_prefetch(tab + pb, 1, 1);
        }
        int32_t a = src[i], b = dst[i];
        if (a < 0 || b < 0 || a >= vcap || b >= vcap) return -1;
        WindowPrep::Entry& ea = w.tab[(size_t)a];
        if (ea.stamp != w.epoch) {
            ea.stamp = w.epoch;
            ea.code = t;
            tids_out[t++] = a;
        }
        lu_out[i] = ea.code;
        WindowPrep::Entry& eb = w.tab[(size_t)b];
        if (eb.stamp != w.epoch) {
            eb.stamp = w.epoch;
            eb.code = t;
            tids_out[t++] = b;
        }
        lv_out[i] = eb.code;
    }
    return t;
}

}  // extern "C"
